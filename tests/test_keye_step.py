"""Keye-VL-2.0's language model, the second half of ``tests/test_keye.py``
(a file of its own so that the suite's workers share the time): the expert
shares against the uncut layer, the fused step's counters and the indexer
that learns, the harness's own check and the builder's long comparison at a
small size, the presets and ``train_hsdp.py --model keye_vl2_debug``."""

import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from benchmark import cells, worker
from torchft_tpu.coordination import LighthouseServer
from torchft_tpu.models import PRESETS, llama
from torchft_tpu.models.llama import MoEMLP, keye_vl2_30b_a3b, keye_vl2_debug
from torchft_tpu.parallel import auto_mesh
from torchft_tpu.parallel.train import build_model, init_train_state, make_train_step
from tests.test_keye import (
    CPU_GRAD_TOL, FLASH, PUBLISHED, _image_positions, adapter, reference, tiny,
)
from tests.test_nemotron_h import _tiny_table
from tests.test_sdar_moe import _data


def test_the_eight_expert_shares_add_up_to_the_uncut_layer():
    """Eight chips hold two experts each of one layer's sixteen: the routed
    parts the eight compute are the uncut reference layer; no chip drops a
    row and their held shares are the whole."""
    whole = tiny(num_experts=16, expert_parallel_chips=1, expert_parallel_index=0)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 64))
    full = MoEMLP(adapter.model_config(whole, 32))
    params = full.init(jax.random.PRNGKey(0), x)["params"]
    m = x.reshape(-1, 64)
    stacks = ("experts_gate", "experts_up", "experts_down")
    with jax.default_matmul_precision("highest"):
        want, _ = reference.experts(m, params, whole, lambda a: a)
        assert jnp.allclose(full.apply({"params": params}, x).reshape(want.shape), want,
                            rtol=1e-4, atol=1e-5)
        total, held_share = jnp.zeros_like(want), 0.0
        for index in range(8):
            share = tiny(num_experts=2, expert_parallel_chips=8, expert_parallel_index=index)
            own = {k: v[2 * index : 2 * index + 2] if k in stacks else v
                   for k, v in params.items()}
            out, sown = MoEMLP(adapter.model_config(share, 32)).apply(
                {"params": own}, x, mutable=["intermediates"])
            sown = sown["intermediates"]
            total = total + out.reshape(want.shape)
            held_share += float(sown["moe_held_share"][0])
            assert float(sown["moe_dropped"][0]) == 0.0
            assert jnp.allclose(out.reshape(want.shape),
                                reference.experts(m, own, share, lambda a: a)[0],
                                rtol=1e-4, atol=1e-5)
    assert jnp.allclose(total, want, rtol=1e-4, atol=1e-5)
    assert held_share == pytest.approx(1.0) and float(jnp.linalg.norm(total)) > 0.1


def test_the_step_trains_the_indexer_and_hands_on_the_counters():
    cfg = keye_vl2_debug(dtype=jnp.float32, **FLASH)
    mesh = auto_mesh(1, devices=jax.devices()[:1])
    model = build_model(cfg, mesh)
    state, sh = init_train_state(model, mesh, jax.random.PRNGKey(0), (2, 64))
    data = _data(cfg.vocab_size, 2, 64)
    step = make_train_step(model, mesh, sh, donate=False)
    new, metrics = step(state, data)
    assert {"dsa_index_kl", "dsa_kept_share", "dsa_tiles_run_share", "moe_held_share"} <= set(metrics)
    assert float(metrics["dsa_kept_share"]) == pytest.approx(
        (16 * 17 // 2 + 48 * 16) / (64 * 65 // 2))
    assert 0.0 < float(metrics["dsa_index_kl"]) < 5.0
    moved = new.params["layers_0"]["attn"]["indexer"]["wq_index"]["kernel"]
    assert not jnp.array_equal(moved, state.params["layers_0"]["attn"]["indexer"]["wq_index"]["kernel"])
    # a few steps on one batch: the indexer's loss falls
    for _ in range(3):
        new, after = step(new, data)
    assert float(after["dsa_index_kl"]) < float(metrics["dsa_index_kl"])


def test_position_ids_ride_the_microbatches():
    """A batch's ``position_ids`` [3,B,S] need no switch on the step: the
    model's ``mrope_section`` says a batch may carry them. Under
    ``accum_steps`` they are split along their rows' axis: two microbatches
    of one row each give the mean of the rows' own steps' losses, and the
    rows' ids exchanged give another."""
    cfg = keye_vl2_debug(dtype=jnp.float32)
    mesh = auto_mesh(1, devices=jax.devices()[:1])
    model = build_model(cfg, mesh)
    state, sh = init_train_state(model, mesh, jax.random.PRNGKey(0), (2, 32))
    ids = jnp.concatenate(
        [_image_positions(1, 32, start=8), _image_positions(1, 32, start=2, rows=3)], axis=1)
    data = dict(_data(cfg.vocab_size, 2, 32), position_ids=ids)
    whole = make_train_step(model, mesh, sh, donate=False)
    halves = make_train_step(model, mesh, sh, donate=False, accum_steps=2)
    row = lambda d, i: {k: v[:, i : i + 1] if k == "position_ids" else v[i : i + 1]  # noqa: E731
                        for k, v in d.items()}
    alone = [float(whole(state, row(data, i))[1]["loss"]) for i in range(2)]
    assert float(halves(state, data)[1]["loss"]) == pytest.approx(sum(alone) / 2, rel=1e-5)
    exchanged = dict(data, position_ids=ids[:, ::-1])
    assert abs(float(halves(state, exchanged)[1]["loss"]) - sum(alone) / 2) > 1e-4
    # and a batch without them is the model counting positions itself
    plain = {k: v for k, v in data.items() if k != "position_ids"}
    counted = dict(plain, position_ids=jnp.broadcast_to(jnp.arange(32), (3, 2, 32)))
    assert float(whole(state, plain)[1]["loss"]) == float(whole(state, counted)[1]["loss"])


def test_the_harness_check_passes_at_a_small_size(tmp_path, monkeypatch):
    """worker.reference_check as the chip run makes it: a sample of 64
    tokens no longer than the file's topk of 128 is compared under a topk of
    16, on both sides."""
    first = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: first)
    monkeypatch.setattr(worker, "CHECK_SEQ", 64)
    c = tiny(sa_config=dict(tiny()["sa_config"], topk=128))
    cell = cells.load_cell("w", _tiny_table(tmp_path, c))
    cell.mix.update(batch=1, seq=64)
    out = worker.reference_check(worker.Ctx(cell, 3000000001, 0, False))
    assert out["ok"] and out["grad_rel_l2_worst"] < 1e-3 and out["loss_rel_diff"] < 1e-5


def test_the_builders_long_comparison_at_a_small_size(tmp_path):
    """``tools/reference_compare.py`` as the chip run makes it at 16,384
    tokens: the cell's own model on a sequence several times ``topk`` long
    against the reference in query blocks, with the share of the reference's
    selection the program's own indexer selected too; the reference under a
    selection off by one key a row handed in the system's place."""
    from tools import reference_compare

    cell = cells.load_cell("w", _tiny_table(tmp_path, tiny()))
    out = reference_compare.compare(cell, 96, 3000000001, query_block=32)
    assert out["ok"] and (out["tokens"], out["query_block"], out["compared"]) == (
        96, 32, "system")
    assert out["grad_rel_l2_worst"] < 1e-4 and out["loss_rel_diff"] < 1e-5
    assert out["selection_agreement"] == 1.0  # float32 on both sides
    off = reference_compare.compare(
        cell, 96, 3000000001, query_block=32, departure="selection_off_by_one")
    assert off["compared"] == "reference under selection_off_by_one"
    assert off["grad_rel_l2_worst"] > 100 * CPU_GRAD_TOL
    assert "selection_agreement" not in off


def test_selection_agreement_pairs_each_layer_with_itself(tmp_path):
    """Six published layers, so that the tree holds ``layers_10``, which as a
    string sorts ahead of ``layers_2``: every layer's share is read against
    the reference's selection of THAT layer (float32 on both sides: 1.0),
    where two different layers' selections share far less."""
    from tools import reference_compare

    c = tiny(num_hidden_layers=6)
    cell = cells.load_cell("w", _tiny_table(tmp_path, c))
    cfg = adapter.sample_config(adapter.model_config(c, 64), 64)
    mesh = auto_mesh(1, devices=jax.devices()[:1])
    data = _data(c["vocab_size"], 1, 64)
    params = build_model(cfg, mesh).init(jax.random.PRNGKey(3), data["inputs"])["params"]
    assert "layers_10" in params
    assert reference_compare.selection_agreement(cell, cfg, mesh, params, data) == [1.0] * 6
    kept = reference.selections(params, data, c)
    assert float(jnp.sum(kept[1] & kept[5]) / jnp.sum(kept[1])) < 0.8


def test_the_presets():
    cfg = keye_vl2_30b_a3b()
    assert (cfg.sparse_topk, cfg.indexer_heads, cfg.indexer_head_dim) == (2048, 16, 64)
    assert cfg.mrope_section == (16, 24, 24) and cfg.layer_pattern == "*E" * 48
    assert cfg.num_experts == 128 and cfg.num_experts_per_tok == 8 and cfg.qk_norm == "head"
    assert PRESETS["keye_vl2_30b_a3b"] is keye_vl2_30b_a3b
    assert PRESETS["keye_vl2_debug"] is keye_vl2_debug
    published = adapter.model_config(
        dict(PUBLISHED, num_hidden_layers=48, num_experts=128, expert_parallel_chips=1,
             vocab_size=151936, vocab_parallel_chips=1), 16384)
    for f in dataclasses.fields(cfg):
        # the file's own, not the published model's: the layout, the run, the table's scale
        if f.name not in ("max_seq_len", "attn_impl", "experts_held", "embed_init_std"):
            assert getattr(published, f.name) == getattr(cfg, f.name), f.name
    # a plain model's stack is what it was: no policy, one table a rotary
    assert llama.llama_debug().sparse_topk is None and llama.llama_debug().mrope_section is None


@pytest.mark.timeout(300)
def test_train_hsdp_runs_the_debug_preset(tmp_path):
    """``train_hsdp.py --model keye_vl2_debug``: one group, the Manager in
    the loop, two committed steps on the CPU, the indexer's loss counted."""
    lighthouse = LighthouseServer(
        bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=20000, quorum_tick_ms=50)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", TORCHFT_LIGHTHOUSE=lighthouse.address(),
               REPLICA_GROUP_ID="0", NUM_REPLICA_GROUPS="1",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("XLA_FLAGS", None)  # one device: the preset's mesh of one
    try:
        proc = subprocess.run(
            [sys.executable, "train_hsdp.py", "--model", "keye_vl2_debug", "--steps", "2",
             "--batch", "2", "--seq", "32", "--result-dir", str(tmp_path / "out")],
            cwd=root, env=env, capture_output=True, text=True, timeout=240,
        )
    finally:
        lighthouse.shutdown()
    assert proc.returncode == 0, proc.stderr[-3000:]
    steps = [line for line in proc.stderr.splitlines() if " loss " in line]
    assert len(steps) == 2 and "dsa_index_kl" in steps[-1], steps
    assert cells.load_json(str(tmp_path / "out" / "group0.json"))["final_step"] == 2
