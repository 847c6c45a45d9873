"""Keye-VL-2.0's language model: the program's stack (a rotary QK-normed
attention whose keys an indexer selects, multimodal rotary positions, expert
layers that hold a share of the experts) against the benchmark's plain
reference at a small size on the CPU, in float32 with seeded weights: loss
and every gradient leaf, the indexer's among them; the selected flash
family's skipped tile pairs under a clustered selection (the kernels
themselves and the exact selection: tests/test_keye_kernels.py); M-RoPE against
the plain table; where the indexer's loss reaches. The shares, the step, the
harness's check and the presets: tests/test_keye_step.py."""

import dataclasses
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells
from benchmark.tests import test_keye_reference as _reference_tests
from torchft_tpu.models import llama
from torchft_tpu.models.llama import rope_table
from torchft_tpu.ops import sparse_index as dsa
from torchft_tpu.parallel import auto_mesh
from torchft_tpu.parallel.train import build_model, make_grad_step, state_shardings
from tests.test_sdar_moe import _data, _leaf_errors

adapter = cells.arch_module("keye_vl2", "adapter")
reference = cells.arch_module("keye_vl2", "reference")
tiny, PUBLISHED = _reference_tests.tiny, _reference_tests.PUBLISHED

# The benchmark's own tests of this architecture (benchmark/tests is not in
# tier-1's path), collected here under their own names, no body copied.
for _name, _obj in vars(_reference_tests).items():
    if _name.startswith("test_") and callable(_obj):
        globals()[_name] = _obj

# The selected kernels through the interpreter at a size the CPU affords.
FLASH = {"attn_impl": "flash", "flash_min_seq": 16, "flash_block_q": 16, "flash_block_k": 16}


def _image_positions(batch, seq, start=8, side=4, rows=6):
    """[3, B, S]: text, then an image span of ``rows`` x ``side`` patches
    whose height and width ids differ from the temporal one, then text."""
    t = np.arange(seq)
    inside = (t >= start) & (t < start + rows * side)
    height = np.where(inside, start + (t - start) // side, t)
    width = np.where(inside, start + (t - start) % side, t)
    temporal = np.where(inside, start, t)
    return jnp.broadcast_to(jnp.asarray(np.stack([temporal, height, width]))[:, None], (3, batch, seq))


def _unsettle(params, seed=7):
    """Norm scales off 1, the LayerNorm's bias off 0: one left out has to show."""
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        a + 0.1 * jax.random.normal(k, a.shape) if a.ndim == 1 else a
        for a, k in zip(leaves, keys)
    ])


def _setup(c, seq, batch=2, seed=0, positions=False, **cfg_overrides):
    cfg = dataclasses.replace(
        adapter.sample_config(adapter.model_config(c, seq), seq),
        **{"remat": False, **cfg_overrides})
    mesh = auto_mesh(1, devices=jax.devices()[:1])
    model = build_model(cfg, mesh)
    data = _data(c["vocab_size"], batch, seq, seed + 1)
    if positions:
        data["position_ids"] = _image_positions(batch, seq)
    params = _unsettle(model.init(jax.random.PRNGKey(seed), data["inputs"])["params"])
    return model, mesh, params, data


def _grads(model, mesh, params, data, **kw):
    sh = state_shardings(model, mesh, data["inputs"].shape)
    return make_grad_step(model, mesh, sh, **kw)(params, data)


def _reference(c, **options):
    return jax.jit(lambda p, b: reference.loss_and_grads(p, b, c, **options))


# -- (a) the program against the reference ------------------------------------------


@pytest.mark.parametrize("seq,index,flash,positions,remat", [
    (64, 1, False, True, False), (64, 0, True, True, True), (32, 3, True, False, False)])
def test_loss_and_every_gradient_match_the_reference(seq, index, flash, positions, remat, caplog):
    """Through ``make_grad_step``: the dense form and the selected kernels
    (interpreted, under remat's kept selection too), with an image span's
    positions and without, three of the chip's shares. A sequence of 64
    under a topk of 16: most rows really select."""
    c = tiny(expert_parallel_index=index)
    with caplog.at_level(logging.INFO, logger=llama.logger.name):
        llama._ATTN_NOTED.clear()
        model, mesh, params, data = _setup(
            c, seq, positions=positions, remat=remat, **(FLASH if flash else {}))
        loss, grads = _grads(model, mesh, params, data)
    assert model.cfg.sparse_topk == 16 < seq
    want_loss, want = _reference(c)(params, data)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    errs = _leaf_errors(grads, want)
    assert max(errs.values()) < CPU_GRAD_TOL, max(errs.items(), key=lambda kv: kv[1])
    index_leaves = [k for k in errs if "indexer" in k]
    assert len(index_leaves) == 5 * c["num_hidden_layers"]
    for path, leaf in jax.tree_util.tree_leaves_with_path(grads):
        if "indexer" in jax.tree_util.keystr(path):
            assert float(jnp.abs(leaf).max()) > 1e-6, jax.tree_util.keystr(path)
    said = "flash/selected" if flash else "dense/selected"
    assert any(said in r.getMessage() and "topk=16" in r.getMessage()
               and ("tiles=16x16" in r.getMessage()) == flash for r in caplog.records)


def test_the_reference_in_query_blocks_is_the_reference():
    c = tiny()
    _, _, params, data = _setup(c, 64, positions=True)
    loss, want = _reference(c)(params, data)
    blocked_loss, blocked = _reference(c, query_block=16)(params, data)
    assert float(blocked_loss) == pytest.approx(float(loss), rel=1e-6)
    assert max(_leaf_errors(blocked, want).values()) < 1e-5


# What the float32 comparisons of this file hold the program to.
CPU_GRAD_TOL = 2e-4


@pytest.mark.parametrize("departure,least", [
    ("selection_off_by_one", 100 * CPU_GRAD_TOL), ("no_indexer_loss", reference.GRAD_REL_L2_TOL)])
def test_a_departure_is_another_result(departure, least):
    """A selection off by one key a row reads orders over what this file
    holds the program to (on the chip bf16 hides a single key of 256: the
    float32 comparison here is what holds the selection to the key); a step
    that leaves L_I out leaves the indexer's leaves at exactly zero, which
    reads 1.0 on each: over the chip's limit too."""
    c = tiny()
    _, _, params, data = _setup(c, 64, seed=2)
    _, want = _reference(c)(params, data)
    _, got = _reference(c, departure=departure)(params, data)
    errs = {k: v for k, v in _leaf_errors(got, want).items() if v == v}
    worst = max(errs, key=errs.get)
    assert errs[worst] > least, (worst, errs[worst])
    assert departure != "no_indexer_loss" or ("indexer" in worst and errs[worst] == 1.0)


def test_dsa_probs_ms_is_the_probabilities_pass_alone_a_part_of_dsa_index_ms():
    """The table's entry, and the reading on a hand-made trace of two steps
    whose operations are named as the compiled step names them: the two
    modes of ``dsa_index_kl`` and nothing else of the indexer's."""
    import os

    from benchmark import trace_reduce, worker

    table = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))["per_layer"]
    entry = next(e for e in table if e["name"] == "dsa_probs_ms")  # the table grows at its end
    assert {k: v for k, v in entry.items() if k != "unit"} == {
        "name": "dsa_probs_ms", "better": "lower", "source": "device_trace",
        "layer": "indexer and selection", "moves": "tok_s_chip", "workloads": ["keye-raw"]}
    cell = cells.load_cell("keye-raw")
    readers = worker.load_metric_readers(cell, "")
    ops = {
        "dsa_index_kl.7 bf16[1,16384,16384]": 0.125, "dsa_index_kl.3 f32[1,16384,128]": 0.0625,
        "dsa_index_scores.4 f32[1,16384,16384]": 0.5, "dsa_index_scores_bwd.2 (f32[1,16,16384,64]": 0.25,
        "flash_attention_selected.3 bf16[1,32,16384,128]": 1.0, "fusion.9 (pred[512,16384]{1,0}, s32[512]{0})": 2.0,
    }
    trace = trace_reduce.Trace((0.0, 8.0), 1, sum(ops.values()), ops, [], {})
    run = {"cell": cell, "records": [], "trace": trace, "traced_steps": 2}
    assert readers["dsa_probs_ms"](run) == (0.125 + 0.0625) * 1e3 / 2
    assert readers["dsa_index_ms"](run) == readers["dsa_probs_ms"](run) + (0.5 + 0.25) * 1e3 / 2
    # no trace, or a trace without the kernel (the ``jax.numpy`` form): nothing
    assert readers["dsa_probs_ms"]({**run, "trace": None, "traced_steps": 0}) is None
    bare = trace_reduce.Trace((0.0, 8.0), 1, 1.0, {"fusion.3 (f32[1,8,512,16384]{3,2,1,0}, f32[1": 1.0}, [], {})
    assert readers["dsa_probs_ms"]({**run, "trace": bare}) is None
    assert readers["dsa_index_ms"]({**run, "trace": bare}) == 500.0


def test_the_indexers_loss_reaches_the_indexer_alone():
    """L_I's gradient reaches only the indexer's leaves, and the rest's
    gradient is what the reference gives with ``indexer_loss_coef`` 0; the
    indexer's own scales with the coefficient and has no other source."""
    c = tiny()
    model, mesh, params, data = _setup(c, 64, positions=True)
    _, grads = _grads(model, mesh, params, data)
    _, without = _reference(dict(c, indexer_loss_coef=0.0))(params, data)
    _, doubled = _grads(
        build_model(dataclasses.replace(model.cfg, indexer_loss_coef=2.0), mesh),
        mesh, params, data)
    for (path, got), ref, twice in zip(
        jax.tree_util.tree_leaves_with_path(grads), jax.tree_util.tree_leaves(without),
        jax.tree_util.tree_leaves(doubled),
    ):
        name = jax.tree_util.keystr(path)
        if "indexer" in name:
            assert float(jnp.abs(ref).max()) == 0.0, name
            assert jnp.allclose(twice, 2.0 * got, rtol=1e-5, atol=1e-9), name
        else:
            assert jnp.allclose(got, ref, rtol=2e-4, atol=1e-7), name
            assert jnp.array_equal(twice, got), name


# -- (b) a clustered selection ----------------------------------------------------------


def _clustered_scores(q_index, k_index, weights):
    """Index scores under which a query keeps its own tile of 16 and the
    lowest columns: what a trained selection does, here by fiat."""
    seq = q_index.shape[1]
    t, s = jnp.arange(seq)[:, None], jnp.arange(seq)[None, :]
    return jnp.broadcast_to(
        jnp.where(s // 16 == t // 16, 1e3, -s.astype(jnp.float32)), (q_index.shape[0], seq, seq))


def test_a_clustered_selection_skips_tiles_and_changes_nothing(monkeypatch):
    """Every query keeps its own tile and the first: the table of tile pairs
    to run then has holes, the kernels skip them forward and backward, and
    loss and gradients are the dense form's under the same selection."""
    monkeypatch.setattr(dsa, "index_scores", _clustered_scores)
    c = tiny(sa_config=dict(tiny()["sa_config"], topk=24))
    seq = 128
    outs = {}
    for name, overrides in (("dense", {}), ("flash", FLASH)):
        cfg = dataclasses.replace(adapter.model_config(c, seq), remat=False, **overrides)
        mesh = auto_mesh(1, devices=jax.devices()[:1])
        model = build_model(cfg, mesh)
        data = _data(c["vocab_size"], 1, seq, 3)
        params = model.init(jax.random.PRNGKey(0), data["inputs"])["params"]
        sh = state_shardings(model, mesh, (1, seq))
        outs[name] = make_grad_step(model, mesh, sh, with_metrics=True)(params, data)
    (loss, metrics), grads = outs["flash"]
    (want_loss, want_metrics), want = outs["dense"]
    # 8 q tiles of 16: a tile runs itself and the first two (24 keys), not its causal 1..8
    assert float(metrics["dsa_tiles_run_share"]) == pytest.approx((1 + 2 + 6 * 3) / 36)
    assert float(want_metrics["dsa_tiles_run_share"]) == 1.0  # one tile: the dense form
    assert float(metrics["dsa_kept_share"]) == pytest.approx(
        (24 * 25 // 2 + 104 * 24) / (128 * 129 // 2))
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    errs = {k: v for k, v in _leaf_errors(grads, want).items() if v == v}
    assert max(errs.values()) < 1e-4, max(errs.items(), key=lambda kv: kv[1])


# -- (c) positions ----------------------------------------------------------------------


def test_mrope_takes_each_pairs_id_by_section_and_equal_ids_are_the_plain_table():
    pos = jax.random.randint(jax.random.PRNGKey(0), (3, 2, 24), 0, 500)
    cos, sin = rope_table(pos, 16, 1e7, jnp.float32, (2, 3, 3))
    assert cos.shape == (2, 24, 8)
    for lo, hi, component in ((0, 2, 0), (2, 5, 1), (5, 8, 2)):
        plain_cos, plain_sin = rope_table(pos[component], 16, 1e7, jnp.float32)
        assert jnp.array_equal(cos[..., lo:hi], plain_cos[..., lo:hi])
        assert jnp.array_equal(sin[..., lo:hi], plain_sin[..., lo:hi])
    # equal ids, as [3, B, S] or as [B, S]: the plain table, bit for bit
    same = jnp.broadcast_to(pos[0], (3, 2, 24))
    plain = rope_table(pos[0], 16, 1e7, jnp.bfloat16)
    for given in (same, pos[0]):
        got = rope_table(given, 16, 1e7, jnp.bfloat16, (2, 3, 3))
        assert jnp.array_equal(got[0], plain[0]) and jnp.array_equal(got[1], plain[1])
    with pytest.raises(ValueError, match="mrope_section"):
        rope_table(pos, 16, 1e7, jnp.float32, (2, 3, 4))


def test_positions_reach_the_model_and_a_plain_model_refuses_three_ids():
    c = tiny()
    model, mesh, params, data = _setup(c, 32, positions=True)
    loss, _ = _grads(model, mesh, params, data)
    plain = {k: v for k, v in data.items() if k != "position_ids"}
    plain_loss, _ = _grads(model, mesh, params, plain)
    assert abs(float(loss) - float(plain_loss)) > 1e-4
    same = dict(plain, position_ids=jnp.broadcast_to(jnp.arange(32), (3, 2, 32)))
    assert float(_grads(model, mesh, params, same)[0]) == float(plain_loss)
    with pytest.raises(ValueError, match="mrope_section"):
        llama.Transformer(llama.llama_debug(layer_pattern="*D", num_layers=1)).init(
            jax.random.PRNGKey(0), plain["inputs"][:, :8], same["position_ids"][:, :, :8])
