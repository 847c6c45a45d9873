"""Ouro's looped language model, the second half of ``tests/test_ouro.py``
(a file of its own so that the suite's workers share the time): the fused
step's counters with and without accumulation and through the split step,
the harness's own check at a small size with its controls (ONE compiled
sample for the sound case and every control), the builder's long comparison
at a small size, the presets and ``train_hsdp.py --model ouro_debug``."""

import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from benchmark import cells
from torchft_tpu.coordination import LighthouseServer
from torchft_tpu.models import PRESETS, llama
from torchft_tpu.models.llama import ouro_2_6b, ouro_debug
from torchft_tpu.parallel import auto_mesh
from torchft_tpu.parallel.train import (
    build_model,
    init_train_state,
    make_split_grad_step,
    make_train_step,
)
from tests.harness_controls import dead_leaf, shared_check
from tests.test_nemotron_h import _tiny_table
from tests.test_ouro import adapter, reference, tiny
from tests.test_sdar_moe import _data

LOOP_METRICS = {
    "loop_ce_1", "loop_ce_2", "loop_ce_3", "loop_ce_4",
    "loop_exit_step_mean", "loop_exit_entropy", "loop_p_last"}


def test_the_step_hands_on_the_loops_counters_and_accumulates_them():
    """``make_train_step`` returns the loop's metrics as scalars beside the
    loss (the raw trainer reads every one as a float); two microbatches
    give their means; the split step carries the same; the gate moves."""
    cfg = ouro_debug(dtype=jnp.float32)
    mesh = auto_mesh(1, devices=jax.devices()[:1])
    model = build_model(cfg, mesh)
    state, sh = init_train_state(model, mesh, jax.random.PRNGKey(0), (2, 32))
    data = _data(cfg.vocab_size, 2, 32)
    whole = make_train_step(model, mesh, sh, donate=False)
    new, metrics = whole(state, data)
    assert set(metrics) == LOOP_METRICS | {"loss", "grad_norm"}
    assert all(v.shape == () for v in metrics.values())
    assert 0.0 < float(metrics["loop_exit_entropy"]) < 1.3863
    assert 1.0 < float(metrics["loop_exit_step_mean"]) < 4.0
    gate, was = new.params["exit_gate"]["kernel"], state.params["exit_gate"]["kernel"]
    assert not jnp.array_equal(gate[:-1], was[:-1])
    assert float(was[-1, 0]) == 0.0 and float(gate[-1, 0]) != 0.0  # the bias, the last row
    halves = make_train_step(model, mesh, sh, donate=False, accum_steps=2)
    row = lambda i: {k: v[i : i + 1] for k, v in data.items()}  # noqa: E731
    alone = [whole(state, row(i))[1] for i in range(2)]
    _, both = halves(state, data)
    for name in LOOP_METRICS | {"loss"}:
        assert float(both[name]) == pytest.approx(
            (float(alone[0][name]) + float(alone[1][name])) / 2, rel=1e-5), name
    loss, split, (grads, loads) = make_split_grad_step(model, mesh, sh)(state.params, data)
    assert set(split) == LOOP_METRICS and loads is None
    assert float(loss) == pytest.approx(float(metrics["loss"]), rel=1e-6)
    assert float(jnp.linalg.norm(grads["exit_gate"]["kernel"])) > 0.0


# -- the harness's check and its controls ----------------------------------------------------


@pytest.fixture(scope="module")
def checked(tmp_path_factory):
    """``worker.reference_check`` as the chip run makes it, at a small size
    in float32, run ONCE: the sound case. Its sample, the system's and the
    reference's results are kept, and a control is the same check with
    another result handed to it in the system's place
    (``tests/harness_controls.py``)."""
    c = tiny(num_hidden_layers=1, layer_types=["full_attention"])
    cell = cells.load_cell("w", _tiny_table(tmp_path_factory.mktemp("ouro"), c))
    cell.mix.update(batch=1, seq=32)
    return shared_check(cell, 32)


def test_the_harness_check_passes_at_a_small_size(checked):
    out = checked.sound
    assert out["ok"] and out["grad_rel_l2_worst"] < 1e-3 and out["loss_rel_diff"] < 1e-5
    assert (out["grad_rel_l2_tol"], out["loss_rel_tol"]) == (
        reference.GRAD_REL_L2_TOL, reference.LOSS_REL_TOL)
    assert out["tokens"] == 32 and reference.GRAD_REL_L2_TOL < 1.0
    # handing the check its own system's result back is the sound case again
    again = checked.control(checked.kept["system"])
    assert again["ok"] and again["grad_rel_l2_worst"] == out["grad_rel_l2_worst"]


@pytest.mark.parametrize("departure", ["unshared", "norm_outside"])
def test_the_check_refuses_a_departure(checked, departure):
    """The reference computing the other model, in the system's place."""
    out = checked.control(checked.departed(departure=departure))
    assert not out["ok"] and out["grad_rel_l2_worst"] > reference.GRAD_REL_L2_TOL


def test_the_check_refuses_a_dead_gate_and_reads_one_that_learns_from_the_entropy_alone(checked):
    """A gate whose gradient is zero reads 1.0 and fails whatever the limit
    under 1 is; a gate whose weights' cotangent is dropped (it then learns
    from the entropy term alone) is another gradient on the gate's leaf
    and on no other."""
    loss, grads = checked.kept["system"]
    dead = dead_leaf(grads, "exit_gate", "kernel")
    out = checked.control((loss, dead))
    assert not out["ok"] and out["loss_rel_diff"] < 1e-5
    assert out["grad_rel_l2_worst"] == pytest.approx(1.0)
    assert "exit_gate" in out["grad_rel_l2_worst_leaf"]
    entropy_only = checked.control(checked.departed(departure="gate_entropy_only"))
    assert "exit_gate" in entropy_only["grad_rel_l2_worst_leaf"]
    assert entropy_only["grad_rel_l2_worst"] > 0.05 and entropy_only["loss_rel_diff"] < 1e-5
    no_entropy = checked.control(checked.departed(departure="no_entropy"))
    assert not no_entropy["ok"] and no_entropy["loss_rel_diff"] > reference.LOSS_REL_TOL


def test_the_check_tells_float8_from_bfloat16(checked):
    """The reference with its matmul operands rounded, in the system's
    place: bfloat16 passes both limits, float8 reads above it on both and
    over the gradient's. (The chip's readings at the published widths set
    the limits: PERF.md section 6.)"""
    fp8 = checked.control(checked.departed(operand_dtype=jnp.float8_e4m3fn))
    bf16 = checked.control(checked.departed(operand_dtype=jnp.bfloat16))
    assert bf16["ok"] and 1e-4 < bf16["grad_rel_l2_worst"] < reference.GRAD_REL_L2_TOL
    assert fp8["grad_rel_l2_worst"] > 4 * bf16["grad_rel_l2_worst"]
    assert fp8["loss_rel_diff"] > bf16["loss_rel_diff"]
    assert not fp8["ok"]


def test_the_builders_long_comparison_at_a_small_size(tmp_path):
    """``tools/reference_compare.py`` as the chip run makes it at 8,192
    tokens: the cell's own model against the reference in query blocks,
    the gate's leaf read beside the worst; a departure handed in the
    system's place."""
    from tools import reference_compare

    c = tiny(num_hidden_layers=1, layer_types=["full_attention"])
    cell = cells.load_cell("w", _tiny_table(tmp_path, c))
    out = reference_compare.compare(cell, 32, 3000000001, query_block=16, leaves="exit_gate")
    assert out["ok"] and (out["tokens"], out["query_block"], out["compared"]) == (32, 16, "system")
    assert out["grad_rel_l2_worst"] < 1e-4 and out["loss_rel_diff"] < 1e-5
    assert sorted(out["leaf_readings"]) == ["['exit_gate']['kernel']"]
    assert max(out["leaf_readings"].values()) <= out["grad_rel_l2_worst"]
    off = reference_compare.compare(cell, 32, 3000000001, departure="unshared")
    assert off["compared"] == "reference under unshared" and not off["ok"]
    assert "leaf_readings" not in off


# -- the presets --------------------------------------------------------------------------


def test_the_presets():
    cfg = ouro_2_6b()
    assert (cfg.hidden_size, cfg.num_layers, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.intermediate_size, cfg.vocab_size) == (2048, 48, 16, 16, 128, 5632, 49152)
    assert cfg.layer_pattern == "*D" * 48 and cfg.norm_after_mixer == "both"
    assert (cfg.loop_steps, cfg.loop_entropy_coef) == (4, 0.05)
    assert PRESETS["ouro_2_6b"] is ouro_2_6b and PRESETS["ouro_debug"] is ouro_debug
    published = adapter.model_config(
        dict(cells.load_json(os.path.join(cells.HERE, "configs", "ouro-2.6b-l6t4.json")),
             num_hidden_layers=48, layer_types=["full_attention"] * 48), 65536)
    for f in dataclasses.fields(cfg):
        if f.name not in ("attn_impl",):  # the file's own run group
            assert getattr(published, f.name) == getattr(cfg, f.name), f.name
    # a plain model's stack is what it was: one visit a layer, no gate
    plain = llama.llama_debug()
    assert (plain.loop_steps, plain.loop_entropy_coef) == (1, 0.0)
    assert ouro_debug().loop_steps == 4 and ouro_debug().layer_pattern == "*D*D"


def test_the_loop_says_once_how_it_was_traced(caplog):
    llama._LOOP_NOTED.clear()
    toks = jnp.zeros((1, 8), jnp.int32)
    with caplog.at_level("INFO", logger="torchft_tpu.models.llama"):
        for _ in range(2):
            jax.eval_shape(lambda: llama.Transformer(ouro_debug()).init(jax.random.PRNGKey(0), toks))
    said = [r.getMessage() for r in caplog.records if r.getMessage().startswith("loop:")]
    assert said == ["loop: steps=4 layers=2 sublayers=4 traced=scan"]


@pytest.mark.timeout(300)
def test_train_hsdp_runs_the_debug_preset(tmp_path):
    """``train_hsdp.py --model ouro_debug``: one group, the Manager in the
    loop, two committed steps on the CPU, the loop's counters counted."""
    lighthouse = LighthouseServer(
        bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=20000, quorum_tick_ms=50)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", TORCHFT_LIGHTHOUSE=lighthouse.address(),
               REPLICA_GROUP_ID="0", NUM_REPLICA_GROUPS="1",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("XLA_FLAGS", None)  # one device: the preset's mesh of one
    try:
        proc = subprocess.run(
            [sys.executable, "train_hsdp.py", "--model", "ouro_debug", "--steps", "2",
             "--batch", "2", "--seq", "32", "--result-dir", str(tmp_path / "out")],
            cwd=root, env=env, capture_output=True, text=True, timeout=240,
        )
    finally:
        lighthouse.shutdown()
    assert proc.returncode == 0, proc.stderr[-3000:]
    steps = [line for line in proc.stderr.splitlines() if " loss " in line]
    assert len(steps) == 2 and "loop_exit_entropy" in steps[-1], steps
    assert cells.load_json(str(tmp_path / "out" / "group0.json"))["final_step"] == 2
