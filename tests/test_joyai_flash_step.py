"""JoyAI-LLM-Flash, the second half of ``tests/test_joyai_flash.py`` (a
file of its own so that the suite's workers share the time): the fused
step's counters, the sharding rules on a mesh, two replicas fed one batch,
the harness's own check with its controls and the cell end to end on a tiny
table (the benchmark's own tests, collected here), the presets and
``train_hsdp.py --model joyai_flash``."""

import dataclasses
import logging
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells
from benchmark.tests import test_joyai_reference as _reference_tests
from torchft_tpu.coordination import LighthouseServer
from torchft_tpu.models import PRESETS, MLAConfig, joyai_flash_debug, joyai_llm_flash, llama
from torchft_tpu.models.llama import MoEMLP, apply_rope, rope_table
from torchft_tpu.models.mla import (
    LatentAttention,
    apply_rope_interleaved,
)
from torchft_tpu.parallel import auto_mesh, make_mesh
from torchft_tpu.parallel.sharding import param_specs
from torchft_tpu.parallel.train import (
    build_model,
    init_train_state,
    make_grad_step,
    make_train_step,
    state_shardings,
)
from tests.harness_controls import dead_leaf, shared_check
from tests.test_ft_step import two_replicas
from tests.test_nemotron_h import _tiny_table
from tests.test_sdar_moe import _data, _leaf_errors
from tests.test_joyai_flash import (  # noqa: F401
    _setup,
    adapter,
    tiny,
)


def test_the_train_step_reports_the_counters_moves_the_biases_and_accumulates():
    model, mesh, params, data = _setup(tiny(), 32, batch=4)
    state, sh = init_train_state(model, mesh, jax.random.PRNGKey(0), (4, 32))
    new, metrics = make_train_step(model, mesh, sh, donate=False)(state, data)
    assert {"loss", "loss_main", "loss_mtp", "grad_norm", "moe_held_share", "moe_dropped",
            "router_bias_abs_max"} <= set(metrics) and "moe_load" not in metrics
    assert float(metrics["loss"]) == pytest.approx(
        float(metrics["loss_main"]) + 0.3 * float(metrics["loss_mtp"]), rel=1e-6)
    assert float(metrics["moe_dropped"]) == 0.0 and int(new.step) == 1
    assert float(metrics["router_bias_abs_max"]) == pytest.approx(1e-3)
    # the module's own selection bias moves with the stack's
    for name in ("layers_3", "layers_5"):
        assert float(jnp.abs(new.params[name]["mlp"]["router_bias"]).max()) == pytest.approx(1e-3)
    assert float(jnp.abs(new.params["mtp_0"]["layers_1"]["mlp"]["router_bias"]).max()) == (
        pytest.approx(1e-3))
    _, m2 = make_train_step(model, mesh, sh, donate=False, accum_steps=2)(state, data)
    assert np.isfinite(float(m2["loss"])) and float(m2["loss_mtp"]) > 0


def test_the_rules_name_the_new_leaves_and_a_sharded_mesh_computes_the_same_step():
    """fsdp=2 x tp=2 on four virtual devices against one device."""
    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    cfg = joyai_flash_debug(dtype=jnp.float32)
    shapes = jax.eval_shape(lambda: build_model(cfg, None).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    specs = param_specs(shapes)
    attn, module = specs["layers_2"]["attn"], specs["mtp_0"]
    P = jax.sharding.PartitionSpec
    assert attn["wq_a"]["kernel"] == attn["wkv_a"]["kernel"] == P("fsdp", None)
    assert attn["wq_b"]["kernel"] == attn["wkv_b"]["kernel"] == P("fsdp", "tp", None)
    assert attn["wo"]["kernel"] == P("tp", None, "fsdp")
    assert attn["q_norm"]["scale"] == attn["kv_norm"]["scale"] == P()
    assert module["eh_proj"]["kernel"] == P("tp", "fsdp")
    assert module["hnorm"]["scale"] == module["enorm"]["scale"] == P()
    assert module["layers_0"]["attn"]["wq_b"]["kernel"] == P("fsdp", "tp", None)
    data = _data(cfg.vocab_size, 4, 64)
    seen = []
    for mesh in (auto_mesh(1, devices=jax.devices()[:1]), make_mesh(fsdp=2, tp=2)):
        model = build_model(cfg, mesh)
        state, sh = init_train_state(model, mesh, jax.random.PRNGKey(0), (4, 64))
        new, metrics = make_train_step(model, mesh, sh, donate=False)(state, data)
        seen.append([float(metrics[k]) for k in ("loss", "loss_mtp", "grad_norm",
                                                  "moe_held_share")])
    # a sharded contraction adds in another order; an assignment may flip at a tie
    assert seen[0][0] == pytest.approx(seen[1][0], rel=1e-4)
    assert seen[0][1] == pytest.approx(seen[1][1], rel=1e-4)
    assert seen[0][2] == pytest.approx(seen[1][2], rel=2e-3)
    assert seen[0][3] == pytest.approx(seen[1][3], abs=2 / 768)


@pytest.mark.timeout(300)
def test_two_replicas_fed_one_batch_commit_bitwise_equal_parameters():
    """``FTStep`` over the split step: the loads of every expert layer, the
    module's among them, ride the allreduce beside the gradients, and both
    replicas hold the same parameters and selection biases bit for bit."""
    (losses0, leaves0), (losses1, leaves1) = two_replicas(joyai_flash_debug, "joyai")
    assert losses0 == losses1 and len(losses0) == 2 and losses0[0] != losses0[1]
    assert all(np.array_equal(a, b) for a, b in zip(leaves0, leaves1))


def test_the_presets():
    cfg = joyai_llm_flash()
    assert (cfg.hidden_size, cfg.num_layers, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.intermediate_size, cfg.dense_intermediate_size, cfg.vocab_size,
            cfg.max_seq_len) == (2048, 40, 32, 32, 64, 768, 7168, 129280, 131072)
    assert cfg.mla == MLAConfig(1536, 512, 128, 64, 128) and cfg.mla.qk_head_dim == 192
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.router_score, cfg.routed_scaling,
            cfg.shared_expert_size, cfg.tie_embeddings, cfg.experts_held, cfg.rope_theta,
            cfg.norm_eps, cfg.mtp_layers, llama.MTP_BLOCK, cfg.layer_pattern) == (
        256, 8, "sigmoid", 2.5, 768, False, None, 3.2e7, 1e-6, 1, "*E", "*D" + "*E" * 39)
    published, small = _reference_tests.PUBLISHED, joyai_flash_debug()
    cut = adapter.model_config(published, 8192)
    assert cut.layer_pattern == cfg.layer_pattern[:12] and small.layer_pattern == "*D*E*E"
    same = ("hidden_size", "num_heads", "num_kv_heads", "head_dim", "intermediate_size",
            "dense_intermediate_size", "rope_theta", "norm_eps", "mla", "num_experts",
            "num_experts_per_tok", "router_score", "routed_scaling", "gate_eps",
            "shared_expert_size", "router_aux_coef", "router_bias_update_rate", "mtp_layers",
            "mtp_loss_coef", "tie_embeddings", "expert_capacity_factor")
    assert all(getattr(cut, k) == getattr(cfg, k) for k in same)
    assert PRESETS["joyai_flash"] is joyai_flash_debug
    assert (small.experts_held, small.mtp_layers, small.vocab_size) == ((0, 4), 1, 256)


@pytest.mark.timeout(300)
def test_train_hsdp_runs_the_small_preset(tmp_path):
    """``train_hsdp.py --model joyai_flash``: one group, the Manager in the
    loop, three committed steps on the CPU."""
    lighthouse = LighthouseServer(
        bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=20000, quorum_tick_ms=50)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", TORCHFT_LIGHTHOUSE=lighthouse.address(),
               REPLICA_GROUP_ID="0", NUM_REPLICA_GROUPS="1",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("XLA_FLAGS", None)  # one device: the preset's mesh of one
    try:
        proc = subprocess.run(
            [sys.executable, "train_hsdp.py", "--model", "joyai_flash", "--steps", "3",
             "--batch", "2", "--seq", "32", "--result-dir", str(tmp_path / "out")],
            cwd=root, env=env, capture_output=True, text=True, timeout=240,
        )
    finally:
        lighthouse.shutdown()
    assert proc.returncode == 0, proc.stderr[-3000:]
    steps = [line for line in proc.stderr.splitlines() if " loss " in line]
    assert len(steps) == 3 and all("loss_mtp" in line for line in steps), steps
    assert "asked=dense/mla traced=dense/mla seq=32" in proc.stderr
    assert cells.load_json(str(tmp_path / "out" / "group0.json"))["final_step"] == 3


# -- the benchmark's own two long tests of this architecture ----------------------------

# The cell end to end on a tiny table (``run.py`` as a subprocess), as
# benchmark/tests/test_joyai_reference.py has it.
test_the_cell_runs_end_to_end_on_a_tiny_table = (
    _reference_tests.test_the_cell_runs_end_to_end_on_a_tiny_table)


@pytest.mark.timeout(600)
def test_the_harness_check_passes_and_float8_bf16_parameters_and_a_dead_leaf_fail(tmp_path):
    """benchmark/tests/test_joyai_reference.py's test of this name, on ONE
    compiled sample (``tests/harness_controls.py``; there every control
    traces and compiles the whole check again): worker.reference_check as
    the chip run makes it, at a small size in float32; then the same check
    with a planted fault handed to it in the system's place: the reference
    computed in float8, the module's loss left out, or one leaf's gradient
    left at zero. Each comes out not correct through the harness's own
    comparison, by one of the reference's two limits; the reference in
    bfloat16 reads under float8 on both."""
    reference = cells.arch_module("joyai_flash", "reference")
    cell = cells.load_cell("w", _tiny_table(tmp_path, tiny()))
    cell.mix.update(batch=1, seq=48)
    check = shared_check(cell, 48)
    out = check.sound
    assert out["ok"] and out["grad_rel_l2_worst"] < 1e-3 and out["loss_rel_diff"] < 1e-5
    grad_tol, loss_tol = reference.GRAD_REL_L2_TOL, reference.LOSS_REL_TOL
    assert (out["grad_rel_l2_tol"], out["loss_rel_tol"]) == (grad_tol, loss_tol)
    # A leaf whose gradient never moves reads 1.0: the limit lies under it,
    # and the median leaf's (stated for the harness's owed edit) under that.
    assert reference.GRAD_REL_L2_MEDIAN_TOL < grad_tol < 1.0

    no_module = check.control(check.departed(config=dict(cell.config, mtp_loss_coef=0.0)))
    assert not no_module["ok"] and no_module["loss_rel_diff"] > 0.1
    fp8 = check.control(check.departed(operand_dtype=jnp.float8_e4m3fn))
    bf16 = check.control(check.departed(operand_dtype=jnp.bfloat16))
    assert not fp8["ok"] and (
        fp8["grad_rel_l2_worst"] > grad_tol or fp8["loss_rel_diff"] > loss_tol)
    assert fp8["grad_rel_l2_worst"] > bf16["grad_rel_l2_worst"] > 1e-3
    assert bf16["grad_rel_l2_worst"] < grad_tol and bf16["loss_rel_diff"] < fp8["loss_rel_diff"]

    loss, grads = check.kept["reference"]
    for path in (("layers_2", "attn", "wkv_b", "kernel"), ("mtp_0", "eh_proj", "kernel")):
        out = check.control((loss, dead_leaf(grads, *path)))
        assert not out["ok"] and out["loss_rel_diff"] == 0.0
        assert out["grad_rel_l2_worst"] == pytest.approx(1.0)
        assert out["grad_rel_l2_worst_leaf"] == "".join(f"['{k}']" for k in path)
