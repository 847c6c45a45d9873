"""SDAR-30B-A3B-Chat: block-diffusion training in the program (a noisy and
a clean stream through one trunk under the block-diffusion mask, a 1/t
weighted loss over masked positions from noise that is a pure function of
the batch, a renormalised softmax router over experts of which a share is
held) against the benchmark's plain reference at a small size on the CPU,
in float32 with seeded weights (the Pallas kernels' own tests are in
tests/test_flash_attention.py); what each stream may and may not see; the shares
of an expert layer against the whole layer; two replicas under Managers;
the presets and ``train_hsdp.py --model sdar_moe``."""

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
from benchmark.tests import test_sdar_reference as _reference_tests
from torchft_tpu.coordination import LighthouseServer
from torchft_tpu.models import llama, sdar_30b_a3b, sdar_moe_debug
from torchft_tpu.models.llama import MoEMLP, block_diffusion_attention
from torchft_tpu.parallel import auto_mesh
from torchft_tpu.parallel.train import (
    TrainState,
    build_model,
    diffusion_streams,
    init_train_state,
    make_eval_step,
    make_grad_step,
    make_train_step,
    state_shardings,
)
from tests.harness_controls import dead_leaf, shared_check
from tests.test_ft_step import two_replicas

adapter = cells.arch_module("sdar_moe", "adapter")
reference = cells.arch_module("sdar_moe", "reference")
tiny = _reference_tests.tiny

# The benchmark's own tests of this architecture (benchmark/tests is not in
# tier-1's path), collected here under their own names, no body copied.
for _name, _obj in vars(_reference_tests).items():
    if _name.startswith("test_") and callable(_obj):
        globals()[_name] = _obj


def test_the_harness_check_passes_and_float8_a_dropped_weight_and_a_dead_leaf_fail(tmp_path):  # noqa: F811
    """benchmark/tests/test_sdar_reference.py's test of this name on ONE
    compiled sample (``tests/harness_controls.py``; there every control
    traces and compiles the whole check again): worker.reference_check as
    the chip run makes it, at a small size in float32; then the same check
    with a planted fault handed to it in the system's place: the reference
    computed in float8, or without the 1/t, or with one leaf's gradient
    left at zero. Each comes out not correct through the harness's own
    comparison, by one of the reference's two limits; the reference in
    bfloat16 reads under float8 on both (at 64 wide its loss is over the
    limit sized at 2,048 wide, so ``ok`` is not asked of it here)."""
    cell = cells.load_cell("w", _reference_tests._tiny_table(tmp_path, tiny()))
    cell.mix.update(batch=1, seq=48)
    check = shared_check(cell, 48)
    out = check.sound
    assert out["ok"] and out["grad_rel_l2_worst"] < 1e-3 and out["loss_rel_diff"] < 1e-5
    grad_tol, loss_tol = reference.GRAD_REL_L2_TOL, reference.LOSS_REL_TOL
    assert (out["grad_rel_l2_tol"], out["loss_rel_tol"]) == (grad_tol, loss_tol)
    assert grad_tol < 1.0  # a leaf whose gradient never moves reads 1.0
    unweighted = check.control(check.departed(weigh_by_t=False))
    assert not unweighted["ok"] and unweighted["loss_rel_diff"] > loss_tol
    fp8 = check.control(check.departed(operand_dtype=jnp.float8_e4m3fn))
    bf16 = check.control(check.departed(operand_dtype=jnp.bfloat16))
    assert not fp8["ok"] and (
        fp8["grad_rel_l2_worst"] > grad_tol or fp8["loss_rel_diff"] > loss_tol)
    assert fp8["grad_rel_l2_worst"] > bf16["grad_rel_l2_worst"] > 1e-3
    assert bf16["grad_rel_l2_worst"] < grad_tol and bf16["loss_rel_diff"] < fp8["loss_rel_diff"]
    loss, grads = check.kept["reference"]
    dead = check.control((loss, dead_leaf(grads, "layers_1", "mlp", "router", "kernel")))
    assert not dead["ok"] and dead["loss_rel_diff"] == 0.0
    assert dead["grad_rel_l2_worst"] == pytest.approx(1.0)
    assert dead["grad_rel_l2_worst_leaf"] == "['layers_1']['mlp']['router']['kernel']"


def test_the_cell_is_found_by_its_arch_key_with_its_metrics(monkeypatch):
    """The benchmark's test of this name pins the schedule the cell compiled
    while every call ran tiles of 512 (288 a head; its file is a
    ``benchmark`` PR's to restate: PERF.md section 7): run here with the
    tiles held to 512, and the schedule the kernels now choose beside it."""
    monkeypatch.setattr(
        llama, "block_diffusion_attention",
        lambda cfg, rows: block_diffusion_attention(
            dataclasses.replace(cfg, flash_block_q=512, flash_block_k=512), rows),
    )
    _reference_tests.test_the_cell_is_found_by_its_arch_key_with_its_metrics()
    cfg = adapter.model_config(cells.load_cell("sdar-raw").config, 8192)
    assert block_diffusion_attention(cfg, 16384) == ("flash", pytest.approx(
        (8192 * 8192 + 8192 * 4) / (80 * 1024 * 1024)))


def _data(vocab, batch, seq, seed=1):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (batch, seq + 1), 0, vocab)
    return {"inputs": toks[:, :-1], "targets": toks[:, 1:],
            "mask": jnp.ones((batch, seq), jnp.int32)}


def _setup(c, seq, batch=2, seed=0, **cfg_overrides):
    cfg = dataclasses.replace(adapter.model_config(c, seq), remat=False, **cfg_overrides)
    mesh = auto_mesh(1, devices=jax.devices()[:1])
    model = build_model(cfg, mesh)
    data = _data(c["vocab_size"], batch, seq, seed + 1)
    # as the harness does it: parameters from ``init`` on one plain stream
    params = model.init(jax.random.PRNGKey(seed), data["inputs"])["params"]
    return model, mesh, params, data


def _leaf_errors(got, want):
    errs = jax.tree_util.tree_map(
        lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)), got, want
    )
    return {jax.tree_util.keystr(k): v for k, v in jax.tree_util.tree_leaves_with_path(errs)}


# -- (b) the system against the reference -------------------------------------


@pytest.mark.parametrize("seq,index,attn,aux", [
    (64, 1, "flash", 0.001), (32, 0, "dense", 0.0), (16, 3, "dense", 0.01)])
def test_loss_and_every_gradient_match_the_reference(seq, index, attn, aux, caplog):
    """Through ``make_grad_step`` on a plain inputs/targets/mask batch;
    the chip's share the first, a middle and the last; the kernels
    (interpreted, two tiles a stream) and the dense fallback."""
    c = tiny(expert_parallel_index=index, router_aux_loss_coef=aux,
             run={"attn_impl": attn, "compute_dtype": "float32", "param_dtype": "float32"})
    with caplog.at_level(logging.INFO, logger="torchft_tpu.models.llama"):
        llama._ATTN_NOTED.clear()
        model, mesh, params, data = _setup(
            c, seq, flash_min_seq=64, flash_block_q=32, flash_block_k=32)
        sh = state_shardings(model, mesh, (2, seq))
        with jax.default_matmul_precision("highest"):
            loss, grads = make_grad_step(model, mesh, sh)(params, data)
    assert f"asked={attn}/block_diffusion traced={attn}/block_diffusion seq={2 * seq}" in caplog.text
    loss_ref, grads_ref = jax.jit(lambda p, b: reference.loss_and_grads(p, b, c))(params, data)
    assert float(loss) == pytest.approx(float(loss_ref), rel=1e-5)
    errs = _leaf_errors(grads, grads_ref)
    assert len(errs) == 27 and max(errs.values()) < 2e-4, errs
    # the reference without its 1/t is another loss: the weight is in the program
    off, _ = jax.jit(lambda p, b: reference.loss_and_grads(p, b, c, weigh_by_t=False))(params, data)
    assert abs(float(off) - float(loss)) > 0.1 * float(loss)


# -- (c) what each stream sees -------------------------------------------------


def test_the_streams_see_what_block_diffusion_lets_them():
    """The clean stream's hidden states of block j depend on clean blocks
    <= j alone; a noisy block's on its own noisy tokens and clean blocks
    < j alone."""
    cfg = sdar_moe_debug(dtype=jnp.float32, experts_held=None)  # every expert: all of the layer
    model = build_model(cfg, None)
    length, b, j = 32, cfg.block_length, 3
    x0 = jax.random.randint(jax.random.PRNGKey(0), (1, length), 0, 250)
    x_t = jnp.where(jax.random.bernoulli(jax.random.PRNGKey(1), 0.5, x0.shape), 255, x0)
    params = model.init(jax.random.PRNGKey(2), x0)["params"]

    def hidden(x_t, x0):
        h = model.apply({"params": params}, jnp.concatenate([x_t, x0], axis=1),
                        return_hidden=True)
        return h[:, :length], h[:, length:]

    block = slice(j * b, (j + 1) * b)
    noisy, clean = hidden(x_t, x0)
    moved = lambda a, b_: float(jnp.abs(a - b_).max())  # noqa: E731
    # all of x_t changed, and the clean tokens after block j: clean block j stands
    _, clean2 = hidden((x_t + 1) % 250, x0.at[:, (j + 1) * b :].add(1))
    assert moved(clean[:, : (j + 1) * b], clean2[:, : (j + 1) * b]) == 0.0
    assert moved(clean[:, (j + 1) * b :], clean2[:, (j + 1) * b :]) > 1e-3
    # clean tokens of blocks >= j changed, and every OTHER noisy block: noisy block j stands
    other = x_t.at[:, : j * b].add(1).at[:, (j + 1) * b :].add(1) % 250
    noisy2, _ = hidden(other, x0.at[:, j * b :].add(1))
    assert moved(noisy[:, block], noisy2[:, block]) == 0.0
    # ... and it does follow its own noisy tokens and the clean blocks before it
    noisy3, _ = hidden(x_t.at[:, j * b].add(1), x0)
    noisy4, _ = hidden(x_t, x0.at[:, (j - 1) * b].add(1))
    assert moved(noisy[:, block], noisy3[:, block]) > 1e-3
    assert moved(noisy[:, block], noisy4[:, block]) > 1e-3
    # both streams sit at rotary positions 0..L-1: given as such, nothing moves
    at = jnp.tile(jnp.arange(length), 2)[None]
    h = model.apply({"params": params}, jnp.concatenate([x_t, x0], axis=1), at,
                    return_hidden=True)
    assert moved(h[:, :length], noisy) == 0.0


# -- (d) the noise -------------------------------------------------------------


def test_the_noise_is_a_pure_function_of_the_batch_and_follows_the_schedule():
    c = tiny(diffusion_t_min=0.2, diffusion_t_max=0.8)
    model, mesh, params, data = _setup(c, 64, batch=4)
    sh = state_shardings(model, mesh, (4, 64))
    step = make_grad_step(model, mesh, sh, with_metrics=True)
    (loss, metrics), grads = step(params, data)
    (again, _), grads2 = step(params, data)
    assert float(loss) == float(again)
    assert all(jnp.array_equal(a, b) for a, b in zip(
        jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(grads2)))
    assert "targets" in data  # not read: any targets give the same loss
    (same, _), _ = step(params, dict(data, targets=jnp.zeros_like(data["targets"])))
    assert float(same) == float(loss)
    # another batch draws other noise; the eval step and the reference the same
    other = dict(data, inputs=data["inputs"].at[0, 0].add(1))
    (l_other, m_other), _ = step(params, other)
    assert float(m_other["diffusion_masked_share"]) != float(metrics["diffusion_masked_share"])
    l_ref, _ = jax.jit(lambda p, b: reference.loss_and_grads(p, b, c))(params, other)
    assert float(l_other) == pytest.approx(float(l_ref), rel=1e-5)
    assert float(make_eval_step(model, mesh, sh)(params, other)) == pytest.approx(
        float(l_other), rel=1e-6)
    # the schedule: t a block in [t_min, t_max), a position masked where u < t,
    # the weight 1/t there and 0 elsewhere, the mask token in x_t
    cfg = model.cfg
    both, weights, masked = diffusion_streams(cfg, data["inputs"], data["mask"])
    x_t, x0 = both[:, :64], both[:, 64:]
    assert jnp.array_equal(x0, data["inputs"])
    assert jnp.array_equal(x_t, jnp.where(masked, 255, data["inputs"]))
    t = jnp.where(masked, 1.0 / jnp.where(masked, weights, 1.0), jnp.nan)
    per_block = t.reshape(4, 16, 4)
    assert float(jnp.nanmin(t)) >= 0.2 and float(jnp.nanmax(t)) < 0.8 + 1e-6
    spread = jnp.nanmax(per_block, axis=-1) - jnp.nanmin(per_block, axis=-1)
    assert float(jnp.nanmax(spread)) == 0.0  # one t a block
    assert float(metrics["diffusion_masked_share"]) == pytest.approx(float(masked.mean()))
    assert jnp.array_equal(masked, reference.noise(data, c)[1])
    # half the data positions count where half the mask is off
    half = dict(data, mask=data["mask"].at[:, 32:].set(0))
    (_, m_half), _ = step(params, half)
    assert float(m_half["diffusion_masked_share"]) == pytest.approx(float(masked[:, :32].mean()))
    # over many blocks the masked share is the schedule's mean, (t_min + t_max) / 2
    big = jax.random.randint(jax.random.PRNGKey(3), (8, 4096), 0, 255)
    _, _, many = diffusion_streams(cfg, big, jnp.ones_like(big))
    assert float(many.mean()) == pytest.approx(0.5, abs=0.01)
    with pytest.raises(ValueError, match="do not divide"):
        diffusion_streams(cfg, big[:, :30], jnp.ones((8, 30), jnp.int32))


def test_the_train_step_reports_both_counters_and_accumulates():
    model, mesh, params, data = _setup(tiny(), 32, batch=4)
    state, sh = init_train_state(model, mesh, jax.random.PRNGKey(0), (4, 32))
    new, metrics = make_train_step(model, mesh, sh, donate=False)(state, data)
    assert {"loss", "grad_norm", "diffusion_masked_share", "bd_kept_share", "moe_held_share",
            "moe_dropped", "router_aux"} <= set(metrics)
    assert float(metrics["bd_kept_share"]) == pytest.approx((32 * 32 + 32 * 4) / 64 ** 2)
    assert float(metrics["moe_dropped"]) == 0.0 and int(new.step) == 1
    # each microbatch draws the noise of its own tokens
    two = make_train_step(model, mesh, sh, donate=False, accum_steps=2)
    _, m2 = two(state, data)
    _, m2_again = two(state, dict(data))
    assert float(m2["loss"]) == float(m2_again["loss"]) and np.isfinite(float(m2["loss"]))
    assert float(m2["loss"]) != float(metrics["loss"])
    assert float(m2["bd_kept_share"]) == float(metrics["bd_kept_share"])


# -- (e) the expert layer ------------------------------------------------------


def test_the_eight_shares_add_up_to_the_whole_layer_under_renormalised_gates():
    """Eight chips hold two experts each of one layer's sixteen. The parts
    the eight compute are the uncut reference layer, whose gates are the
    chosen probabilities over their sum."""
    whole = tiny(num_experts=16, expert_parallel_chips=1, expert_parallel_index=0)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, whole["hidden_size"]))
    layer = MoEMLP(adapter.model_config(whole, 32))
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    m = x.reshape(-1, whole["hidden_size"])
    with jax.default_matmul_precision("highest"):
        want, balance = reference.experts(m, params, whole, lambda a: a)
        total, held_share = jnp.zeros_like(want), 0.0
        for index in range(8):
            part = MoEMLP(adapter.model_config(
                tiny(num_experts=2, expert_parallel_chips=8, expert_parallel_index=index), 32))
            own = dict(params, **{
                k: params[k][2 * index : 2 * index + 2]
                for k in ("experts_gate", "experts_up", "experts_down")})
            out, sown = part.apply({"params": own}, x, mutable=["intermediates"])
            sown = sown["intermediates"]
            total = total + out.reshape(want.shape)
            held_share += float(sown["moe_held_share"][0])
            assert float(sown["moe_dropped"][0]) == 0.0
            assert float(sown["router_aux"][0]) == pytest.approx(float(balance), rel=1e-5)
    assert jnp.allclose(total, want, rtol=1e-4, atol=1e-5)
    assert held_share == pytest.approx(1.0) and float(jnp.linalg.norm(want)) > 0.1
    # renormalised: not OLMoE's layer, whose gates are the softmax's own values
    plain = MoEMLP(dataclasses.replace(adapter.model_config(whole, 32), norm_topk_prob=False))
    assert not jnp.allclose(plain.apply({"params": params}, x).reshape(want.shape), want,
                            rtol=1e-2, atol=1e-3)


# -- (h) two replicas under Managers --------------------------------------------


@pytest.mark.timeout(300)
def test_two_replicas_fed_one_batch_commit_bitwise_equal_parameters():
    """No RNG in the state and none in the loop (``FTStep``): two replicas that
    see the same tokens draw the same noise, compute the same loss and, after the
    Managers' allreduce and commit, hold the same parameters bit for bit."""
    assert {f.name for f in dataclasses.fields(TrainState)} == {"step", "params", "opt_state"}
    (losses0, leaves0), (losses1, leaves1) = two_replicas(sdar_moe_debug, "sdar")
    assert losses0 == losses1 and len(losses0) == 2 and losses0[0] != losses0[1]
    assert all(np.array_equal(a, b) for a, b in zip(leaves0, leaves1))
    cfg = sdar_moe_debug(dtype=jnp.float32)
    start = build_model(cfg, None).init(
        jax.random.PRNGKey(0), jnp.zeros((2, 32), jnp.int32))["params"]
    assert not all(np.array_equal(a, np.asarray(b)) for a, b in zip(
        leaves0, jax.tree_util.tree_leaves(start)))  # the commits moved them


# -- the presets and the entry point ---------------------------------------------


def test_the_presets():
    cfg = sdar_30b_a3b()
    assert (cfg.hidden_size, cfg.num_layers, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.intermediate_size, cfg.vocab_size, cfg.max_seq_len) == (
        2048, 48, 32, 4, 128, 768, 151936, 32768)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.norm_topk_prob, cfg.router_score,
            cfg.qk_norm, cfg.tie_embeddings, cfg.experts_held, cfg.rope_theta, cfg.norm_eps,
            cfg.objective, cfg.block_length, cfg.layer_pattern) == (
        128, 8, True, "softmax", "head", False, None, 1e6, 1e-6, "block_diffusion", 4, "*E" * 48)
    published, small = _reference_tests.PUBLISHED, sdar_moe_debug()
    cut = adapter.model_config(published, 8192)
    assert cut.layer_pattern == cfg.layer_pattern[:12] and small.layer_pattern == "*E*E"
    same = ("hidden_size", "num_heads", "num_kv_heads", "head_dim", "intermediate_size",
            "rope_theta", "norm_eps", "qk_norm", "num_experts", "num_experts_per_tok",
            "norm_topk_prob", "router_aux_coef", "objective", "block_length",
            "diffusion_t_min", "diffusion_t_max", "tie_embeddings",
            "expert_capacity_factor")
    assert all(getattr(cut, k) == getattr(cfg, k) for k in same)
    assert (small.objective, small.experts_held, small.mask_token_id, small.vocab_size) == (
        "block_diffusion", (0, 4), 255, 256)
    with pytest.raises(ValueError, match="objective"):
        model = build_model(dataclasses.replace(small, objective="diffusion"), None)
        state = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
        make_grad_step(model, auto_mesh(1, devices=jax.devices()[:1]),
                       state_shardings(model, auto_mesh(1, devices=jax.devices()[:1]), (1, 8)))(
            state["params"], _data(256, 1, 8))
    with pytest.raises(ValueError, match="two-stream mask"):
        llama.Transformer(
            dataclasses.replace(small, attn_impl="ring", attn_fn=lambda *a: a[0])
        ).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


@pytest.mark.timeout(300)
def test_train_hsdp_runs_the_small_preset(tmp_path):
    """``train_hsdp.py --model sdar_moe``: one group, the Manager in the
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
            [sys.executable, "train_hsdp.py", "--model", "sdar_moe", "--steps", "3",
             "--batch", "2", "--seq", "32", "--result-dir", str(tmp_path / "out")],
            cwd=root, env=env, capture_output=True, text=True, timeout=240,
        )
    finally:
        lighthouse.shutdown()
    assert proc.returncode == 0, proc.stderr[-3000:]
    steps = [line for line in proc.stderr.splitlines() if " loss " in line]
    assert len(steps) == 3 and all("diffusion_masked_share" in line for line in steps), steps
    assert "asked=dense/block_diffusion traced=dense/block_diffusion seq=64" in proc.stderr
    assert cells.load_json(str(tmp_path / "out" / "group0.json"))["final_step"] == 3
