"""LFM2-8B-A1B: the program's stack of two sub-layers a published layer
(gated short convolutions, rotary attention with per-head QK norms, a
dense feed-forward, SiLU-gated experts of which a share is held under a
sigmoid router whose selection bias the step updates, a tied head)
against the benchmark's plain reference at a small size on the CPU, in
float32 with seeded weights; the shares of an expert layer against the
whole layer; the bias update's rule; the sharded mesh; the counts; the
adapter's refusals; the harness's own check."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark import cells, worker
from benchmark.tests import test_lfm2_reference as _reference_tests
from torchft_tpu.models import llama
from torchft_tpu.models.llama import (
    Attention,
    MoEMLP,
    ShortConvMixer,
    lfm2_8b_a1b,
    lfm2_moe_debug,
)
from torchft_tpu.parallel import auto_mesh, make_mesh
from torchft_tpu.parallel.train import (
    build_model,
    init_train_state,
    make_grad_step,
    make_train_step,
    state_shardings,
    update_router_bias,
)
from tests.harness_controls import shared_check

adapter = cells.arch_module("lfm2_moe", "adapter")
reference = cells.arch_module("lfm2_moe", "reference")
flops = cells.arch_module("lfm2_moe", "flops")
CONFIG_FILE = os.path.join(cells.HERE, "configs", "lfm2-8b-a1b-l5e8.json")
PUBLISHED = cells.load_json(CONFIG_FILE)
tiny = _reference_tests.tiny

# The benchmark's own tests of this architecture (benchmark/tests is not in
# tier-1's path), collected here under their own names, no body copied.
for _name, _obj in vars(_reference_tests).items():
    if _name.startswith("test_") and callable(_obj):
        globals()[_name] = _obj


def test_the_harness_check_passes_and_a_lower_precision_or_a_missing_norm_fails(tmp_path):  # noqa: F811
    """benchmark/tests/test_lfm2_reference.py's test of this name on ONE
    compiled sample (``tests/harness_controls.py``; there every control
    traces and compiles the whole check again): worker.reference_check as
    the chip run makes it, at a small size in float32; then the same check
    with the reference computed as another model or in another precision
    handed to it in the system's place: the harness's own comparison says
    not correct, by the reference's limits."""
    cell = cells.load_cell("w", _reference_tests._tiny_table(tmp_path, tiny()))
    cell.mix.update(batch=1, seq=48)
    check = shared_check(cell, 48)
    out = check.sound
    assert out["ok"] and out["grad_rel_l2_worst"] < 1e-3 and out["loss_rel_diff"] < 1e-5
    assert "router_bias" not in out["grad_rel_l2_worst_leaf"]
    assert reference.GRAD_REL_L2_MEDIAN_TOL < reference.GRAD_REL_L2_TOL == out["grad_rel_l2_tol"]
    unnormed = check.control(check.departed(per_head_norm=False))
    assert not unnormed["ok"] and unnormed["grad_rel_l2_worst"] > reference.GRAD_REL_L2_TOL
    fp8 = check.control(check.departed(operand_dtype=jnp.float8_e4m3fn))
    bf16 = check.control(check.departed(operand_dtype=jnp.bfloat16))
    assert fp8["grad_rel_l2_worst"] > bf16["grad_rel_l2_worst"] > 1e-3


def _setup(c, seq, batch=2, seed=0):
    cfg = dataclasses.replace(adapter.model_config(c, seq), remat=False)
    mesh = auto_mesh(1, devices=jax.devices()[:1])
    model = build_model(cfg, mesh)
    toks = jax.random.randint(jax.random.PRNGKey(seed + 1), (batch, seq + 1), 0, c["vocab_size"])
    data = {"inputs": toks[:, :-1], "targets": toks[:, 1:],
            "mask": jnp.ones((batch, seq), jnp.int32)}
    params = model.init(jax.random.PRNGKey(seed), data["inputs"])["params"]
    return model, mesh, params, data


def _reference(c, **options):
    return jax.jit(lambda p, b: reference.loss_and_grads(p, b, c, **options))


def _leaf_errors(got, want):
    errs = jax.tree_util.tree_map(
        lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)), got, want
    )
    return {jax.tree_util.keystr(k): v for k, v in jax.tree_util.tree_leaves_with_path(errs)}


def _randomise(params, seed=5):
    """Selection biases start at zero, norm scales and the taps at simple
    values: give each a value of its own so that a swapped one shows."""
    params = jax.tree_util.tree_map(lambda a: a, params)
    for i, layer in enumerate(params.values()):
        key = jax.random.PRNGKey(seed + i)
        if "router_bias" in layer.get("mlp", {}):
            layer["mlp"]["router_bias"] = 0.3 * jax.random.normal(
                key, layer["mlp"]["router_bias"].shape)
        if "attn" in layer:
            for name in ("q_norm", "k_norm"):
                layer["attn"][name]["scale"] = 1.0 + 0.3 * jax.random.normal(
                    jax.random.fold_in(key, len(name)), layer["attn"][name]["scale"].shape)
    return params


@pytest.mark.parametrize("seq,index,biased,aux", [
    (40, 1, False, 0.0), (64, 0, True, 0.0), (16, 3, True, 0.01)])
def test_loss_and_every_gradient_match_the_reference(seq, index, biased, aux):
    """Published layers 1-5 (pattern CD*ECECECE); the chip's share the
    first, a middle and the last; with and without a balance term."""
    c = tiny(expert_parallel_index=index, router_aux_loss_coef=aux)
    model, mesh, params, data = _setup(c, seq)
    if biased:
        params = _randomise(params)
    sh = state_shardings(model, mesh, (2, seq))
    with jax.default_matmul_precision("highest"):
        loss, grads = make_grad_step(model, mesh, sh)(params, data)
    loss_ref, grads_ref = _reference(c)(params, data)
    assert float(loss) == pytest.approx(float(loss_ref), rel=1e-5)
    errs = _leaf_errors(grads, grads_ref)
    bias = {k for k in errs if "router_bias" in k}
    assert len(bias) == 4  # no gradient reaches a selection bias: 0 / 0
    for both in (grads, grads_ref):
        assert all(not jnp.any(layer["mlp"]["router_bias"])
                   for layer in both.values() if "router_bias" in layer.get("mlp", {}))
    assert len(errs) == 53 and max(v for k, v in errs.items() if k not in bias) < 2e-4, errs


def test_the_short_convolution_is_the_reference_tap_for_tap():
    c = tiny()
    cfg = adapter.model_config(c, 24)
    mixer = ShortConvMixer(cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 24, c["hidden_size"]))
    params = mixer.init(jax.random.PRNGKey(0), x)["params"]
    assert params["conv_kernel"].shape == (3, c["hidden_size"])
    with jax.default_matmul_precision("highest"):
        got = mixer.apply({"params": params}, x)
        want = reference.short_conv(x, params, lambda a: a)
        assert jnp.allclose(got, want, rtol=1e-5, atol=1e-6)
        # Causal: what comes after a position does not reach it, and the
        # last tap sits on the position itself.
        later = x.at[:, 10:].set(0.0)
        assert jnp.allclose(mixer.apply({"params": params}, later)[:, :10], got[:, :10], atol=1e-6)
        only_last = dict(params, conv_kernel=params["conv_kernel"].at[:2].set(0.0))
        b, g, u = jnp.split(x @ params["in_proj"]["kernel"], 3, axis=-1)
        assert jnp.allclose(
            mixer.apply({"params": only_last}, x),
            (g * params["conv_kernel"][2] * b * u) @ params["out_proj"]["kernel"],
            rtol=1e-5, atol=1e-6,
        )


def test_the_per_head_norm_is_not_the_whole_projection_norm():
    """``qk_norm="head"``: one 16-vector for the queries and one for the
    keys, each head normalised alone, the reference's attention; ``True``
    keeps OLMoE's norm over the whole projection, another function."""
    c = tiny()
    cfg = adapter.model_config(c, 32)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 32, c["hidden_size"]))
    cos, sin = llama.rope_table(
        jnp.broadcast_to(jnp.arange(32), (2, 32)), cfg.head_dim, cfg.rope_theta, jnp.float32)
    attn = Attention(cfg)
    params = attn.init(jax.random.PRNGKey(0), x, cos, sin)["params"]
    assert params["q_norm"]["scale"].shape == params["k_norm"]["scale"].shape == (16,)
    params["q_norm"]["scale"] = 1.0 + 0.3 * jax.random.normal(jax.random.PRNGKey(7), (16,))
    with jax.default_matmul_precision("highest"):
        got = attn.apply({"params": params}, x, cos, sin)
        assert jnp.allclose(got, reference.attention(x, params, c, lambda a: a),
                            rtol=1e-4, atol=1e-5)
        assert not jnp.allclose(
            got, reference.attention(x, params, c, lambda a: a, per_head_norm=False), atol=1e-3)
    whole = Attention(dataclasses.replace(cfg, qk_norm=True))
    shapes = jax.eval_shape(lambda: whole.init(jax.random.PRNGKey(0), x, cos, sin))["params"]
    assert shapes["q_norm"]["scale"].shape == (64,) and shapes["k_norm"]["scale"].shape == (32,)


def _expert_layer(c, seq=32):
    cfg = dataclasses.replace(adapter.model_config(c, seq), remat=False)
    layer = MoEMLP(cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, seq, c["hidden_size"]))
    return layer, x


def test_the_gates_carry_the_published_epsilon_and_the_bias_only_chooses():
    c = tiny()
    layer, x = _expert_layer(c)
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    m = x.reshape(-1, c["hidden_size"])
    s0, g0, idx0 = reference.route(m, params, c)
    biased = dict(params, router_bias=jnp.zeros(16).at[5].set(10.0))
    s1, g1, idx1 = reference.route(m, biased, c)
    assert jnp.all(jnp.any(idx1 == 5, axis=-1)) and not jnp.all(jnp.any(idx0 == 5, axis=-1))
    picked = jnp.take_along_axis(s1, idx1, axis=-1)
    assert jnp.allclose(g1, picked / (picked.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    with jax.default_matmul_precision("highest"):
        for p in (params, biased):  # expert 5 is held (4..7 here)
            got = layer.apply({"params": p}, x)
            want, _, _ = reference.experts(m, p, c, lambda a: a)
            assert jnp.allclose(got.reshape(want.shape), want, rtol=1e-4, atol=1e-5)
    assert layer.cfg.gate_eps == 1e-6 and llama.LlamaConfig().gate_eps == 1e-20


def test_the_four_shares_add_up_to_the_whole_layer():
    """Four chips hold four experts each of one layer's sixteen. The parts
    the four compute (there is no shared expert to count once) are the
    uncut reference layer, and the loads each sows are the whole layer's."""
    whole = tiny(num_experts=16, expert_parallel_chips=1, expert_parallel_index=0)
    layer, x = _expert_layer(whole)
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    params["router_bias"] = 0.2 * jax.random.normal(jax.random.PRNGKey(4), (16,))
    m = x.reshape(-1, whole["hidden_size"])
    with jax.default_matmul_precision("highest"):
        want, _, load = reference.experts(m, params, whole, lambda a: a)
        total, held_share = jnp.zeros_like(want), 0.0
        for index in range(4):
            part, _ = _expert_layer(tiny(expert_parallel_index=index))
            own = dict(params, **{
                k: params[k][4 * index : 4 * index + 4]
                for k in ("experts_gate", "experts_up", "experts_down")})
            out, sown = part.apply({"params": own}, x, mutable=["intermediates"])
            sown = sown["intermediates"]
            total = total + out.reshape(want.shape)
            held_share += float(sown["moe_held_share"][0])
            assert float(sown["moe_dropped"][0]) == 0.0
            assert jnp.array_equal(sown["moe_load"][0], load)
    assert jnp.allclose(total, want, rtol=1e-4, atol=1e-5)
    assert held_share == pytest.approx(1.0) and float(load.sum()) == 64 * 3
    assert float(jnp.linalg.norm(want)) > 0.1


def test_the_bias_update_is_the_sign_rule_and_skips_weight_decay():
    old = {"layers_1": {"mlp": {"router_bias": jnp.array([0.5, -0.25, 0.0, 0.0]),
                                "router": {"kernel": jnp.ones((2, 4))}}},
           "layers_3": {"mlp": {"router_bias": jnp.zeros(4)}}}
    decayed = jax.tree_util.tree_map(lambda a: 0.9 * a, old)
    loads = jnp.array([9.0, 1.0, 5.0, 5.0, 2.0, 2.0, 2.0, 2.0])
    new = update_router_bias(decayed, old, loads, 0.1)
    # Over the mean (5): down; under: up; at the mean: still. From the OLD
    # value: the optimizer's decay of a leaf that gets no gradient is undone.
    assert jnp.allclose(new["layers_1"]["mlp"]["router_bias"], jnp.array([0.4, -0.15, 0.0, 0.0]))
    assert jnp.allclose(new["layers_3"]["mlp"]["router_bias"], jnp.zeros(4))
    assert jnp.allclose(new["layers_1"]["mlp"]["router"]["kernel"], 0.9)
    for bias, load in ((old["layers_1"]["mlp"]["router_bias"], loads[:4]),):
        assert jnp.allclose(new["layers_1"]["mlp"]["router_bias"],
                            reference.bias_update(bias, load, 0.1))


def _data(cfg, batch, seq, seed=1):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (batch, seq + 1), 0, cfg.vocab_size)
    return {"inputs": toks[:, :-1], "targets": toks[:, 1:],
            "mask": jnp.ones((batch, seq), jnp.int32)}


def test_the_step_moves_the_biases_by_its_own_loads_and_reports_them(caplog):
    cfg = lfm2_moe_debug()
    mesh = auto_mesh(1, devices=jax.devices()[:1])
    model = build_model(cfg, mesh)
    state, sh = init_train_state(model, mesh, jax.random.PRNGKey(0), (2, 64))
    data = _data(cfg, 2, 64)
    new, metrics = make_train_step(model, mesh, sh, donate=False)(state, data)
    assert set(metrics) == {"loss", "grad_norm", "router_aux", "moe_max_load", "moe_dropped",
                            "moe_held_share", "moe_held_run_share",
                            "moe_held_token_run_share", "router_bias_abs_max"}
    assert float(metrics["router_bias_abs_max"]) == pytest.approx(cfg.router_bias_update_rate)
    assert float(metrics["moe_dropped"]) == 0.0
    assert float(metrics["moe_held_share"]) == pytest.approx(4 / 16, abs=0.06)
    # Each layer's bias moved against that layer's own loads.
    _, inter = model.apply({"params": state.params}, data["inputs"], mutable=["intermediates"])
    for name in ("layers_3", "layers_5", "layers_7", "layers_9"):
        load = inter["intermediates"][name]["mlp"]["moe_load"][0]
        assert float(load.sum()) == 2 * 64 * cfg.num_experts_per_tok
        assert jnp.allclose(
            new.params[name]["mlp"]["router_bias"],
            reference.bias_update(jnp.zeros(16), load, cfg.router_bias_update_rate))
    # Two microbatches: the loads add up, the update is one.
    acc, acc_metrics = make_train_step(model, mesh, sh, donate=False, accum_steps=2)(state, data)
    assert float(acc_metrics["router_bias_abs_max"]) == pytest.approx(1e-3)
    # The loop's gradient step hands the loads out, and an apply step
    # that gives them to update_router_bias moves what the fused step moves.
    (_, logged), _ = make_grad_step(model, mesh, sh, with_metrics=True)(state.params, data)
    loads = logged.pop("moe_load")
    assert loads.shape == (4 * 16,) and all(v.shape == () for v in logged.values())
    moved = update_router_bias(state.params, state.params, loads, cfg.router_bias_update_rate)
    for name in ("layers_3", "layers_5", "layers_7", "layers_9"):
        assert jnp.array_equal(moved[name]["mlp"]["router_bias"],
                               new.params[name]["mlp"]["router_bias"])
    # Without the metrics no load leaves the step: said once, as a warning.
    with caplog.at_level("WARNING", logger="torchft_tpu.parallel.train"):
        make_grad_step(model, mesh, sh)
    assert "cannot move the selection biases" in caplog.text
    # A model that does not ask keeps its biases and sows no load.
    quiet = build_model(dataclasses.replace(cfg, router_bias_update_rate=0.0), mesh)
    still, quiet_metrics = make_train_step(quiet, mesh, sh, donate=False)(state, data)
    assert "router_bias_abs_max" not in quiet_metrics
    assert not jnp.any(still.params["layers_3"]["mlp"]["router_bias"])


def test_a_sharded_mesh_computes_the_same_step():
    """fsdp=2 x tp=2 on four virtual devices: GSPMD partitions the split
    of in_proj's output, the held dispatch and the grouped matmuls
    (train_hsdp.py --model lfm2_moe)."""
    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    c = tiny()
    cfg = adapter.model_config(c, 64)
    data = _data(cfg, 4, 64)
    seen = []
    for mesh in (auto_mesh(1, devices=jax.devices()[:1]), make_mesh(fsdp=2, tp=2)):
        model = build_model(cfg, mesh)
        state, sh = init_train_state(model, mesh, jax.random.PRNGKey(0), (4, 64))
        new, metrics = make_train_step(model, mesh, sh, donate=False)(state, data)
        seen.append([float(metrics[k]) for k in ("loss", "grad_norm", "moe_held_share",
                                                  "router_bias_abs_max")]
                    + [float(jnp.sum(new.params["layers_5"]["mlp"]["router_bias"]))])
    # A sharded contraction adds in another order: the loss agrees to
    # rounding, and one assignment of the 3,072 may flip between two
    # experts whose scores tie, which moves what follows it a little.
    assert seen[0][0] == pytest.approx(seen[1][0], rel=1e-4)
    assert seen[0][1] == pytest.approx(seen[1][1], rel=2e-3)
    assert seen[0][2] == pytest.approx(seen[1][2], abs=2 / 3072)
    rate = c["router_bias_update_rate"]
    assert seen[0][3] == seen[1][3] == pytest.approx(rate)
    assert seen[0][4] == pytest.approx(seen[1][4], abs=4 * rate)


def test_the_presets():
    cfg = lfm2_8b_a1b()
    assert (cfg.hidden_size, cfg.num_layers, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.intermediate_size, cfg.dense_intermediate_size, cfg.vocab_size) == (
        2048, 24, 32, 8, 64, 1792, 7168, 65536)
    pattern = cfg.layer_pattern
    assert len(pattern) == 48 and pattern[1::2] == "DD" + "E" * 22
    assert pattern[0::2] == "".join(
        "*" if i in (2, 6, 10, 14, 18, 21) else "C" for i in range(24))
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.shared_expert_size, cfg.qk_norm,
            cfg.gate_eps, cfg.tie_embeddings, cfg.experts_held, cfg.rope_theta) == (
        32, 4, 0, "head", 1e-6, True, None, 1e6)
    # The cell's slice is layers 1-5 of it.
    assert adapter.pattern(PUBLISHED) == pattern[2:12] == lfm2_moe_debug().layer_pattern
    with pytest.raises(ValueError, match="none of"):
        build_model(dataclasses.replace(lfm2_moe_debug(), layer_pattern="CX"), None).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
