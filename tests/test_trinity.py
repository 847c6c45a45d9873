"""Trinity-Mini (AFMoE): the program's stack (a windowed rotary attention
three layers in four and a global rope-free one the fourth, every one
QK-normed and output-gated, a norm before AND after every sub-layer, the
embedded rows times sqrt(hidden), a dense layer and then expert layers that
hold a share of the experts beside a shared one under a sigmoid router whose
selection bias the step moves) against the benchmark's plain reference at a
small size on the CPU, in float32 with seeded weights; the departures the
comparison has to see; the sandwich against a hand-written layer; the
embedding's scale; both attention kinds against masked XLA attention; the
expert shares against the uncut layer; the bias's rule; what the PR leaves
alone (the existing presets' trees, the programs of the old placements); the
adapter's refusals; the presets and ``train_hsdp.py --model trinity_debug``."""

import dataclasses
import hashlib
import logging
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells
from benchmark.tests import test_afmoe_reference as _reference_tests
from torchft_tpu.coordination import LighthouseServer
from torchft_tpu.models import PRESETS, llama
from torchft_tpu.models.llama import (
    Attention,
    MixerLayer,
    MoEMLP,
    Transformer,
    trinity_debug,
    trinity_mini,
    window_attention,
    window_mask,
)
from torchft_tpu.ops import flash_attention as fa
from torchft_tpu.parallel import auto_mesh
from torchft_tpu.parallel.train import (
    build_model,
    init_train_state,
    make_grad_step,
    make_train_step,
    state_shardings,
)
from tests.test_nemotron_h import _tiny_table
from tests.test_sdar_moe import _data, _leaf_errors

adapter = cells.arch_module("afmoe", "adapter")
reference = cells.arch_module("afmoe", "reference")
flops = cells.arch_module("afmoe", "flops")
tiny, PUBLISHED, unsettle = (
    _reference_tests.tiny, _reference_tests.PUBLISHED, _reference_tests.unsettle)

# The benchmark's own tests of this architecture (benchmark/tests is not in
# tier-1's path), collected here under their own names, no body copied.
for _name, _obj in vars(_reference_tests).items():
    if _name.startswith("test_") and callable(_obj):
        globals()[_name] = _obj


# -- (b) the program against the reference -----------------------------------------


def _setup(c, seq, batch=2, seed=0, **cfg_overrides):
    # through sample_config, as every comparison with the reference is: a
    # sequence no longer than the window is compared under a quarter of itself
    cfg = dataclasses.replace(
        adapter.sample_config(adapter.model_config(c, seq), seq), remat=False, **cfg_overrides)
    mesh = auto_mesh(1, devices=jax.devices()[:1])
    model = build_model(cfg, mesh)
    data = _data(c["vocab_size"], batch, seq, seed + 1)
    # norms' scales off 1 and selection biases off 0: a norm left out or a
    # bias that weighs has to show
    params = unsettle(model.init(jax.random.PRNGKey(seed), data["inputs"])["params"])
    return model, mesh, params, data


def _grads(cfg, params, data):
    mesh = auto_mesh(1, devices=jax.devices()[:1])
    model = build_model(cfg, mesh)
    shape = data["inputs"].shape
    with jax.default_matmul_precision("highest"):
        return make_grad_step(model, mesh, state_shardings(model, mesh, shape))(params, data)


def _system(c, seq, **cfg_overrides):
    model, mesh, params, data = _setup(c, seq, **cfg_overrides)
    loss, grads = _grads(model.cfg, params, data)
    return params, data, float(loss), grads


def _sound(errs):
    """Every leaf's error but the selection biases', whose gradient is zero
    on both sides (0/0)."""
    bias = {k for k in errs if "router_bias" in k}
    assert len(bias) == 4 and all(errs[k] != errs[k] for k in bias)
    return {k: v for k, v in errs.items() if k not in bias}


# The CPU comparison's limit on a gradient leaf: float32 on both sides, so
# what is left is the order of the sums (the worst leaf reads 1e-6 to 1e-5).
# Anything rounded to bf16 (2^-9) reads above it, and so does a band edge
# moved by one position.
CPU_GRAD_TOL = 2e-4
FLASH = dict(attn_impl="flash", flash_min_seq=32, flash_block_q=16, flash_block_k=16)
# an attention sub-layer's 5 projections, 2 QK norms and 2 norms; a dense
# one's 3 matrices and 2 norms; an expert one's 3 stacks, router, bias, 3
# shared matrices and 2 norms; the table, the head and the final norm
LEAVES = 5 * 9 + 5 + 4 * 10 + 3


@pytest.mark.parametrize("seq,index,kernels", [
    (40, 1, {}), (64, 0, FLASH), (96, 3, FLASH), (8, 2, {}),
])
def test_loss_and_every_gradient_match_the_reference(seq, index, kernels):
    """Published layers 1-5 (a windowed attention and a dense layer, then a
    whole period with expert layers) under a window of 12: shorter than
    every sequence but the last; dense under the band mask and through the
    banded kernels interpreted at tiles of 16, the band several tiles wide
    at 96 under its sample window; four expert ranks."""
    c = tiny(expert_parallel_index=index)
    params, data, loss, grads = _system(c, seq, **kernels)
    loss_ref, grads_ref = jax.jit(lambda p, b: reference.loss_and_grads(p, b, c))(params, data)
    assert loss == pytest.approx(float(loss_ref), rel=1e-5)
    errs = _leaf_errors(grads, grads_ref)
    assert len(errs) == LEAVES
    errs = _sound(errs)
    assert max(errs.values()) < CPU_GRAD_TOL, errs
    assert max(errs.values()) < reference.GRAD_REL_L2_TOL
    assert abs(loss - float(loss_ref)) / float(loss_ref) < reference.LOSS_REL_TOL


@pytest.mark.parametrize("what", [
    "bf16_master_weights", "no_post_norm", "no_embed_scale", "gate_on_stream",
    "rope_everywhere", "bias_in_gates"])
def test_a_departure_fails_the_comparison(what):
    """Each of the six reads over the CPU limit: the comparison can see
    what it is there to see. Where the program's configuration can spell
    the departure it is the program that departs; the gate's input and the
    bias in the gates it cannot spell, so there the reference departs."""
    c, seq = tiny(), 40
    model, mesh, params, data = _setup(c, seq)
    _, grads_ref = jax.jit(lambda p, b: reference.loss_and_grads(p, b, c))(params, data)
    base = model.cfg
    if what == "bf16_master_weights":
        rounded = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), params)
        _, grads = _grads(base, rounded, data)
    elif what in ("gate_on_stream", "bias_in_gates"):
        _, grads = jax.jit(
            lambda p, b: reference.loss_and_grads(p, b, c, departure=what))(params, data)
    else:
        changed = {
            "no_post_norm": dict(norm_after_mixer=False),
            "no_embed_scale": dict(embed_scale=1.0),
            "rope_everywhere": dict(rope=True),
        }[what]
        shown = params
        if what == "no_post_norm":  # that stack has no such leaves
            bare = lambda tree: {  # noqa: E731
                k: {n: v for n, v in layer.items() if n != "post_norm"}
                if k.startswith("layers_") else layer for k, layer in tree.items()}
            shown, grads_ref = bare(params), bare(grads_ref)
        _, grads = _grads(dataclasses.replace(base, **changed), shown, data)
    errs = {k: v for k, v in _leaf_errors(grads, grads_ref).items() if v == v}
    assert max(errs.values()) > 10 * CPU_GRAD_TOL, what


# -- (c) the sandwich, the scale, the two attention kinds -----------------------------


def _rms(x, scale, eps=1e-5):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def test_the_sandwich_is_a_hand_written_layer_and_the_old_placements_are_what_they_were():
    """x + post_norm(mixer(norm(x))) for a dense feed-forward by hand; the
    tree gains ``post_norm`` under "both" alone; the two old placements
    keep their trees and their values."""
    cfg = trinity_debug(dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 64))
    layer = MixerLayer(cfg, "D")
    params = unsettle(layer.init(jax.random.PRNGKey(0), x)["params"])
    assert set(params) == {"norm", "post_norm", "mlp"}
    mlp = params["mlp"]
    ffn = lambda h: (jax.nn.silu(h @ mlp["gate"]["kernel"]) * (h @ mlp["up"]["kernel"])  # noqa: E731
                     ) @ mlp["down"]["kernel"]
    with jax.default_matmul_precision("highest"):
        got = layer.apply({"params": params}, x)
        want = x + _rms(ffn(_rms(x, params["norm"]["scale"])), params["post_norm"]["scale"])
        assert jnp.allclose(got, want, rtol=1e-5, atol=1e-5)
        old = {k: v for k, v in params.items() if k != "post_norm"}
        for placement, by_hand in (
            (False, x + ffn(_rms(x, params["norm"]["scale"]))),
            (True, x + _rms(ffn(x), params["norm"]["scale"])),
        ):
            plain = MixerLayer(dataclasses.replace(cfg, norm_after_mixer=placement), "D")
            assert set(plain.init(jax.random.PRNGKey(0), x)["params"]) == {"norm", "mlp"}
            assert jnp.allclose(plain.apply({"params": old}, x), by_hand, rtol=1e-5, atol=1e-5)
            assert float(jnp.abs(plain.apply({"params": old}, x) - got).max()) > 1e-2
    with pytest.raises(ValueError, match="norm_after_mixer"):
        MixerLayer(dataclasses.replace(cfg, norm_after_mixer="after"), "D").init(
            jax.random.PRNGKey(0), x)
    # every kind of the stack's takes the second norm
    for kind in "W*E":
        args = (x, jnp.ones((2, 8, 8)), jnp.zeros((2, 8, 8))) if kind in "W*" else (x,)
        assert "post_norm" in MixerLayer(cfg, kind).init(jax.random.PRNGKey(0), *args)["params"]


def test_the_embeddings_scale_is_a_factor_of_the_forward_pass_and_of_the_tables_gradient():
    """``embed_scale`` s on a table E is the unscaled model on s E (the head
    is untied), and the table's gradient is s times that model's."""
    base = trinity_debug(dtype=jnp.float32, embed_scale=1.0)
    scaled = dataclasses.replace(base, embed_scale=8.0)
    assert trinity_debug().embed_scale == 8.0 and llama.LlamaConfig().embed_scale == 1.0
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, 256)
    params = jax.jit(lambda: Transformer(base).init(jax.random.PRNGKey(0), toks)["params"])()
    by_hand = dict(params, embed={"embedding": 8.0 * params["embed"]["embedding"]})
    loss = lambda cfg: jax.jit(jax.value_and_grad(lambda p: jnp.mean(  # noqa: E731
        jnp.square(Transformer(cfg).apply({"params": p}, toks)))))
    with jax.default_matmul_precision("highest"):
        got, g = loss(scaled)(params)
        want, g_hand = loss(base)(by_hand)
        unscaled, _ = loss(base)(params)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    assert float(got) != pytest.approx(float(unscaled), rel=1e-3)
    assert jnp.allclose(g["embed"]["embedding"], 8.0 * g_hand["embed"]["embedding"],
                        rtol=1e-4, atol=1e-7)
    assert jnp.allclose(g["lm_head"]["kernel"], g_hand["lm_head"]["kernel"], rtol=1e-4, atol=1e-7)
    # a prediction module's second lookup is scaled too
    mtp = llama.joyai_flash_debug(dtype=jnp.float32, layer_pattern="*D", num_layers=1)
    toks = toks[:1, :8]
    hidden = lambda cfg: jax.jit(lambda p: Transformer(cfg).apply(  # noqa: E731
        {"params": p}, toks, return_hidden=True))
    p = jax.jit(lambda: Transformer(mtp).init(jax.random.PRNGKey(0), toks)["params"])()
    doubled = dict(p, embed={"embedding": 2.0 * p["embed"]["embedding"]})
    with jax.default_matmul_precision("highest"):
        a = hidden(dataclasses.replace(mtp, embed_scale=2.0))(p)
        b = hidden(mtp)(doubled)
    assert len(a) == 2 and all(jnp.allclose(x, y, rtol=1e-4, atol=1e-5) for x, y in zip(a, b))


@pytest.mark.parametrize("window", [True, False])
def test_both_attention_kinds_normed_and_gated_match_masked_xla_attention(window):
    """'W' + per-head QK norms + the output gate, and '*' + no rotary
    embedding + QK norms + gate, through the flash kernels (interpreted, the
    band four tiles wide) against the same module on plain XLA attention
    under the explicit mask, forward and every gradient; and by hand for
    one head, so that the norms, the rotation and the gate are where the
    equations put them."""
    cfg = trinity_debug(dtype=jnp.float32, sliding_window=40)
    seq = 96
    x = jax.random.normal(jax.random.PRNGKey(1), (2, seq, 64))
    cos, sin = llama.rope_table(
        jnp.broadcast_to(jnp.arange(seq), (2, seq)), 16, cfg.rope_theta, jnp.float32)
    dense = Attention(cfg, window=window)
    flash = Attention(dataclasses.replace(cfg, **FLASH), window=window)
    params = unsettle(dense.init(jax.random.PRNGKey(0), x, cos, sin)["params"])
    assert set(params) == {"wq", "wk", "wv", "wg", "wo", "q_norm", "k_norm"}
    out = lambda mod: lambda p, x: mod.apply({"params": p}, x, cos, sin)  # noqa: E731
    loss = lambda mod: lambda p, x: jnp.sum(jnp.sin(out(mod)(p, x)))  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want, g_want = jax.value_and_grad(loss(dense), argnums=(0, 1))(params, x)
        got, g_got = jax.value_and_grad(loss(flash), argnums=(0, 1))(params, x)
        assert jnp.allclose(out(flash)(params, x), out(dense)(params, x), rtol=1e-4, atol=1e-5)
        assert float(got) == pytest.approx(float(want), abs=1e-3)  # a sum that cancels
        assert max(_leaf_errors(g_got, g_want).values()) < CPU_GRAD_TOL
        # by hand: head 3 (on key/value head 1) of the first sequence
        q = _rms(jnp.einsum("sh,hd->sd", x[0], params["wq"]["kernel"][:, 3]),
                 params["q_norm"]["scale"])
        k = _rms(jnp.einsum("sh,hd->sd", x[0], params["wk"]["kernel"][:, 1]),
                 params["k_norm"]["scale"])
        v = jnp.einsum("sh,hd->sd", x[0], params["wv"]["kernel"][:, 1])
        if window:
            rot = lambda t: llama.apply_rope(t[None, :, None], cos[:1], sin[:1])[0, :, 0]  # noqa: E731
            q, k = rot(q), rot(k)
        mask = window_mask(seq, 40) if window else jnp.tril(jnp.ones((seq, seq), bool))
        probs = jax.nn.softmax(jnp.where(mask, q @ k.T / 4.0, -jnp.inf), axis=-1)
        head = probs @ v * jax.nn.sigmoid(jnp.einsum("sh,hd->sd", x[0], params["wg"]["kernel"][:, 3]))
        only = dict(params, wo={"kernel": jnp.zeros_like(params["wo"]["kernel"]).at[3].set(
            jnp.eye(16, 64))})
        assert jnp.allclose(out(flash)(only, x)[0, :, :16], head, rtol=1e-4, atol=1e-5)
    tiles, kept = window_attention(flash.cfg, seq) if window else (None, None)
    assert not window or (tiles == (16, 16) and 0.5 < kept < 1.0)


def test_the_eight_expert_shares_add_up_to_the_uncut_layer():
    """Eight chips hold two experts each of one layer's sixteen. The routed
    parts the eight compute, with the shared expert (which every chip
    computes alike) counted once, are the uncut reference layer; no chip
    drops a row and their held shares are the whole."""
    whole = tiny(num_experts=16, expert_parallel_chips=1, expert_parallel_index=0)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 64))
    full = MoEMLP(adapter.model_config(whole, 32))
    params = full.init(jax.random.PRNGKey(0), x)["params"]
    params["router_bias"] = 0.2 * jax.random.normal(jax.random.PRNGKey(4), (16,))
    m = x.reshape(-1, 64)
    stacks = ("experts_gate", "experts_up", "experts_down")
    with jax.default_matmul_precision("highest"):
        want = reference.experts(m, params, whole, lambda a: a)
        assert jnp.allclose(full.apply({"params": params}, x).reshape(want.shape), want,
                            rtol=1e-4, atol=1e-5)
        # what every chip computes alike, by hand
        shared = (
            jax.nn.silu(m @ params["shared_gate"]["kernel"]) * (m @ params["shared_up"]["kernel"])
        ) @ params["shared_down"]["kernel"]
        total, held_share = jnp.zeros_like(want), 0.0
        for index in range(8):
            share = tiny(num_experts=2, expert_parallel_chips=8, expert_parallel_index=index)
            own = {k: v[2 * index : 2 * index + 2] if k in stacks else v
                   for k, v in params.items()}
            out, sown = MoEMLP(adapter.model_config(share, 32)).apply(
                {"params": own}, x, mutable=["intermediates"])
            sown = sown["intermediates"]
            total = total + out.reshape(want.shape) - shared
            held_share += float(sown["moe_held_share"][0])
            assert float(sown["moe_dropped"][0]) == 0.0
            # the share's own reference is the share
            assert jnp.allclose(out.reshape(want.shape),
                                reference.experts(m, own, share, lambda a: a),
                                rtol=1e-4, atol=1e-5)
    assert jnp.allclose(total + shared, want, rtol=1e-4, atol=1e-5)
    assert held_share == pytest.approx(1.0)
    assert float(jnp.linalg.norm(total)) > 0.1 and float(jnp.linalg.norm(shared)) > 0.1


# -- (d) the steps, the counters, the bias -------------------------------------------


def test_the_step_moves_the_bias_against_the_load_and_hands_on_the_counters(caplog):
    """The fused step: the selection bias of every expert layer moves by
    the file's 0.001 towards the experts the router under-used, from zero
    (no gradient reaches it, no weight decay touches it), and the step
    hands on the band's, the experts' and the bias's counters."""
    # float32: a rounding that flips one row's choice between the step and
    # the second forward pass below would move a load by one
    cfg = trinity_debug(dtype=jnp.float32, **FLASH)
    assert cfg.router_bias_update_rate == 1e-3
    mesh = auto_mesh(1, devices=jax.devices()[:1])
    model = build_model(cfg, mesh)
    state, sh = init_train_state(model, mesh, jax.random.PRNGKey(0), (2, 64))
    data = _data(cfg.vocab_size, 2, 64)
    llama._ATTN_NOTED.clear()
    with caplog.at_level(logging.INFO, logger="torchft_tpu.models.llama"):
        new, metrics = make_train_step(model, mesh, sh, donate=False)(state, data)
    assert ("attention: asked=flash/window traced=flash/window seq=64 window=16 tiles=16x16"
            in caplog.text)
    assert "attention: asked=flash traced=flash seq=64 tiles=16x16" in caplog.text
    assert "WARNING" not in [r.levelname for r in caplog.records]
    assert set(metrics) == {
        "loss", "grad_norm", "swa_kept_share", "moe_held_share", "moe_held_run_share",
        "moe_held_token_run_share",
        "moe_dropped", "moe_max_load", "router_aux", "router_bias_abs_max"}
    assert int(new.step) == 1 and np.isfinite(float(metrics["loss"]))
    assert float(metrics["router_bias_abs_max"]) == pytest.approx(1e-3)
    assert float(metrics["swa_kept_share"]) == pytest.approx(
        (16 * 17 // 2 + 48 * 16) / (7 * 256))
    assert 0.0 < float(metrics["moe_held_share"]) < 1.0 and float(metrics["moe_dropped"]) == 0.0
    _, sown = model.apply({"params": state.params}, data["inputs"], mutable=["intermediates"])
    for name in ("layers_3", "layers_5", "layers_7", "layers_9"):
        load = sown["intermediates"][name]["mlp"]["moe_load"][0]
        assert float(load.sum()) == 2 * 64 * 3
        bias = new.params[name]["mlp"]["router_bias"]
        assert jnp.allclose(bias, reference.bias_update(jnp.zeros(16), load, 1e-3))
        assert {round(float(v), 6) for v in np.abs(np.asarray(bias))} <= {0.0, 0.001}
    # out of the gradient: the loss's gradient at the bias is exactly zero
    _, grads = _grads(cfg, state.params, data)
    assert all(float(jnp.abs(grads[n]["mlp"]["router_bias"]).max()) == 0.0
               for n in ("layers_3", "layers_9"))
    # the published cell's schedule, from shapes alone
    cut = adapter.model_config(PUBLISHED, 16384)
    assert window_attention(cut, 16384) == ((512, 512), pytest.approx(0.800, abs=5e-4))
    assert llama.held_buffer_rows(cut, 16384) == 65536 == 4 * 16384 * 8 // 8


def test_remat_computes_the_same_step():
    data = _data(256, 2, 64)
    seen = []
    for remat in (False, True):
        cfg = trinity_debug(dtype=jnp.float32, remat=remat)
        mesh = auto_mesh(1, devices=jax.devices()[:1])
        model = build_model(cfg, mesh)
        state, sh = init_train_state(model, mesh, jax.random.PRNGKey(0), (2, 64))
        _, metrics = make_train_step(model, mesh, sh, donate=False)(state, data)
        seen.append([float(metrics[k]) for k in ("loss", "grad_norm", "moe_held_share")])
    assert seen[0] == pytest.approx(seen[1], rel=1e-5)


# -- (e) what this leaves alone ----------------------------------------------------------


# sha256 of the sorted "path shape" lines of each existing debug preset's
# parameter tree, recorded at the commit before this one: the widened
# ``norm_after_mixer`` and ``embed_scale`` add no leaf and move none.
TREES = {
    "debug": "95f08d8aea110493",
    "moe": "ed5f793bdee4d234",
    "nemotron_h": "4163973bda4dc254",
    "lfm2_moe": "b0abbe62d78036c9",
    "sdar_moe": "5f0801a70193d13b",
    "joyai_flash": "9d7bc8385b27dd00",
    "olmo_hybrid": "e56ec3af2050fb71",
    "solar_open2_debug": "c5589eab3cd16dbd",
    "smallthinker_debug": "5b1cfb4d50cecce7",
    "olmoe": "08ed57faaa2a8164",
}


def _tree_digest(cfg):
    shapes = jax.eval_shape(lambda: Transformer(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    lines = sorted(f"{jax.tree_util.keystr(k)} {v.shape}"
                   for k, v in jax.tree_util.tree_leaves_with_path(shapes))
    return hashlib.sha256("\n".join(lines).encode()).hexdigest()[:16]


@pytest.mark.parametrize("name", [
    "debug", "moe", "nemotron_h", "lfm2_moe", "sdar_moe", "joyai_flash", "olmo_hybrid",
    "solar_open2_debug", "smallthinker_debug", "olmoe"])
def test_the_existing_presets_trees_are_what_they_were(name):
    overrides = dict(num_layers=1) if name == "olmoe" else {}
    assert _tree_digest(PRESETS[name](**overrides)) == TREES[name]


def _equations(cfg, toks):
    model = Transformer(cfg)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), toks))["params"]
    jaxpr = jax.make_jaxpr(lambda p: model.apply({"params": p}, toks))(params)
    count = {}

    def walk(j):
        for eqn in j.eqns:
            count[eqn.primitive.name] = count.get(eqn.primitive.name, 0) + 1
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    return count


def test_a_scale_of_one_and_the_old_placements_emit_no_new_operation():
    """``embed_scale`` 1.0 multiplies nothing: a scale is exactly one more
    ``mul`` and nothing else. The two old norm placements are the same
    operations in another order; the sandwich is one more norm a sub-layer."""
    toks = jnp.zeros((1, 8), jnp.int32)
    base = llama.olmo_hybrid_debug(layer_pattern="*D", norm_after_mixer=False)
    plain = _equations(base, toks)
    assert _equations(dataclasses.replace(base, embed_scale=1.0), toks) == plain
    scaled = _equations(dataclasses.replace(base, embed_scale=3.0), toks)
    assert scaled == dict(plain, mul=plain["mul"] + 1)
    after = _equations(dataclasses.replace(base, norm_after_mixer=True), toks)
    assert after == plain
    both = _equations(dataclasses.replace(base, norm_after_mixer="both"), toks)
    assert both["rsqrt"] == plain["rsqrt"] + 2 and both["dot_general"] == plain["dot_general"]


# -- (f) the file, the adapter, the presets -----------------------------------------


def test_the_count_is_the_models_own_count_of_its_tree():
    def own_count(c, seq):
        model = build_model(adapter.model_config(c, seq), None)
        shapes = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, seq), jnp.int32))
        )["params"]
        return sum(x.size for x in jax.tree_util.tree_leaves(shapes))

    assert own_count(PUBLISHED, 256) == flops.total_params(PUBLISHED) == 705_474_304
    assert own_count(tiny(), 32) == flops.total_params(tiny())


def test_the_file_states_its_cuts_and_the_adapter_reads_every_key():
    c = PUBLISHED
    catalog = {  # the catalog row's config, every key
        "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 6144,
        "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 8,
        "load_balance_coeff": 0.001, "max_position_embeddings": 131072, "model_type": "afmoe",
        "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1,
        "num_attention_heads": 32, "num_dense_layers": 2, "num_expert_groups": 1,
        "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 32,
        "num_key_value_heads": 4, "num_limited_groups": 1, "num_shared_experts": 1,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
        "route_scale": 2.826, "score_func": "sigmoid", "sliding_window": 2048,
        "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
        "vocab_size": 200192,
    }
    cut = {"num_hidden_layers", "layer_types", "num_dense_layers", "num_experts", "vocab_size"}
    assert set(c["reduced"]) == cut
    for key, value in catalog.items():
        if key in cut:
            entry = c["reduced"][key]
            assert entry["published"] and entry["run"] and entry["why"]
            assert c[key] != value
        else:
            assert c[key] == value, key
    assert c["layer_types"] == catalog["layer_types"][1:6]  # published layers 1-5
    assert c["num_experts"] * c["expert_parallel_chips"] == 128
    assert c["vocab_size"] * c["vocab_parallel_chips"] == 200192
    assert set(c) - cells.DOC_KEYS == set(adapter.KEYS)
    assert c["stands_for"] and set(c["distortions"]) >= {
        "rows_per_expert", "head_share", "attention_share", "uniform_tokens", "host_share"}
    assert set(c["assumed"]) >= {
        "attention gate", "qk norms", "rope-free global layers", "rotary", "window",
        "sandwich norms", "mup_enabled", "router", "load_balance_coeff", "initial values"}
    own_code = [k for k, v in c["assumed"].items() if "no key of config.json" in v]
    assert set(own_code) >= {"attention gate", "qk norms", "rope-free global layers",
                             "sandwich norms", "router"}
    table = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    entry = next(e for e in table["configs"] if e["name"] == "trinity-mini-l5e16")
    assert set(entry["reduced"]) == cut and entry["source"] == c["source"].split(";")[0]
    cfg = adapter.model_config(c, 16384)
    assert (cfg.layer_pattern, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.rope,
            cfg.sliding_window, cfg.qk_norm, cfg.attn_gate, cfg.norm_after_mixer,
            cfg.vocab_size, cfg.rope_theta, cfg.norm_eps, cfg.embed_scale) == (
        "WDWE*EWEWE", 32, 4, 128, False, 2048, "head", True, "both", 25024, 1e4, 1e-5,
        2048 ** 0.5)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.experts_held, cfg.intermediate_size,
            cfg.dense_intermediate_size, cfg.shared_expert_size, cfg.router_score,
            cfg.routed_scaling, cfg.gate_eps, cfg.expert_act, cfg.router_aux_coef,
            cfg.router_bias_update_rate, cfg.expert_capacity_factor, cfg.embed_init_std) == (
        128, 8, (0, 16), 1024, 6144, 1024, "sigmoid", 2.826, 1e-20, "swiglu", 0.0, 1e-3,
        None, None)
    assert adapter.model_config(dict(c, mup_enabled=False), 16384).embed_scale == 1.0


@pytest.mark.parametrize("key,value,says", [
    ("model_type", "qwen3_moe", "model_type"),
    ("hidden_act", "gelu", "hidden_act"),
    ("rope_scaling", {"type": "yarn"}, "rope_scaling"),
    ("n_group", 8, "n_group"),
    ("topk_group", 4, "topk_group"),
    ("num_limited_groups", 2, "num_limited_groups"),
    ("num_expert_groups", 2, "num_expert_groups"),
    ("num_shared_experts", 2, "num_shared_experts"),
    ("score_func", "softmax", "score_func"),
    ("route_norm", False, "route_norm"),
    ("tie_word_embeddings", True, "tie_word_embeddings"),
    ("max_position_embeddings", 8192, "max_position_embeddings"),
    ("sliding_window", 0, "sliding_window"),
    ("expert_parallel_index", 8, "expert_parallel_index"),
    ("num_key_value_heads", 3, "num_key_value_heads"),
    ("num_experts_per_tok", 129, "num_experts_per_tok"),
    ("vocab_parallel_chips", 0, "vocab_parallel_chips"),
    ("load_balance_coeff", -0.1, "load_balance_coeff"),
    ("num_dense_layers", 6, "num_dense_layers"),
])
def test_the_adapter_refuses_by_name_what_the_program_does_not_compute(key, value, says):
    with pytest.raises(cells.CellError, match=says):
        adapter.model_config(dict(PUBLISHED, **{key: value}), 16384)


def test_the_adapter_refuses_a_layout_off_its_period_and_a_file_with_a_key_to_spare(tmp_path):
    s, f = "sliding_attention", "full_attention"
    with pytest.raises(cells.CellError, match="does not repeat with period"):
        adapter.model_config(dict(PUBLISHED, layer_types=[s, s, f, s, f]), 16384)
    with pytest.raises(cells.CellError, match="more than one full_attention"):
        adapter.model_config(dict(PUBLISHED, layer_types=[f, s, f, s, f]), 16384)
    with pytest.raises(cells.CellError, match="are what the stack is built from"):
        adapter.model_config(dict(PUBLISHED, layer_types=[s, s, f, s]), 16384)
    with pytest.raises(cells.CellError, match="are what the stack is built from"):
        adapter.model_config(dict(PUBLISHED, layer_types=[s, s, "conv", s, s]), 16384)
    with pytest.raises(cells.CellError, match="sequence 131073 exceeds"):
        adapter.model_config(PUBLISHED, 131073)
    # a layer's kind is its entry, wherever in a period the kept run starts
    assert adapter.model_config(
        dict(PUBLISHED, layer_types=[f, s, s, s, f]), 16384).layer_pattern == "*DWEWEWE*E"
    lacking = {k: v for k, v in PUBLISHED.items() if k != "sliding_window"}
    with pytest.raises(cells.CellError, match="sliding_window"):
        adapter.model_config(lacking, 16384)
    with pytest.raises(cells.CellError, match="router_aux_loss_coef"):
        cells.load_cell("w", _tiny_table(tmp_path, tiny(router_aux_loss_coef=0.01)))
    assert cells.load_cell("w", _tiny_table(tmp_path, tiny())).arch_dir.endswith("afmoe")


def test_the_harness_check_passes_at_a_small_size(tmp_path, monkeypatch):
    """``worker.reference_check`` as the chip run makes it, on a sample
    longer than the window."""
    from benchmark import worker

    first = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: first)
    monkeypatch.setattr(worker, "CHECK_SEQ", 48)
    cell = cells.load_cell("w", _tiny_table(tmp_path, tiny()))
    cell.mix.update(batch=1, seq=48)
    out = worker.reference_check(worker.Ctx(cell, 3000000001, 0, False))
    assert out["ok"] and out["grad_rel_l2_worst"] < CPU_GRAD_TOL and out["loss_rel_diff"] < 1e-5


def test_the_checks_sample_keeps_a_band_five_tiles_wide():
    """The harness samples 1,024 tokens, fewer than the window: the sample's
    model and the reference take half of the sample for the window, and the
    banded kernels run it at tiles of 128: a query tile's sweep is five key
    tiles (30 of the causal 36 a head), as the timed step's is at 2,048
    under tiles of 512, so the band's edge and a skipped tile are inside
    what decides ``correct``. A sample longer than the window (the builder's
    comparison) keeps the cell's own window and tiles."""
    cfg = adapter.model_config(PUBLISHED, 16384)
    sample = adapter.sample_config(cfg, 1024)
    assert (sample.sliding_window, sample.flash_block_q, sample.flash_block_k) == (512, 128, 128)
    assert sample.attn_impl == "flash" and sample.flash_min_seq <= 1024
    assert reference.window_at(PUBLISHED, 1024) == sample.sliding_window
    assert fa.choose_tiles("window", 1024, (128,), 128, 128, window=512) == (128, 128)
    kept, run = fa.window_tiles(1024, 512, 128, 128)
    assert (kept, run) == (fa.window_kept(1024, 512), 30 * 128 * 128)
    assert max(fa._band_sweeps(1024, 512, 128, 128)) == 5
    assert max(fa._band_sweeps(16384, 2048, 512, 512)) == 5
    assert fa.window_tiles(16384, 2048) == (31_458_304, 150 * 512 * 512)
    long = adapter.sample_config(cfg, 16384)
    assert (long.sliding_window, long.flash_block_q, long.flash_min_seq) == (2048, 1024, 2048)
    assert window_attention(long, 16384)[0] == (512, 512)  # the bound is not the tile
    # the rest of the sample's model is the cell's
    assert dataclasses.replace(
        sample, sliding_window=2048, flash_block_q=1024, flash_block_k=1024,
        flash_min_seq=cfg.flash_min_seq) == cfg


def test_the_presets():
    cfg = trinity_mini()
    assert (cfg.hidden_size, cfg.num_layers, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.intermediate_size, cfg.dense_intermediate_size, cfg.vocab_size,
            cfg.max_seq_len, cfg.norm_eps, cfg.rope_theta, cfg.sliding_window) == (
        2048, 32, 32, 4, 128, 1024, 6144, 200192, 131072, 1e-5, 1e4, 2048)
    assert cfg.layer_pattern == "WDWDWE*E" + "WEWEWE*E" * 7
    assert len(cfg.layer_pattern) == 2 * cfg.num_layers
    assert (cfg.rope, cfg.qk_norm, cfg.attn_gate, cfg.norm_after_mixer, cfg.tie_embeddings,
            cfg.embed_scale, cfg.router_ahead) == (
        False, "head", True, "both", False, 2048 ** 0.5, False)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.shared_expert_size,
            cfg.experts_held, cfg.expert_act, cfg.router_score, cfg.routed_scaling,
            cfg.router_bias_update_rate, cfg.router_aux_coef) == (
        128, 8, 1024, None, "swiglu", "sigmoid", 2.826, 1e-3, 0.0)
    cut = adapter.model_config(PUBLISHED, 16384)
    same = ("hidden_size", "head_dim", "intermediate_size", "dense_intermediate_size",
            "norm_eps", "qk_norm", "rope", "rope_theta", "sliding_window", "attn_gate",
            "norm_after_mixer", "embed_scale", "tie_embeddings", "num_experts",
            "num_experts_per_tok", "router_score", "routed_scaling", "gate_eps", "expert_act",
            "shared_expert_size", "router_aux_coef", "router_z_coef",
            "router_bias_update_rate", "num_heads", "num_kv_heads")
    assert all(getattr(cut, k) == getattr(cfg, k) for k in same)
    assert cut.layer_pattern == cfg.layer_pattern[2:12]  # published layers 1-5
    small = trinity_debug()
    assert PRESETS["trinity_debug"] is trinity_debug and PRESETS["trinity_mini"] is trinity_mini
    assert (small.layer_pattern, small.sliding_window, small.experts_held, small.embed_scale,
            small.norm_after_mixer) == ("WDWE*EWEWE", 16, (0, 4), 8.0, "both")
    # a model that sets none of this is what it was, and no preset changed meaning
    plain = llama.LlamaConfig()
    assert (plain.norm_after_mixer, plain.embed_scale) == (False, 1.0)
    assert all(PRESETS[n]().embed_scale == 1.0 and PRESETS[n]().norm_after_mixer in (False, True)
               for n in PRESETS if not n.startswith("trinity"))
    with pytest.raises(ValueError, match="pre-normed"):
        Transformer(dataclasses.replace(small, router_ahead=True)).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


@pytest.mark.timeout(300)
def test_train_hsdp_runs_the_small_preset(tmp_path):
    """``train_hsdp.py --model trinity_debug``: one group, the Manager in
    the loop, three committed steps on the CPU, the selection biases moved."""
    lighthouse = LighthouseServer(
        bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=20000, quorum_tick_ms=50)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", TORCHFT_LIGHTHOUSE=lighthouse.address(),
               REPLICA_GROUP_ID="0", NUM_REPLICA_GROUPS="1",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("XLA_FLAGS", None)  # one device: the preset's mesh of one
    try:
        proc = subprocess.run(
            [sys.executable, "train_hsdp.py", "--model", "trinity_debug", "--steps", "3",
             "--batch", "2", "--seq", "32", "--result-dir", str(tmp_path / "out")],
            cwd=root, env=env, capture_output=True, text=True, timeout=240,
        )
    finally:
        lighthouse.shutdown()
    assert proc.returncode == 0, proc.stderr[-3000:]
    steps = [line for line in proc.stderr.splitlines() if " loss " in line]
    assert len(steps) == 3 and "router_bias_abs_max" in steps[-1], steps
    assert cells.load_json(str(tmp_path / "out" / "group0.json"))["final_step"] == 3


def test_the_builders_long_comparison_at_a_small_size(tmp_path):
    """``tools/reference_compare.py`` as the chip run makes it at 16,384
    tokens: the cell's own model on a sequence several windows long against
    the reference in query blocks; the reference under a named departure
    handed in the system's place, and a program whose band is misplaced,
    read orders worse (the reference in float8:
    ``test_rounded_operands_are_another_result``)."""
    from tools import reference_compare

    cell = cells.load_cell("w", _tiny_table(tmp_path, tiny()))
    out = reference_compare.compare(cell, 96, 3000000001, query_block=32)
    assert out["ok"] and (out["tokens"], out["query_block"], out["compared"]) == (
        96, 32, "system")
    assert out["grad_rel_l2_worst"] < CPU_GRAD_TOL and out["loss_rel_diff"] < 1e-5
    assert out["leaves"] == LEAVES
    gone = reference_compare.compare(cell, 96, 3000000001, departure="no_post_norm")
    assert gone["compared"] == "reference under no_post_norm" and gone["query_block"] is None
    assert gone["grad_rel_l2_worst"] > 100 * CPU_GRAD_TOL and not gone["ok"]
    moved = reference_compare.compare(cell, 96, 3000000001, program_window=13)
    assert moved["compared"] == "system under a window of 13"
    assert moved["grad_rel_l2_worst"] > CPU_GRAD_TOL
