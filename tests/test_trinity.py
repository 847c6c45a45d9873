"""Trinity-Mini (AFMoE): the program's stack (a windowed rotary attention
three layers in four and a global rope-free one the fourth, every one
QK-normed and output-gated, a norm before AND after every sub-layer, the
embedded rows times sqrt(hidden), a dense layer and then expert layers that
hold a share of the experts beside a shared one under a sigmoid router whose
selection bias the step moves) against the benchmark's plain reference at a
small size on the CPU, in float32 with seeded weights; the departures the
comparison has to see; the sandwich against a hand-written layer; the
embedding's scale; both attention kinds against masked XLA attention. The
expert shares, the bias's rule, what the PR leaves alone, the adapter's
refusals, the harness's check, the presets and ``train_hsdp.py``:
tests/test_trinity_step.py."""

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
from tests.harness_controls import pin_path_hash
from tests.test_nemotron_h import _tiny_table
from tests.test_sdar_moe import _data, _leaf_errors

adapter = cells.arch_module("afmoe", "adapter")
reference = cells.arch_module("afmoe", "reference")
flops = cells.arch_module("afmoe", "flops")
tiny, PUBLISHED, unsettle = (
    _reference_tests.tiny, _reference_tests.PUBLISHED, _reference_tests.unsettle)
pin_path_hash(_reference_tests)  # ``unsettle`` draws by a leaf's path: the same every run

# The benchmark's own tests of this architecture (benchmark/tests is not in
# tier-1's path) are collected in tests/test_trinity_reference.py.


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


@pytest.fixture(scope="module")
def sound_sample():
    """One seeded sample of 40 tokens and the reference's own gradients on
    it, computed once: what every departure below is read against."""
    c = tiny()
    model, _, params, data = _setup(c, 40)
    _, grads_ref = jax.jit(lambda p, b: reference.loss_and_grads(p, b, c))(params, data)
    return c, model.cfg, params, data, grads_ref


@pytest.mark.parametrize("what", [
    "bf16_master_weights", "no_post_norm", "no_embed_scale", "gate_on_stream",
    "rope_everywhere", "bias_in_gates"])
def test_a_departure_fails_the_comparison(sound_sample, what):
    """Each of the six reads over the CPU limit: the comparison can see
    what it is there to see. Where the program's configuration can spell
    the departure it is the program that departs; the gate's input and the
    bias in the gates it cannot spell, so there the reference departs."""
    c, base, params, data, grads_ref = sound_sample
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


# -- (d) the steps, the counters, the bias -------------------------------------------


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


# -- (f) the file, the adapter, the presets -----------------------------------------


