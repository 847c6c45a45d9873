"""SmallThinker-21BA3B (its banded flash kernels' own tests are in
tests/test_flash_attention.py): the program's stack (a global rope-free attention and three windowed
rotary ones, an expert layer of ReGLU experts after each, its router
reading the attention's input) against the benchmark's plain reference at
a small size on the CPU, in float32 with seeded weights; the expert shares
against the uncut layer. The step's counters, a sharded mesh, the adapter's
refusals, the harness's checks, the presets and ``train_hsdp.py``:
tests/test_smallthinker_step.py."""

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
from benchmark.tests import test_smallthinker_reference as _reference_tests
from torchft_tpu.coordination import LighthouseServer
from torchft_tpu.models import PRESETS, llama
from torchft_tpu.models.llama import (
    Attention,
    MoEMLP,
    smallthinker_21b,
    smallthinker_debug,
    window_attention,
)
from torchft_tpu.ops import flash_attention as fa
from torchft_tpu.parallel import auto_mesh, make_mesh
from torchft_tpu.parallel.sharding import param_specs
from torchft_tpu.parallel.train import (
    build_model,
    init_train_state,
    make_grad_step,
    make_train_step,
    state_shardings,
)
from tests.test_nemotron_h import _tiny_table
from tests.test_sdar_moe import _data, _leaf_errors

adapter = cells.arch_module("smallthinker", "adapter")
reference = cells.arch_module("smallthinker", "reference")
flops = cells.arch_module("smallthinker", "flops")
tiny, PUBLISHED = _reference_tests.tiny, _reference_tests.PUBLISHED

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
    params = model.init(jax.random.PRNGKey(seed), data["inputs"])["params"]
    return model, mesh, params, data


def _system(c, seq, params=None, **cfg_overrides):
    model, mesh, fresh, data = _setup(c, seq, **cfg_overrides)
    params = params or fresh
    with jax.default_matmul_precision("highest"):
        loss, grads = make_grad_step(model, mesh, state_shardings(model, mesh, (2, seq)))(
            params, data)
    return params, data, float(loss), grads


# The CPU comparison's limit on a gradient leaf: float32 on both sides, so
# what is left is the order of the sums (the worst leaf reads 1e-6 to 1e-5).
# Anything rounded to bf16 (2^-9) reads above it, and so does a band edge
# moved by one position.
CPU_GRAD_TOL = 2e-4
FLASH = dict(attn_impl="flash", flash_min_seq=32, flash_block_q=16, flash_block_k=16)


@pytest.mark.parametrize("seq,index,kernels", [
    (40, 1, {}), (64, 0, FLASH), (96, 3, FLASH), (8, 2, {}),
])
def test_loss_and_every_gradient_match_the_reference(seq, index, kernels):
    """One period (a global rope-free attention, three windowed rotary
    ones, an expert layer after each) under a window of 12: shorter than
    every sequence but the last; dense under the band mask and through the
    banded kernels; four expert ranks."""
    c = tiny(expert_parallel_index=index)
    params, data, loss, grads = _system(c, seq, **kernels)
    loss_ref, grads_ref = jax.jit(lambda p, b: reference.loss_and_grads(p, b, c))(params, data)
    assert loss == pytest.approx(float(loss_ref), rel=1e-5)
    errs = _leaf_errors(grads, grads_ref)
    # an attention sub-layer's 4 projections, norm and router, an expert
    # sub-layer's 3 stacks and norm, the table, the head and the final norm
    assert len(errs) == 4 * 6 + 4 * 4 + 3
    assert max(errs.values()) < CPU_GRAD_TOL, errs
    assert max(errs.values()) < reference.GRAD_REL_L2_TOL
    assert abs(loss - float(loss_ref)) / float(loss_ref) < reference.LOSS_REL_TOL


@pytest.fixture(scope="module")
def sound_sample():
    """One seeded sample of 40 tokens and the reference's own gradients on
    it, computed once: what every departure below is read against."""
    c, seq = tiny(), 40
    _, _, params, data = _setup(c, seq)
    _, grads_ref = jax.jit(lambda p, b: reference.loss_and_grads(p, b, c))(params, data)
    return c, seq, params, data, grads_ref


@pytest.mark.parametrize("what", ["band_edge_in", "band_edge_out", "no_window", "rope_everywhere",
                                  "no_rope", "swiglu", "router_after", "bf16"])
def test_a_moved_band_a_misplaced_router_or_a_lower_precision_fails_the_comparison(
    sound_sample, what
):
    """Each departure at the program's side reads over the CPU limit: the
    comparison can see what it is there to see."""
    c, seq, params, data, grads_ref = sound_sample
    base = dataclasses.replace(adapter.model_config(c, seq), remat=False)
    changed = {
        "band_edge_in": dict(sliding_window=11),
        "band_edge_out": dict(sliding_window=13),
        "no_window": dict(sliding_window=seq),
        "rope_everywhere": dict(rope=True),
        "no_rope": dict(layer_pattern="*E*E*E*E", sliding_window=None),
        "swiglu": dict(expert_act="swiglu"),
        "router_after": dict(router_ahead=False),
        "bf16": dict(dtype=jnp.bfloat16),
    }[what]
    mesh = auto_mesh(1, devices=jax.devices()[:1])
    model = build_model(dataclasses.replace(base, **changed), mesh)
    if what == "router_after":  # the same kernels, held where that stack holds them
        params = dict(params)
        for i in range(0, 8, 2):
            attn = dict(params[f"layers_{i}"])
            params[f"layers_{i + 1}"] = dict(
                params[f"layers_{i + 1}"],
                mlp=dict(params[f"layers_{i + 1}"]["mlp"], router=attn.pop("router")))
            params[f"layers_{i}"] = attn
    with jax.default_matmul_precision("highest"):
        _, grads = make_grad_step(model, mesh, state_shardings(model, mesh, (2, seq)))(
            params, data)
    if what == "router_after":
        grads = dict(grads)
        for i in range(0, 8, 2):
            mlp = dict(grads[f"layers_{i + 1}"]["mlp"])
            grads[f"layers_{i}"] = dict(grads[f"layers_{i}"], router=mlp.pop("router"))
            grads[f"layers_{i + 1}"] = dict(grads[f"layers_{i + 1}"], mlp=mlp)
    assert max(_leaf_errors(grads, grads_ref).values()) > CPU_GRAD_TOL


def test_the_router_reads_the_input_of_the_attention_before_it():
    """Another W_o in every attention leaves the FIRST layer's routing what
    it was, bit for bit (its router read the same normed embedding), though
    that layer's experts now read another input; the reference agrees with
    the changed program too."""
    c, seq = tiny(), 40
    model, mesh, params, data = _setup(c, seq)
    changed = dict(params)
    for i in range(0, 8, 2):
        attn = params[f"layers_{i}"]["attn"]
        changed[f"layers_{i}"] = dict(
            params[f"layers_{i}"], attn=dict(attn, wo={"kernel": -2.0 * attn["wo"]["kernel"]}))

    def routed(p):
        out, sown = model.apply({"params": p}, data["inputs"], mutable=["intermediates"])
        first = sown["intermediates"]["layers_1"]["mlp"]
        return out, {k: float(v[0]) for k, v in first.items()}, sown["intermediates"]

    out, first, sown = routed(params)
    out2, first2, sown2 = routed(changed)
    assert first == first2 and set(first) >= {"router_aux", "moe_held_share", "moe_max_load"}
    assert float(jnp.abs(out - out2).max()) > 1e-3
    # the later layers' routers read what the changed attentions wrote
    assert float(sown["layers_3"]["mlp"]["router_aux"][0]) != float(
        sown2["layers_3"]["mlp"]["router_aux"][0])
    # the router is the attention sub-layer's leaf, and the expert layer has none
    assert "router" in params["layers_2"] and "router" not in params["layers_3"]["mlp"]
    with jax.default_matmul_precision("highest"):
        loss, grads = make_grad_step(model, mesh, state_shardings(model, mesh, (2, seq)))(
            changed, data)
    loss_ref, grads_ref = jax.jit(lambda p, b: reference.loss_and_grads(p, b, c))(changed, data)
    assert float(loss) == pytest.approx(float(loss_ref), rel=1e-5)
    assert max(_leaf_errors(grads, grads_ref).values()) < CPU_GRAD_TOL


def test_a_router_ahead_needs_an_attention_layer_to_hold_it():
    cfg = smallthinker_debug()
    toks = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="no attention layer before it"):
        llama.Transformer(dataclasses.replace(cfg, layer_pattern="*EE")).init(
            jax.random.PRNGKey(0), toks)
    with pytest.raises(ValueError, match="layer_pattern stack"):
        llama.Transformer(dataclasses.replace(cfg, layer_pattern=None)).init(
            jax.random.PRNGKey(0), toks)
    with pytest.raises(ValueError, match="sliding_window"):
        llama.Transformer(dataclasses.replace(cfg, sliding_window=None)).init(
            jax.random.PRNGKey(0), toks)
    for impl in ("ring", "ulysses"):
        with pytest.raises(ValueError, match="windowed attention under"):
            Attention(dataclasses.replace(cfg, attn_impl=impl), window=True).init(
                jax.random.PRNGKey(0), jnp.zeros((1, 8, 64)), jnp.ones((1, 8, 8)),
                jnp.zeros((1, 8, 8)))


def test_reglu_is_its_definition():
    """down(relu(gate x) * up x), every expert held and every row by hand."""
    cfg = smallthinker_debug(experts_held=None, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 64))
    layer = MoEMLP(cfg)
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    with jax.default_matmul_precision("highest"):
        got = layer.apply({"params": params}, x).reshape(16, 64)
        rows = x.reshape(16, 64)
        logits = rows @ params["router"]["kernel"]
        for t in range(16):
            top, idx = jax.lax.top_k(logits[t], 3)
            want = jnp.zeros((64,))
            for g, e in zip(jax.nn.softmax(top), idx):
                hidden = jnp.maximum(rows[t] @ params["experts_gate"][e], 0.0) * (
                    rows[t] @ params["experts_up"][e])
                want = want + g * (hidden @ params["experts_down"][e])
            assert jnp.allclose(got[t], want, rtol=1e-4, atol=1e-5)
        silu = MoEMLP(dataclasses.replace(cfg, expert_act="swiglu")).apply({"params": params}, x)
    assert float(jnp.abs(silu.reshape(16, 64) - got).max()) > 1e-3
    with pytest.raises(ValueError, match="expert_act"):
        MoEMLP(dataclasses.replace(cfg, expert_act="geglu")).init(jax.random.PRNGKey(0), x)


def test_the_eight_expert_shares_add_up_to_the_uncut_layer():
    """Eight chips hold two experts each of one layer's sixteen, all handed
    the same logits (the router is replicated). The routed parts the eight
    compute are the uncut reference layer; no chip drops a row and their
    held shares are the whole."""
    whole = tiny(moe_num_primary_experts=16, expert_parallel_chips=1, expert_parallel_index=0)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 64))
    logits = 1.5 * jax.random.normal(jax.random.PRNGKey(2), (2, 32, 16))
    full = MoEMLP(adapter.model_config(whole, 32))
    params = full.init(jax.random.PRNGKey(0), x, logits)["params"]
    assert "router" not in params  # handed logits, the layer holds no router
    m, r = x.reshape(-1, 64), logits.reshape(-1, 16)
    ident = lambda a: a  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want, _ = reference.experts(m, r, params, whole, ident)
        assert jnp.allclose(full.apply({"params": params}, x, logits).reshape(want.shape), want,
                            rtol=1e-4, atol=1e-5)
        total, held_share = jnp.zeros_like(want), 0.0
        for index in range(8):
            share = tiny(moe_num_primary_experts=2, expert_parallel_chips=8,
                         expert_parallel_index=index)
            own = {k: v[2 * index : 2 * index + 2] for k, v in params.items()}
            out, sown = MoEMLP(adapter.model_config(share, 32)).apply(
                {"params": own}, x, logits, mutable=["intermediates"])
            sown = sown["intermediates"]
            total = total + out.reshape(want.shape)
            held_share += float(sown["moe_held_share"][0])
            assert float(sown["moe_dropped"][0]) == 0.0
            # the share's own reference is the share
            assert jnp.allclose(out.reshape(want.shape),
                                reference.experts(m, r, own, share, ident)[0],
                                rtol=1e-4, atol=1e-5)
    assert jnp.allclose(total, want, rtol=1e-4, atol=1e-5)
    assert held_share == pytest.approx(1.0) and float(jnp.linalg.norm(want)) > 0.1


# -- (c) the steps, the counters, the mesh ------------------------------------------


# -- (d) the file, the adapter, the presets -----------------------------------------


