"""Solar-Open2-250B: the Kimi delta mixer's chunked form (a decay a key
channel, sub-blocks of 16) against the position-by-position recurrence,
values and gradients, at decays a factored chunk would overflow on; the
program's stack (a gated rope-free GQA attention, three Kimi delta mixers,
an expert layer after each) against the benchmark's plain reference at a
small size on the CPU, in float32 with seeded weights; the head shares and
the expert shares against the uncut layers; the scalar-decay kernels'
refusal; the harness's own check at a small size. What fails the
comparison, a sharded mesh, the fused step and two replicas under Managers,
the adapter's refusals, the presets and ``train_hsdp.py``:
tests/test_solar_open2_step.py."""

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
from benchmark.tests import test_solar_reference as _reference_tests
from torchft_tpu.coordination import LighthouseServer
from torchft_tpu.models import PRESETS, KDAConfig, gated_delta, llama
from torchft_tpu.models.gated_delta import KimiDeltaMixer, kda_chunked
from torchft_tpu.models.llama import (
    Attention,
    MoEMLP,
    solar_open2_250b,
    solar_open2_debug,
)
from torchft_tpu.ops import gated_delta as gdn_kernel
from torchft_tpu.ops import kda as kda_kernel
from torchft_tpu.parallel import auto_mesh, make_mesh
from torchft_tpu.parallel.sharding import param_specs
from torchft_tpu.parallel.train import (
    build_model,
    init_train_state,
    make_grad_step,
    make_train_step,
    state_shardings,
)
from tests.test_ft_step import two_replicas
from tests.test_nemotron_h import _tiny_table
from tests.test_sdar_moe import _data, _leaf_errors

adapter = cells.arch_module("solar_open2", "adapter")
reference = cells.arch_module("solar_open2", "reference")
flops = cells.arch_module("solar_open2", "flops")
tiny, PUBLISHED = _reference_tests.tiny, _reference_tests.PUBLISHED

# (The benchmark's own tests of this architecture, benchmark/tests/
# test_solar_reference.py, reach tier-1 through tests/test_benchmark_harness.py.)

# -- (a) the chunked form is the recurrence ---------------------------------------

# log-decays g = -exp(u), u uniform in the interval: alpha near 1, alpha
# near 0 (64 positions of it sum far past float32's e^88), both, and the
# mixer's own strongest initial value (A_log = log 16, a step of 0.1).
DECAYS = {"slow": (-9.0, -4.0), "fast": (0.5, 2.0), "spread": (-7.0, 1.5),
          "initial": (0.46, 0.48)}


def _rule_inputs(seq, decay, heads=3, dk=8, dv=12, seed=0):
    """Unit keys, scaled unit queries, beta over the whole of (0, 2), and a
    log-decay of its own for every key channel."""
    k = jax.random.split(jax.random.PRNGKey(seed + seq), 6)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(k[0], (2, seq, heads, dk))) * dk ** -0.5
    key = unit(jax.random.normal(k[1], (2, seq, heads, dk)))
    v = jax.random.normal(k[2], (2, seq, heads, dv))
    lo, hi = DECAYS[decay]
    g = -jnp.exp(jax.random.uniform(k[3], (2, seq, heads, dk), minval=lo, maxval=hi))
    beta = 2.0 * jax.nn.sigmoid(4.0 * jax.random.normal(k[4], (2, seq, heads)))
    return (q, key, v, g, beta), jax.random.normal(k[5], (2, seq, heads, dv))


@pytest.mark.parametrize("chunk,seq,decay", [
    (16, 40, "spread"), (64, 64, "slow"), (64, 150, "spread"), (16, 5, "fast"),
    (64, 128, "fast"), (32, 70, "spread"), (64, 200, "initial"),
])
def test_the_chunked_kda_is_the_recurrence(chunk, seq, decay):
    """Chunks of one, two and four sub-blocks; one chunk, whole chunks, a
    ragged end, less than a chunk; values, the last state, and the gradient
    of every input. Where the decays are strong, a chunk's e^{-G} is past
    float32 (the factored form's overflow) and everything here is finite."""
    args, weigh = _rule_inputs(seq, decay)
    assert float(args[4].max()) > 1.9 and float(args[4].min()) < 0.1
    if decay in ("fast", "initial") and seq >= 64:
        whole_chunk = jnp.sum(args[3][:, :64], axis=1)
        with np.errstate(over="ignore"):
            assert not np.isfinite(np.exp(-np.asarray(whole_chunk, np.float32))).all()
        assert float(whole_chunk.min()) < -100.0
    recurrence = jax.vmap(reference.delta_rule)

    def scalar(rule):
        def f(*a):
            o, last = rule(*a)
            return jnp.sum(o * weigh) + jnp.sum(jnp.sin(last))
        return f

    chunked = lambda *a: kda_chunked(*a, chunk, jnp.float32)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        got, want = jax.jit(chunked)(*args), jax.jit(recurrence)(*args)
        grads = [
            jax.jit(jax.grad(scalar(rule), argnums=(0, 1, 2, 3, 4)))(*args)
            for rule in (chunked, recurrence)
        ]
    assert got[0].shape == want[0].shape and got[1].shape == want[1].shape
    assert all(bool(jnp.isfinite(t).all()) for t in (*got, *grads[0]))
    assert jnp.allclose(got[0], want[0], rtol=2e-4, atol=2e-5)
    assert jnp.allclose(got[1], want[1], rtol=2e-4, atol=2e-5)
    for name, a, b in zip("q k v g beta".split(), *grads):
        assert float(jnp.abs(a - b).max()) <= 2e-4 * float(jnp.abs(b).max()) + 1e-6, name


def test_one_decay_for_all_of_a_heads_channels_is_the_gated_delta_rule():
    """With every channel of a head given the head's one decay, the rule is
    Gated DeltaNet's: the two chunked forms agree."""
    (q, key, v, g, beta), _ = _rule_inputs(100, "spread")
    one = g[..., :1]
    with jax.default_matmul_precision("highest"):
        kda = kda_chunked(q, key, v, jnp.broadcast_to(one, g.shape), beta, 64, jnp.float32)
        gdn = gated_delta.gated_delta_chunked(q, key, v, one[..., 0], beta, 64, jnp.float32)
    assert jnp.allclose(kda[0], gdn[0], rtol=2e-4, atol=2e-5)
    assert jnp.allclose(kda[1], gdn[1], rtol=2e-4, atol=2e-5)


def test_the_chunked_kda_refuses_shapes_it_does_not_compute():
    (q, key, v, g, beta), _ = _rule_inputs(64, "slow")
    with pytest.raises(ValueError, match="kda_chunked"):
        kda_chunked(q, key, v, g[..., 0], beta, 64, jnp.float32)  # one decay a head
    with pytest.raises(ValueError, match="kda_chunked"):
        kda_chunked(q, key, v, g, beta, 24, jnp.float32)  # no whole sub-blocks


@pytest.mark.parametrize("dk,dv,channel", [
    (16, 16, True), (96, 192, False), (128, 128, True), (128, 256, False),
])
def test_the_scalar_decay_kernels_refuse_a_decay_a_channel(dk, dv, channel):
    """At every width the scalar-decay kernels take, ``supports`` answers a
    decay a key channel by width (``ops/kda.py``'s kernels hold values no
    wider than the keys' tile), and the scalar kernels' entry point still
    raises for a g [B, S, H, d] instead of reading it as one decay a head."""
    assert gdn_kernel.supports(64, dk, dv, 8, 8192)
    assert gdn_kernel.supports(64, dk, dv, 8, 8192, channel_decay=True) is channel
    if dk == 16:
        (q, key, v, g, beta), _ = _rule_inputs(64, "slow", heads=2, dk=16, dv=16)
        assert gdn_kernel.gated_delta(q, key, v, g[..., 0], beta, 64, jnp.float32,
                                      interpret=True)[0].shape == v.shape
        with pytest.raises(ValueError, match="kda_chunked"):
            gdn_kernel.gated_delta(q, key, v, g, beta, 64, jnp.float32, interpret=True)


# -- (b) the program against the reference -----------------------------------------


def _setup(c, seq, batch=2, seed=0):
    cfg = dataclasses.replace(adapter.model_config(c, seq), remat=False)
    mesh = auto_mesh(1, devices=jax.devices()[:1])
    model = build_model(cfg, mesh)
    data = _data(c["vocab_size"], batch, seq, seed + 1)
    params = model.init(jax.random.PRNGKey(seed), data["inputs"])["params"]
    return model, mesh, params, data


def _system(c, seq, params=None, data=None):
    model, mesh, fresh, batch = _setup(c, seq)
    params, data = params or fresh, data or batch
    with jax.default_matmul_precision("highest"):
        loss, grads = make_grad_step(model, mesh, state_shardings(model, mesh, (2, seq)))(
            params, data)
    return params, data, float(loss), grads


def _worst(errs):
    """The worst leaf a gradient reaches (a selection bias gets none on
    either side: 0/0)."""
    return max(v for v in errs.values() if v == v)


# The CPU comparison's limit on a gradient leaf: float32 on both sides, so
# what is left is the order of the sums. A chunk's decays are exponentials
# of DIFFERENCES of cumulative log-decays, which at this size's strong
# decays (the random low-rank projection drives a step's log-decay to -30,
# a chunk's sum to the thousands) keep 1e-4 of a difference near 0; the
# worst leaf reads 1e-5 to 3e-5. Anything rounded to bf16 (2^-9) reads
# above it, and so does every dropped term.
CPU_GRAD_TOL = 1e-3


@pytest.mark.parametrize("seq,index", [(40, 1), (64, 0), (130, 1)])
def test_loss_and_every_gradient_match_the_reference(seq, index):
    """One period (a gated attention, three Kimi delta mixers, an expert
    layer after each) at less than a chunk, one chunk and a ragged third
    chunk; the second and the first head rank."""
    c = tiny(head_parallel_index=index)
    params, data, loss, grads = _system(c, seq)
    loss_ref, grads_ref = jax.jit(lambda p, b: reference.loss_and_grads(p, b, c))(params, data)
    assert loss == pytest.approx(float(loss_ref), rel=1e-5)
    errs = _leaf_errors(grads, grads_ref)
    # a mixer's 14 leaves, the attention's 5, an expert layer's 8, a norm a
    # sub-layer, the table, the head and the final norm
    assert len(errs) == 3 * 14 + 5 + 4 * 8 + 8 + 3
    assert sum(v != v for v in errs.values()) == 4  # the selection biases
    assert _worst(errs) < CPU_GRAD_TOL, errs
    assert _worst(errs) < reference.GRAD_REL_L2_TOL
    assert abs(loss - float(loss_ref)) / float(loss_ref) < reference.LOSS_REL_TOL


def test_the_gate_stands_between_the_attention_and_its_output_projection():
    """W_o(Y * sigmoid(x W_g)): with W_g = 0 the gate is a half everywhere
    and the layer is half the ungated one; the gate reads the layer's INPUT
    (its value changes with x where the ungated output is held fixed)."""
    cfg = solar_open2_debug(dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, 64))
    gated = Attention(cfg)
    params = gated.init(jax.random.PRNGKey(0), x, None, None)["params"]
    assert set(params) == {"wq", "wk", "wv", "wg", "wo"}
    assert params["wg"]["kernel"].shape == params["wq"]["kernel"].shape == (64, 4, 16)
    plain = Attention(dataclasses.replace(cfg, attn_gate=False))
    ungated = {k: v for k, v in params.items() if k != "wg"}
    zero = dict(params, wg={"kernel": jnp.zeros_like(params["wg"]["kernel"])})
    with jax.default_matmul_precision("highest"):
        want = plain.apply({"params": ungated}, x, None, None)
        assert jnp.allclose(gated.apply({"params": zero}, x, None, None), 0.5 * want,
                            rtol=1e-5, atol=1e-6)
        assert not jnp.allclose(gated.apply({"params": params}, x, None, None), 0.5 * want,
                                atol=1e-3)
    # a model that sets no gate is what it was
    assert "wg" not in Attention(llama.llama_debug()).init(
        jax.random.PRNGKey(0), x, *llama.rope_table(jnp.arange(24)[None], 16, 1e4, jnp.float32)
    )["params"]


# -- (c) the shares tied to the model -------------------------------------------------


def _columns(kernel, width, index, heads):
    """Columns of head-major ``kernel`` [..., ranks x heads x width] that
    the rank ``index`` holds."""
    return kernel[..., index * heads * width : (index + 1) * heads * width]


def test_the_head_shares_of_a_kimi_delta_mixer_add_up_to_the_uncut_mixer():
    """Four heads, or two ranks of two: every head's convolution, decay,
    recurrence, norm and gate are its own, and the low-rank projections'
    bottleneck is every rank's alike (counted once: each rank holds all of
    W_f_a and W_g_a and its own columns of W_f_b and W_g_b), so the partial
    sums W_o's rows give add up exactly."""
    whole = KDAConfig(num_heads=4, head_dim=16)
    half = dataclasses.replace(whole, num_heads=2)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 70, 64))
    mixer = lambda m: KimiDeltaMixer(m, 64, 1e-5, jnp.float32)  # noqa: E731
    params = mixer(whole).init(jax.random.PRNGKey(0), x)["params"]
    params["g_b_proj"]["bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(4), (64,))
    with jax.default_matmul_precision("highest"):
        want = mixer(whole).apply({"params": params}, x)
        total = 0.0
        for index in range(2):
            cut = lambda name, width=16: {  # noqa: E731
                "kernel": _columns(params[name]["kernel"], width, index, 2)}
            conv, held = params["conv_kernel"], slice(32 * index, 32 * index + 32)
            own = {
                "q_proj": cut("q_proj"), "k_proj": cut("k_proj"), "v_proj": cut("v_proj"),
                "b_proj": cut("b_proj", 1),
                "f_a_proj": params["f_a_proj"], "g_a_proj": params["g_a_proj"],
                "f_b_proj": cut("f_b_proj"),
                "g_b_proj": dict(cut("g_b_proj"), bias=params["g_b_proj"]["bias"][held]),
                "o_proj": {"kernel": params["o_proj"]["kernel"][held]},
                "conv_kernel": jnp.concatenate(
                    [_columns(conv[:, 64 * part : 64 * part + 64], 16, index, 2)
                     for part in range(3)], axis=-1),
                "A_log": params["A_log"][2 * index : 2 * index + 2],
                "dt_bias": params["dt_bias"][held],
                "norm_scale": params["norm_scale"],
            }
            assert set(own) == set(params)
            total = total + mixer(half).apply({"params": own}, x)
    assert jnp.allclose(total, want, rtol=1e-4, atol=1e-5)
    assert float(jnp.linalg.norm(total - want)) < 1e-4 * float(jnp.linalg.norm(want))


def test_the_head_shares_of_the_gated_attention_add_up_to_the_uncut_layer():
    """Four query heads on two key/value heads, or two ranks of two on one:
    a rank holds a key/value head with the query heads that read it and the
    gate's channels of those heads; each share is what the reference
    computes given that share, and the partial sums are the uncut layer's."""
    cfg = solar_open2_debug(dtype=jnp.float32)
    half = dataclasses.replace(cfg, num_heads=2, num_kv_heads=1)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 48, 64))
    params = Attention(cfg).init(jax.random.PRNGKey(0), x, None, None)["params"]
    with jax.default_matmul_precision("highest"):
        want = Attention(cfg).apply({"params": params}, x, None, None)
        total = 0.0
        for index in range(2):
            q_heads, kv_head = slice(2 * index, 2 * index + 2), slice(index, index + 1)
            own = {name: {"kernel": params[name]["kernel"][:, q_heads]} for name in ("wq", "wg")}
            own.update({name: {"kernel": params[name]["kernel"][:, kv_head]}
                        for name in ("wk", "wv")})
            own["wo"] = {"kernel": params["wo"]["kernel"][q_heads]}
            part = Attention(half).apply({"params": own}, x, None, None)
            given = reference.attention(
                x, own, tiny(num_attention_heads=2, num_key_value_heads=1), lambda a: a, None)
            assert jnp.allclose(part, given, rtol=1e-4, atol=1e-5)
            total = total + part
    assert jnp.allclose(total, want, rtol=1e-4, atol=1e-5)


def test_the_expert_shares_add_up_to_the_whole_layer_with_the_shared_expert_once():
    """Four chips hold four experts each of one layer's sixteen. The routed
    parts the four compute, plus the shared expert ONCE, are the uncut
    reference layer."""
    whole = tiny(n_routed_experts=16, expert_parallel_chips=1, expert_parallel_index=0)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, whole["hidden_size"]))
    layer = MoEMLP(adapter.model_config(whole, 32))
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    params = dict(params, router_bias=0.05 * jax.random.normal(jax.random.PRNGKey(2), (16,)))
    m = x.reshape(-1, whole["hidden_size"])
    ident = lambda a: a  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want = reference.experts(m, params, whole, ident)
        shared = want - reference.experts(m, params, whole, ident, shared=False)
        total, with_shared, held_share = jnp.zeros_like(want), jnp.zeros_like(want), 0.0
        for index in range(4):
            share = tiny(expert_parallel_index=index)
            own = dict(params, **{
                k: params[k][4 * index : 4 * index + 4]
                for k in ("experts_gate", "experts_up", "experts_down")})
            out, sown = MoEMLP(adapter.model_config(share, 32)).apply(
                {"params": own}, x, mutable=["intermediates"])
            sown = sown["intermediates"]
            with_shared = with_shared + out.reshape(want.shape)
            total = total + out.reshape(want.shape) - shared  # every chip computes it alike
            held_share += float(sown["moe_held_share"][0])
            assert float(sown["moe_dropped"][0]) == 0.0
            # the share's own reference is the share
            assert jnp.allclose(out.reshape(want.shape),
                                reference.experts(m, own, share, ident), rtol=1e-4, atol=1e-5)
    assert jnp.allclose(total + shared, want, rtol=1e-4, atol=1e-5)
    assert held_share == pytest.approx(1.0) and float(jnp.linalg.norm(shared)) > 0.1
    # counted four times, the shared expert would be three too many
    assert jnp.allclose(with_shared - want, 3 * shared, rtol=1e-4, atol=1e-5)


def test_the_row_buffer_is_whole_tiles_and_the_accepted_cells_keep_theirs():
    """XLA's grouped matmul takes its row tile from the divisors of the
    buffer's length: from 512 rows up the buffer is a multiple of 512. The
    buffers the accepted cells' steps were compiled with are multiples
    already (their programs are what they were), and a test-sized buffer
    stays the multiple of 8 it was."""
    def as_it_was(cfg, tokens):  # the rule before PR 58
        assignments = tokens * cfg.num_experts_per_tok
        share = -(-assignments * cfg.experts_held[1] // cfg.num_experts)
        return min(assignments, -(-int(llama.HELD_ROW_FACTOR * share) // 8) * 8)

    for name, tokens, rows in (("nemotron3-raw", 16384, 24576), ("lfm2-raw", 16384, 65536),
                               ("sdar-raw", 32768, 131072), ("joyai-raw", 16384, 16384)):
        cell = cells.load_cell(name)
        cfg = cell.adapter.model_config(cell.config, 8192)
        assert llama.held_buffer_rows(cfg, tokens) == as_it_was(cfg, tokens) == rows, name
    cfg = adapter.model_config(PUBLISHED, 8192)
    assert (as_it_was(cfg, 16384), llama.held_buffer_rows(cfg, 16384)) == (13112, 13312)
    small = solar_open2_debug()
    for tokens in (64, 128, 512):
        assert llama.held_buffer_rows(small, tokens) == as_it_was(small, tokens)
    # past the threshold the rule rounds up, and never past every assignment
    wide = dataclasses.replace(small, num_experts=64, experts_held=(0, 4))
    assert (as_it_was(wide, 4096), llama.held_buffer_rows(wide, 4096)) == (3072, 3072)
    assert (as_it_was(wide, 4104), llama.held_buffer_rows(wide, 4104)) == (3080, 3584)
    assert llama.held_buffer_rows(small, 1024) == 3072 == 1024 * 3  # T*K at most


# -- (d) the steps, the counters, the mesh ------------------------------------------


# -- (e) the file, the adapter, the presets -----------------------------------------



def test_the_harness_check_passes_at_a_small_size(tmp_path, monkeypatch):
    """``worker.reference_check`` as the chip run makes it: the selection
    biases' gradients are zero on both sides, their relative error 0/0,
    and the worst leaf is the worst of the others."""
    from benchmark import worker

    first = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: first)
    monkeypatch.setattr(worker, "CHECK_SEQ", 48)
    cell = cells.load_cell("w", _tiny_table(tmp_path, tiny()))
    cell.mix.update(batch=1, seq=48)
    out = worker.reference_check(worker.Ctx(cell, 3000000001, 0, False))
    assert out["ok"] and out["grad_rel_l2_worst"] < CPU_GRAD_TOL and out["loss_rel_diff"] < 1e-5
    assert "router_bias" not in out["grad_rel_l2_worst_leaf"]
