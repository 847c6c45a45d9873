"""The channel-decayed delta rule's Pallas kernels (``ops/kda.py``) on the CPU
through the Pallas interpreter, at small shapes the kernels support: values
and the last state against the plain chunked form and against the
position-by-position recurrence, the five gradients (dg a key channel
among them) against autodiff of the plain form in float32 and in bfloat16,
one chunk, several pairs and a ragged end, decays a factored chunk would
overflow on beside channels that hardly decay, a state grown past 100,
padding steps, one key all through a chunk under beta = 2, ``supports``
with and without ``channel_decay``, the mixer's routing with its
``traced=`` note and its counters, and the shape every call leads with
against the benchmark's ``kda_ms.scan_patterns``.

A case compiles an interpreted kernel in seconds and runs it in
milliseconds, so the cases share two sequence lengths and one set of widths
and every compiled function is kept for the cases after it."""

import functools
import logging
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells
from benchmark.metrics import kda_ms
from torchft_tpu.models import KDAConfig, gated_delta as mixer_module
from torchft_tpu.models.gated_delta import KimiDeltaMixer, kda_chunked
from torchft_tpu.ops import gated_delta as gdn
from torchft_tpu.ops import kda
from tests.test_solar_open2 import _rule_inputs

reference = cells.arch_module("solar_open2", "reference")

HEADS, D = 2, 16  # whole sublane tiles
ONE_CHUNK, RAGGED = 64, 150  # half a lane tile; two pairs, the last chunk cut


def _inputs(seq, decay, seed=0):
    """``test_solar_open2._rule_inputs`` at the kernels' widths; 'mixed':
    a log-decay of -60 a step on every fourth channel (a chunk's sum is
    -3,840; e^-60 is the cell's ``kda_decay_min`` of 0) beside channels
    near 0, and values large enough that the state passes 100."""
    mixed = decay == "mixed"
    (q, k, v, g, beta), weigh = _rule_inputs(
        seq, "slow" if mixed else decay, heads=HEADS, dk=D, dv=D, seed=seed
    )
    if mixed:
        g = jnp.where(jnp.arange(D) % 4 == 0, -60.0, g)
        v = 150.0 * v
    return (q, k, v, g, beta), weigh


def _kernel(dtype):
    return lambda *a: kda.kda(*a, gdn.CHUNK, dtype, interpret=True)


def _plain(dtype):
    return lambda *a: kda_chunked(*a, gdn.CHUNK, dtype)


@functools.lru_cache(maxsize=None)
def _values(form, dtype):
    return jax.jit({"kernel": _kernel, "plain": _plain}[form](dtype))


@functools.lru_cache(maxsize=None)
def _grads(form, dtype):
    """d/d(q, k, v, g, beta) of sum(o * weigh) + sum(sin(last state))."""
    rule = {"kernel": _kernel, "plain": _plain}[form](dtype)

    def scalar(weigh, *a):
        o, last = rule(*a)
        return jnp.sum(o * weigh) + jnp.sum(jnp.sin(last))

    return jax.jit(jax.grad(scalar, argnums=(1, 2, 3, 4, 5)))


_recurrence = jax.jit(jax.vmap(reference.delta_rule))


@pytest.mark.parametrize("seq,decay", [
    (ONE_CHUNK, "slow"), (ONE_CHUNK, "fast"), (RAGGED, "spread"), (RAGGED, "initial"),
    (RAGGED, "fast"), (RAGGED, "mixed"),
])
def test_values_and_the_last_state_are_the_plain_forms_and_the_recurrences(seq, decay):
    args, _ = _inputs(seq, decay)
    assert float(args[4].max()) > 1.9 and float(args[4].min()) < 0.1
    with jax.default_matmul_precision("highest"):
        got = _values("kernel", jnp.float32)(*args)
        plain = _values("plain", jnp.float32)(*args)
        want = _recurrence(*args)
    if decay == "mixed":
        assert float(args[3].min()) == -60.0 and float(args[3].max()) > -0.02
        assert float(jnp.abs(want[1]).max()) > 100.0
    scale = max(1.0, float(jnp.abs(want[0]).max()) / 4)
    for other in (plain, want):
        assert got[0].shape == other[0].shape and got[1].shape == other[1].shape
        assert bool(jnp.isfinite(got[0]).all() and jnp.isfinite(got[1]).all())
        assert jnp.allclose(got[0], other[0], rtol=2e-4, atol=2e-5 * scale)
        assert jnp.allclose(got[1], other[1], rtol=2e-4, atol=2e-5 * scale)


@pytest.mark.parametrize("seq,decay", [
    (ONE_CHUNK, "spread"), (RAGGED, "spread"), (RAGGED, "slow"), (RAGGED, "initial"),
    (RAGGED, "mixed"),
])
def test_float32_gradients_are_autodiffs_of_the_plain_form(seq, decay):
    """All five, dg [B, S, H, d] a key channel among them, with the last
    state's gradient entering the sweep."""
    args, weigh = _inputs(seq, decay, seed=1)
    with jax.default_matmul_precision("highest"):
        got = _grads("kernel", jnp.float32)(weigh, *args)
        want = _grads("plain", jnp.float32)(weigh, *args)
    assert got[3].shape == args[3].shape == args[0].shape
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert bool(jnp.isfinite(a).all()), name
        assert float(jnp.abs(a - b).max()) <= 2e-4 * float(jnp.abs(b).max()) + 1e-6, name


def test_values_wider_than_the_keys_are_the_plain_forms_too():
    """``supports`` admits keys and values of different widths (the mixer
    builds one width; ``tests/test_tpu_compile.py`` compiles 16 / 128 and
    128 / 16): values, the last state and the five gradients at 16 / 32."""
    args, weigh = _rule_inputs(RAGGED, "spread", heads=HEADS, dk=D, dv=2 * D, seed=5)
    assert gdn.supports(gdn.CHUNK, D, 2 * D, HEADS, RAGGED, channel_decay=True)
    with jax.default_matmul_precision("highest"):
        got = _values("kernel", jnp.float32)(*args)
        want = _values("plain", jnp.float32)(*args)
        grads = [_grads(form, jnp.float32)(weigh, *args) for form in ("kernel", "plain")]
    assert got[1].shape == (2, HEADS, D, 2 * D)
    assert jnp.allclose(got[0], want[0], rtol=2e-4, atol=2e-5)
    assert jnp.allclose(got[1], want[1], rtol=2e-4, atol=2e-5)
    for name, a, b in zip("q k v g beta".split(), *grads):
        assert float(jnp.abs(a - b).max()) <= 2e-4 * float(jnp.abs(b).max()) + 1e-6, name


def _rel_l2(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize("decay", ["spread", "slow"])
def test_bfloat16_gradients_are_as_near_the_float32_truth_as_the_plain_forms(decay):
    """bfloat16 operands round every product, in either form; each form's
    distance from the float32 gradients is the measure, never one bfloat16
    form against the other."""
    args, weigh = _inputs(RAGGED, decay, seed=2)
    # one row: XLA's CPU runtime has no batched bf16 x bf16 = f32 product,
    # which the PLAIN form asks of it at a batch of two
    args, weigh = tuple(a[:1] for a in args), weigh[:1]
    truth = _grads("plain", jnp.float32)(weigh, *args)
    got = _grads("kernel", jnp.bfloat16)(weigh, *args)
    plain = _grads("plain", jnp.bfloat16)(weigh, *args)
    for name, t, a, b in zip("q k v g beta".split(), truth, got, plain):
        assert a.dtype == t.dtype, name
        assert _rel_l2(a, t) <= 1.5 * _rel_l2(b, t) + 2e-3, (name, _rel_l2(a, t), _rel_l2(b, t))
        assert _rel_l2(a, t) < 0.05, name


def test_steps_of_beta_zero_and_no_decay_neither_read_nor_write_the_state():
    """What a ragged end is padded with, here written into the inputs: the
    state after them is the state before them, and they change no output
    before them."""
    keep = 100
    (q, k, v, g, beta), _ = _inputs(RAGGED, "spread", seed=3)
    still = jnp.arange(RAGGED)[None, :, None] < keep
    args = (q, k, v, jnp.where(still[..., None], g, 0.0), jnp.where(still, beta, 0.0))
    got = _values("kernel", jnp.float32)(*args)
    want = _values("plain", jnp.float32)(*(a[:, :keep] for a in args))
    assert jnp.allclose(got[0][:, :keep], want[0], rtol=2e-4, atol=2e-5)
    assert jnp.allclose(got[1], want[1], rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("seq", [ONE_CHUNK, RAGGED])
def test_one_key_all_through_a_chunk_under_beta_two_is_still_the_recurrence(seq):
    """A = 2 x strictly-lower ones in every chunk: the case a power series
    of A cannot cancel in float32 and the substitution must."""
    (q, key, v, g, beta), _ = _inputs(seq, "slow", seed=4)
    key = jnp.broadcast_to(key[:, :1], key.shape)
    args = (q, key, v, jnp.zeros_like(g), jnp.full_like(beta, 2.0))
    with jax.default_matmul_precision("highest"):
        got = _values("kernel", jnp.float32)(*args)
        want = _recurrence(*args)
    assert jnp.allclose(got[0], want[0], rtol=1e-3, atol=1e-3)
    assert jnp.allclose(got[1], want[1], rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("chunk,dk,dv,heads,seq,scalar,channel", [
    (64, 128, 128, 8, 8192, True, True),  # solar-open2-raw
    (64, 128, 128, 64, 1048576, True, True),  # the published heads and context
    (64, 16, 16, 4, 5, True, True),  # solar_open2_debug; any sequence, any heads
    (64, 64, 128, 2, 100, True, True),
    (64, 96, 192, 15, 8192, True, False),  # olmo-hybrid-raw: values past the tile of G
    (64, 128, 256, 8, 8192, True, False),
    (64, 8, 8, 4, 64, False, False),  # no whole sublane tile
    (64, 128, 136, 8, 8192, False, False),
    (128, 128, 128, 8, 8192, False, False),  # the chunk is the pair's half, nothing else
    (16, 128, 128, 8, 8192, False, False),
    (64, 144, 128, 8, 8192, False, False),  # past what a program's heads hold in VMEM
])
def test_supports_reads_shapes_only(chunk, dk, dv, heads, seq, scalar, channel):
    """One table for both rules' kernels: the same chunk and sublane tiles;
    a decay a key channel takes values no wider than its keys' tile."""
    assert gdn.supports(chunk, dk, dv, heads, seq) is scalar
    assert gdn.supports(chunk, dk, dv, heads, seq, channel_decay=True) is channel
    z = lambda *s: jnp.zeros(s)  # noqa: E731
    if not channel:
        with pytest.raises(ValueError, match="kda_chunked"):
            kda.kda(z(1, 8, 1, dk), z(1, 8, 1, dk), z(1, 8, 1, dv), z(1, 8, 1, dk),
                    z(1, 8, 1), chunk, jnp.float32, interpret=True)
    elif dk == 16:  # and one decay a head is not read as a channel's
        with pytest.raises(ValueError, match="gated_delta"):
            kda.kda(z(1, 8, 1, dk), z(1, 8, 1, dk), z(1, 8, 1, dv), z(1, 8, 1),
                    z(1, 8, 1), chunk, jnp.float32, interpret=True)


def _mixer(d, heads=HEADS, hidden=32):
    return KimiDeltaMixer(
        KDAConfig(num_heads=heads, head_dim=d), hidden_size=hidden, dtype=jnp.float32
    )


@pytest.mark.parametrize("d,form,level", [
    (D, "kda-kernel", logging.INFO),
    (8, "kda-xla", logging.INFO),  # widths the kernels were never meant for
    (144, "kda-xla", logging.WARNING),  # whole tiles that fell back
])
def test_the_mixer_takes_the_form_the_shapes_allow_and_says_which(
    d, form, level, monkeypatch, caplog
):
    """Routed by ``supports`` alone, from the rank of g and the widths; the
    note, once a (form, chunk, seq), is the record of which form a step
    traced. Where the kernels run, the mixer's output and its sown counters
    are the plain form's."""
    monkeypatch.setattr(gdn, "_interpret", lambda: True)
    seq = 48
    mixer = _mixer(d, heads=HEADS if form == "kda-kernel" else 1)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, seq, 32))
    params = mixer.init(jax.random.PRNGKey(1), x)
    monkeypatch.setattr(mixer_module, "_NOTED", set())
    caplog.clear()
    with caplog.at_level(logging.INFO, logger=mixer_module.__name__):
        y, state = mixer.apply(params, x, mutable="intermediates")
        mixer.apply(params, x)
    notes = [r for r in caplog.records if r.getMessage().startswith("gated_delta: traced=")]
    assert [(r.getMessage(), r.levelno) for r in notes] == [
        (f"gated_delta: traced={form} chunk=64 seq={seq}", level)
    ]
    if form == "kda-kernel":
        monkeypatch.setattr(gdn, "supports", lambda *a, **k: False)
        want, want_state = mixer.apply(params, x, mutable="intermediates")
        assert jnp.allclose(y, want, rtol=2e-4, atol=2e-5)
        for name in ("kda_state_abs_max", "kda_decay_min", "kda_beta_mean"):
            assert jnp.allclose(
                state["intermediates"][name][0], want_state["intermediates"][name][0],
                rtol=2e-4,
            ), name


@pytest.mark.parametrize("call", ["kda_fwd", "kda_fwd_saving", "kda_bwd"])
def test_every_call_leads_with_a_chunk_laid_result_the_metrics_match(call):
    """``kda_ms`` and ``kda_roofline`` name the rule's device operations by
    the shape of an operation's FIRST result; at the cell's shapes that is
    [2, 128, 8, 1, 64] for all three calls (shapes only: nothing runs)."""
    b, s, h, d = 2, 8192, 8, 128
    f32 = jnp.float32
    arg = lambda *shape, dtype=f32: jax.ShapeDtypeStruct(shape, dtype)  # noqa: E731
    q, v, row = arg(b, h * d, s), arg(b, h * d, s, dtype=jnp.bfloat16), arg(b, h, s)
    fwd = functools.partial(kda.kda_fwd, dtype=jnp.dtype(jnp.bfloat16), interpret=True)
    if call == "kda_bwd":
        saved = jax.eval_shape(functools.partial(fwd, save=True), q, q, v, q, row)
        out = jax.eval_shape(
            functools.partial(kda.kda_bwd, dtype=jnp.dtype(jnp.bfloat16), interpret=True),
            q, q, v, q, row, saved[1], *saved[3:], saved[2],
        )
        assert [(o.shape, o.dtype) for o in out[1:]] == [
            (q.shape, f32), (q.shape, f32), (v.shape, jnp.bfloat16), (q.shape, f32)]
    else:
        out = jax.eval_shape(
            functools.partial(fwd, save=call.endswith("saving")), q, q, v, q, row
        )
        assert out[1].shape == v.shape and out[2].shape == (b, h, d, d)
        assert len(out) == (5 if call.endswith("saving") else 3)
    dims = {"b": b, "s": s, "nc": s // 64, "c": 64, "h": h, "d": d, "conv": 3 * h * d, "k": 4}
    first = f"{call}.1 (f32[{','.join(map(str, out[0].shape))}]{{4,3,2,1,0}}, "
    assert out[0].shape == (b, s // 64, h, 1, 64) and out[0].dtype == f32
    scan = kda_ms.any_of(kda_ms.scan_patterns(dims))
    assert re.search(scan, first), first
    # and a result laid out by position or by head would be invisible to them
    for hidden in (f"{call}.1 (f32[{b},{h * d},{s}]", f"{call}.1 (f32[{b},{s},{h},{d}]"):
        assert not re.search(scan, hidden)
