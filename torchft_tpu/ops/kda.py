"""Pallas TPU kernels for the chunked delta rule with a decay a KEY CHANNEL
(Kimi Delta Attention, arXiv:2510.26692), forward and backward.

``models/gated_delta.py:kda_chunked`` is the plain form and the contract:
q, k [B, S, H, d_k] normalised (q scaled), v [B, S, H, d_v], g [B, S, H,
d_k] (<= 0) and beta [B, S, H] in float32, chunks of 64 in sub-blocks of
16; o [B, S, H, d_v] in float32 and the state after the last position
[B, H, d_k, d_v]; a ragged end is padded with steps of beta = 0, g = 0. In
plain XLA it writes to HBM, for every chunk, four decayed copies of K, the
sub-blocks' products, A, T, W, U, V', Q K^T and the entering state. Here
all of that lives in VMEM.

What is ``ops/gated_delta.py``'s (read its module text first) and is used
from there as it is: positions along lanes, operands [B, H d, S]; one
program a (batch, head, PAIR of chunks), the pair axis innermost and
sequential; a pair's C x C matrices as the diagonal blocks of one
transposed [128, 128] ([j, i]); T = (I + A)^-1 by ``_unit_lower_inverse``;
the differentiated forward pass saves each chunk's entering state and T;
the backward pass is one reverse sweep with the state's gradient in
scratch.

What is this rule's own: the decay sits inside the sums,

    KK_ij = sum_c k_ic k_jc exp(G_ic - G_jc)   (A_ij = beta_i KK_ij, i > j)
    P_ij  = sum_c q_ic k_jc exp(G_ic - G_jc)   (i >= j)

with G a [d_k, 128] float32 tile, the running sum of g along lanes a
channel. Both matrices are built in two parts, every exponential of a
difference <= 0 (no (k e^G) (k e^-G): that overflows inside a chunk):

- a sub-block's rows against EARLIER sub-blocks' columns: MXU products of
  re-based operands in the compute type, (x_i e^{G_i - G_r}) with
  (k_j e^{G_r - G_j}), r the first row of i's sub-block; one row operand
  serves all sub-blocks, the column operand is one a sub-block index
  (three), and a product's columns outside its sub-block are masked off;
- the 16 x 16 diagonal sub-blocks channel by channel in float32 on the
  VPU: for each of the 16 offsets s = i - j one tile exp(G_i - G_{i-s})
  (G and k turned s lanes), which serves K K^T and Q K^T both; the sum over
  the channels is one sub-diagonal, a [1, 128] row, selected into [j, i].

The state is held ONE way round, [d_k, d_v] float32: its rows are the key
channels, as G's are, so a chunk's decay e^{G_C} is a column broadcast
along lanes, and the decay's gradient through the state a sum along
lanes. Products that need it from the left transposed contract its first
dimension. The backward pass returns dg for every key channel, each entry
a sum of like terms: the gradient of log E_ij = G_i - G_j goes into G_i
with i's row terms and out of G_j with j's column terms, the SAME products
that make dq and dk, times q or k; then one reverse running sum.

Precisions are ``kda_chunked``'s: g, G and every exponential float32; A, T
and the carried state float32; matmul operands in the compute type with
float32 accumulation, cast where the plain form casts.

Every call's first result is chunk-laid, [B, nc, H, 1, C] (the
benchmark's ``kda_ms.scan_patterns`` name a device operation by the shape
of its first result): the backward pass's is dbeta; the forward pass's,
the least cumulative log-decay of a position's channels, is written for
that alone and nothing reads it. The calls are named ``kda_fwd`` and
``kda_bwd``, each under a jit of that name.
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from torchft_tpu.ops import gated_delta as gdn
from torchft_tpu.ops.gated_delta import (
    _BLOCK,
    _HIGHEST,
    _NT,
    _PAIR,
    _PARAMS,
    _TN,
    CHUNK,
    _by_chunk,
    _dims,
    _dot,
    _iota2,
    _masks,
    _running_sum,
    _specs,
    _unit_lower_inverse,
    kernel_layout,
    supports,
)

__all__ = ["kda", "kda_bwd", "kda_fwd", "kernel_layout"]

Dtype = Any
_SUBS = CHUNK // _BLOCK  # sub-blocks a chunk
_LOG_CHUNK, _LOG_BLOCK = CHUNK.bit_length() - 1, _BLOCK.bit_length() - 1


# ---------------------------------------------------------------------------
# What both kernels compute first, in one place.
# ---------------------------------------------------------------------------


def _offsets(cum, k):
    """For each offset s = i - j inside a 16 x 16 diagonal sub-block:
    (s, where [j, i] holds that sub-diagonal, e^{G_i - G_{i-s}} [d_k, 128]
    (zero where i - s is before i's sub-block), k_{i-s} decayed by it)."""
    n = _PAIR
    row, lane = _iota2((n, n), 0), _iota2((n, n), 1)
    # [j, i] -> i - j inside a diagonal sub-block, -1 elsewhere: made once,
    # so that an offset's mask is one comparison
    offset = jnp.where(
        (row >> _LOG_BLOCK == lane >> _LOG_BLOCK) & (row <= lane), lane - row, -1
    )
    in_block = _iota2((1, n), 1) & (_BLOCK - 1)
    yield 0, offset == 0, None, k
    for s in range(1, _BLOCK):
        e = jnp.exp(jnp.where(in_block >= s, cum - pltpu.roll(cum, s, 1), -jnp.inf))
        yield s, offset == s, e, pltpu.roll(k, s, 1) * e


def _pair_algebra(m, q_ref, k_ref, v_ref, g_ref, beta_ref, dtype, inverse):
    """A pair of chunks' cumulative log-decays a channel, the re-based
    operands, the transposed C x C matrices, T, W^T, U^T and the decayed keys
    and queries, positions along lanes. ``inverse``: A^T -> T (the backward
    pass reads what the forward pass saved and does not invert again)."""
    f32 = jnp.float32
    n = _PAIR
    beta = beta_ref[pl.ds(pl.program_id(1), 1), :]  # every head's rows come
    q, k = q_ref[...].astype(f32), k_ref[...].astype(f32)
    v = v_ref[...].astype(f32)
    cum = _running_sum(g_ref[...])  # G [d_k, 128]
    lane = _iota2((1, n), 1)
    at = lambda x, i: x[:, i : i + 1]  # noqa: E731 - one position's column
    first = m["first"]

    # Rows decayed back to their sub-block's first row: e^{G_i - G_r}.
    base = at(cum, 0)
    for b in range(1, n // _BLOCK):
        base = jnp.where(lane >= b * _BLOCK, at(cum, b * _BLOCK), base)
    rel = jnp.exp(cum - base)
    qr, kr = (q * rel).astype(dtype), (k * rel).astype(dtype)
    # Columns before sub-block I of their chunk, decayed up to I's first
    # row: e^{G_r - G_j}; one product a sub-block index, its columns kept.
    row2, lane2 = _iota2((n, n), 0), _iota2((n, n), 1)
    same = (row2 >> _LOG_CHUNK) == (lane2 >> _LOG_CHUNK)
    kk_t = jnp.zeros((n, n), f32)
    qk_t = jnp.zeros((n, n), f32)
    earlier = []
    for i in range(1, _SUBS):
        r = i * _BLOCK
        to_r = jnp.where(first, at(cum, r), at(cum, CHUNK + r)) - cum
        decay = jnp.exp(jnp.where((lane & (CHUNK - 1)) < r, to_r, -jnp.inf))
        cols = (k * decay).astype(dtype)
        mask = same & (((lane2 >> _LOG_BLOCK) & (_SUBS - 1)) == i)
        kk_t = jnp.where(mask, _dot(cols, kr, _TN), kk_t)
        qk_t = jnp.where(mask, _dot(cols, qr, _TN), qk_t)
        earlier.append((decay, cols, mask))
    # The diagonal sub-blocks, channel by channel.
    rows = lambda x: jnp.sum(x, axis=0, keepdims=True)  # noqa: E731
    for s, on, _, ks in _offsets(cum, k):
        qk_t = jnp.where(on, rows(q * ks), qk_t)
        if s:
            kk_t = jnp.where(on, rows(k * ks), kk_t)

    t = inverse(beta * kk_t)  # A^T: strictly upper inside a chunk's block
    td = t.astype(dtype)
    grow = jnp.exp(cum)  # e^G: the entering state's decay to position i
    whole = jnp.where(first, at(cum, CHUNK - 1), at(cum, n - 1))  # G_C
    to_end = jnp.exp(whole - cum)
    kb = (beta * grow * k).astype(dtype)
    vb = (beta * v).astype(dtype)
    return dict(
        cum=cum, beta=beta, grow=grow, to_end=to_end, rel=rel, q=q, k=k, v=v,
        qr=qr, kr=kr, earlier=earlier, kk_t=kk_t, qk_t=qk_t,
        t=t, td=td, kb=kb, vb=vb,
        w=_dot(kb, td, _NT).astype(dtype),  # W^T [d_k, 128]
        u=_dot(vb, td, _NT),  # U^T [d_v, 128] float32
        ke=(k * to_end).astype(dtype),  # K e^{G_C - G}
        qg=(q * grow).astype(dtype),  # Q e^G
    )


def _chunk_decay(cum, c):
    """[d_k, 1]: e^{G_C} of chunk ``c`` of the pair, a channel."""
    last = (c + 1) * CHUNK - 1
    return jnp.exp(cum[:, last : last + 1])


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    q_ref, k_ref,  # [d_k, 128]
    v_ref,  # [d_v, 128]
    g_ref,  # [d_k, 128] f32
    beta_ref,  # [H, 128] f32, every head's
    least_ref,  # out [2, 1, 64] f32: the channels' least cumulative log-decay
    o_ref,  # out [d_v, 128] f32
    last_ref,  # out [d_k, d_v] f32: the state after this pair
    # with save: entering [2, d_k, d_v] f32, t [128, 128] f32; then scratch
    *rest,
    dtype: Dtype, save: bool,
):
    if save:
        entering_ref, t_ref, state_ref, tr_ref, rows_ref = rest
    else:
        state_ref, tr_ref, rows_ref = rest

    @pl.when(pl.program_id(2) == 0)
    def _start():
        state_ref[...] = jnp.zeros_like(state_ref)

    m = _masks()
    a = _pair_algebra(
        m, q_ref, k_ref, v_ref, g_ref, beta_ref, dtype,
        lambda a_t: _unit_lower_inverse(a_t, m, tr_ref, rows_ref),
    )
    cum, first = a["cum"], m["first"]
    _by_chunk(least_ref, jnp.min(cum, axis=0, keepdims=True))
    if save:
        t_ref[...] = a["t"]

    state = state_ref[...]  # S [d_k, d_v]
    v_new, from_state = None, None
    for c in range(2):
        here = first if c == 0 else ~first
        sd = state.astype(dtype)
        if save:
            entering_ref[c] = state
        v_c = a["u"] - _dot(sd, a["w"], _TN)  # V'^T = U^T - S^T W^T
        o_c = _dot(sd, a["qg"], _TN)
        v_new = v_c if c == 0 else jnp.where(first, v_new, v_c)
        from_state = o_c if c == 0 else jnp.where(first, from_state, o_c)
        v_here = jnp.where(here, v_c, 0.0).astype(dtype)
        state = state * _chunk_decay(cum, c) + _dot(a["ke"], v_here, _NT)
    state_ref[...] = state
    last_ref[...] = state
    o_ref[...] = _dot(v_new.astype(dtype), a["qk_t"].astype(dtype)) + from_state


@functools.partial(jax.jit, static_argnames=("dtype", "save", "interpret"))
def kda_fwd(q, k, v, g, beta, dtype, save, interpret):
    """(least, o, last state) or, with ``save``, also the residuals (each
    chunk's entering state, T). q, k [B, H d_k, S], v [B, H d_v, S], g
    [B, H d_k, S] and beta [B, H, S] float32; o as v, float32; the state
    [B, H, d_k, d_v]. Its own jit so that the device operation is named for
    it whatever transforms it."""
    bsz, seq, heads, dk, dv = _dims(q, v, beta)
    pairs = seq // _PAIR
    sp = _specs(pairs, heads, dk, dv)
    f32 = jnp.float32
    out_shape = [
        jax.ShapeDtypeStruct((bsz, 2 * pairs, heads, 1, CHUNK), f32),
        jax.ShapeDtypeStruct(v.shape, f32),
        jax.ShapeDtypeStruct((bsz, heads, dk, dv), f32),
    ]
    out_specs = [sp["chunk_row"], sp["v"], sp["state"]]
    if save:
        out_shape += [
            jax.ShapeDtypeStruct((bsz, heads, 2 * pairs, dk, dv), f32),
            jax.ShapeDtypeStruct((bsz, heads, pairs, _PAIR, _PAIR), f32),
        ]
        out_specs += [sp["entering"], sp["t"]]
    return pl.pallas_call(
        functools.partial(_fwd_kernel, dtype=dtype, save=save),
        out_shape=out_shape,
        grid=(bsz, heads, pairs),
        in_specs=[sp["k"], sp["k"], sp["v"], sp["k"], sp["row"]],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((dk, dv), f32),
            pltpu.VMEM((_PAIR, _PAIR), f32),
            pltpu.VMEM((2, _BLOCK, _PAIR // _BLOCK, _PAIR), f32),
        ],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="kda_fwd",
    )(q, k, v, g, beta)


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------


def _bwd_kernel(
    q_ref, k_ref,  # [d_k, 128], as ``_fwd_kernel``
    v_ref,  # [d_v, 128]
    g_ref,  # [d_k, 128] f32
    beta_ref,  # [H, 128] f32
    do_ref,  # [d_v, 128] f32
    entering_ref,  # [2, d_k, d_v] f32: the state entering each chunk
    t_ref,  # [128, 128] f32
    dlast_ref,  # [d_k, d_v] f32: gradient of the state after the last position
    dbeta_ref,  # out [2, 1, 64] f32
    dq_ref, dk_ref,  # out [d_k, 128]
    dv_ref,  # out [d_v, 128]
    dg_ref,  # out [d_k, 128] f32
    dstate_ref,  # scratch [d_k, d_v] f32: gradient of the state LEAVING this pair
    *, dtype: Dtype,
):
    @pl.when(pl.program_id(2) == 0)
    def _start():
        dstate_ref[...] = dlast_ref[...]

    m = _masks()
    a = _pair_algebra(
        m, q_ref, k_ref, v_ref, g_ref, beta_ref, dtype, lambda a_t: t_ref[...]
    )
    cum, beta, grow, to_end, first = a["cum"], a["beta"], a["grow"], a["to_end"], m["first"]
    q, k, v, t, td, rel = a["q"], a["k"], a["v"], a["t"], a["td"], a["rel"]
    w, ke, qg, p_t = a["w"], a["ke"], a["qg"], a["qk_t"]
    dod = do_ref[...].astype(dtype)

    # V'^T of both chunks, from the entering states the forward pass saved
    entering = [entering_ref[c].astype(dtype) for c in range(2)]
    v_new = a["u"] - jnp.where(
        first, _dot(entering[0], w, _TN), _dot(entering[1], w, _TN)
    )
    vd = v_new.astype(dtype)
    dv_inside = _dot(dod, p_t.astype(dtype), _NT)  # dV'^T from o inside the chunk
    dp_t = _dot(vd, dod, _TN)  # [j, i]

    # The state's gradient, last chunk first: what leaves a chunk reaches V'
    # through K e^{G_C - G}, and the entering state through its rows' decays,
    # Q e^G and W.
    dstate = dstate_ref[...]
    dv_new = dke = through = None
    for c in (1, 0):
        here = first if c == 0 else ~first
        dsd = dstate.astype(dtype)
        dv_c = dv_inside + _dot(dsd, ke, _TN)  # dV'^T [d_v, 128]
        dke_c = _dot(dsd, vd)  # d(K e^{G_C - G})^T [d_k, 128]
        decay = _chunk_decay(cum, c)
        through_c = decay * jnp.sum(dstate * entering_ref[c], axis=1, keepdims=True)
        dv_here = jnp.where(here, dv_c, 0.0).astype(dtype)
        qg_here = jnp.where(here, qg, jnp.zeros_like(qg))
        dstate = dstate * decay + _dot(qg_here, dod, _NT) - _dot(w, dv_here, _NT)
        if c == 1:
            dv_new, dke, through = dv_c, dke_c, through_c
        else:
            dv_new = jnp.where(first, dv_c, dv_new)
            dke = jnp.where(first, dke_c, dke)
            through = jnp.where(first, through_c, through)  # [d_k, 128]
    dstate_ref[...] = dstate

    dvd = dv_new.astype(dtype)  # dU^T = dV'^T
    dw = -jnp.where(first, _dot(entering[0], dvd), _dot(entering[1], dvd))
    dqg = jnp.where(first, _dot(entering[0], dod), _dot(entering[1], dod))
    dwd = dw.astype(dtype)
    # dT^T [j, i], then dA^T = -(T dT^T T) inside the chunks' strict triangles
    dt_t = _dot(a["vb"], dvd, _TN) + _dot(a["kb"], dwd, _TN)
    m_t = jnp.where(
        m["strict"], -_dot(_dot(t, dt_t, precision=_HIGHEST), t, precision=_HIGHEST), 0.0
    )
    dvb = _dot(dvd, td)  # d(beta V)^T
    dkb = _dot(dwd, td)  # d(beta e^G K)^T

    # Through the decayed products: d(KK)^T = dA^T beta and d(P)^T, [j, i].
    # A row's part (position i as x_i) and a column's (position j as k_j),
    # first against the earlier sub-blocks on the MXU, then the diagonal
    # sub-blocks offset by offset.
    dkk_t = m_t * beta
    dqk_t = jnp.where(m["upper"], dp_t, 0.0)
    zero = jnp.zeros_like(k)
    dq_row, dk_row, dk_col = zero, zero, zero
    for decay, cols, mask in a["earlier"]:
        dkk_i = jnp.where(mask, dkk_t, 0.0).astype(dtype)
        dqk_i = jnp.where(mask, dqk_t, 0.0).astype(dtype)
        dq_row += _dot(cols, dqk_i)
        dk_row += _dot(cols, dkk_i)
        dk_col += decay * (_dot(a["kr"], dkk_i, _NT) + _dot(a["qr"], dqk_i, _NT))
    dq_row, dk_row = dq_row * rel, dk_row * rel
    rows = lambda x: jnp.sum(x, axis=0, keepdims=True)  # noqa: E731
    for s, on, e, ks in _offsets(cum, k):
        dqk_s = rows(jnp.where(on, dqk_t, 0.0))  # [1, 128]: d(P)_{i, i-s}
        dq_row += dqk_s * ks
        if s == 0:
            dk_col += dqk_s * q
            continue
        dkk_s = rows(jnp.where(on, dkk_t, 0.0))
        dk_row += dkk_s * ks
        dk_col += pltpu.roll((dkk_s * k + dqk_s * q) * e, _PAIR - s, 1)

    dq_ref[...] = (dq_row + dqg * grow).astype(dq_ref.dtype)
    dk_ref[...] = (
        dk_row + dk_col + dkb * (beta * grow) + dke * to_end
    ).astype(dk_ref.dtype)
    dv_ref[...] = (dvb * beta).astype(dv_ref.dtype)
    _by_chunk(
        dbeta_ref, rows(m_t * a["kk_t"]) + rows(dkb * (grow * k)) + rows(dvb * v)
    )

    # dg_k a channel, sums of like terms (no difference of rounded totals):
    # the gradient of log E_ij = G_i - G_j into G_i with i's row terms and
    # out of G_j with j's column terms, the products that made dq and dk;
    # the entering state's part in o_i and in W_i; position j's part in the
    # leaving state, over j < k; and the entering state's part in the
    # leaving state, for every k.
    into = q * dq_row + k * dk_row + dqg * (q * grow) + dkb * (beta * grow * k)
    out_of = k * dk_col
    leaving = dke * (k * to_end)
    lane = _iota2(leaving.shape, 1) & (CHUNK - 1)
    before = jnp.where(lane >= 1, pltpu.roll(leaving, 1, 1), 0.0)  # j < k: shifted by one
    dg_ref[...] = _running_sum(into - out_of, reverse=True) + _running_sum(before) + through


@functools.partial(jax.jit, static_argnames=("dtype", "interpret"))
def kda_bwd(q, k, v, g, beta, do, entering, t, dlast, dtype, interpret):
    """(dbeta by chunk, dq, dk, dv, dg) by one reverse sweep over the pairs
    of chunks; layouts as ``kda_fwd``'s, dg as g."""
    bsz, seq, heads, dk, dv = _dims(q, v, beta)
    pairs = seq // _PAIR
    sp = _specs(pairs, heads, dk, dv, reverse=True)
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_bwd_kernel, dtype=dtype),
        out_shape=[
            jax.ShapeDtypeStruct((bsz, 2 * pairs, heads, 1, CHUNK), f32),
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct(g.shape, f32),
        ],
        grid=(bsz, heads, pairs),
        in_specs=[
            sp["k"], sp["k"], sp["v"], sp["k"], sp["row"], sp["v"], sp["entering"],
            sp["t"], sp["state"],
        ],
        out_specs=[sp["chunk_row"], sp["k"], sp["k"], sp["v"], sp["k"]],
        scratch_shapes=[pltpu.VMEM((dk, dv), f32)],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="kda_bwd",
    )(q, k, v, g, beta, do, entering, t, dlast)


# ---------------------------------------------------------------------------
# custom_vjp over the kernels' layouts, and the wrapper in the model's
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _kda(q, k, v, g, beta, dtype, interpret):
    return tuple(kda_fwd(q, k, v, g, beta, dtype, False, interpret)[1:3])


def _kda_fwd(q, k, v, g, beta, dtype, interpret):
    _, o, last, *saved = kda_fwd(q, k, v, g, beta, dtype, True, interpret)
    return (o, last), (q, k, v, g, beta, *saved)


def _kda_bwd(dtype, interpret, res, cts):
    q, k, v, g, beta, entering, t = res
    do, dlast = cts
    dbeta, dq, dk, dv, dg = kda_bwd(
        q, k, v, g, beta, do.astype(jnp.float32), entering, t,
        dlast.astype(jnp.float32), dtype, interpret,
    )
    bsz, _, heads, _, _ = dbeta.shape
    # [B, nc, H, 1, C] -> [B, H, S]: chunk-laid, as what leaves a kernel first.
    dbeta = jnp.swapaxes(dbeta[:, :, :, 0], 1, 2).reshape(bsz, heads, -1)
    return dq, dk, dv, dg, dbeta


_kda.defvjp(_kda_fwd, _kda_bwd)


def kda(
    q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array,
    chunk: int, dtype: Dtype, interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """``kda_chunked`` by the kernels; differentiable in all five inputs.
    The caller has asked ``supports(..., channel_decay=True)``."""
    bsz, seq, heads, dk = q.shape
    dv = v.shape[-1]
    if g.shape != q.shape or not supports(chunk, dk, dv, heads, seq, channel_decay=True):
        raise ValueError(
            f"kda: chunk {chunk}, keys of {dk}, values of {dv}, a decay of "
            f"shape {g.shape} are not the kernels' shapes; use kda_chunked "
            "(gated_delta for one decay a head)"
        )
    pad = -seq % _PAIR
    if pad:
        q, k, v, g, beta = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (q, k, v, g, beta)
        )
    o, last = _kda(
        *kernel_layout(q, k, v, g, beta),
        jnp.dtype(dtype), gdn._interpret() if interpret is None else interpret,
    )
    return jnp.swapaxes(o, 1, 2).reshape(bsz, seq + pad, heads, dv)[:, :seq], last
