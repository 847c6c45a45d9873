"""Gated DeltaNet mixer (arXiv:2412.06464; flash-linear-attention's
``GatedDeltaNet``, whose ``linear_*`` keys Olmo-Hybrid's config.json
carries): linear attention whose state a head is *corrected* by a
rank-one delta, not only decayed and added to.

For an input x [B, S, hidden], H heads, keys of d_k, values of d_v::

    q, k, v = silu(conv1d_causal_depthwise([x W_q | x W_k | x W_v]))   no bias
    q_t = q_t / |q_t|_2 / sqrt(d_k),  k_t = k_t / |k_t|_2              a head
    beta_t = sigmoid(x_t W_b)   (x 2 where ``allow_neg_eigval``)       [H]
    g_t = -exp(A_log) * softplus(x_t W_a + dt_bias),  alpha_t = exp(g_t)
    S_t = alpha_t S_{t-1} + k_t u_t^T,   S_0 = 0 in R^{d_k x d_v}
    u_t = beta_t (v_t - alpha_t S_{t-1}^T k_t),   o_t = S_t^T q_t
    y_t = RMSNorm_{d_v}(o_t) * w * silu(x_t W_g);  out = concat_h(y_t) W_o

so S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T, with
eigenvalues in (-1, 1) where beta reaches 2.

``gated_delta_chunked`` is the recurrence in chunks of ``CHUNK`` (the
WY / UT transform of flash-linear-attention) in plain XLA, its backward
pass autodiff, and the contract a fused kernel would have to meet. Inside
a chunk, with G the cumulative log-decay and Gamma_ij = exp(G_i - G_j)::

    A = strict_lower(diag(beta) (K K^T * Gamma)),   T = (I + A)^-1
    W = T (beta e^G K),   U = T (beta V)

and over the chunks, the state carried in float32::

    V' = U - W S;   O = (Q e^G) S + (Q K^T * Gamma * lower) V'
    S <- e^{G_C} S + (K e^{G_C - G})^T V'

Every exponential is of a difference <= 0 in float32; the matrix
multiplications take operands in the compute type and accumulate in
float32. T is float32 throughout (``unit_lower_inverse``).

Kimi Delta Attention (Kimi Linear arXiv:2510.26692; flash-linear-attention's
``KimiDeltaAttention``, whose ``linear_attn_config`` / ``kda_*`` keys
Solar-Open2's config.json carries) is the same rule with a decay for every
KEY CHANNEL, alpha_t in (0,1)^d a head::

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T

and another mixer around it (``KimiDeltaMixer``: low-rank decay and gate
projections, a sigmoid-gated norm, keys and values of one width).
``kda_chunked`` is its chunked form in plain XLA: the contract, the
fallback and the tests' oracle of the fused kernels (``ops/kda.py``, which
the mixer takes where ``supports(..., channel_decay=True)`` says the shapes
fit). With G [C, d] the cumulative log-decays of a chunk, Gamma no longer
factors out of the products::

    A_ij = beta_i sum_c k_ic k_jc exp(G_ic - G_jc)        i > j
    P_ij =        sum_c q_ic k_jc exp(G_ic - G_jc)        i >= j
    W = T (beta K * e^G),  U = T (beta V),  V' = U - W S
    O = (Q * e^G) S + P V';   S <- Diag(e^{G_C}) S + (K * e^{G_C - G})^T V'

and (k_i e^{G_i}) . (k_j e^{-G_j}) overflows float32 inside one chunk (64
steps of a log-decay of -1.6, the mixer's own strongest initial value, are
-102), so the chunk is cut into sub-blocks of ``SUB`` = 16 rows
(flash-linear-attention's cut): a sub-block's rows against EARLIER
sub-blocks' columns are a matmul of (x_i e^{G_i - G_r}) with
(k_j e^{G_r - G_j}), r the sub-block's first row, both exponents <= 0; the
16 x 16 diagonal sub-blocks take exp(G_ic - G_jc) channel by channel, no
matmul. Precisions as above.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from torchft_tpu.ops import gated_delta as gdn_kernel
from torchft_tpu.ops import kda as kda_kernel
from torchft_tpu.models.mamba2 import (
    _a_log_init,
    _dt_bias_init,
    causal_conv1d,
    conv_kernel_init,
)

Dtype = Any

logger = logging.getLogger(__name__)
_NOTED: set = set()

# The chunk of the WY form (flash-linear-attention's): one value is in use,
# so it is a constant and no field of the configuration.
CHUNK = 64
_HIGHEST = jax.lax.Precision.HIGHEST


def _note(form: str, chunk: int, seq: int, meant: bool = False) -> None:
    """Says once per (form, chunk, seq), at trace time, which form of the
    delta rule a step took ("kernel" / "xla", "kda-kernel" / "kda-xla"); at
    WARNING where widths the kernels are ``meant`` for fell back to plain
    XLA."""
    if (form, chunk, seq) not in _NOTED:
        _NOTED.add((form, chunk, seq))
        logger.log(
            logging.WARNING if meant and form.endswith("xla") else logging.INFO,
            "gated_delta: traced=%s chunk=%d seq=%d", form, chunk, seq,
        )


@dataclasses.dataclass(frozen=True)
class GatedDeltaConfig:
    # The heads HELD by the model that is built (a chip's share under
    # head-parallel tensor parallelism holds some of the published heads).
    num_heads: int = 30
    key_head_dim: int = 96
    value_head_dim: int = 192
    conv_kernel: int = 4
    # True: beta in (0, 2), so a state's eigenvalues reach down to -1.
    allow_neg_eigval: bool = True
    # dt_bias and A_log start as the Mamba-2 mixer's do (``_dt_bias_init``).
    dt_min: float = 0.001
    dt_max: float = 0.1
    dt_floor: float = 1e-4

    @property
    def key_dim(self) -> int:
        return self.num_heads * self.key_head_dim

    @property
    def value_dim(self) -> int:
        return self.num_heads * self.value_head_dim

    @property
    def conv_dim(self) -> int:
        return 2 * self.key_dim + self.value_dim


@dataclasses.dataclass(frozen=True)
class KDAConfig:
    """Kimi Delta Attention's sizes. ``num_heads`` are the heads HELD (as
    ``GatedDeltaConfig``'s); keys and values are ``head_dim`` wide, and so
    is the bottleneck of the two low-rank projections."""

    num_heads: int = 64
    head_dim: int = 128
    conv_kernel: int = 4
    allow_neg_eigval: bool = True
    # dt_bias (a key channel) and A_log (a head) start as the Mamba-2 mixer's.
    dt_min: float = 0.001
    dt_max: float = 0.1
    dt_floor: float = 1e-4

    @property
    def key_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        return 3 * self.key_dim


@jax.custom_vjp
def unit_lower_inverse(a: jax.Array) -> jax.Array:
    """(I + a)^-1 for strictly lower-triangular ``a`` [..., C, C], float32:
    one batched triangular solve against the identity. XLA's expansion
    inverts the C x C block row by row in float32 at the highest precision
    (a loop of C batched steps, its own, not the program's) and is backward
    stable whatever the keys. The other exact form, the product (I - a)
    (I + a^2)(I + a^4)...(I + a^{C/2}) of 2 log2(C) - 2 batched matmuls, was
    built and measured and is not kept: at the cell's shapes (3,840 blocks
    of 64 x 64) it is the slower one on a v5e, 6.2 ms against 3.8 forward
    (my chip run, PR 54; float32 matmuls at HIGHEST are six passes each),
    and keys that repeat inside a chunk under beta near 2 make a's powers
    grow to where float32 cancels (entries of a^32 reach 1e27 for C = 64).
    The backward pass is dA = -T^T dT T^T: two matmuls, no second solve."""
    eye = jnp.eye(a.shape[-1], dtype=a.dtype)
    return jax.lax.linalg.triangular_solve(
        eye + a, jnp.broadcast_to(eye, a.shape),
        left_side=True, lower=True, unit_diagonal=True,
    )


def _inverse_fwd(a):
    t = unit_lower_inverse(a)
    return t, t


def _inverse_bwd(t, dt):
    tt = jnp.swapaxes(t, -1, -2)
    return (-jnp.matmul(jnp.matmul(tt, dt, precision=_HIGHEST), tt, precision=_HIGHEST),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def _states_and_outputs(w, u, k_end, decay, q_in, scores, dtype: Dtype):
    """What both chunked rules do once a chunk's algebra is done. The
    recurrence over the chunk states, the state carried in float32: what
    enters each chunk and the chunk's corrected values V' = U - W S,
    S <- decay * S + K_end^T V'; then the outputs of all chunks at once,
    O = Q_in S + scores V'. w, k_end, q_in: [B, nc, C, H, d_k] and scores
    [B, nc, H, C, C] in the compute type; u [B, nc, C, H, d_v] float32;
    ``decay`` [B, nc, H, 1 | d_k] float32, the factor of a state's rows
    over a whole chunk. Returns (o [B, nc, C, H, d_v] float32, the last
    state [B, H, d_k, d_v])."""
    f32 = jnp.float32
    bsz, _, _, heads, dk = w.shape

    def carry_state(state, inputs):
        w_c, u_c, k_c, decay_c = inputs
        v_new = u_c - jnp.einsum(
            "bihd,bhde->bihe", w_c, state.astype(dtype), preferred_element_type=f32
        )
        new = decay_c[..., None] * state + jnp.einsum(
            "bihd,bihe->bhde", k_c, v_new.astype(dtype), preferred_element_type=f32
        )
        return new, (state.astype(dtype), v_new.astype(dtype))

    chunks_first = lambda x: jnp.moveaxis(x, 1, 0)  # noqa: E731
    last, (entering, v_new) = jax.lax.scan(
        carry_state, jnp.zeros((bsz, heads, dk, u.shape[-1]), f32),
        (chunks_first(w), chunks_first(u), chunks_first(k_end), chunks_first(decay)),
    )
    entering = jnp.moveaxis(entering, 0, 1)  # [B, nc, H, d_k, d_v]
    v_new = jnp.moveaxis(v_new, 0, 1)  # [B, nc, C, H, d_v]
    o = jnp.einsum(
        "bchij,bcjhe->bcihe", scores, v_new, preferred_element_type=f32,
    ) + jnp.einsum(
        "bcihd,bchde->bcihe", q_in, entering, preferred_element_type=f32,
    )
    return o, last


def gated_delta_chunked(
    q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array,
    chunk: int, dtype: Dtype,
) -> Tuple[jax.Array, jax.Array]:
    """The gated delta rule, chunked. q, k: [B, S, H, d_k], normalised
    (q scaled); v: [B, S, H, d_v]; g: [B, S, H] the log-decays (<= 0) and
    beta: [B, S, H], both float32. Returns o [B, S, H, d_v] in float32 and
    the state after the last position [B, H, d_k, d_v]. A sequence that is
    no multiple of ``chunk`` is padded with steps of beta = 0, g = 0, which
    neither decay nor write the state."""
    bsz, seq, heads, _ = q.shape
    dv = v.shape[-1]
    pad = -seq % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (q, k, v, g, beta)
        )
    nc = (seq + pad) // chunk
    f32 = jnp.float32
    # Laid out by chunk, [B, nc, C, H, ...]: no other tensor of a step is.
    by_chunk = lambda t: t.reshape(bsz, nc, chunk, heads, *t.shape[3:])  # noqa: E731
    qc, kc, vc = (by_chunk(t).astype(f32) for t in (q, k, v))
    beta = by_chunk(beta.astype(f32))
    cum = jnp.cumsum(by_chunk(g.astype(f32)), axis=2)  # G: [B, nc, C, H]
    cum_t = jnp.moveaxis(cum, 2, -1)  # [B, nc, H, C]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    gamma = jnp.exp(
        jnp.where(lower, cum_t[..., :, None] - cum_t[..., None, :], -jnp.inf)
    )  # [B, nc, H, C, C], zero above the diagonal
    kd = kc.astype(dtype)
    kk = jnp.einsum("bcihd,bcjhd->bchij", kd, kd, preferred_element_type=f32)
    a = jnp.where(
        jnp.tril(lower, -1), jnp.moveaxis(beta, 2, -1)[..., None] * kk * gamma, 0.0
    )
    t = unit_lower_inverse(a).astype(dtype)  # [B, nc, H, C, C]
    into = jnp.exp(cum)[..., None]  # e^G: the entering state's decay to row i
    w = jnp.einsum(
        "bchij,bcjhd->bcihd", t, (beta[..., None] * into * kc).astype(dtype),
        preferred_element_type=f32,
    )
    u = jnp.einsum(
        "bchij,bcjhd->bcihd", t, (beta[..., None] * vc).astype(dtype),
        preferred_element_type=f32,
    )
    whole = cum[:, :, -1]  # G_C: [B, nc, H]
    k_end = (kc * jnp.exp(whole[:, :, None] - cum)[..., None]).astype(dtype)

    qk = jnp.einsum(
        "bcihd,bcjhd->bchij", qc.astype(dtype), kd, preferred_element_type=f32
    )
    o, last = _states_and_outputs(
        w.astype(dtype), u, k_end, jnp.exp(whole)[..., None],
        (qc * into).astype(dtype), (qk * gamma).astype(dtype), dtype,
    )
    return o.reshape(bsz, nc * chunk, heads, dv)[:, :seq], last


# Kimi Delta Attention's sub-block inside a chunk (flash-linear-attention's):
# one value is in use, so it is a constant and no field of the configuration.
SUB = 16


def _channel_decayed_products(xs, k, cum, sub: int, dtype: Dtype):
    """For each x of ``xs``: P_ij = sum_c x_ic k_jc exp(G_ic - G_jc) where
    i >= j and 0 above the diagonal, [B, nc, H, C, C] float32. x, k and the
    cumulative log-decays ``cum`` (G) are [B, nc, C, H, d] float32. Every
    exponential is of a difference <= 0: the module's text says how the
    sub-blocks of ``sub`` rows arrange that."""
    bsz, nc, chunk, heads, d = k.shape
    ns, f32 = chunk // sub, jnp.float32
    blocks = lambda t: t.reshape(bsz, nc, ns, sub, heads, d)  # noqa: E731
    ks, gs = blocks(k), blocks(cum)
    keep = jnp.tril(jnp.ones((sub, sub), bool))[:, :, None, None]
    within = jnp.exp(jnp.where(
        keep, gs[:, :, :, :, None] - gs[:, :, :, None, :], -jnp.inf
    ))  # [B, nc, ns, i, j, H, d]: the diagonal sub-blocks' own decays
    first = gs[:, :, :, 0]  # G at each sub-block's first row: [B, nc, ns, H, d]
    to_first = jnp.exp(gs - first[:, :, :, None])  # rows decayed back to it
    # Columns before sub-block I, decayed up to I's first row.
    earlier = [
        (k[:, :, : i * sub] * jnp.exp(first[:, :, i, None] - cum[:, :, : i * sub]))
        .astype(dtype)
        for i in range(1, ns)
    ]
    out = []
    for x in xs:
        xb = blocks(x)
        diagonal = jnp.moveaxis(
            jnp.sum(xb[:, :, :, :, None] * ks[:, :, :, None, :] * within, axis=-1),
            -1, 2,
        )  # [B, nc, H, ns, i, j]
        rows_rel = (xb * to_first).astype(dtype)
        rows = []
        for i in range(ns):
            parts = [diagonal[:, :, :, i]]
            if i:
                parts.insert(0, jnp.einsum(
                    "bcihd,bcjhd->bchij", rows_rel[:, :, i], earlier[i - 1],
                    preferred_element_type=f32,
                ))
            if i < ns - 1:
                parts.append(
                    jnp.zeros((bsz, nc, heads, sub, chunk - (i + 1) * sub), f32)
                )
            rows.append(jnp.concatenate(parts, axis=-1))
        out.append(jnp.concatenate(rows, axis=-2))
    return out


def kda_chunked(
    q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array,
    chunk: int, dtype: Dtype,
) -> Tuple[jax.Array, jax.Array]:
    """The delta rule with a decay a key channel, chunked. q, k:
    [B, S, H, d_k], normalised (q scaled); v: [B, S, H, d_v]; g:
    [B, S, H, d_k] the log-decays (<= 0) and beta: [B, S, H], both float32.
    Returns o [B, S, H, d_v] in float32 and the state after the last
    position [B, H, d_k, d_v]. ``chunk`` is a multiple of ``SUB`` or less
    than it; a sequence that is no multiple of ``chunk`` is padded with
    steps of beta = 0, g = 0, which neither decay nor write the state."""
    bsz, seq, heads, _ = q.shape
    dv = v.shape[-1]
    sub = min(SUB, chunk)
    if g.shape != q.shape or chunk % sub:
        raise ValueError(
            f"kda_chunked: a decay of shape {g.shape} for keys {q.shape} in "
            f"chunks of {chunk} (sub-blocks of {SUB})"
        )
    pad = -seq % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (q, k, v, g, beta)
        )
    nc = (seq + pad) // chunk
    f32 = jnp.float32
    by_chunk = lambda t: t.reshape(bsz, nc, chunk, heads, *t.shape[3:])  # noqa: E731
    qc, kc, vc = (by_chunk(t).astype(f32) for t in (q, k, v))
    beta = by_chunk(beta.astype(f32))
    cum = jnp.cumsum(by_chunk(g.astype(f32)), axis=2)  # G: [B, nc, C, H, d_k]
    kk, qk = _channel_decayed_products((kc, qc), kc, cum, sub, dtype)
    strictly_lower = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    a = jnp.where(strictly_lower, jnp.moveaxis(beta, 2, -1)[..., None] * kk, 0.0)
    t = unit_lower_inverse(a).astype(dtype)  # [B, nc, H, C, C]
    into = jnp.exp(cum)  # e^G: the entering state's decay to row i, a channel
    w = jnp.einsum(
        "bchij,bcjhd->bcihd", t, (beta[..., None] * into * kc).astype(dtype),
        preferred_element_type=f32,
    )
    u = jnp.einsum(
        "bchij,bcjhd->bcihd", t, (beta[..., None] * vc).astype(dtype),
        preferred_element_type=f32,
    )
    whole = cum[:, :, -1]  # G_C: [B, nc, H, d_k]
    k_end = (kc * jnp.exp(whole[:, :, None] - cum)).astype(dtype)

    # The gated-delta rule's scan, the state's ROWS decayed each by its own
    # channel's e^{G_C}.
    o, last = _states_and_outputs(
        w.astype(dtype), u, k_end, jnp.exp(whole), (qc * into).astype(dtype),
        qk.astype(dtype), dtype,
    )
    return o.reshape(bsz, nc * chunk, heads, dv)[:, :seq], last


def _unit(t: jax.Array) -> jax.Array:
    """A head's vector [..., d] over its length, in float32."""
    t = t.astype(jnp.float32)
    return t * jax.lax.rsqrt(jnp.sum(jnp.square(t), axis=-1, keepdims=True) + 1e-6)


class GatedDeltaMixer(nn.Module):
    m: GatedDeltaConfig
    hidden_size: int
    norm_eps: float = 1e-6
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        m, f32 = self.m, jnp.float32
        bsz, seq, _ = x.shape
        heads, dk, dv = m.num_heads, m.key_head_dim, m.value_head_dim
        dense = lambda f, name: nn.Dense(  # noqa: E731
            f, use_bias=False, dtype=self.dtype, param_dtype=self.param_dtype,
            name=name,
        )
        qkv = jnp.concatenate(
            [dense(m.key_dim, "q_proj")(x), dense(m.key_dim, "k_proj")(x),
             dense(m.value_dim, "v_proj")(x)], axis=-1,
        )
        with jax.named_scope("gated_delta/conv"):
            kernel = self.param(
                "conv_kernel", conv_kernel_init(m.conv_kernel),
                (m.conv_kernel, m.conv_dim), self.param_dtype,
            )
            qkv = nn.silu(causal_conv1d(qkv, kernel)).astype(self.dtype)
        q, k, v = jnp.split(qkv, [m.key_dim, 2 * m.key_dim], axis=-1)

        a_log = self.param("A_log", _a_log_init, (heads,), self.param_dtype)
        dt_bias = self.param("dt_bias", _dt_bias_init(m), (heads,), self.param_dtype)
        with jax.named_scope("gated_delta/rule"):
            by_head = lambda t: t.reshape(bsz, seq, heads, dk)  # noqa: E731
            q, k = _unit(by_head(q)) * dk ** -0.5, _unit(by_head(k))
            beta = jax.nn.sigmoid(dense(heads, "b_proj")(x).astype(f32))
            if m.allow_neg_eigval:
                beta = 2.0 * beta
            g = -jnp.exp(a_log.astype(f32)) * jax.nn.softplus(
                dense(heads, "a_proj")(x).astype(f32) + dt_bias.astype(f32)
            )
            kernel = gdn_kernel.supports(CHUNK, dk, dv, heads, seq)
            _note(
                "kernel" if kernel else "xla", CHUNK, seq,
                meant=gdn_kernel.meant_for(dk, dv),
            )
            o, state = (gdn_kernel.gated_delta if kernel else gated_delta_chunked)(
                q, k, v.reshape(bsz, seq, heads, dv), g, beta, CHUNK, self.dtype
            )
            self.sow("intermediates", "gdn_state_abs_max", jnp.max(jnp.abs(state)))
            self.sow("intermediates", "gdn_decay_min", jnp.exp(jnp.min(g)))
            self.sow("intermediates", "gdn_beta_mean", jnp.mean(beta))

        with jax.named_scope("gated_delta/gated_norm"):
            weight = self.param(
                "norm_scale", nn.initializers.ones, (dv,), self.param_dtype
            )
            gate = dense(m.value_dim, "g_proj")(x).astype(f32).reshape(o.shape)
            o = o * jax.lax.rsqrt(
                jnp.mean(jnp.square(o), axis=-1, keepdims=True) + self.norm_eps
            )
            y = (o * weight.astype(f32) * nn.silu(gate)).astype(self.dtype)
        return dense(self.hidden_size, "o_proj")(y.reshape(bsz, seq, m.value_dim))


class KimiDeltaMixer(nn.Module):
    """Kimi Delta Attention (flash-linear-attention's ``KimiDeltaAttention``)
    for x [B, S, hidden], H held heads of d = ``head_dim``::

        [q | k | v] = silu(conv1d_causal_depthwise([x W_q | x W_k | x W_v]))
        q = q / |q|_2 / sqrt(d),  k = k / |k|_2                       a head
        g = -exp(A_log_h) softplus(W_f_b W_f_a x + dt_bias)   in R^d, <= 0
        beta = sigmoid(x W_b)             (x 2 where ``allow_neg_eigval``)
        o = the delta rule over (q, k, v, g, beta)    ``ops/kda.py`` where
                   ``supports`` admits the widths, ``kda_chunked`` otherwise
        y = W_o concat_h[RMSNorm_d(o) * w * sigmoid(W_g_b W_g_a x + b_g)]

    W_f_a and W_g_a go down to d channels (one bottleneck for all heads),
    W_f_b and W_g_b up to H d; dt_bias is a key channel's, A_log a head's."""

    m: KDAConfig
    hidden_size: int
    norm_eps: float = 1e-5
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        m, f32 = self.m, jnp.float32
        bsz, seq, _ = x.shape
        heads, d = m.num_heads, m.head_dim
        dense = lambda f, name, bias=False: nn.Dense(  # noqa: E731
            f, use_bias=bias, dtype=self.dtype, param_dtype=self.param_dtype,
            name=name,
        )
        by_head = lambda t: t.reshape(bsz, seq, heads, d)  # noqa: E731
        qkv = jnp.concatenate(
            [dense(m.key_dim, name)(x) for name in ("q_proj", "k_proj", "v_proj")],
            axis=-1,
        )
        with jax.named_scope("kda/conv"):
            kernel = self.param(
                "conv_kernel", conv_kernel_init(m.conv_kernel),
                (m.conv_kernel, m.conv_dim), self.param_dtype,
            )
            qkv = nn.silu(causal_conv1d(qkv, kernel)).astype(self.dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)

        a_log = self.param("A_log", _a_log_init, (heads,), self.param_dtype)
        dt_bias = self.param(
            "dt_bias", _dt_bias_init(m), (m.key_dim,), self.param_dtype
        )
        with jax.named_scope("kda/gates"):
            step = by_head(
                dense(m.key_dim, "f_b_proj")(dense(d, "f_a_proj")(x)).astype(f32)
                + dt_bias.astype(f32)
            )
            g = -jnp.exp(a_log.astype(f32))[:, None] * jax.nn.softplus(step)
            beta = jax.nn.sigmoid(dense(heads, "b_proj")(x).astype(f32))
            if m.allow_neg_eigval:
                beta = 2.0 * beta
        with jax.named_scope("kda/rule"):
            kernel = gdn_kernel.supports(CHUNK, d, d, heads, seq, channel_decay=True)
            _note(
                "kda-kernel" if kernel else "kda-xla", CHUNK, seq,
                meant=gdn_kernel.meant_for(d, d),
            )
            o, state = (kda_kernel.kda if kernel else kda_chunked)(
                _unit(by_head(q)) * d ** -0.5, _unit(by_head(k)), by_head(v),
                g, beta, CHUNK, self.dtype,
            )
            self.sow("intermediates", "kda_state_abs_max", jnp.max(jnp.abs(state)))
            self.sow("intermediates", "kda_decay_min", jnp.exp(jnp.min(g)))
            self.sow("intermediates", "kda_beta_mean", jnp.mean(beta))

        with jax.named_scope("kda/gated_norm"):
            weight = self.param(
                "norm_scale", nn.initializers.ones, (d,), self.param_dtype
            )
            gate = by_head(
                dense(m.key_dim, "g_b_proj", bias=True)(dense(d, "g_a_proj")(x))
            ).astype(f32)
            o = o * jax.lax.rsqrt(
                jnp.mean(jnp.square(o), axis=-1, keepdims=True) + self.norm_eps
            )
            y = (o * weight.astype(f32) * jax.nn.sigmoid(gate)).astype(self.dtype)
        return dense(self.hidden_size, "o_proj")(y.reshape(bsz, seq, m.key_dim))
