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
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

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


def _note(chunk: int, seq: int) -> None:
    """Says once per (chunk, seq), at trace time, which form of the delta
    rule a step took (plain XLA is the only one)."""
    if (chunk, seq) not in _NOTED:
        _NOTED.add((chunk, seq))
        logger.info("gated_delta: traced=xla chunk=%d seq=%d", chunk, seq)


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
    bsz, seq, heads, dk = q.shape
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

    # The recurrence over the chunk states: what enters each chunk, and
    # the chunk's corrected values V' = U - W S.
    def carry_state(state, inputs):
        w_c, u_c, k_c, decay_c = inputs
        v_new = u_c - jnp.einsum(
            "bihd,bhde->bihe", w_c, state.astype(dtype), preferred_element_type=f32
        )
        new = decay_c[..., None, None] * state + jnp.einsum(
            "bihd,bihe->bhde", k_c, v_new.astype(dtype), preferred_element_type=f32
        )
        return new, (state.astype(dtype), v_new.astype(dtype))

    chunks_first = lambda x: jnp.moveaxis(x, 1, 0)  # noqa: E731
    last, (entering, v_new) = jax.lax.scan(
        carry_state, jnp.zeros((bsz, heads, dk, dv), f32),
        (chunks_first(w.astype(dtype)), chunks_first(u), chunks_first(k_end),
         chunks_first(jnp.exp(whole))),
    )
    entering = jnp.moveaxis(entering, 0, 1)  # [B, nc, H, d_k, d_v]
    v_new = jnp.moveaxis(v_new, 0, 1)  # [B, nc, C, H, d_v]

    qd = qc.astype(dtype)
    qk = jnp.einsum("bcihd,bcjhd->bchij", qd, kd, preferred_element_type=f32)
    o = jnp.einsum(
        "bchij,bcjhe->bcihe", (qk * gamma).astype(dtype), v_new,
        preferred_element_type=f32,
    ) + jnp.einsum(
        "bcihd,bchde->bcihe", (qc * into).astype(dtype), entering,
        preferred_element_type=f32,
    )
    return o.reshape(bsz, nc * chunk, heads, dv)[:, :seq], last


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
            def unit(t):  # a head's vector over its length, in float32
                t = t.reshape(bsz, seq, heads, dk).astype(f32)
                return t * jax.lax.rsqrt(
                    jnp.sum(jnp.square(t), axis=-1, keepdims=True) + 1e-6
                )

            q, k = unit(q) * dk ** -0.5, unit(k)
            beta = jax.nn.sigmoid(dense(heads, "b_proj")(x).astype(f32))
            if m.allow_neg_eigval:
                beta = 2.0 * beta
            g = -jnp.exp(a_log.astype(f32)) * jax.nn.softplus(
                dense(heads, "a_proj")(x).astype(f32) + dt_bias.astype(f32)
            )
            _note(CHUNK, seq)
            o, state = gated_delta_chunked(
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
