"""Mamba-2 mixer (arXiv:2405.21060) as Nemotron-H uses it (arXiv:2504.03624,
``modeling_nemotron_h``): one projection in, a causal depthwise
convolution, a selective state-space recurrence with a scalar decay a
head, a grouped gated RMSNorm, one projection out.

For an input u [B, S, hidden], with H heads of width P, G groups and a
state of N a group::

    [z | xBC | dt] = in_proj(u)            widths H*P | H*P + 2*G*N | H
    xBC = silu(conv1d_causal_depthwise(xBC) + conv_bias)
    x [H, P], B [G, N], C [G, N] = split(xBC)     head h reads group h // (H/G)
    delta = softplus(dt + dt_bias),  A = -exp(A_log)
    S_t = exp(delta_t A) S_{t-1} + delta_t x_t (outer) B_t     S: [P, N], S_{-1} = 0
    y_t = S_t C_t + D x_t
    y = RMSNorm over each group of H*P/G channels of (y * silu(z)), times a weight
    out = out_proj(y)

The recurrence is computed in chunks (section 6 of the Mamba-2 paper): the
quadratic form inside a chunk, one state a chunk, a ``lax.scan`` over the
chunk states, and each chunk's entering state to its outputs. Decays are
float32; the operands of the matrix multiplications are in the compute
type with float32 accumulation. ``ssd_chunked`` is that in plain XLA, its
backward pass autodiff; where ``ops/ssd.py``'s kernels take the shapes
the mixer runs them instead (same contract, forward and backward), chosen
by shape alone and noted once at trace time.
"""

from __future__ import annotations

import dataclasses
import logging
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from torchft_tpu.ops import ssd as ssd_kernel

Dtype = Any

logger = logging.getLogger(__name__)
_SSD_NOTED: set = set()


def _note_ssd(traced: str, chunk: int, seq: int) -> None:
    """Says once per (traced, chunk, seq), at trace time, which form of the
    scan a step really took, so that a chip run can prove which branch it
    compiled. A chunk of a whole lane tile is a published width: the plain
    form there is a fallback and a WARNING."""
    key = (traced, chunk, seq)
    if key not in _SSD_NOTED:
        _SSD_NOTED.add(key)
        fell_back = traced == "xla" and chunk % 128 == 0
        logger.log(
            logging.WARNING if fell_back else logging.INFO,
            "ssd: traced=%s chunk=%d seq=%d", traced, chunk, seq,
        )


@dataclasses.dataclass(frozen=True)
class Mamba2Config:
    num_heads: int = 64
    head_dim: int = 64
    n_groups: int = 8
    state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    # dt_bias starts as the inverse softplus of a log-uniform step in
    # [dt_min, dt_max] floored at dt_floor; A_log as log U[1, 16]; D as 1.
    dt_min: float = 0.001
    dt_max: float = 0.1
    dt_floor: float = 1e-4

    @property
    def d_inner(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.state_size


def causal_conv1d(
    x: jax.Array, kernel: jax.Array, bias: Optional[jax.Array] = None
) -> jax.Array:
    """Depthwise causal convolution. x: [B, S, C]; kernel: [K, C], its last
    row multiplying the current position; float32 out."""
    k, s = kernel.shape[0], x.shape[1]
    f32 = jnp.float32
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))  # in x's type: half the bytes
    taps = [padded[:, i : i + s].astype(f32) * kernel[i].astype(f32) for i in range(k)]
    return sum(taps) if bias is None else sum(taps) + bias.astype(f32)


def ssd_chunked(
    x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array,
    chunk: int, dtype: Dtype,
) -> jax.Array:
    """The selective state-space recurrence, chunked. x: [B, S, H, P];
    dt: [B, S, H] (after softplus) and a: [H] (negative) in float32;
    b, c: [B, S, G, N]. Returns y [B, S, H, P] in float32, without the
    D skip. A sequence that is no multiple of ``chunk`` is padded with
    steps of dt = 0, which neither decay nor write the state."""
    bsz, seq, heads, p = x.shape
    g, n = b.shape[2], b.shape[3]
    r = heads // g
    pad = -seq % chunk
    if pad:
        x, dt, b, c = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (x, dt, b, c)
        )
    nc = (seq + pad) // chunk
    f32 = jnp.float32
    dt = dt.astype(f32).reshape(bsz, nc, chunk, g, r)
    # x_t * delta_t, the state's input, in the matmuls' operand type.
    xdt = (x.reshape(bsz, nc, chunk, g, r, p).astype(f32) * dt[..., None])
    bc = b.reshape(bsz, nc, chunk, g, n).astype(dtype)
    cc = c.reshape(bsz, nc, chunk, g, n).astype(dtype)
    # cum[..., i]: the log of the decay from the chunk's start through i.
    cum = jnp.cumsum(
        jnp.moveaxis(dt * a.astype(f32).reshape(g, r), 2, -1), axis=-1
    )  # [B, nc, G, R, Q]

    # Inside a chunk: y_i += sum_{j<=i} (C_i . B_j) exp(cum_i - cum_j) x_j dt_j.
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(
        jnp.where(lower, cum[..., :, None] - cum[..., None, :], -jnp.inf)
    )  # [B, nc, G, R, Q, Q]
    cb = jnp.einsum("bcign,bcjgn->bcgij", cc, bc, preferred_element_type=f32)
    y = jnp.einsum(
        "bcgrij,bcjgrp->bcigrp",
        (cb[:, :, :, None] * decay).astype(dtype), xdt.astype(dtype),
        preferred_element_type=f32,
    )

    # Each chunk's own contribution to the state at its end.
    to_end = jnp.exp(cum[..., -1:] - cum)  # [B, nc, G, R, Q]
    states = jnp.einsum(
        "bcjgrp,bcjgn->bcgrpn",
        (xdt * jnp.moveaxis(to_end, -1, 2)[..., None]).astype(dtype), bc,
        preferred_element_type=f32,
    )  # [B, nc, G, R, P, N]

    # The recurrence over the chunk states: what enters each chunk.
    def carry_state(state, inputs):
        decay_c, own = inputs
        return decay_c[..., None, None] * state + own, state

    whole = jnp.exp(cum[..., -1])  # [B, nc, G, R]
    _, entering = jax.lax.scan(
        carry_state, jnp.zeros_like(states[:, 0]),
        (jnp.moveaxis(whole, 1, 0), jnp.moveaxis(states, 1, 0)),
    )
    entering = jnp.moveaxis(entering, 0, 1)  # [B, nc, G, R, P, N]

    # The entering state to the chunk's outputs: y_i += exp(cum_i) C_i S.
    from_state = jnp.einsum(
        "bcign,bcgrpn->bcigrp", cc, entering.astype(dtype),
        preferred_element_type=f32,
    )
    y = y + from_state * jnp.moveaxis(jnp.exp(cum), -1, 2)[..., None]
    return y.reshape(bsz, nc * chunk, heads, p)[:, :seq]


def _dt_bias_init(m: Mamba2Config):
    def init(key, shape, dtype=jnp.float32):
        lo, hi = math.log(m.dt_min), math.log(m.dt_max)
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32) * (hi - lo) + lo)
        dt = jnp.maximum(dt, m.dt_floor)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)  # softplus^-1

    return init


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)).astype(dtype)


def conv_kernel_init(kernel_len: int):
    """Uniform within 1/sqrt(fan-in); a depthwise kernel's fan-in is its
    length (the published implementation's default for kernel and bias)."""
    bound = kernel_len ** -0.5

    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, -bound, bound)

    return init


class Mamba2Mixer(nn.Module):
    m: Mamba2Config
    hidden_size: int
    norm_eps: float = 1e-5
    out_init_scale: float = 1.0  # rescale_prenorm_residual: 1/sqrt(layers)
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, u: jax.Array) -> jax.Array:
        m, f32 = self.m, jnp.float32
        bsz, seq, _ = u.shape
        heads, p, g, n = m.num_heads, m.head_dim, m.n_groups, m.state_size
        dense = lambda f, name, scale=1.0: nn.Dense(  # noqa: E731
            f, use_bias=False, dtype=self.dtype, param_dtype=self.param_dtype,
            kernel_init=nn.initializers.variance_scaling(
                scale * scale, "fan_in", "truncated_normal"
            ),
            name=name,
        )
        zxbcdt = dense(m.d_inner + m.conv_dim + heads, "in_proj")(u)
        z, xbc, dt = jnp.split(zxbcdt, [m.d_inner, m.d_inner + m.conv_dim], axis=-1)

        with jax.named_scope("mamba2/conv"):
            conv_init = conv_kernel_init(m.conv_kernel)
            kernel = self.param(
                "conv_kernel", conv_init, (m.conv_kernel, m.conv_dim), self.param_dtype
            )
            bias = self.param("conv_bias", conv_init, (m.conv_dim,), self.param_dtype)
            xbc = nn.silu(causal_conv1d(xbc, kernel, bias)).astype(self.dtype)
        x, b, c = jnp.split(xbc, [m.d_inner, m.d_inner + g * n], axis=-1)

        a_log = self.param("A_log", _a_log_init, (heads,), self.param_dtype)
        dt_bias = self.param("dt_bias", _dt_bias_init(m), (heads,), self.param_dtype)
        skip = self.param("D", nn.initializers.ones, (heads,), self.param_dtype)
        with jax.named_scope("mamba2/ssd"):
            x = x.reshape(bsz, seq, heads, p)
            delta = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
            kernel = ssd_kernel.supports(m.chunk_size, p, n, heads // g, seq)
            _note_ssd("kernel" if kernel else "xla", m.chunk_size, seq)
            y = (ssd_kernel.ssd if kernel else ssd_chunked)(
                x, delta, -jnp.exp(a_log.astype(f32)),
                b.reshape(bsz, seq, g, n), c.reshape(bsz, seq, g, n),
                m.chunk_size, self.dtype,
            )
            y = y + skip.astype(f32)[:, None] * x.astype(f32)

        with jax.named_scope("mamba2/gated_norm"):
            weight = self.param(
                "norm_scale", nn.initializers.ones, (m.d_inner,), self.param_dtype
            )
            y = y.reshape(bsz, seq, g, m.d_inner // g)
            y = y * nn.silu(z.astype(f32)).reshape(y.shape)
            y = y * jax.lax.rsqrt(
                jnp.mean(jnp.square(y), axis=-1, keepdims=True) + self.norm_eps
            )
            y = (y.reshape(bsz, seq, m.d_inner) * weight.astype(f32)).astype(self.dtype)
        return dense(self.hidden_size, "out_proj", self.out_init_scale)(y)
