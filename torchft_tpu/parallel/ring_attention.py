"""Ring attention: exact causal attention with the sequence sharded over a
mesh axis (context parallelism for long sequences).

Each chip holds one query block and streams every key/value block past it on
the ICI ring via ``ppermute``, folding each block into a numerically-stable
online softmax (flash-attention accumulation in fp32). Communication
overlaps compute — XLA schedules the ppermute DMA of block i+1 against the
matmuls of block i.

The reference has no long-context code (SURVEY.md §2.3: CP/ring absent —
delegated to torchtitan); here it is first-class because the TPU design
treats sequence as just another mesh axis.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

# Replication check off: ppermute inside fori_loop.
shard_map = partial(jax.shard_map, check_vma=False)


def _flash_fold_supported(sq: int, skv: int) -> bool:
    from torchft_tpu.ops.flash_attention import supports

    # The pallas fold needs block-divisible shard lengths; tiny shards
    # (tests, debug models) stay on the fused-XLA dense fold.
    return sq >= 256 and skv >= 256 and supports(sq) and supports(skv)


def ring_attention_shard_flash(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str = "sp",
) -> jax.Array:
    """Pallas-accelerated per-shard ring body: each streamed k/v block is
    folded with :func:`ops.flash_attention.flash_attention_block` (on-chip
    blocked attention at GLOBAL positions) and merged via the online-softmax
    combine. Same semantics as :func:`ring_attention_shard` with
    ``causal=True``; preferred for production shard sizes (the dense fold
    materializes [B,H,Sq,Skv] fp32 scores per step)."""
    from torchft_tpu.ops.flash_attention import flash_attention_block

    axis_size = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, sq, hq, dh = q.shape
    skv = k.shape[1]
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
    q_off = idx * sq

    out0 = jnp.zeros((b, sq, hq, dh), jnp.float32)
    lse0 = jnp.full((b, hq, sq), -jnp.inf, jnp.float32)

    def fold(i, k_blk, v_blk, out, lse):
        src = (idx - i) % axis_size
        o_blk, lse_blk = flash_attention_block(
            q, k_blk, v_blk, q_off, src * skv
        )
        new_lse = jnp.logaddexp(lse, lse_blk)
        safe = jnp.where(jnp.isfinite(new_lse), new_lse, 0.0)
        w_old = jnp.where(jnp.isfinite(lse), jnp.exp(lse - safe), 0.0)
        w_new = jnp.where(jnp.isfinite(lse_blk), jnp.exp(lse_blk - safe), 0.0)
        wt = lambda w: jnp.swapaxes(w, 1, 2)[..., None]  # noqa: E731
        out = out * wt(w_old) + o_blk.astype(jnp.float32) * wt(w_new)
        return out, new_lse

    def body(i, carry):
        k_blk, v_blk, out, lse = carry
        out, lse = fold(i, k_blk, v_blk, out, lse)
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return k_blk, v_blk, out, lse

    k_blk, v_blk, out, lse = jax.lax.fori_loop(
        0, axis_size - 1, body, (k, v, out0, lse0)
    )
    out, _ = fold(axis_size - 1, k_blk, v_blk, out, lse)
    return out.astype(q.dtype)


def ring_attention_shard(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str = "sp",
    causal: bool = True,
) -> jax.Array:
    """Per-shard body (run under shard_map). q: [B, Sq, Hq, Dh] local block;
    k/v: [B, Skv, Hkv, Dh] local block. Returns [B, Sq, Hq, Dh]."""
    axis_size = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, sq, hq, dh = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    scale = dh**-0.5
    qg = q.reshape(b, sq, hkv, g, dh).astype(jnp.float32) * scale
    q_pos = idx * sq + jnp.arange(sq)

    m0 = jnp.full((b, hkv, g, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, sq), jnp.float32)
    acc0 = jnp.zeros((b, hkv, g, sq, dh), jnp.float32)
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]

    def fold(i, k_blk, v_blk, m, l, acc):
        # After i forward rotations this chip holds the block that started
        # on chip (idx - i) mod axis_size.
        src = (idx - i) % axis_size
        scores = jnp.einsum(
            "bqkgd,bskd->bkgqs", qg, k_blk.astype(jnp.float32)
        )
        if causal:
            k_pos = src * skv + jnp.arange(skv)
            mask = q_pos[:, None] >= k_pos[None, :]
            scores = jnp.where(mask[None, None, None], scores, -jnp.inf)
        blk_max = jnp.max(scores, axis=-1)
        new_m = jnp.maximum(m, blk_max)
        safe_m = jnp.where(jnp.isfinite(new_m), new_m, 0.0)
        correction = jnp.where(
            jnp.isfinite(m), jnp.exp(m - safe_m), 0.0
        )
        probs = jnp.exp(scores - safe_m[..., None])  # masked -> exp(-inf)=0
        l = l * correction + probs.sum(axis=-1)
        acc = acc * correction[..., None] + jnp.einsum(
            "bkgqs,bskd->bkgqd", probs, v_blk.astype(jnp.float32)
        )
        return new_m, l, acc

    def body(i, carry):
        k_blk, v_blk, m, l, acc = carry
        m, l, acc = fold(i, k_blk, v_blk, m, l, acc)
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return k_blk, v_blk, m, l, acc

    # Rotate only axis_size-1 times; the last block folds outside the loop
    # so its ppermute (whose result would be discarded) is never issued.
    k_blk, v_blk, m, l, acc = jax.lax.fori_loop(
        0, axis_size - 1, body, (k, v, m0, l0, acc0)
    )
    _, l, acc = fold(axis_size - 1, k_blk, v_blk, m, l, acc)
    out = acc / jnp.where(l == 0.0, 1.0, l)[..., None]
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, hq, dh)
    return out.astype(q.dtype)


def make_ring_attention(
    mesh: Mesh,
    *,
    batch_axes: Tuple[str, ...] = ("dp", "fsdp"),
    seq_axis: str = "sp",
    head_axis: Optional[str] = "tp",
    causal: bool = True,
    use_flash: Optional[bool] = None,
):
    """Returns attn_fn(q, k, v) usable inside a pjit'd program: shards
    [B, S, H, Dh] with batch over ``batch_axes``, sequence over ``seq_axis``,
    heads over ``head_axis``, and runs the ring per shard.

    ``use_flash``: fold each streamed block with the Pallas kernel
    (ops/flash_attention.py) instead of the dense einsum. Default (None)
    auto-selects it for causal rings with production-sized shards."""
    spec = P(batch_axes, seq_axis, head_axis, None)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    def attn_fn(q, k, v):
        sq, skv = q.shape[1], k.shape[1]
        flash = use_flash
        if flash is None:
            flash = causal and _flash_fold_supported(sq, skv)
        if flash:
            assert causal, "flash ring fold is causal-only"
            return ring_attention_shard_flash(q, k, v, axis_name=seq_axis)
        return ring_attention_shard(q, k, v, axis_name=seq_axis, causal=causal)

    return attn_fn
