"""Multi-head latent attention (DeepSeek-V2 arXiv:2405.04434 section 2.1;
DeepSeek-V3 arXiv:2412.19437 section 2.1.1, ``modeling_deepseek_v3``) in
its TRAINING form: queries and keys/values each come through a low-rank
bottleneck with a norm of its own, a head's query and key are a rope-free
part and a rotary part, and ONE rotary key a position serves every head.

For x [B, S, hidden], H heads, ranks r_q and r_kv, widths Dn (rope-free),
Dr (rotary) and Dv (values)::

    c_q = RMSNorm(x W_qa)                            r_q
    [q_nope | q_rope] = c_q W_qb                     H x (Dn + Dr)
    [c_kv | k_rope] = x W_kva                        r_kv + Dr
    [k_nope | v] = RMSNorm(c_kv) W_kvb               H x (Dn + Dv)
    q_rope, k_rope = rotary(q_rope), rotary(k_rope)  k_rope: one a position
    score = (q_nope . k_nope + q_rope . k_rope) / sqrt(Dn + Dr), causal
    out = (softmax(score) v)[H x Dv] W_o

Keys and values are expanded per head; the absorbed form, in which the
cache holds c_kv and k_rope alone, is serving's and is not built. No
biases. The rotary part's channels are paired (2i, 2i+1) as the published
weights store them (``rope_interleave``); they are laid out half-split
([evens | odds]) before the rotation, in the queries and the key alike,
so the scores are those of a rotation of adjacent pairs.

Where ``ops/flash_attention.py``'s latent kernels take the shapes the
mixer runs them (``flash_attention_mla``: the rotary key read as one head,
its gradient summed over the heads in the kernel); ``latent_dense_attention``
is the fallback and the CPU tests' comparison. Which one a step took is
noted once at trace time (``flash/mla`` or ``dense/mla``).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


def apply_rope_interleaved(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotary embedding of x [B, S, H, D] whose channels are adjacent pairs
    (2i, 2i+1): pair i turns by the i-th angle. The result is laid out
    half-split, [evens' | odds'], a permutation of the channels that the
    queries and the key share and a dot product does not see."""
    x1, x2 = x[..., 0::2], x[..., 1::2]
    cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    return jnp.concatenate((x1 * cos - x2 * sin, x2 * cos + x1 * sin), axis=-1)


def latent_dense_attention(
    q_nope: jax.Array, q_rope: jax.Array, k_nope: jax.Array, k_rope: jax.Array,
    v: jax.Array,
) -> jax.Array:
    """Plain causal latent attention, softmax in float32. q_nope, k_nope:
    [B,S,H,Dn]; q_rope: [B,S,H,Dr]; k_rope: [B,S,Dr] (shared by the heads);
    v: [B,S,H,Dv]. Returns [B,S,H,Dv]."""
    s = q_nope.shape[1]
    scale = (q_nope.shape[-1] + q_rope.shape[-1]) ** -0.5
    scores = (
        jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope).astype(jnp.float32)
        + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope).astype(jnp.float32)
    ) * scale
    keep = jnp.tril(jnp.ones((s, s), dtype=bool))
    probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)


class LatentAttention(nn.Module):
    """The mixer a ``layer_pattern``'s '*' builds where ``cfg.mla`` is set
    (``cfg``: the LlamaConfig). cos, sin: the rotary tables at
    ``qk_rope_head_dim``."""

    cfg: Any

    @nn.compact
    def __call__(self, x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
        # llama.py imports this file: its norm and note are taken at call time.
        from torchft_tpu.models.llama import RMSNorm, _note_attention
        from torchft_tpu.ops.flash_attention import choose_tiles, flash_attention_mla

        cfg, m = self.cfg, self.cfg.mla
        dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
        kind = dict(use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype)
        down = lambda f, name: nn.Dense(f, name=name, **kind)  # noqa: E731
        up = lambda f, name: nn.DenseGeneral(  # noqa: E731
            features=(cfg.num_heads, f), axis=-1, name=name, **kind
        )
        norm = lambda name: RMSNorm(cfg.norm_eps, cfg.param_dtype, name=name)  # noqa: E731

        q = up(dn + dr, "wq_b")(norm("q_norm")(down(m.q_lora_rank, "wq_a")(x)))
        kv_a = down(m.kv_lora_rank + dr, "wkv_a")(x)
        c_kv, k_rope = kv_a[..., : m.kv_lora_rank], kv_a[..., m.kv_lora_rank :]
        kv = up(dn + dv, "wkv_b")(norm("kv_norm")(c_kv))
        q_nope, q_rope = q[..., :dn], q[..., dn:]
        k_nope, v = kv[..., :dn], kv[..., dn:]
        q_rope = apply_rope_interleaved(q_rope, cos, sin)
        k_rope = apply_rope_interleaved(k_rope[:, :, None, :], cos, sin)[:, :, 0]

        seq = x.shape[1]
        if cfg.attn_impl not in ("flash", "dense"):
            raise ValueError(
                f"latent attention under attn_impl={cfg.attn_impl!r}: it "
                "exists for 'flash' and 'dense'"
            )
        tiles = choose_tiles(
            "mla", seq, (dn, dr, dv), cfg.flash_block_q, cfg.flash_block_k
        )
        if cfg.attn_impl == "flash" and seq >= cfg.flash_min_seq and tiles is not None:
            _note_attention("flash/mla", "flash/mla", seq, tiles)
            out = flash_attention_mla(
                q_nope, q_rope, k_nope, k_rope, v,
                block_q=cfg.flash_block_q, block_k=cfg.flash_block_k,
            )
        else:
            _note_attention(f"{cfg.attn_impl}/mla", "dense/mla", seq)
            out = latent_dense_attention(q_nope, q_rope, k_nope, k_rope, v)
        return nn.DenseGeneral(
            features=cfg.hidden_size, axis=(-2, -1), name="wo", **kind
        )(out)
