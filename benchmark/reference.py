"""The plain reference: a pre-norm decoder (RMSNorm, grouped-query
attention with rotary embeddings, SwiGLU, untied or tied head) and its
mean next-token cross-entropy, in straightforward ``jax.numpy``.

Float32 throughout under ``default_matmul_precision("highest")`` (on a
TPU a float32 matmul otherwise runs in bf16 passes), a Python loop over
the layers, the full score matrix, the full logits: no kernel, no scan,
no remat, no chunking. It reads the published keys of the configuration
file and the parameter tree the program trains (flax names, layers
stacked on a leading axis), and shares no code with ``models/llama.py``
or ``parallel/train.py``. Rotary embedding is the half-split form of the
published implementations (`rotate_half`), which is also the layout the
checkpoints' q/k projections assume.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: [B, S, heads, d]. Pairs (i, i + d/2) rotate by pos * theta^(-2i/d)."""
    d = x.shape[-1]
    pos = jnp.arange(x.shape[1], dtype=jnp.float32)
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None] * inv[None, :]  # [S, d/2]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    a, b = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(x, p, c):
    heads, kv_heads = c["num_attention_heads"], c["num_key_value_heads"]
    q = jnp.einsum("bsh,hnd->bsnd", x, p["wq"]["kernel"])
    k = jnp.einsum("bsh,hnd->bsnd", x, p["wk"]["kernel"])
    v = jnp.einsum("bsh,hnd->bsnd", x, p["wv"]["kernel"])
    theta = float(c["rope_theta"])
    q, k = _rope(q, theta), _rope(k, theta)
    # Query head n attends through KV head n // (heads / kv_heads).
    k = jnp.repeat(k, heads // kv_heads, axis=2)
    v = jnp.repeat(v, heads // kv_heads, axis=2)
    s = q.shape[1]
    scores = jnp.einsum("bqnd,bknd->bnqk", q, k) / jnp.sqrt(float(q.shape[-1]))
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bnqk,bknd->bqnd", probs, v)
    return jnp.einsum("bqnd,ndh->bqh", out, p["wo"]["kernel"])


def _mlp(x, p):
    gate = x @ p["gate"]["kernel"]
    up = x @ p["up"]["kernel"]
    return (jax.nn.silu(gate) * up) @ p["down"]["kernel"]


def logits(params: Any, tokens: jax.Array, c: Dict[str, Any]) -> jax.Array:
    eps = float(c["rms_norm_eps"])
    x = params["embed"]["embedding"][tokens]
    layers = params["layers"]
    for i in range(c["num_hidden_layers"]):
        p = jax.tree_util.tree_map(lambda a: a[i], layers)
        x = x + _attention(_rms_norm(x, p["attn_norm"]["scale"], eps), p["attn"], c)
        x = x + _mlp(_rms_norm(x, p["mlp_norm"]["scale"], eps), p["mlp"])
    x = _rms_norm(x, params["final_norm"]["scale"], eps)
    if c["tie_word_embeddings"]:
        return x @ params["embed"]["embedding"].T
    return x @ params["lm_head"]["kernel"]


def loss(params: Any, batch: Dict[str, jax.Array], c: Dict[str, Any]) -> jax.Array:
    """Mean over unmasked positions of -log softmax(logits)[target]."""
    z = logits(params, batch["inputs"], c)
    z = z - jnp.max(z, axis=-1, keepdims=True)
    logp = z - jnp.log(jnp.sum(jnp.exp(z), axis=-1, keepdims=True))
    picked = jnp.take_along_axis(logp, batch["targets"][..., None], axis=-1)[..., 0]
    mask = batch["mask"].astype(jnp.float32)
    return -(picked * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def loss_and_grads(params: Any, batch: Dict[str, jax.Array], c: Dict[str, Any]):
    """(loss, gradient tree), float32 at the highest matmul precision."""
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(lambda p: loss(p, batch, c))(params)
