"""The plain reference of OLMoE's decoder (arXiv:2409.02060, and the
published ``modeling_olmoe``) and its training loss, in straightforward
``jax.numpy``: float32 under ``default_matmul_precision("highest")``, a
Python loop over the layers, the full score matrix, the full logits,
every expert applied to every token and weighted by its gate (zero where
the token did not choose it). No kernel, no sort, no scan, no remat, no
chunking; it shares no code with ``models/llama.py`` or
``parallel/train.py`` (RMSNorm and the half-split rotary embedding are
``dense_decoder``'s reference's).

The layer, for hidden states x [T, 2048] of T tokens (pre-norm block):

    a  = RMSNorm(x)
    q  = RMSNorm_2048(a Wq),  k = RMSNorm_2048(a Wk),  v = a Wv
         (learned scale, eps 1e-5, over the WHOLE projection, before the
         split into 16 heads of 128 and before RoPE; no bias, no clip_qkv)
    x  = x + Wo . causal_softmax_attention(RoPE(q), RoPE(k), v)
    m  = RMSNorm(x)
    z  = m Wr                       router logits, 64 experts, float32
    p  = softmax(z)
    (g, idx) = top_8(p)             NOT renormalised (norm_topk_prob false)
    y_t = sum_k g[t,k] . W_down[idx[t,k]] (silu(W_gate[idx[t,k]] m_t) * W_up[idx[t,k]] m_t)
    x  = x + y                      every assignment computed, none dropped

The loss: mean next-token cross-entropy + router_aux_loss_coef . L_LB +
router_z_loss_coef . L_z, both means over the layers of

    L_LB = E . sum_e f_e . P_e     f_e = assignments to e / (T . K) (no gradient),
                                   P_e = mean_t p[t,e]; 1 at uniform routing
    L_z  = mean_t (logsumexp_e z[t,e])^2

with the paper's coefficients 0.01 and 0.001. Hugging Face's
``load_balancing_loss_func`` leaves out the /K (8 times this value) and
pools the layers' tokens before the product (the same at one layer).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from benchmark import cells

_dense = cells.arch_module("dense_decoder", "reference")
_rms_norm, _rope = _dense._rms_norm, _dense._rope

# The check's tolerances: system (bf16 matmuls with fp32 accumulation,
# fp32 router) against this reference, per gradient leaf as
# |g_sys - g_ref|_2 / |g_ref|_2 and for the loss as a relative difference.
# Measured on the chip at the published widths, 1024-token sample, twelve
# seeds (my chip runs, PR 27): the worst leaf is always one of the three
# expert weights, 0.0488-0.0657; every other leaf 0.006-0.011 (the dense
# decoder's bf16 roundings); the loss 3.4e-7 to 6.5e-5. The expert leaves
# stand out because a top-8 choice flips where the 8th and 9th router
# logits (median gap 0.05) are closer than the bf16 rounding of the
# router's input moves them (mean 0.006): 50, 59 and 52 of the sample's
# 8,192 assignments went to another expert than in the reference, one a
# token, and each moves a whole row of gradient from one expert to
# another. It is the precision, not the program: this reference with its
# own matmul operands rounded to bf16 (``operand_dtype``) reads 0.050-0.061
# on three of those seeds. The tolerances are twice and three times the
# worst seen.
# The next precision down fails both: with operands rounded to float8
# (e4m3) the reference reads 1.01 on its worst leaf and 0.166 on its best
# (the three seeds' smallest), and 3.0e-4 to 4.4e-4 on the loss. A wrong
# norm axis, renormalised gates or a dropped assignment give errors of
# 0.1 to 1 on the leaves behind them (tests/test_olmoe.py).
GRAD_REL_L2_TOL = 0.13
LOSS_REL_TOL = 2e-4


def _attention(x, p, c, r):
    heads = c["num_attention_heads"]
    eps = float(c["rms_norm_eps"])
    b, s, _ = x.shape
    width = lambda w: w.reshape(w.shape[0], -1)  # noqa: E731 - [H, heads*d]
    q = _rms_norm(r(x) @ r(width(p["wq"]["kernel"])), p["q_norm"]["scale"], eps)
    k = _rms_norm(r(x) @ r(width(p["wk"]["kernel"])), p["k_norm"]["scale"], eps)
    v = r(x) @ r(width(p["wv"]["kernel"]))
    split = lambda t: t.reshape(b, s, heads, -1)  # noqa: E731
    theta = float(c["rope_theta"])
    q, k, v = _rope(split(q), theta), _rope(split(k), theta), split(v)
    scores = jnp.einsum("bqnd,bknd->bnqk", r(q), r(k)) / jnp.sqrt(float(q.shape[-1]))
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    probs = jax.nn.softmax(jnp.where(causal[None, None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bnqk,bknd->bqnd", r(probs), r(v))
    return jnp.einsum("bqnd,ndh->bqh", r(out), r(p["wo"]["kernel"]))


def route(m, p, c):
    """Router logits z [T, E], gates g [T, K] and experts idx [T, K]."""
    z = m @ p["router"]["kernel"]
    g, idx = jax.lax.top_k(jax.nn.softmax(z, axis=-1), c["num_experts_per_tok"])
    return z, g, idx


def _experts(m, p, c, r):
    """m: [T, H]. Returns (y [T, H], L_LB, L_z) of one layer."""
    n_experts = c["num_experts"]
    z, g, idx = route(m, p, c)
    chosen = jax.nn.one_hot(idx, n_experts, dtype=m.dtype)  # [T, K, E]
    weight = jnp.einsum("tk,tke->te", g, chosen)  # g where chosen, else 0
    gate = jnp.einsum("th,ehi->eti", r(m), r(p["experts_gate"]))
    up = jnp.einsum("th,ehi->eti", r(m), r(p["experts_up"]))
    each = jnp.einsum("eti,eih->eth", r(jax.nn.silu(gate) * up), r(p["experts_down"]))
    y = jnp.einsum("te,eth->th", weight, each)
    f = jax.lax.stop_gradient(chosen.sum(axis=(0, 1)) / (idx.shape[0] * idx.shape[1]))
    balance = n_experts * jnp.sum(f * jax.nn.softmax(z, axis=-1).mean(axis=0))
    lse = jnp.log(jnp.sum(jnp.exp(z - z.max(-1, keepdims=True)), axis=-1)) + z.max(-1)
    return y, balance, jnp.mean(lse * lse)


def loss(params: Any, batch: Dict[str, jax.Array], c: Dict[str, Any], r=lambda a: a):
    """``r`` rounds the operands of the block's matrix multiplications
    (identity here; ``loss_and_grads`` says what it is for)."""
    eps = float(c["rms_norm_eps"])
    x = params["embed"]["embedding"][batch["inputs"]]
    b, s, h = x.shape
    balance = z_loss = 0.0
    for i in range(c["num_hidden_layers"]):
        p = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
        x = x + _attention(_rms_norm(x, p["attn_norm"]["scale"], eps), p["attn"], c, r)
        m = _rms_norm(x, p["mlp_norm"]["scale"], eps).reshape(b * s, h)
        y, lb, lz = _experts(m, p["mlp"], c, r)
        x = x + y.reshape(b, s, h)
        balance, z_loss = balance + lb, z_loss + lz
    x = _rms_norm(x, params["final_norm"]["scale"], eps)
    logits = r(x) @ r(params["lm_head"]["kernel"])
    logits = logits - jnp.max(logits, axis=-1, keepdims=True)
    logp = logits - jnp.log(jnp.sum(jnp.exp(logits), axis=-1, keepdims=True))
    picked = jnp.take_along_axis(logp, batch["targets"][..., None], axis=-1)[..., 0]
    mask = batch["mask"].astype(jnp.float32)
    ce = -(picked * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    layers = c["num_hidden_layers"]
    return (
        ce
        + float(c["router_aux_loss_coef"]) * balance / layers
        + float(c["router_z_loss_coef"]) * z_loss / layers
    )


def loss_and_grads(
    params: Any, batch: Dict[str, jax.Array], c: Dict[str, Any],
    operand_dtype: Optional[Any] = None,
):
    """(loss, gradient tree), float32 at the highest matmul precision.
    ``operand_dtype`` rounds the operands of every matrix multiplication
    of the block and the head to that type first (the router's stay
    float32, as the configuration states): what a run in that precision
    computes, for sizing the tolerances above against the next precision
    down. The check never passes it."""
    if c["tie_word_embeddings"]:
        raise cells.CellError("this reference has an untied head only")
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    r = (
        (lambda a: a) if operand_dtype is None
        else (lambda a: a.astype(operand_dtype).astype(jnp.float32))
    )
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(lambda p: loss(p, batch, c, r))(params)
