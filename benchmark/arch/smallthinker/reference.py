"""The plain reference of a SmallThinker mixture-of-experts decoder (the
published config.json of PowerInfer/SmallThinker-21BA3B-Instruct;
arXiv:2507.20984) and its next-token training loss, in straightforward
``jax.numpy``: float32 under ``default_matmul_precision("highest")``, a
Python loop over the layers, an explicit boolean mask over the [S, S]
square, the full logits, every held expert applied to every row and
weighted by its gate (zero where the row did not choose it). No kernel, no
sort, no tile schedule; it shares no code with ``torchft_tpu/models``,
``torchft_tpu/ops`` or ``parallel/train.py``.

A layer, for x [B, S, 2560] and the published layer index l (eps 1e-6):

    h  = RMSNorm(x)
    r  = h W_r                     [B, S, 64] float32: the router reads the
                                   PRE-attention h (the model's own design)
    q, k, v = h W_q, h W_k, h W_v  28 x 128, 4 x 128, 4 x 128; no bias, no QK norm
    rope_layout[l] = 1: q and k rotated over the whole head width
        (theta 1.5e6, half-split pairs: channel c pairs with c + 64); 0: not
    row i sees column j where j <= i and, where sliding_window_layout[l] = 1,
        also i - j < sliding_window_size (the position itself counted)
    y  = x + softmax(q k^T / sqrt(128) + mask) v W_o     7 query heads a key/value head
    h2 = RMSNorm(y)
    e_1..e_6 = the top 6 of r;  g = softmax over those six logits
    out = y + sum_i g_i down_{e_i}(relu(gate_{e_i} h2) * up_{e_i} h2)
        over the HELD e_i only

The departure the configuration states: this chip holds experts
``first .. first + moe_num_primary_experts - 1`` of the router's
``moe_num_primary_experts x expert_parallel_chips`` = 64; what the absent
ones would add is left out, and the partial result goes on to the next
layer. Likewise the vocabulary: ids, logits and loss are over this chip's
slice. Then a final RMSNorm and the untied head.

The loss: the mean over the masked positions of the cross-entropy against
batch["targets"], plus ``router_aux_loss_coef`` times the mean over the
layers of E sum_e f_e P_e, f_e = the share of all rows' six choices that
went to e (no gradient), P_e = the mean over the rows of softmax(r)[., e],
over all 64 router outputs (``assumed`` in the configuration file: the
published file names no balance term).

``query_block``: for a sequence whose [heads, S, S] scores do not fit the
chip (16,384: 30 GB), the same mathematics a block of query rows at a
time (``_in_blocks``: a ``lax.map`` over the blocks, each block's rows of
the SAME [S, S] mask against every key), each block, each layer and the
head's row blocks under ``jax.checkpoint`` so that the backward pass holds
one of them at a time. The harness's check (1,024 tokens) passes None and
runs the whole square at once.

A sequence no longer than the window would see no band (the published
layer is then a causal one), and the harness's check samples 1,024 tokens:
such a sequence is compared under a window of a quarter of its length
(``window_at``: the published window's share of the published context),
and ``adapter.sample_config`` gives the program's sample the same.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

# The check's tolerances: system (bf16 matmul operands with fp32
# accumulation, a bf16 residual stream and rotary tables, float32 router
# and softmaxes) against this reference, per gradient leaf as
# |g_sys - g_ref|_2 / |g_ref|_2 and for the loss as a relative difference.
# Measured on the chip at the published widths on the harness's own sample
# (1,024 tokens under a window of 256, ``window_at``; the system through the
# banded kernels at tiles of 128; ``tools/reference_compare.py`` and the
# cell's runs; my chip runs, PR 60; PERF.md section 6 has the whole account).
#
# Gradients, the worst leaf: the system read 0.111-0.197 on 38 seeds (30 of
# the tool's, 8 of the cell's own runs; median 0.131; the worst leaf a held
# expert stack's gate or a router kernel, about half each; the median leaf
# 0.034-0.050), and this reference with its matmul operands rounded to bf16
# handed to the check in the system's place 0.082-0.165 on 6: the system's
# bulk is its precision's. The next precision down, the same with float8
# (e4m3, ``operand_dtype``), read 1.050-1.457 on 12 seeds, always a router
# kernel, the MEDIAN leaf 1.0. A leaf whose gradient never moves reads 1.0
# by arithmetic. A program that runs no band (a window of 1,024 against
# this file's 256) read 0.868 and 0.883 with a median leaf of 0.54, one
# whose band is half as wide 1.11 and 1.17; one whose band is ONE position
# too wide reads 0.165-0.171, inside the sound readings: bf16 hides a
# single position, and tier-1's float32 comparison on the CPU (2e-4) is
# what holds the edge to the position. The limit is the geometric mean of
# the largest sound reading and the smallest float8 one, 2.3 times the one
# and 2.3 times under the other, 2.2 times under a dead leaf and 1.9 times
# under a missing band.
#
# The readings are this narrow because the configuration's embedding table
# has unit variance (``embedding_init_std``, ``assumed`` in the file): under
# flax's default table the routing collapsed from the third layer on, a
# layer's held experts got between a third and none of the rows, and the
# worst leaf read 0.17 to inf (PERF.md section 6).
#
# Loss: 3.7e-7 to 1.3e-4 over those 38 seeds (1.0e-5 and 2.5e-5 at 16,384
# tokens). The limit is 3.9 times the largest seen; it does not tell the
# precisions apart (float8 8.5e-6 to 5.4e-4: the loss of 1,024 random
# tokens under random weights is nearly all the head's), the gradient limit
# does that; a missing band it refuses too (2.3e-3 and 2.8e-3).
GRAD_REL_L2_TOL = 0.45
LOSS_REL_TOL = 5e-4


SAMPLE_WINDOW_SHARE = 4  # max_position_embeddings / sliding_window_size, as published


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def visible(seq: int, window: Optional[int]) -> jax.Array:
    """[S, S] boolean: row i sees column j where j <= i and, under a
    window, i - j < window."""
    i, j = jnp.arange(seq)[:, None], jnp.arange(seq)[None, :]
    see = j <= i
    return see if window is None else see & (i - j < window)


def window_at(c: Dict[str, Any], seq: int) -> int:
    """The windowed layers' window over ``seq`` positions: the published
    one, and for a sequence no longer than it (where the published model's
    windowed layers are causal ones and a comparison would see no band) a
    quarter of the sequence, the published window's share of the published
    context. The harness's sample of 1,024 tokens is compared under a
    window of 256."""
    window = c["sliding_window_size"]
    return window if seq > window else max(1, seq // SAMPLE_WINDOW_SHARE)


def _rotary(x, theta):
    """x: [B, S, heads, D] at positions 0..S-1. Half-split pairs."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attend(q, k, v, see, r):
    """q: [B, Q, heads, D] (any block of query rows), k, v: [B, S, heads, D],
    see: [Q, S]."""
    scores = jnp.einsum("bqnd,bknd->bnqk", r(q), r(k)) / jnp.sqrt(float(q.shape[-1]))
    probs = jax.nn.softmax(jnp.where(see[None, None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("bnqk,bknd->bqnd", r(probs), r(v))


def attention(h, p, c, layer: int, r, query_block: Optional[int] = None):
    heads, kv_heads = c["num_attention_heads"], c["num_key_value_heads"]
    seq = h.shape[1]
    q = jnp.einsum("bsh,hnd->bsnd", r(h), r(p["wq"]["kernel"]))
    k = jnp.einsum("bsh,hnd->bsnd", r(h), r(p["wk"]["kernel"]))
    v = jnp.einsum("bsh,hnd->bsnd", r(h), r(p["wv"]["kernel"]))
    if c["rope_layout"][layer]:
        q, k = _rotary(q, float(c["rope_theta"])), _rotary(k, float(c["rope_theta"]))
    k = jnp.repeat(k, heads // kv_heads, axis=2)
    v = jnp.repeat(v, heads // kv_heads, axis=2)
    window = window_at(c, seq) if c["sliding_window_layout"][layer] else None
    see = visible(seq, window)
    if query_block is None:
        out = _attend(q, k, v, see, r)
    else:
        out = _in_blocks(
            lambda qb, sb: _attend(qb, k, v, sb, r), query_block,
            jnp.moveaxis(q, 1, 0), see,
        )
        out = jnp.moveaxis(out, 0, 1)
    return jnp.einsum("bqnd,ndh->bqh", r(out), r(p["wo"]["kernel"]))


def _in_blocks(fn, block: int, *rows):
    """``fn`` over blocks of ``block`` leading rows of each of ``rows``, one
    block after the other (``lax.map``: the backward pass then holds one
    block's intermediates at a time, each block recomputed under
    ``jax.checkpoint``), the results joined along the leading axis. The
    first of ``rows`` is [S, B, ...] and a block reaches ``fn`` as
    [B, block, ...]; the others are [S, ...]."""
    n = rows[0].shape[0] // block
    assert n * block == rows[0].shape[0], (rows[0].shape, block)
    split = [a.reshape(n, block, *a.shape[1:]) for a in rows]

    def one(parts):
        return jnp.moveaxis(fn(jnp.moveaxis(parts[0], 0, 1), *parts[1:]), 1, 0)

    out = jax.lax.map(jax.checkpoint(one), split)
    return out.reshape(n * block, *out.shape[2:])


def experts(m, logits, p, c, r):
    """m: [T, H], the expert layer's own normed input; logits: [T, E], the
    router's, from the PRE-attention input. Returns (y [T, H], balance
    term) of one layer: the held experts' part of the routed sum."""
    held = c["moe_num_primary_experts"]
    n_experts = held * c["expert_parallel_chips"]
    first = c["expert_parallel_index"] * held
    top, idx = jax.lax.top_k(logits, c["moe_num_active_primary_experts"])
    g = jax.nn.softmax(top, axis=-1)  # over the chosen logits only
    chosen = jax.nn.one_hot(idx, n_experts, dtype=m.dtype)  # [T, K, E]
    weight = jnp.einsum("tk,tke->te", g, chosen)[:, first : first + held]
    # Every held expert over every row, the experts' axis one einsum index
    # (a Python loop over them compiled eight bodies a layer).
    hidden = jnp.maximum(
        jnp.einsum("th,ehf->etf", r(m), r(p["experts_gate"])), 0.0
    ) * jnp.einsum("th,ehf->etf", r(m), r(p["experts_up"]))
    out = jnp.einsum("etf,efh->eth", r(hidden), r(p["experts_down"]))
    y = jnp.einsum("te,eth->th", weight, out)
    load = jax.lax.stop_gradient(chosen.sum(axis=(0, 1)))
    probs = jax.nn.softmax(logits, axis=-1)
    balance = n_experts * jnp.sum(load / (idx.shape[0] * idx.shape[1]) * probs.mean(axis=0))
    return y, balance


def _layer(x, attn, ffn, c, layer: int, r, query_block):
    """One published layer: (x after it, its balance term). The parameter
    tree is the program's: a published layer is two entries, each with its
    own ``norm``; the router's kernel lies with the attention's."""
    eps = float(c["rms_norm_eps"])
    h = _rms_norm(x, attn["norm"]["scale"], eps)
    logits = h @ attn["router"]["kernel"]  # float32, never rounded
    y = x + attention(h, attn["attn"], c, layer, r, query_block)
    h2 = _rms_norm(y, ffn["norm"]["scale"], eps)
    rows = h2.shape[0] * h2.shape[1]
    out, balance = experts(
        h2.reshape(rows, -1), logits.reshape(rows, -1), ffn["mlp"], c, r
    )
    return y + out.reshape(y.shape), balance


def _picked_logp(hidden, head, targets, r):
    """log softmax(hidden @ head)[target] a row. hidden: [T, H]."""
    logits = r(hidden) @ r(head)
    logits = logits - jnp.max(logits, axis=-1, keepdims=True)
    logp = logits - jnp.log(jnp.sum(jnp.exp(logits), axis=-1, keepdims=True))
    return jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]


def loss(
    params: Any, batch: Dict[str, jax.Array], c: Dict[str, Any],
    r=lambda a: a, query_block: Optional[int] = None,
):
    """``r`` rounds the operands of the matrix multiplications (identity
    here; ``loss_and_grads`` says what the options are for)."""
    eps, n_layers = float(c["rms_norm_eps"]), c["num_hidden_layers"]
    x = params["embed"]["embedding"][batch["inputs"]]
    balance = 0.0
    for i in range(n_layers):
        layer = lambda x, attn, ffn, i=i: _layer(x, attn, ffn, c, i, r, query_block)  # noqa: E731
        if query_block is not None:
            layer = jax.checkpoint(layer)
        x, lb = layer(x, params[f"layers_{2 * i}"], params[f"layers_{2 * i + 1}"])
        balance = balance + lb
    hidden = _rms_norm(x, params["final_norm"]["scale"], eps).reshape(-1, x.shape[-1])
    head, targets = params["lm_head"]["kernel"], batch["targets"].reshape(-1)
    if query_block is None:
        picked = _picked_logp(hidden, head, targets, r)
    else:
        picked = _in_blocks(
            lambda hb, tb: _picked_logp(hb[0], head, tb, r)[None], query_block,
            hidden[:, None], targets,
        )[:, 0]
    mask = batch["mask"].astype(jnp.float32).reshape(-1)
    ce = -(picked * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return ce + float(c["router_aux_loss_coef"]) * balance / n_layers


def loss_and_grads(
    params: Any, batch: Dict[str, jax.Array], c: Dict[str, Any],
    operand_dtype: Optional[Any] = None, query_block: Optional[int] = None,
):
    """(loss, gradient tree), float32 at the highest matmul precision.
    The options are never passed by the harness's check: ``operand_dtype``
    rounds the operands of every matrix multiplication but the router's to
    that type first (what a run in that precision computes: it sizes the
    tolerances above); ``query_block`` computes the same in blocks of that
    many query rows (the builder's comparison at 16,384 tokens)."""
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    r = (
        (lambda a: a) if operand_dtype is None
        else (lambda a: a.astype(operand_dtype).astype(jnp.float32))
    )
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(lambda p: loss(p, batch, c, r, query_block))(params)
