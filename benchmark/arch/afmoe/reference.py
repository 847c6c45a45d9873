"""The plain reference of an AFMoE decoder (the published config.json of
arcee-ai/Trinity-Mini, model_type ``afmoe``; the layer is the model's own
code, ``modeling_afmoe.py`` of the transformers library) and its next-token
training loss, in straightforward ``jax.numpy``: float32 under
``default_matmul_precision("highest")``, a Python loop over the layers, an
explicit boolean mask over the [S, S] square, the full logits, every held
expert applied to every row and weighted by its gate (zero where the row
did not choose it). No kernel, no sort, no tile schedule; it shares no code
with ``torchft_tpu/models``, ``torchft_tpu/ops`` or ``parallel/train.py``.

A published layer l, for x [B, S, 2048] (eps 1e-5; no projection has a bias):

    x0 = E[tokens] * sqrt(2048)                  mup_enabled
    h  = RMSNorm_in(x)
    q, k, v = h W_q, h W_k, h W_v                32 x 128, 4 x 128, 4 x 128
    g  = h W_g                                   [B, S, 32, 128]: the output gate, of the NORMED input
    q, k = RMSNorm_q(q), RMSNorm_k(k)            over each head's 128 values, one learned vector each
    layer_types[l] = sliding_attention: q and k rotated over the whole head
        width (theta 1e4, half-split pairs: channel c pairs with c + 64);
        full_attention: not rotated
    row i sees column j where j <= i and, on a sliding layer, also
        i - j < sliding_window (the position itself counted)
    a  = softmax(q k^T / sqrt(128) + mask) v     8 query heads a key/value head
    y  = x + RMSNorm_post_attn((a * sigmoid(g)) W_o)
    h2 = RMSNorm_pre_mlp(y)
    l < num_dense_layers:   f = W_down(silu(W_gate h2) * W_up h2)          width 6144
    else: s = sigmoid(h2 W_r)                    [B, S, 128] float32
          e_1..e_8 = the top 8 of s + b          b: the selection bias, no gradient
          w_i = 2.826 * s[e_i] / (sum_j s[e_j] + 1e-20)    the scores WITHOUT b
          f = shared(h2) + sum_i w_i expert_{e_i}(h2)      over the HELD e_i only;
                                                 both SwiGLU as above, width 1024
    out = y + RMSNorm_post_mlp(f)

then a final RMSNorm and the untied head. The loss is the mean over the
masked positions of the cross-entropy against batch["targets"]; no balance
term is in it (none is a key of the published file: the selection bias does
the balancing). The bias gets no gradient; the step's move of it is not
part of the loss and ``bias_update`` below states its rule.

The departures the configuration states: this chip holds experts
``first .. first + num_experts - 1`` of the router's ``num_experts x
expert_parallel_chips`` = 128; what the absent ones would add is left out,
the shared expert is computed whole, and the partial result goes on to the
next layer. Likewise the vocabulary: ids, logits and loss are over this
chip's slice. The layers are the file's ``layer_types`` in order (the kept
run starts at published layer 1, so a layer's kind is its ENTRY, never its
index modulo ``global_attn_every_n_layers``), the first
``num_dense_layers`` of them dense.

``query_block``: for a sequence whose [heads, S, S] scores do not fit the
chip (16,384: 34 GB), the same mathematics a block of query rows at a time
(``_in_blocks``: a ``lax.map`` over the blocks, each block's rows of the
SAME [S, S] mask against every key), each block, each layer and the head's
row blocks under ``jax.checkpoint`` so that the backward pass holds one of
them at a time. The harness's check (1,024 tokens) passes None and runs the
whole square at once.

A sequence no longer than the window would see no band (the published
layer is then a causal one), and the harness's check samples 1,024 tokens:
such a sequence is compared under a window of half its length
(``window_at``), 512 at 1,024: four of the sample's tiles of 128, so that a
query tile's sweep is five key tiles, as the timed shape's is (a window of
2,048 under tiles of 512). ``adapter.sample_config`` gives the program's
sample the same.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

# The check's tolerances: system (bf16 matmul operands with fp32
# accumulation, a bf16 residual stream, rotary tables, norms' outputs and
# gate product, float32 router and softmaxes) against this reference, per
# gradient leaf as |g_sys - g_ref|_2 / |g_ref|_2 and for the loss as a
# relative difference. Measured on the chip at the published widths on the
# harness's own sample (1,024 tokens under a window of 512, ``window_at``;
# the system through the banded kernels at tiles of 128;
# ``tools/reference_compare.py`` and the cell's runs; my chip runs, PR 65;
# PERF.md section 6 has the whole account).
#
# Gradients, the worst leaf: the system read 0.223-0.380 on 23 seeds (16 of
# the tool's, 0.226-0.306; 7 of the cell's own runs, 0.223-0.291 and one
# 0.380; median 0.255), ALWAYS an expert layer's router kernel (the second
# worst leaf 0.21-0.25, the median leaf 0.048-0.064), and 0.238 and 0.242 at
# the timed 16,384 tokens under the published window. This reference with
# its matmul operands rounded to bf16 handed to the check in the system's
# place read 0.211-0.257 on 4, on router kernels too: the system's bulk is
# its precision's. A router's gradient is this loose because the rows'
# top-8 of 128 sigmoid scores lie close together and a rounding of the
# normed input flips a choice, which moves the row from one expert's gate
# to another's; the tail is the few rows a seed's rounding flips (0.380 is
# one run of 23). The next precision down, the same with float8 (e4m3,
# ``operand_dtype``), read 1.049-1.087 on 6 seeds, the MEDIAN leaf 1.0. A
# leaf whose gradient never moves reads 1.0 by arithmetic. The departures
# (``DEPARTURES``, the reference computing the other model in the system's
# place, 2 seeds each): no post-norm 1.84 and 1.87, no embedding scale 1.54
# and 1.67, the gate on the stream 1.04 and 1.06 (median leaf 0.34), a
# rotated global layer 1.09 and 1.19 (median 0.20); a program that runs no
# band (a window of 1,024 against this file's 512) 0.887 and 0.904 (median
# 0.53). The bias in the gates cannot show on the chip's check (a fresh
# bias is zero); tier-1's float32 comparison on the CPU holds it, and each
# of the others, to 2e-4 (tests/test_trinity.py). The limit lies between
# the largest sound reading and the smallest float8 one with the more room
# above the sound one, since a fresh seed can read higher: 1.58 times over
# 0.380, 1.75 times under float8 and the nearest departure, 1.48 times
# under a missing band.
#
# Loss: 1.4e-6 to 3.4e-4 over those 23 seeds (2.3e-5 and 2.4e-5 at 16,384
# tokens). The limit is the harness's accepted cells' 1e-3 (nemotron_h,
# joyai_flash, olmo_hybrid), 2.9 times the largest seen. It does not tell the
# precisions apart (float8 2.9e-4 to 1.3e-3; bf16 operands 2.4e-5 to
# 4.6e-5: the loss of 1,024 random tokens under random weights is nearly
# all the head's), the gradient limit does that; a missing band it refuses
# too (1.8e-3 and 2.1e-3).
GRAD_REL_L2_TOL = 0.6
LOSS_REL_TOL = 1e-3


SAMPLE_WINDOW_SHARE = 2  # a sample no longer than the window keeps half of itself
GATE_EPS = 1e-20  # what the model's code adds to the chosen scores' sum
# Another model under this one's name, each by one step: what
# ``loss_and_grads(..., departure=)`` computes in place of the equations
# above, so that a tolerance can be shown to refuse it (the harness's check
# never passes one).
DEPARTURES = (
    "no_post_norm",     # y = x + mixer(norm(x)): the second norm left out
    "no_embed_scale",   # the embedded rows as the table holds them
    "gate_on_stream",   # g = x W_g: the gate reads the stream, not the normed input
    "rope_everywhere",  # a full_attention layer's queries and keys rotated too
    "bias_in_gates",    # the gates from s + b, the bias weighing as well as choosing
)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def visible(seq: int, window: Optional[int]) -> jax.Array:
    """[S, S] boolean: row i sees column j where j <= i and, under a
    window, i - j < window."""
    i, j = jnp.arange(seq)[:, None], jnp.arange(seq)[None, :]
    see = j <= i
    return see if window is None else see & (i - j < window)


def window_at(c: Dict[str, Any], seq: int) -> int:
    """The sliding layers' window over ``seq`` positions: the published
    one, and for a sequence no longer than it (where the published model's
    sliding layers are causal ones and a comparison would see no band) half
    of the sequence. The harness's sample of 1,024 tokens is compared under
    a window of 512."""
    window = c["sliding_window"]
    return window if seq > window else max(1, seq // SAMPLE_WINDOW_SHARE)


def sliding(c: Dict[str, Any], layer: int) -> bool:
    return c["layer_types"][layer] == "sliding_attention"


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


def attention(
    h, p, c, layer: int, r, query_block: Optional[int] = None, gate_input=None,
    rotate_all: bool = False,
):
    """h: the sub-layer's NORMED input, which the gate reads too
    (``gate_input`` and ``rotate_all``: a departure's)."""
    heads, kv_heads = c["num_attention_heads"], c["num_key_value_heads"]
    seq, eps = h.shape[1], float(c["rms_norm_eps"])
    q = jnp.einsum("bsh,hnd->bsnd", r(h), r(p["wq"]["kernel"]))
    k = jnp.einsum("bsh,hnd->bsnd", r(h), r(p["wk"]["kernel"]))
    v = jnp.einsum("bsh,hnd->bsnd", r(h), r(p["wv"]["kernel"]))
    gate = jnp.einsum(
        "bsh,hnd->bsnd", r(h if gate_input is None else gate_input), r(p["wg"]["kernel"])
    )
    q = _rms_norm(q, p["q_norm"]["scale"], eps)
    k = _rms_norm(k, p["k_norm"]["scale"], eps)
    if sliding(c, layer) or rotate_all:
        q, k = _rotary(q, float(c["rope_theta"])), _rotary(k, float(c["rope_theta"]))
    k = jnp.repeat(k, heads // kv_heads, axis=2)
    v = jnp.repeat(v, heads // kv_heads, axis=2)
    see = visible(seq, window_at(c, seq) if sliding(c, layer) else None)
    if query_block is None:
        out = _attend(q, k, v, see, r)
    else:
        out = _in_blocks(
            lambda qb, sb: _attend(qb, k, v, sb, r), query_block,
            jnp.moveaxis(q, 1, 0), see,
        )
        out = jnp.moveaxis(out, 0, 1)
    return jnp.einsum("bqnd,ndh->bqh", r(out * _sigmoid(gate)), r(p["wo"]["kernel"]))


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


def _gated_ffn(m, gate, up, down, r):
    return r(_silu(r(m) @ r(gate)) * (r(m) @ r(up))) @ r(down)


def layout(c: Dict[str, Any]):
    """(the router's width, the first expert held, how many are held)."""
    held = c["num_experts"]
    return held * c["expert_parallel_chips"], c["expert_parallel_index"] * held, held


def route(m, p, c, bias_in_gates: bool = False):
    """(scores [T, E] float32, chosen experts [T, K], their gates [T, K])."""
    s = _sigmoid(m @ p["router"]["kernel"])  # float32, never rounded
    _, idx = jax.lax.top_k(s + p["router_bias"], c["num_experts_per_tok"])
    # the gates weigh by the scores WITHOUT the bias (with it: a departure's)
    picked = jnp.take_along_axis(s + p["router_bias"] if bias_in_gates else s, idx, axis=-1)
    gates = float(c["route_scale"]) * picked / (picked.sum(-1, keepdims=True) + GATE_EPS)
    return s, idx, gates


def experts(m, p, c, r, bias_in_gates: bool = False):
    """m: [T, H], the expert layer's normed input. The held experts' part
    of the routed sum (every held expert over every row, one after the
    other, weighted by its gate) plus the shared expert, whole."""
    n_experts, first, held = layout(c)
    _, idx, gates = route(m, p, c, bias_in_gates)
    chosen = jax.nn.one_hot(idx, n_experts, dtype=m.dtype)  # [T, K, E]
    weight = jnp.einsum("tk,tke->te", gates, chosen)[:, first : first + held]

    def one(y, e):
        w_e, gate, up, down = e
        return y + w_e[:, None] * _gated_ffn(m, gate, up, down, r), None

    routed, _ = jax.lax.scan(
        jax.checkpoint(one), jnp.zeros_like(m),
        (weight.T, p["experts_gate"], p["experts_up"], p["experts_down"]),
    )
    shared = _gated_ffn(
        m, p["shared_gate"]["kernel"], p["shared_up"]["kernel"],
        p["shared_down"]["kernel"], r,
    )
    return routed + shared


def bias_update(bias, load, rate):
    """The step's out-of-gradient move of a selection bias (the recipe's
    ``load_balance_coeff``): towards the experts the router under-used.
    ``load`` [E]: the assignments each expert got from the step's tokens.
    (The recipe also re-centres b; a common shift changes no top-k.)"""
    return bias + rate * jnp.sign(load.mean() - load)


def _layer(x, attn, ffn, c, layer: int, r, query_block, departure=None):
    """One published layer. The parameter tree is the program's: a
    published layer is two entries, each with its ``norm`` (before) and its
    ``post_norm`` (after)."""
    eps = float(c["rms_norm_eps"])

    def post(y, scale):
        return y if departure == "no_post_norm" else _rms_norm(y, scale, eps)

    h = _rms_norm(x, attn["norm"]["scale"], eps)
    y = x + post(
        attention(
            h, attn["attn"], c, layer, r, query_block,
            gate_input=x if departure == "gate_on_stream" else None,
            rotate_all=departure == "rope_everywhere",
        ),
        attn["post_norm"]["scale"],
    )
    h2 = _rms_norm(y, ffn["norm"]["scale"], eps)
    rows = h2.reshape(-1, h2.shape[-1])
    if layer < c["num_dense_layers"]:
        mlp = ffn["mlp"]
        f = _gated_ffn(
            rows, mlp["gate"]["kernel"], mlp["up"]["kernel"], mlp["down"]["kernel"], r
        )
    else:
        f = experts(rows, ffn["mlp"], c, r, departure == "bias_in_gates")
    return y + post(f.reshape(y.shape), ffn["post_norm"]["scale"])


def _picked_logp(hidden, head, targets, r):
    """log softmax(hidden @ head)[target] a row. hidden: [T, H]."""
    logits = r(hidden) @ r(head)
    logits = logits - jnp.max(logits, axis=-1, keepdims=True)
    logp = logits - jnp.log(jnp.sum(jnp.exp(logits), axis=-1, keepdims=True))
    return jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]


def embed_scale(c: Dict[str, Any]) -> float:
    return float(c["hidden_size"]) ** 0.5 if c["mup_enabled"] else 1.0


def loss(
    params: Any, batch: Dict[str, jax.Array], c: Dict[str, Any],
    r=lambda a: a, query_block: Optional[int] = None, departure: Optional[str] = None,
):
    """``r`` rounds the operands of the matrix multiplications (identity
    here; ``loss_and_grads`` says what the options are for)."""
    if departure is not None and departure not in DEPARTURES:
        raise ValueError(f"departure {departure!r} is none of {DEPARTURES}")
    eps = float(c["rms_norm_eps"])
    x = params["embed"]["embedding"][batch["inputs"]]
    if departure != "no_embed_scale":
        x = x * embed_scale(c)
    for i in range(c["num_hidden_layers"]):
        layer = lambda x, attn, ffn, i=i: _layer(  # noqa: E731
            x, attn, ffn, c, i, r, query_block, departure)
        if query_block is not None:
            layer = jax.checkpoint(layer)
        x = layer(x, params[f"layers_{2 * i}"], params[f"layers_{2 * i + 1}"])
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
    return -(picked * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def loss_and_grads(
    params: Any, batch: Dict[str, jax.Array], c: Dict[str, Any],
    operand_dtype: Optional[Any] = None, query_block: Optional[int] = None,
    departure: Optional[str] = None,
):
    """(loss, gradient tree), float32 at the highest matmul precision.
    The options are never passed by the harness's check: ``operand_dtype``
    rounds the operands of every matrix multiplication but the router's to
    that type first (what a run in that precision computes: it sizes the
    tolerances above); ``query_block`` computes the same in blocks of that
    many query rows (the builder's comparison at 16,384 tokens);
    ``departure`` computes one of ``DEPARTURES`` instead (what the
    tolerances have to refuse)."""
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    r = (
        (lambda a: a) if operand_dtype is None
        else (lambda a: a.astype(operand_dtype).astype(jnp.float32))
    )
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda p: loss(p, batch, c, r, query_block, departure)
        )(params)
