"""The plain reference of a Solar-Open2 model (config.json of model_type
``solar_open2``; Kimi Delta Attention arXiv:2510.26692 as
flash-linear-attention's ``KimiDeltaAttention`` writes it; the attention
gate arXiv:2505.06708) and its training loss, in straightforward
``jax.numpy``: float32 under ``default_matmul_precision("highest")``, a
a Python loop over the layers, the delta rule ONE
POSITION AT A TIME (a ``lax.scan`` over the sequence, no chunk, no
sub-block, no triangular inverse), the full score matrix, the full logits,
every held expert over every row weighted by what the row's choices give
it (zero where the row did not choose it). No kernel, no sort,
no chunking; it shares no code with ``torchft_tpu/models``,
``torchft_tpu/ops`` or ``parallel/train.py``. (One departure from "no
remat": each published layer is a ``jax.checkpoint``, because the
recurrence's backward pass keeps a state of H x 128 x 128 values a
position, 0.5 GB a mixer at the check's 1,024 tokens and 8 heads, and the
check holds the parameters and two gradient trees of 3.4 GB each beside
this program; the equations are untouched.)

The stack, for x = embed(tokens) [T, 4096] and eps = ``rms_norm_eps``::

    h = x + mixer(RMSNorm(x));  y = h + experts(RMSNorm(h))      a layer
    logits = RMSNorm(y_last) W_head                              untied

Kimi delta attention (every layer not in ``gqa_layers``), H =
``linear_attn_config.num_heads`` HELD heads of d = 128, a = RMSNorm(x):

    [q | k | v] = silu(conv([a W_q | a W_k | a W_v]))   causal, depthwise, 4
                                                        taps, zeros before, no bias
    q_t = q_t / sqrt(|q_t|^2 + 1e-6) / sqrt(d),  k_t = k_t / sqrt(|k_t|^2 + 1e-6)
    g_t = -exp(A_log_h) softplus(W_f_b W_f_a a_t + dt_bias)    in R^d a head
    beta_t = sigmoid(a_t W_b), doubled where ``kda_allow_neg_eigval``
    Sbar = Diag(exp(g_t)) S_{t-1};  u_t = beta_t (v_t - Sbar^T k_t)
    S_t = Sbar + k_t u_t^T;   o_t = S_t^T q_t                  S_0 = 0
    y_t = RMSNorm_d(o_t) * w * sigmoid(W_g_b W_g_a a_t + b_g); out = concat(y_t) W_o

Gated attention (``gqa_layers``): ``num_attention_heads`` HELD query heads
on ``num_key_value_heads`` HELD key/value heads of width ``head_dim``,
causal softmax at 1/sqrt(128), no rotary embedding, no QK norm, no bias;
out = (P v * sigmoid(a W_gate)) W_o, the gate a value a channel.

Experts (router over ``n_routed_experts x expert_parallel_chips`` = 320,
eight a row, width 1280), m = RMSNorm(h):
    s = sigmoid(m W_r)                                  float32
    idx = top_8(s + b)                                  b: the selection bias
    gate = routed_scaling_factor * s[idx] / (sum(s[idx]) + 1e-20)
    y = sum_i gate_i down_{idx_i}(silu(gate_{idx_i} m) * up_{idx_i} m)
        over the HELD idx_i only, + shared(m)           one expert, width 1280

The departures the configuration states: this chip holds an eighth of each
mixer's heads, and experts ``first .. first + n_routed_experts - 1``. What
the absent heads would add to W_o's sum and the absent experts to a row
is left out, and the partial result goes on. Likewise the vocabulary:
ids, logits and loss are over this chip's slice. The selection bias gets
no gradient and nothing moves it.

The loss: mean next-token cross-entropy over the vocabulary's slice.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from benchmark import cells

# The check's tolerances: system (bf16 matmul operands with fp32
# accumulation, a bf16 residual stream; float32 decays, their cumulative
# sums and exponentials, T and carried state, router, softmaxes and norms)
# against this reference, per gradient leaf as |g_sys - g_ref|_2 /
# |g_ref|_2 and for the loss as a relative difference. Measured on the chip
# at the published widths by the harness's own arithmetic
# (worker.reference_check's sample, keys and comparison, 1,024 tokens; my
# chip runs, PR 58: eight seeds in one process, then the check itself in
# the runs of the cell, sixteen seeds in all; PERF.md section 6 has the
# account).
#
# Gradients. The system's worst leaf read 0.290-0.502 (median of the
# seeds 0.40), ALWAYS a router kernel of one of the last two expert layers
# (layers_5, layers_7), the next leaves that layer's held expert stacks
# (0.29-0.34); the MEDIAN leaf 0.063-0.075, the best (the final norm, a
# mixer's A_log) 0.026-0.041; no leaf of a Kimi delta mixer or of the
# gated attention is among a seed's worst five. It is the precision, not
# the program: this reference with its own matmul operands rounded to bf16
# (``operand_dtype``) reads 0.311-0.336 on the same router leaves and
# 0.054-0.057 on the median leaf against itself in float32 (two seeds). The
# router stands out for the reason it does in ``joyai_flash``, ``lfm2_moe``
# and ``sdar_moe``: this chip computes only the assignments that land on
# its eight of 320 experts, about 200 of a sample's 8,192, so the router's
# gradient comes through few rows, and a row whose 8th and 9th score lie
# within a bf16 rounding changes sides. The next precision down, operands
# rounded to float8 (e4m3), reads 1.003-1.009 on the worst leaf and 1.000
# on the MEDIAN leaf (the cotangents fall under float8's smallest value and
# most gradients come out zero; 0.59-0.61 on its best leaf), and each of
# ``DROPS`` left out reads 1.68-9.16 on its worst leaf and 0.69-1.00 on its
# best. The limit lies between the two readings with room on both sides:
# 1.43 times the largest sound reading of sixteen, 1.39 times under the
# least float8 one, their geometric middle (0.71) rounded up because fresh
# seeds read higher than sixteen have, and under 1.0, what a leaf whose gradient
# never moves reads.
#
# Loss. 8.8e-6 to 2.6e-4 over the sixteen seeds (the bf16 reference 2.5e-6
# and 5.1e-5): the limit is the harness's other mixer cells'
# (``nemotron_h``, ``joyai_flash``, ``olmo_hybrid``: 1e-3), 3.9 times the
# largest seen. It does NOT tell the precisions apart (float8 reads 2.9e-4
# and 1.3e-3: the loss of 1,024 random tokens under random weights is
# nearly all the head's); every dropped term is outside it (1.6e-3 to
# 8.8e-3: the mixer's gate, the attention's gate, the decay, the shared
# expert, two seeds each). The gradient limit is the one that decides.
GRAD_REL_L2_TOL = 0.72
LOSS_REL_TOL = 1e-3
# What ``loss_and_grads`` can leave out, to show that the limits see it.
DROPS = ("beta_doubling", "decay", "kda_gate", "out_norm", "attn_gate", "shared")


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def delta_rule(q, k, v, g, beta):
    """q, k, g: [T, H, d]; v: [T, H, dv]; beta: [T, H]. The recurrence with
    a decay a key channel, one position at a time. Returns (o [T, H, dv],
    S_T [H, d, dv])."""

    def step(state, inputs):
        q_t, k_t, v_t, g_t, b_t = inputs
        decayed = jnp.exp(g_t)[:, :, None] * state
        u_t = b_t[:, None] * (v_t - jnp.einsum("hde,hd->he", decayed, k_t))
        state = decayed + k_t[:, :, None] * u_t[:, None, :]
        return state, jnp.einsum("hde,hd->he", state, q_t)

    zero = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), q.dtype)
    last, o = jax.lax.scan(step, zero, (q, k, v, g, beta))
    return o, last


def kda(a, p, c, r, drop):
    linear = c["linear_attn_config"]
    heads, d = linear["num_heads"], linear["head_dim"]
    eps = float(c["rms_norm_eps"])
    proj = lambda name, m=a: r(m) @ r(p[name]["kernel"])  # noqa: E731
    qkv = jnp.concatenate([proj("q_proj"), proj("k_proj"), proj("v_proj")], axis=-1)
    taps = p["conv_kernel"].shape[0]
    padded = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
    qkv = _silu(sum(
        padded[:, j : j + qkv.shape[1]] * p["conv_kernel"][j] for j in range(taps)
    ))
    bsz, t = qkv.shape[:2]
    q, k, v = (m.reshape(bsz, t, heads, d) for m in jnp.split(qkv, 3, axis=-1))
    q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) / jnp.sqrt(float(d))
    k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    step = proj("f_b_proj", proj("f_a_proj")) + p["dt_bias"]
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(step.reshape(bsz, t, heads, d))
    if drop == "decay":
        g = jnp.zeros_like(g)
    beta = jax.nn.sigmoid(proj("b_proj"))
    if c["kda_allow_neg_eigval"] and drop != "beta_doubling":
        beta = 2.0 * beta
    o, _ = jax.vmap(delta_rule)(r(q), r(k), r(v), g, beta)
    if drop != "out_norm":
        o = _rms_norm(o, p["norm_scale"], eps)
    if drop != "kda_gate":
        gate = proj("g_b_proj", proj("g_a_proj")) + p["g_b_proj"]["bias"]
        o = o * jax.nn.sigmoid(gate).reshape(o.shape)
    return r(o.reshape(bsz, t, heads * d)) @ r(p["o_proj"]["kernel"])


def attention(a, p, c, r, drop):
    q = jnp.einsum("bsh,hnd->bsnd", r(a), r(p["wq"]["kernel"]))
    k = jnp.einsum("bsh,hnd->bsnd", r(a), r(p["wk"]["kernel"]))
    v = jnp.einsum("bsh,hnd->bsnd", r(a), r(p["wv"]["kernel"]))
    group = q.shape[2] // k.shape[2]  # query heads a key/value head, in order
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = q.shape[1]
    scores = jnp.einsum("bqnd,bknd->bnqk", r(q), r(k)) / jnp.sqrt(float(q.shape[-1]))
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    probs = jax.nn.softmax(jnp.where(causal[None, None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bnqk,bknd->bqnd", r(probs), r(v))
    if drop != "attn_gate":
        out = out * jax.nn.sigmoid(jnp.einsum("bsh,hnd->bsnd", r(a), r(p["wg"]["kernel"])))
    return jnp.einsum("bqnd,ndh->bqh", r(out), r(p["wo"]["kernel"]))


def _swiglu(m, gate, up, down, r):
    return r(_silu(r(m) @ r(gate)) * (r(m) @ r(up))) @ r(down)


def experts(m, p, c, r, shared: bool = True):
    """m: [T, H]. One expert layer: the held experts' part of the routed
    sum plus the shared expert (``shared=False`` leaves that out: the
    shares-add-up test counts it once)."""
    held = c["n_routed_experts"]
    first = c["expert_parallel_index"] * held
    s = jax.nn.sigmoid(m @ p["router"]["kernel"])
    _, idx = jax.lax.top_k(s + p["router_bias"], c["num_experts_per_tok"])
    g = jnp.take_along_axis(s, idx, axis=-1)
    g = float(c["routed_scaling_factor"]) * g / (g.sum(axis=-1, keepdims=True) + 1e-20)
    # A row's choices, one after another: what they give each held expert.
    weight = sum(
        g[:, i, None] * (idx[:, i, None] == first + jnp.arange(held))
        for i in range(c["num_experts_per_tok"])
    )  # [T, held], zero where the row did not choose the expert
    # Every held expert over every row, the experts as one batched product
    # (a Python loop over them compiles to eight times the program).
    hidden = _silu(jnp.einsum("th,ehi->eti", r(m), r(p["experts_gate"]))) * jnp.einsum(
        "th,ehi->eti", r(m), r(p["experts_up"])
    )
    each = jnp.einsum("eti,eih->eth", r(hidden), r(p["experts_down"]))
    y = jnp.sum(weight.T[:, :, None] * each, axis=0)
    if shared:
        y = y + _swiglu(
            m, p["shared_gate"]["kernel"], p["shared_up"]["kernel"],
            p["shared_down"]["kernel"], r,
        )
    return y


def layer(x, mixer, ffn, c, r, drop):
    """One published layer over the program's two tree entries, each with
    its own ``norm``: the mixer (told apart by what the entry holds), then
    the expert layer."""
    eps = float(c["rms_norm_eps"])
    a = _rms_norm(x, mixer["norm"]["scale"], eps)
    if "kda" in mixer:
        x = x + kda(a, mixer["kda"], c, r, drop)
    else:
        x = x + attention(a, mixer["attn"], c, r, drop)
    m = _rms_norm(x, ffn["norm"]["scale"], eps)
    y = experts(m.reshape(-1, m.shape[-1]), ffn["mlp"], c, r, shared=drop != "shared")
    return x + y.reshape(m.shape)


def loss(
    params: Any, batch: Dict[str, jax.Array], c: Dict[str, Any],
    r=lambda a: a, drop: Optional[str] = None,
):
    """``r`` rounds the operands of the matrix multiplications but the
    router's (identity here; ``loss_and_grads`` says what the options are
    for)."""
    x = params["embed"]["embedding"][batch["inputs"]]
    one = jax.checkpoint(lambda x, mixer, ffn: layer(x, mixer, ffn, c, r, drop))
    # A Python loop over the layers: one scan over the three delta layers'
    # stacked parameters compiles in two thirds of the time and holds a
    # second copy of them and of their gradient, 3.9 GB the check does not
    # have (``memory_analysis()`` for a described v5e: 6.9 GB of temporaries
    # against 2.7).
    for i in range(c["num_hidden_layers"]):
        mixer, ffn = params[f"layers_{2 * i}"], params[f"layers_{2 * i + 1}"]
        if ("attn" in mixer) != (i in c["gqa_layers"]):
            raise cells.CellError(f"layer {i}: gqa_layers and the parameters disagree")
        x = one(x, mixer, ffn)
    x = _rms_norm(x, params["final_norm"]["scale"], float(c["rms_norm_eps"]))
    logits = r(x) @ r(params["lm_head"]["kernel"])
    logits = logits - jnp.max(logits, axis=-1, keepdims=True)
    logp = logits - jnp.log(jnp.sum(jnp.exp(logits), axis=-1, keepdims=True))
    picked = jnp.take_along_axis(logp, batch["targets"][..., None], axis=-1)[..., 0]
    mask = batch["mask"].astype(jnp.float32)
    return -(picked * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def loss_and_grads(
    params: Any, batch: Dict[str, jax.Array], c: Dict[str, Any],
    operand_dtype: Optional[Any] = None, drop: Optional[str] = None,
):
    """(loss, gradient tree), float32 at the highest matmul precision.
    The options size the tolerances above and are never passed by the
    check: ``operand_dtype`` rounds the operands of every matrix
    multiplication but the router's (and the recurrence's q, k, v) to that
    type first, what a run in that precision computes; ``drop`` leaves one
    of ``DROPS`` out."""
    if drop is not None and drop not in DROPS:
        raise cells.CellError(f"drop {drop!r} is none of {DROPS}")
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    r = (
        (lambda a: a) if operand_dtype is None
        else (lambda a: a.astype(operand_dtype).astype(jnp.float32))
    )
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(lambda p: loss(p, batch, c, r, drop))(params)
