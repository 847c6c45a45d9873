"""The plain reference of an Olmo-Hybrid model (config.json of model_type
``olmo_hybrid``; Gated DeltaNet arXiv:2412.06464 as flash-linear-attention
writes it; OLMo 2's layer arXiv:2501.00656) and its training loss, in
straightforward ``jax.numpy``: float32 under
``default_matmul_precision("highest")``, a Python loop over the layers,
the delta rule ONE POSITION AT A TIME (a ``lax.scan`` over the sequence,
no chunk, no triangular inverse), the full score matrix, the full logits.
No kernel, no chunking; it shares no code with ``torchft_tpu/models`` or
``parallel/train.py``. (One departure from "no remat": each gated-delta
mixer is a ``jax.checkpoint``, because the recurrence's backward pass keeps
a state of H x 96 x 192 values a position, 1.1 GB a layer at the check's
1,024 tokens and 15 heads; the equations are untouched.)

The stack, for x = embed(tokens) [T, 3840] and eps = ``rms_norm_eps``::

    h = x + RMSNorm(mixer(x));  y = h + RMSNorm(MLP(h))        a layer
    MLP(h) = W_down(silu(W_gate h) * W_up h)                   width 11008
    logits = RMSNorm(y_last) W_head                            untied

"linear_attention", H = ``linear_num_key_heads`` HELD heads, keys of 96,
values of 192:

    [q | k | v] = silu(conv([x W_q | x W_k | x W_v]))   causal, depthwise, 4
                                                        taps, zeros before, no bias
    q_t = q_t / sqrt(|q_t|^2 + 1e-6) / sqrt(96),  k_t = k_t / sqrt(|k_t|^2 + 1e-6)
    beta_t = sigmoid(x_t W_b), doubled where ``linear_allow_neg_eigval``
    g_t = -exp(A_log) softplus(x_t W_a + dt_bias)
    Sbar = exp(g_t) S_{t-1};  u_t = beta_t (v_t - Sbar^T k_t)
    S_t = Sbar + k_t u_t^T;   o_t = S_t^T q_t                  S_0 = 0
    y_t = RMSNorm_192(o_t) * w * silu(x_t W_g);  out = concat(y_t) W_o

"full_attention": ``num_attention_heads`` HELD heads of width hidden /
(held x ``head_parallel_chips``) = 128, one key/value head a query head;
q and k each through one RMSNorm over the whole held projection before the
heads; causal softmax at 1/sqrt(128); no rotary embedding, no bias.

The departure the configuration states: this chip holds half of each
layer's heads. What the absent heads would add to W_o's sum is left out,
and the partial sum goes on into the layer's norm.

The loss: mean next-token cross-entropy over the vocabulary's slice.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from benchmark import cells

# The check's tolerances: system (bf16 matmul operands with fp32
# accumulation; float32 decays, T and carried state) against this
# reference, per gradient leaf as |g_sys - g_ref|_2 / |g_ref|_2 and for the
# loss as a relative difference. Measured on the chip at the published
# widths (15 heads held), 1,024-token sample, thirteen seeds (my chip runs,
# PR 54):
#
# Gradients. The worst leaf is a projection or a per-head vector of the
# LAST gated-delta layer (layers_4: q_proj, k_proj, A_log), 0.088-0.199
# over the seeds (median 0.142; the median LEAF reads 0.07-0.09, the
# best, final_norm, 0.03): q and k reach the rule through a head's L2
# normalisation, whose gradient is what is left of two terms that nearly
# cancel, and the last mixer's is averaged over the fewest paths. It is
# the precision, not the program: this reference with its own matmul
# operands rounded to bf16 (``operand_dtype``) reads 0.073-0.098 against
# itself in float32 on the same leaves (two seeds) and 0.10-0.15 against
# the system, which rounds at more places (T, V', the stacked states, every
# stored activation). The next precision down fails on every LEAF, on both
# seeds tried: operands rounded to float8 (e4m3) read 0.75-0.87 on the best
# leaf and 1.03 on the worst of them against the float32 reference (30,000
# against the system, on the embedding); and each term of ``DROPS`` left
# out reads 1.38-11.0 on its worst leaf and 0.64-1.04 on its best. The
# limit is the geometric middle of 0.199 and 1.03: 2.3 times the worst
# sound reading, ten of the seeds' standard deviations (0.03) above it, and
# under float8's BEST leaf.
#
# Loss. 5e-6 to 2.3e-4 over thirteen seeds (twelve under 1e-4, one at
# 2.28e-4; the bf16 reference 2.6e-5 to 1.4e-4). The limit is the one the
# harness's other mixer cells have (``nemotron_h``, ``joyai_flash``: 1e-3),
# 4.4 times the worst seen. It does NOT tell the precisions apart (float8
# reads 6e-5 to 9e-4: the loss of 1,024 random tokens under random weights
# is nearly all the head's), and of the dropped terms it catches the gate
# and the output norm on both seeds (1.8e-3 to 5.3e-3), beta and the decay
# on one (1.0e-4 to 3.7e-3); the gradient limit is the one that decides.
GRAD_REL_L2_TOL = 0.45
LOSS_REL_TOL = 1e-3
# What ``loss_and_grads`` can leave out, to show that the limits see it.
DROPS = ("beta_doubling", "decay", "gate", "out_norm")


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def delta_rule(q, k, v, g, beta):
    """q, k: [T, H, dk]; v: [T, H, dv]; g, beta: [T, H]. The recurrence,
    one position at a time. Returns (o [T, H, dv], S_T [H, dk, dv])."""

    def step(state, inputs):
        q_t, k_t, v_t, g_t, b_t = inputs
        decayed = jnp.exp(g_t)[:, None, None] * state
        u_t = b_t[:, None] * (v_t - jnp.einsum("hde,hd->he", decayed, k_t))
        state = decayed + k_t[:, :, None] * u_t[:, None, :]
        return state, jnp.einsum("hde,hd->he", state, q_t)

    zero = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), q.dtype)
    last, o = jax.lax.scan(step, zero, (q, k, v, g, beta))
    return o, last


def _gated_delta(x, p, c, r, drop):
    heads, dk, dv = (
        c["linear_num_key_heads"], c["linear_key_head_dim"], c["linear_value_head_dim"]
    )
    eps = float(c["rms_norm_eps"])
    proj = lambda name: r(x) @ r(p[name]["kernel"])  # noqa: E731
    qkv = jnp.concatenate([proj("q_proj"), proj("k_proj"), proj("v_proj")], axis=-1)
    taps = p["conv_kernel"].shape[0]
    padded = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
    qkv = _silu(sum(
        padded[:, j : j + qkv.shape[1]] * p["conv_kernel"][j] for j in range(taps)
    ))
    bsz, t = qkv.shape[:2]
    q, k, v = jnp.split(qkv, [heads * dk, 2 * heads * dk], axis=-1)
    q, k = q.reshape(bsz, t, heads, dk), k.reshape(bsz, t, heads, dk)
    v = v.reshape(bsz, t, heads, dv)
    q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) / jnp.sqrt(float(dk))
    k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    beta = jax.nn.sigmoid(proj("b_proj"))
    if c["linear_allow_neg_eigval"] and drop != "beta_doubling":
        beta = 2.0 * beta
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(proj("a_proj") + p["dt_bias"])
    if drop == "decay":
        g = jnp.zeros_like(g)
    o, _ = jax.vmap(delta_rule)(r(q), r(k), r(v), g, beta)
    if drop != "out_norm":
        o = _rms_norm(o, p["norm_scale"], eps)
    if drop != "gate":
        o = o * _silu(proj("g_proj")).reshape(o.shape)
    return r(o.reshape(bsz, t, heads * dv)) @ r(p["o_proj"]["kernel"])


def _attention(x, p, c, r):
    eps = float(c["rms_norm_eps"])
    q = jnp.einsum("bsh,hnd->bsnd", r(x), r(p["wq"]["kernel"]))
    k = jnp.einsum("bsh,hnd->bsnd", r(x), r(p["wk"]["kernel"]))
    v = jnp.einsum("bsh,hnd->bsnd", r(x), r(p["wv"]["kernel"]))
    whole = lambda m, scale: _rms_norm(  # noqa: E731 - over all held channels
        m.reshape(*m.shape[:2], -1), scale, eps
    ).reshape(m.shape)
    q, k = whole(q, p["q_norm"]["scale"]), whole(k, p["k_norm"]["scale"])
    s = q.shape[1]
    scores = jnp.einsum("bqnd,bknd->bnqk", r(q), r(k)) / jnp.sqrt(float(q.shape[-1]))
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    probs = jax.nn.softmax(jnp.where(causal[None, None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bnqk,bknd->bqnd", r(probs), r(v))
    return jnp.einsum("bqnd,ndh->bqh", r(out), r(p["wo"]["kernel"]))


def _mlp(x, p, r):
    hidden = _silu(r(x) @ r(p["gate"]["kernel"])) * (r(x) @ r(p["up"]["kernel"]))
    return r(hidden) @ r(p["down"]["kernel"])


def loss(
    params: Any, batch: Dict[str, jax.Array], c: Dict[str, Any],
    r=lambda a: a, drop: Optional[str] = None,
):
    """``r`` rounds the operands of the matrix multiplications (identity
    here; ``loss_and_grads`` says what the options are for)."""
    eps = float(c["rms_norm_eps"])
    x = params["embed"]["embedding"][batch["inputs"]]
    linear = jax.checkpoint(lambda x, p: _gated_delta(x, p, c, r, drop))
    for i, kind in enumerate(c["layer_types"]):
        mixer, ffn = params[f"layers_{2 * i}"], params[f"layers_{2 * i + 1}"]
        if kind == "linear_attention":
            mixed = linear(x, mixer["gdn"])
        elif kind == "full_attention":
            mixed = _attention(x, mixer["attn"], c, r)
        else:
            raise cells.CellError(f"layer type {kind!r}")
        x = x + _rms_norm(mixed, mixer["norm"]["scale"], eps)
        x = x + _rms_norm(_mlp(x, ffn["mlp"], r), ffn["norm"]["scale"], eps)
    x = _rms_norm(x, params["final_norm"]["scale"], eps)
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
    multiplication (and the recurrence's q, k, v) to that type first, what
    a run in that precision computes; ``drop`` leaves one of ``DROPS`` out."""
    if drop is not None and drop not in DROPS:
        raise cells.CellError(f"drop {drop!r} is none of {DROPS}")
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    r = (
        (lambda a: a) if operand_dtype is None
        else (lambda a: a.astype(operand_dtype).astype(jnp.float32))
    )
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(lambda p: loss(p, batch, c, r, drop))(params)
