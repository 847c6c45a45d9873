"""The plain reference of a JoyAI-LLM-Flash decoder (the published
config.json of model_type "joyai_llm_flash"; every layer's equations are
DeepSeek-V3's, arXiv:2412.19437 sections 2.1 and 2.2, and so are the
keys) and its training loss, multi-token prediction included, in
straightforward ``jax.numpy``: float32 under
``default_matmul_precision("highest")``, the layers one after another (those
that are alike as one ``lax.scan`` over their stacked parameters), a
dense causal mask, keys and values expanded for every head, the full
logits twice, every held expert applied to every row and weighted by its
gate (zero where the row did not choose it; the held experts as one
batched product). No kernel, no sort, no chunking, no remat; it shares no
code with ``torchft_tpu/models``, ``torchft_tpu/ops`` or
``parallel/train.py``.

A layer, for x [B, T, 2048] (eps 1e-6):

    h = x + attention(RMSNorm(x));   x = h + ffn(RMSNorm(h))

attention (32 heads; ranks 1536 and 512; a query and key of 128 rope-free
and 64 rotary channels, values of 128; no bias):
    c_q = RMSNorm(a W_qa);  [q_nope | q_rope] = c_q W_qb       a head
    [c_kv | k_r] = a W_kva;  [k_nope | v] = RMSNorm(c_kv) W_kvb  a head
    q_rope and the ONE k_r a position rotated: channels (2i, 2i+1) are a
        pair that turns by pos * theta^(-2i/64), theta 3.2e7, in place
        (``rope_interleave``; no scaling)
    score = (q_nope . k_nope + q_rope . k_r) / sqrt(192), causal, softmax
    out = (P v)[32 x 128] W_o
ffn: SwiGLU of width 7168 in the first ``first_k_dense_replace`` layers;
after them experts (router over ``n_routed_experts x
expert_parallel_chips`` = 256, eight a row, width 768):
    s = sigmoid(a W_r)                                  float32
    idx = top_8(s + b)                                  b: the selection bias
    g = 2.5 * s[idx] / (sum(s[idx]) + 1e-20)
    y = sum_i g_i down_{idx_i}(silu(gate_{idx_i} a) * up_{idx_i} a)
        over the HELD idx_i only, + shared(a)           one expert, width 768
  The departure the configuration states: this chip holds experts
  ``first .. first + n_routed_experts - 1``; what the absent ones would
  add is left out, and the partial result goes on to the next layer.
  Likewise the vocabulary: ids, logits and losses are over this chip's
  slice. The selection bias gets no gradient; the step's update of it is
  not part of the loss (``bias_update`` states its rule).

The loss, for inputs t_0..t_{T-1} and targets (t_1..t_T):

    main = mean_p mask_p CE(head(RMSNorm_f(x_p)), t_{p+1})
    h'   = [RMSNorm(x) | RMSNorm(Emb(t_{p+1}))] M        x BEFORE RMSNorm_f
    x'   = one sparse layer as above on h'               (the module)
    mtp  = sum_p w_p CE(head(RMSNorm_f(x'_p)), t_{p+2}) / sum_p w_p
           w_p = mask_p mask_{p+1}, 0 in the last row (t_{T+1} is not in
           the batch)
    loss = main + mtp_loss_coef * mtp

with the final norm RMSNorm_f, the table Emb and the head shared. A
second module (``num_nextn_predict_layers`` 2) continues from x' with
Emb(t_{p+2}) against t_{p+3}, and the modules' losses are averaged. No
balance term (``noaux_tc``).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

# The check's tolerances: system (bf16 matmul operands with fp32
# accumulation, a bf16 residual stream and rotary tables, float32 router,
# softmaxes and norms) against this reference, per gradient leaf as
# |g_sys - g_ref|_2 / |g_ref|_2 and for the loss as a relative difference.
# Measured on the chip at the published widths by the harness's own
# arithmetic (worker.reference_check's sample, keys and comparison, 1,024
# tokens; my chip runs, PR 51: 36 seeds in one process, then the check
# itself in fifteen runs of the cell; PERF.md section 6 has the account).
#
# Gradients. The system's worst leaf read 0.246-0.533 on the 36 seeds
# (median 0.357) and 0.256-0.494 in the cell's runs: a router kernel on 34
# seeds of 36 (most often layers 9 and 11, the last two of the stack, and
# the module's), a held expert stack on two, never an attention leaf; the
# MEDIAN leaf 0.041-0.059. It is the precision, not the program: this
# reference with its own matmul operands rounded to bf16 (``operand_dtype``)
# reads 0.242-0.524 and 0.036-0.054 on the same seeds. The router stands
# out for the reason it does in ``lfm2_moe`` and ``sdar_moe``: under random
# weights a layer's rows choose nearly the same eight of 256 experts, this
# chip computes only the assignments that land on its eight, and a layer
# that holds none of the common choice passes its router a gradient through
# the rows that sit on the boundary between the 8th and the 9th score, which
# a bf16 rounding flips. The next precision down, operands rounded to
# float8 (e4m3), reads 1.091-1.927 on the worst leaf (median 1.27, always a
# router) and 1.000 on the MEDIAN leaf on all 36: the cotangents fall under
# float8's smallest value and most gradients come out zero. The limit lies
# between the two readings with room on both sides, 1.5 times the largest
# sound reading and 1.36 times under the least float8 one, and under 1.0,
# what a leaf whose gradient never moves reads. (``sdar_moe`` found no such
# limit: its largest sound reading was 1.44. Here the largest of fifty-one
# is 0.53; PERF.md section 7(25) says what the odds are worth.) The median
# leaf's limit is stated for the harness's owed edit (PERF.md section
# 7(21)) and read by this architecture's tests: 4 times the largest sound
# reading, a quarter of float8's.
#
# Loss. 1.9e-6 to 2.7e-4 over the 36 seeds (median 4.1e-5; the bf16
# reference 4.3e-7 to 1.9e-4): the limit is ``nemotron_h``'s, 3.7 times the
# largest seen, and decides what it can: the module's term left out
# (``mtp_loss_coef`` 0) reads 0.23, two orders over it. It does NOT tell
# the precisions apart (float8 2.1e-5 to 2.3e-3, inside it on 23 seeds of
# 36: the loss of 1,024 random tokens under random weights is nearly all
# the head's); the gradient limit does that.
GRAD_REL_L2_TOL = 0.8
GRAD_REL_L2_MEDIAN_TOL = 0.25
LOSS_REL_TOL = 1e-3


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _rotary_pairs(x, theta):
    """x: [B, T, heads, D]. Adjacent channels (2i, 2i+1) turn together by
    pos * theta^(-2i/D), in place, at positions 0..T-1."""
    d, t = x.shape[-1], x.shape[1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1)
    return turned.reshape(x.shape)


def attention(a, p, c, r):
    heads, dn, dr = c["num_attention_heads"], c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    rank, eps, theta = c["kv_lora_rank"], float(c["rms_norm_eps"]), float(c["rope_theta"])
    t = a.shape[1]
    c_q = _rms_norm(r(a) @ r(p["wq_a"]["kernel"]), p["q_norm"]["scale"], eps)
    q = jnp.einsum("btr,rnd->btnd", r(c_q), r(p["wq_b"]["kernel"]))
    kv_a = r(a) @ r(p["wkv_a"]["kernel"])
    c_kv = _rms_norm(kv_a[..., :rank], p["kv_norm"]["scale"], eps)
    kv = jnp.einsum("btr,rnd->btnd", r(c_kv), r(p["wkv_b"]["kernel"]))
    q_rope = _rotary_pairs(q[..., dn:], theta)
    k_r = _rotary_pairs(kv_a[..., None, rank:], theta)  # one head
    query = jnp.concatenate([q[..., :dn], q_rope], axis=-1)
    key = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_r, (*k_r.shape[:2], heads, dr))], axis=-1
    )
    scores = jnp.einsum("bqnd,bknd->bnqk", r(query), r(key)) / jnp.sqrt(float(dn + dr))
    causal = jnp.tril(jnp.ones((t, t), dtype=bool))
    probs = jax.nn.softmax(jnp.where(causal[None, None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bnqk,bknd->bqnd", r(probs), r(kv[..., dn:]))
    return jnp.einsum("bqnd,ndh->bqh", r(out), r(p["wo"]["kernel"]))


def _swiglu(m, gate, up, down, r):
    return r(_silu(r(m) @ r(gate)) * (r(m) @ r(up))) @ r(down)


def experts(m, p, c, r, shared: bool = True):
    """m: [T, H]. One expert layer: the held experts' part of the routed
    sum plus the shared expert (``shared=False`` leaves that out: the
    shares-add-up test counts it once)."""
    held = c["n_routed_experts"]
    n_experts = held * c["expert_parallel_chips"]
    first = c["expert_parallel_index"] * held
    s = jax.nn.sigmoid(m @ p["router"]["kernel"])
    _, idx = jax.lax.top_k(s + p["router_bias"], c["num_experts_per_tok"])
    g = jnp.take_along_axis(s, idx, axis=-1)
    g = float(c["routed_scaling_factor"]) * g / (g.sum(axis=-1, keepdims=True) + 1e-20)
    chosen = jax.nn.one_hot(idx, n_experts, dtype=m.dtype)  # [T, K, E]
    weight = jnp.einsum("tk,tke->te", g, chosen)[:, first : first + held]
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


def layer(x, op, ffn, c, r):
    """One published layer over the program's two tree entries, each with
    its own ``norm``: attention, then the dense or the expert feed-forward
    (told apart by what the entry holds)."""
    eps = float(c["rms_norm_eps"])
    x = x + attention(_rms_norm(x, op["norm"]["scale"], eps), op["attn"], c, r)
    a = _rms_norm(x, ffn["norm"]["scale"], eps)
    mlp = ffn["mlp"]
    if "router" in mlp:
        y = experts(a.reshape(-1, a.shape[-1]), mlp, c, r).reshape(a.shape)
    else:
        y = _swiglu(a, mlp["gate"]["kernel"], mlp["up"]["kernel"], mlp["down"]["kernel"], r)
    return x + y


def _cross_entropy(x, params, c, r, targets, weights):
    """sum_p weights_p CE(head(RMSNorm_f(x_p)), targets_p) / sum_p weights_p."""
    hidden = _rms_norm(x, params["final_norm"]["scale"], float(c["rms_norm_eps"]))
    logits = r(hidden) @ r(params["lm_head"]["kernel"])
    logits = logits - jnp.max(logits, axis=-1, keepdims=True)
    logp = logits - jnp.log(jnp.sum(jnp.exp(logits), axis=-1, keepdims=True))
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -(picked * weights).sum() / jnp.maximum(weights.sum(), 1.0)


def losses(params: Any, batch: Dict[str, jax.Array], c: Dict[str, Any], r=lambda a: a):
    """(loss, main, mtp). ``r`` rounds the operands of the matrix
    multiplications but the router's (identity here; ``loss_and_grads``
    says what the option is for)."""
    eps = float(c["rms_norm_eps"])
    table = params["embed"]["embedding"]
    targets, mask = batch["targets"], batch["mask"].astype(jnp.float32)
    x = table[batch["inputs"]]
    entries = [
        (params[f"layers_{2 * i}"], params[f"layers_{2 * i + 1}"])
        for i in range(c["num_hidden_layers"])
    ]
    dense = c["first_k_dense_replace"]
    for op, ffn in entries[:dense]:
        x = layer(x, op, ffn, c, r)
    if entries[dense:]:
        # The layers after the leading dense ones are alike: one scan over
        # their stacked parameters, so that they compile once (unrolled, the
        # check's set-up is minutes of compiling).
        alike = jax.tree_util.tree_map(lambda *leaves: jnp.stack(leaves), *entries[dense:])
        x, _ = jax.lax.scan(lambda x, pair: (layer(x, *pair, c, r), None), x, alike)
    main = _cross_entropy(x, params, c, r, targets, mask)
    modules, mtp, weights, t = c["num_nextn_predict_layers"], 0.0, mask, mask.shape[1]
    for k in range(modules):
        m = params[f"mtp_{k}"]
        # Position p has seen t_0..t_{p+k}; the token after them is
        # targets[p + k], and the module predicts targets[p + k + 1]. Past
        # the batch the rolled rows hold early tokens: their weight is 0.
        ahead = lambda a, n: jnp.roll(a, -n, axis=1)  # noqa: E731
        both = jnp.concatenate([
            _rms_norm(x, m["hnorm"]["scale"], eps),
            _rms_norm(table[ahead(targets, k)], m["enorm"]["scale"], eps),
        ], axis=-1)
        x = layer(r(both) @ r(m["eh_proj"]["kernel"]), m["layers_0"], m["layers_1"], c, r)
        weights = weights * ahead(mask, k + 1) * (jnp.arange(t) < t - k - 1)
        mtp = mtp + _cross_entropy(x, params, c, r, ahead(targets, k + 1), weights) / modules
    return main + float(c["mtp_loss_coef"]) * mtp, main, mtp


def bias_update(bias, load, rate):
    """The step's out-of-gradient move of a selection bias (DeepSeek-V3
    section 2.1.2): towards the experts that got fewer than the mean of
    the assignments ``load`` [E] counts."""
    return bias + rate * jnp.sign(load.mean() - load)


def loss_and_grads(
    params: Any, batch: Dict[str, jax.Array], c: Dict[str, Any],
    operand_dtype: Optional[Any] = None,
):
    """(loss, gradient tree), float32 at the highest matmul precision.
    ``operand_dtype`` sizes the tolerances above and is never passed by
    the check: it rounds the operands of every matrix multiplication but
    the router's to that type first (what a run in that precision
    computes)."""
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    r = (
        (lambda a: a) if operand_dtype is None
        else (lambda a: a.astype(operand_dtype).astype(jnp.float32))
    )
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(lambda p: losses(p, batch, c, r)[0])(params)
