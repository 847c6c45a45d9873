"""Sharded training step: init, loss, grads, optimizer update — all compiled
as one pjit program over the (dp, fsdp, sp, tp) mesh.

This is the inner (per-replica-group) step of the fault-tolerant trainer:
everything here rides ICI via XLA collectives; the outer replica-axis
gradient/pseudograd averaging is host-driven by the Manager (DDP: per-step;
DiLoCo: per-outer-step). Reference analog: the torchtitan train step the
reference composes with (SURVEY.md §2.3) — here it is in-repo.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from torchft_tpu.models.llama import LlamaConfig, Transformer
from torchft_tpu.parallel.ring_attention import make_ring_attention
from torchft_tpu.parallel.sharding import (
    batch_sharding,
    param_specs,
    params_spec_dict,
    path_keys,
    tree_specs_like,
)

logger = logging.getLogger(__name__)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    step: jax.Array
    params: Any
    opt_state: Any


def build_model(cfg: LlamaConfig, mesh: Optional[Mesh]) -> Transformer:
    """Binds the mesh-bound context-parallel attention when requested:
    ``ring`` (ppermute k/v streaming) or ``ulysses`` (all-to-all
    seq<->head re-shard; parallel/ulysses.py)."""
    if cfg.attn_impl == "ring":
        assert mesh is not None, "ring attention requires a mesh"
        cfg = dataclasses.replace(cfg, attn_fn=make_ring_attention(mesh))
    elif cfg.attn_impl == "ulysses":
        from torchft_tpu.parallel.ulysses import make_ulysses_attention

        assert mesh is not None, "ulysses attention requires a mesh"
        cfg = dataclasses.replace(cfg, attn_fn=make_ulysses_attention(mesh))
    return Transformer(cfg)


def state_shardings(
    model: Transformer,
    mesh: Mesh,
    sample_tokens_shape: Tuple[int, int],
    optimizer: Optional[optax.GradientTransformation] = None,
) -> TrainState:
    """TrainState-of-NamedShardings, derived from abstract init (no FLOPs)."""
    optimizer = optimizer or _DEFAULT_OPT

    def abstract_init():
        tokens = jnp.zeros(sample_tokens_shape, jnp.int32)
        params = model.init(jax.random.PRNGKey(0), tokens)["params"]
        return params

    params_shape = jax.eval_shape(abstract_init)
    specs = param_specs(params_shape)
    spec_dict = params_spec_dict(params_shape)
    opt_shape = jax.eval_shape(lambda p: optimizer.init(p), params_shape)
    opt_specs = tree_specs_like(opt_shape, spec_dict)
    to_sharding = lambda s: NamedSharding(mesh, s)  # noqa: E731
    return TrainState(
        step=to_sharding(P()),
        params=jax.tree_util.tree_map(to_sharding, specs),
        opt_state=jax.tree_util.tree_map(
            to_sharding, opt_specs, is_leaf=lambda x: isinstance(x, P)
        ),
    )


_DEFAULT_OPT = optax.adamw(3e-4, b1=0.9, b2=0.95, weight_decay=0.1)


def default_optimizer() -> optax.GradientTransformation:
    """The optimizer init_train_state uses when none is given; callers that
    later apply updates to that opt_state must use this same transform."""
    return _DEFAULT_OPT


def init_train_state(
    model: Transformer,
    mesh: Mesh,
    rng: jax.Array,
    sample_tokens_shape: Tuple[int, int],
    optimizer: Optional[optax.GradientTransformation] = None,
) -> Tuple[TrainState, TrainState]:
    """Initializes the state *born sharded* (out_shardings on init — no
    host-side full copy, required at 8B scale). Returns (state, shardings)."""
    optimizer = optimizer or _DEFAULT_OPT
    shardings = state_shardings(model, mesh, sample_tokens_shape, optimizer)

    def init_fn(rng):
        tokens = jnp.zeros(sample_tokens_shape, jnp.int32)
        params = model.init(rng, tokens)["params"]
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=optimizer.init(params),
        )

    state = jax.jit(init_fn, out_shardings=shardings)(rng)
    return state, shardings


# The head and loss never hold the [B,S,V] logits (float32, >2 GB at
# 16k tokens of a 32k vocabulary): rows go through it C tokens a row of the
# batch at a time (``_head_loss_sum``). TORCHFT_LOSS_CHUNK sets C; unset
# (0) it is derived from the shapes by ``loss_chunk``.
from torchft_tpu import knobs as _knobs

_LOSS_CHUNK = _knobs.get_int("TORCHFT_LOSS_CHUNK")
# Rows (B x C) a chunk should reach, and the most its float32 logits may
# take. The weight gradient's float32 accumulator is read and written
# whole every chunk: 8*H*V bytes under 2*R*H*V FLOP, R/4 FLOP a byte,
# level with a v5e's ridge (240 FLOP/B) at 960 rows and hidden under the
# matmul at twice that. On that chip, the head and loss of 16,384 tokens
# alone: 138.7 ms at 1,024 rows, 125.1 at 2,048, 125.5 at 4,096 under a
# 92.5k vocabulary; 82.6, 81.0, 80.9 under 32k; 27.3, 28.4, 29.6 under
# 16k (PERF.md section 6, PR 42). The byte cap keeps the chunk's
# temporaries (logits, their gradient in the compute dtype) a small part
# of a 16 GB chip beside a fine-tune's state: 2,048 rows of a 92.5k
# vocabulary are 758 MB.
_LOSS_CHUNK_ROWS = 2048
_LOSS_CHUNK_LOGITS_BYTES = 1 << 30


def loss_chunk(B: int, S: int, V: int) -> int:
    """Tokens a row of the batch in one chunk of the head and loss, from
    the shapes alone: the smallest divisor of ``S`` that is a multiple of
    128 and brings the chunk to ``_LOSS_CHUNK_ROWS`` rows, among those
    whose float32 logits ``4*B*C*V`` stay under
    ``_LOSS_CHUNK_LOGITS_BYTES`` (the largest of those where none gets
    there, 128 where none fits). A sequence no multiple of 128 divides
    gets ``min(128, S)``: one chunk if it is shorter, else the caller's
    plain full-logits path."""
    divisors = [c for c in range(128, S + 1, 128) if S % c == 0]
    if not divisors:
        return min(128, S)
    fits = [
        c for c in divisors if 4 * B * c * V <= _LOSS_CHUNK_LOGITS_BYTES
    ] or divisors[:1]
    return next((c for c in fits if B * c >= _LOSS_CHUNK_ROWS), fits[-1])


def _lm_head_projection(model: Transformer, params):
    """The vocab projection [H, V] straight from the param pytree — same
    tensors as the model's own head. Both head forms compute in cfg.dtype:
    flax's Dense casts input+kernel to ``dtype``, and Embed.attend promotes
    query AND embedding to ``dtype`` too (so the model's
    ``attend(x.astype(param_dtype))`` still multiplies in cfg.dtype)."""
    cfg = model.cfg
    if cfg.tie_embeddings:
        return params["embed"]["embedding"].T, cfg.dtype
    return params["lm_head"]["kernel"], cfg.dtype


def _apply_with_aux(model: Transformer, params, inputs, **kw):
    """model.apply + what MoEMLP and the delta-rule mixers sow per layer,
    by name and stacked over the layers ({} for a model with none of
    them): ``router_aux``, ``router_z``, ``moe_max_load``, ``moe_dropped``;
    the ``gdn_*`` and ``kda_*`` counters; a windowed attention layer's
    ``swa_kept_share``; a selected one's ``dsa_index_kl`` (the indexer's
    loss, which the step's loss adds) and its two shares."""
    cfg = model.cfg
    if (
        cfg.num_experts <= 0 and cfg.gated_delta is None and cfg.kda is None
        and "W" not in (cfg.layer_pattern or "") and cfg.sparse_topk is None
    ):
        return model.apply({"params": params}, inputs, **kw), {}
    out, inter = model.apply(
        {"params": params}, inputs, mutable=["intermediates"], **kw
    )
    sown: dict = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(inter):
        name = next(
            k.key for k in reversed(path) if isinstance(k, jax.tree_util.DictKey)
        )
        sown.setdefault(name, []).append(jnp.ravel(leaf))
    return out, {k: jnp.concatenate(v) for k, v in sown.items()}


# What an expert layer sows, and how the layers' values become one metric.
_SOWN_OVER_LAYERS = (
    ("router_aux", jnp.mean),
    ("router_z", jnp.mean),
    ("moe_max_load", jnp.max),
    ("moe_dropped", jnp.sum),
    ("moe_held_share", jnp.mean),
    # Of the held dispatch's row buffer, the share in the row tiles its
    # loops ran (``MoEMLP._sorted_held``): 1 where the buffer is one tile.
    ("moe_held_run_share", jnp.mean),
    # Of the step's tokens, the share in the token tiles that the same
    # dispatch's token side ran (``llama._gather_sum``): 1 where the step
    # is one tile.
    ("moe_held_token_run_share", jnp.mean),
    # From the attention under the block-diffusion mask: of the score
    # entries it computes, the share its mask keeps
    # (``block_diffusion_attention``).
    ("bd_kept_share", jnp.mean),
    # From the windowed attention layers: of the score entries the tiles
    # they run hold, the share the band keeps (``window_attention``).
    ("swa_kept_share", jnp.mean),
    # From the selected attention layers (``Attention._selected``): the
    # indexer's loss (nats a token; the step's loss adds
    # ``indexer_loss_coef`` x its mean over the layers), the selected
    # entries over the causal ones, and the tile pairs the kernels ran over
    # the causal tile pairs.
    ("dsa_index_kl", jnp.mean),
    ("dsa_kept_share", jnp.mean),
    ("dsa_tiles_run_share", jnp.mean),
    # From the gated-delta mixers (models/gated_delta.py): the largest
    # |entry| of a state at a sequence's end (with eigenvalues down to -1
    # a state that grows is the failure to see), the smallest decay and
    # the mean beta of the step.
    ("gdn_state_abs_max", jnp.max),
    ("gdn_decay_min", jnp.min),
    ("gdn_beta_mean", jnp.mean),
    # The same three from the Kimi delta mixers, whose decay is a key
    # channel's: the smallest is over every channel of the step.
    ("kda_state_abs_max", jnp.max),
    ("kda_decay_min", jnp.min),
    ("kda_beta_mean", jnp.mean),
    # Not a scalar: every expert's assignments, layer after layer in the
    # parameter tree's order, for the selection-bias update of
    # ``make_train_step`` or of a loop around ``make_grad_step`` (sown
    # only by a model that asks for it).
    ("moe_load", lambda loads: loads),
)


def update_router_bias(params, old_params, loads, rate: float):
    """``params`` with every ``router_bias`` leaf set to its value in
    ``old_params`` plus ``rate * sign(mean load - load_e)``: the selection
    bias moves towards the experts the router under-used, out of the
    gradient (arXiv:2408.15664; DeepSeek-V3 arXiv:2412.19437 section 2.1.2).
    ``loads`` holds each expert layer's ``moe_load``, layer after layer in
    the tree's order (what ``_apply_with_aux`` gathers). Written from the
    OLD value: AdamW's weight decay, which would pull a bias that gets no
    gradient back towards zero every step, does not touch it."""
    at = 0

    def leaf(path, new, old):
        nonlocal at
        if path_keys(path)[-1] != "router_bias":
            return new
        load = loads[at : at + old.size].reshape(old.shape)
        at += old.size
        under = jnp.sign(load.mean(axis=-1, keepdims=True) - load)
        return old + rate * under.astype(old.dtype)

    return jax.tree_util.tree_map_with_path(leaf, params, old_params)


def router_bias_abs_max(params) -> jax.Array:
    """The largest |selection bias| over the expert layers."""
    return jnp.max(jnp.stack([
        jnp.max(jnp.abs(leaf))
        for path, leaf in jax.tree_util.tree_leaves_with_path(params)
        if path_keys(path)[-1] == "router_bias"
    ]))


def apply_update(optimizer, rate: float, params, opt_state, reduced):
    """The fused and the split step's update -> (params, opt_state).
    ``reduced`` = (grads, loads): the loads are every expert's assignments
    where the recipe moves the selection biases at ``rate``, else None."""
    grads, loads = reduced
    updates, opt_state = optimizer.update(grads, opt_state, params)
    new = optax.apply_updates(params, updates)
    if loads is not None:
        new = update_router_bias(new, params, loads, rate)
    return new, opt_state


def _head_loss_chunks(h, w, targets, weights, C: int):
    """What both passes of ``_head_loss_sum`` scan over: the head in the
    compute dtype and the chunks of the hidden states, the targets and the
    weights, the ``B*C`` rows of a chunk as one axis ([n,B*C,...]: three
    plain matmuls a chunk where a batch axis would make them batched
    convolutions, a fifth slower on a v5e)."""
    B, S, _ = h.shape
    n = S // C
    chunks = tuple(
        jnp.moveaxis(x.reshape(B, n, C, *x.shape[2:]), 1, 0).reshape(
            n, B * C, *x.shape[2:]
        )
        for x in (h, targets, weights)
    )
    return w.astype(h.dtype), chunks


def _unchunked(x, B: int, S: int, C: int):
    """[S/C, B*C, ...] as the chunks' scan stacks it -> [B, S, ...]."""
    return jnp.moveaxis(x.reshape(S // C, B, C, *x.shape[2:]), 0, 1).reshape(
        B, S, *x.shape[2:]
    )


def _chunk_rows(hc, tc, wc):
    """A chunk's rows' cross-entropies [R], its float32 logits [R,V],
    their log-sum-exp and where the targets sit in them."""
    logits = jnp.dot(hc, wc, preferred_element_type=jnp.float32)
    top = logits.max(axis=-1, keepdims=True)
    lse = jnp.log(jnp.exp(logits - top).sum(axis=-1)) + top[..., 0]
    hit = jnp.arange(logits.shape[-1]) == tc[..., None]
    picked = jnp.where(hit, logits, 0.0).sum(axis=-1)
    return lse - picked, logits, lse, hit


def _chunk_loss(hc, tc, mc, wc):
    """A chunk's weighted loss sum (``mc`` each row's weight), its
    float32 logits [R,V], their log-sum-exp and where the targets sit in
    them."""
    rows, logits, lse, hit = _chunk_rows(hc, tc, wc)
    return (rows * mc).sum(), logits, lse, hit


def _chunk_grads(hc, mc, wc, logits, lse, hit):
    """A chunk's gradients while its logits are live: of its hidden states
    [R,H] in their dtype and, float32, of the head [H,V] (its part of the
    sum over the chunks), under the rows' weights ``mc``."""
    # Into the MXU in the compute dtype, as the cotangent of float32
    # logits goes at the chip's default precision, and written out
    # once: fused into its two matmuls it is formed again from the
    # float32 logits for every tile of each (a v5e, 4 x 4096 tokens of
    # a 32k vocabulary: dW 38.9 ms a step so, 25.9 + 4.5 behind the
    # barrier).
    dlogits = jax.lax.optimization_barrier((
        (jnp.exp(logits - lse[..., None]) - hit) * mc[..., None]
    ).astype(hc.dtype))
    dh = jax.lax.dot_general(
        dlogits, wc, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(hc.dtype)  # [R,H]
    dw = jax.lax.dot_general(
        hc, dlogits, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [H,V]
    return dh, dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _head_loss_sum(h, w, targets, weights, C: int):
    """The weighted sum of the cross-entropies of ``h`` [B,S,H] (in the
    compute dtype) against ``targets`` under the head ``w`` [H,V]
    (float32), ``C`` tokens a row at a time: logits from operands in
    ``h``'s dtype accumulated in float32, log-sum-exp and loss in float32.
    ``weights`` [B,S] is float32 and any value a position: a 0/1 mask for
    the next-token loss, masked/t for block diffusion. What the sum is
    divided by (the data positions) is the caller's and no part of the
    weights. ``S % C == 0``.

    Differentiated, a chunk forms its own gradient while its logits are
    live: ``dlogits = (softmax - onehot) * weight`` needs nothing the forward
    pass does not hold there, so the vocabulary-wide matmul runs three
    times a chunk (logits, dh, dW) and never a fourth for logits
    recomputed, and the backward pass is two multiplies by the scalar
    cotangent. dW accumulates across the chunks in float32.

    ``weights`` gets no cotangent here: every caller's are constants of
    the batch. ``_head_loss_rows`` is the sibling whose weights are
    functions of parameters (a looped model's exit distribution); it
    shares the chunk's two bodies (``_chunk_rows``, ``_chunk_grads``) and
    is a function of its own so that this one's program, which every other
    model's step holds, stays what it was."""
    wc, chunks = _head_loss_chunks(h, w, targets, weights, C)

    def chunk(total, xs):
        return total + _chunk_loss(*xs, wc)[0], None

    return jax.lax.scan(chunk, jnp.zeros((), jnp.float32), chunks)[0]


def _head_loss_sum_fwd(h, w, targets, weights, C: int):
    B, S, _ = h.shape
    wc, chunks = _head_loss_chunks(h, w, targets, weights, C)

    def chunk(carry, xs):
        total, dw = carry
        hc, tc, mc = xs
        loss, logits, lse, hit = _chunk_loss(hc, tc, mc, wc)
        dh, dw_chunk = _chunk_grads(hc, mc, wc, logits, lse, hit)
        dw = dw + dw_chunk
        return (total + loss, dw), dh

    (total, dw), dh = jax.lax.scan(
        chunk,
        (jnp.zeros((), jnp.float32), jnp.zeros(w.shape, jnp.float32)),
        chunks,
    )
    return total, (_unchunked(dh, B, S, C), dw)


def _head_loss_sum_bwd(C, res, g):
    dh, dw = res
    return (dh * g).astype(dh.dtype), dw * g, None, None


_head_loss_sum.defvjp(_head_loss_sum_fwd, _head_loss_sum_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _head_loss_rows_vjp(h, w, targets, weights, C: int):
    wc, chunks = _head_loss_chunks(h, w, targets, weights, C)

    def chunk(total, xs):
        hc, tc, mc = xs
        rows = _chunk_rows(hc, tc, wc)[0]
        return total + (rows * mc).sum(), rows

    total, rows = jax.lax.scan(chunk, jnp.zeros((), jnp.float32), chunks)
    return total, _unchunked(rows, *h.shape[:2], C)


def _head_loss_rows_fwd(h, w, targets, weights, C: int):
    B, S, _ = h.shape
    wc, chunks = _head_loss_chunks(h, w, targets, weights, C)

    def chunk(carry, xs):
        total, dw = carry
        hc, tc, mc = xs
        rows, logits, lse, hit = _chunk_rows(hc, tc, wc)
        dh, dw_chunk = _chunk_grads(hc, mc, wc, logits, lse, hit)
        return (total + (rows * mc).sum(), dw + dw_chunk), (dh, rows)

    (total, dw), (dh, rows) = jax.lax.scan(
        chunk,
        (jnp.zeros((), jnp.float32), jnp.zeros(w.shape, jnp.float32)),
        chunks,
    )
    rows = _unchunked(rows, B, S, C)
    return (total, rows), (_unchunked(dh, B, S, C), dw, rows)


def _head_loss_rows_bwd(C, res, g):
    dh, dw, rows = res
    g = g[0]  # the rows' own cotangent is dropped: ``_head_loss_rows``
    return (dh * g).astype(dh.dtype), dw * g, None, rows * g


_head_loss_rows_vjp.defvjp(_head_loss_rows_fwd, _head_loss_rows_bwd)


def _head_loss_rows(h, w, targets, weights, C: int):
    """``_head_loss_sum`` for weights that are functions of parameters:
    ``(sum, rows)``, the same weighted sum and the rows' own cross-entropies
    [B,S] float32. Differentiated, the sum hands ``weights`` its cotangent,
    each row's cross-entropy (``lse - picked``, which a chunk has in hand),
    beside the hidden states' and the head's formed as there, under the
    weights' values. ``rows`` is for the caller's metrics and carries no
    gradient (detached here: the one pass forms gradients under ``weights``
    alone)."""
    total, rows = _head_loss_rows_vjp(h, w, targets, weights, C)
    return total, jax.lax.stop_gradient(rows)


# The constant folded with a batch's tokens into its noise key.
NOISE_SEED = 0


def diffusion_streams(cfg: LlamaConfig, inputs, mask):
    """Block diffusion's input and weights for x_0 = ``inputs`` [B,L]:
    ``([x_t | x_0] [B,2L], weights [B,L] float32, masked [B,L] bool)``.
    The noise is a pure function of the batch: the key is ``NOISE_SEED``
    folded with the wrapping uint32 sum of the batch's own tokens, so no
    RNG lives in ``TrainState``: a step that is discarded and run again,
    or replayed by a healed replica, draws the same noise, and replicas
    differ in noise because they differ in data. The key is split in two;
    the first half draws t [B,L/b] from U(``diffusion_t_min``,
    ``diffusion_t_max``), one a sequence and block, the second a uniform u
    [B,L]; position p of block j is masked where u_p < t_j (probability
    t_j), x_t holds ``mask_token_id`` there and x_0's token elsewhere, and
    the weight is mask_p * masked_p / t_j. (The order and shapes are the
    contract with a reference that shares no code:
    benchmark/configs/sdar-30b-a3b-l6e16.json, ``assumed``.)"""
    B, L = inputs.shape
    b = cfg.block_length
    if b <= 0 or L % b:
        raise ValueError(f"block diffusion: blocks of {b} do not divide {L}")
    key = jax.random.fold_in(
        jax.random.PRNGKey(NOISE_SEED), jnp.sum(inputs.astype(jnp.uint32))
    )
    key_t, key_u = jax.random.split(key)
    t = jnp.repeat(
        jax.random.uniform(
            key_t, (B, L // b), jnp.float32,
            cfg.diffusion_t_min, cfg.diffusion_t_max,
        ),
        b, axis=1,
    )
    masked = jax.random.uniform(key_u, (B, L), jnp.float32) < t
    x_t = jnp.where(masked, jnp.asarray(cfg.mask_token_id, inputs.dtype), inputs)
    weights = mask.astype(jnp.float32) * masked / t
    return jnp.concatenate([x_t, inputs], axis=1), weights, masked


def exit_distribution(z):
    """A looped model's distribution over its steps, a position at a time:
    for the exit gate's logits ``z`` [T, ...] (float32) ``(log p, p)`` with
    p_t = sigma(z_t) prod_{j<t} (1 - sigma(z_j)) for t < T and p_T =
    prod_{j<T} (1 - sigma(z_j)), what is left (z_T is not read). Formed
    from logs, log(1 - sigma(z)) = -softplus(z) and log sigma(z) =
    -softplus(-z), so that a saturated gate gives a small p and a finite
    log, never a 0 whose log is not."""
    stay = -jax.nn.softplus(z[:-1])  # log(1 - lambda_j), j < T
    before = jnp.concatenate([jnp.zeros_like(z[:1]), jnp.cumsum(stay, axis=0)])
    leave = jnp.concatenate([-jax.nn.softplus(-z[:-1]), jnp.zeros_like(z[:1])])
    log_p = before + leave
    return log_p, jnp.exp(log_p)


def _loss_and_metrics(model: Transformer, params, inputs, targets, mask, positions=None):
    """(loss, metrics), by the model's ``objective``. ``positions``: a
    batch's own ``position_ids`` ([3,B,S] under ``mrope_section``), None for
    the model's default.

    "next_token": the mean over the data positions (``mask``) of the
    cross-entropy of each position's successor (``targets``).
    "block_diffusion": x_0 = ``inputs``; the trunk runs once over
    ``diffusion_streams``' [x_t | x_0], only the noisy stream's L rows
    reach the head, and the loss is sum_p weight_p CE(head(h_noisy[p]),
    x_0[p]) / sum_p mask_p: the token AT a masked position, weighed by 1/t;
    ``targets`` is not read.

    A next-token model with ``mtp_layers`` prediction modules
    (``LlamaConfig.mtp_layers``) adds ``mtp_loss_coef`` x the mean over the
    modules of module k's cross-entropy against the token k + 1 places on,
    through the shared head and the same ``_head_loss_sum``: all S rows,
    the targets rolled by k, the last k rows' weight 0 (their successors
    lie past the batch) and the others' the product of the masks they
    span; ``loss_main`` and ``loss_mtp`` are among the metrics.

    A looped model (``LlamaConfig.loop_steps`` = T > 1; next-token) is
    handed every step's normed states and exit logits and trains on
    sum_p mask_p (sum_t p_t[p] CE_t[p] - ``loop_entropy_coef`` H(p[p])) /
    sum_p mask_p: the expectation under ``exit_distribution`` of the
    steps' cross-entropies less beta x its entropy, in float32. The T x B
    rows go through the shared head in ONE pass of ``_head_loss_rows``
    (targets and mask tiled, weights p x mask), whose cotangent of the
    weights, the rows' cross-entropies, is how the gate learns from the
    data. Its metrics: ``loop_ce_1`` .. ``loop_ce_T`` (each step's mean
    cross-entropy; scalars, because a step's metrics are averaged over
    microbatches and read as floats), ``loop_exit_step_mean`` (mean of
    sum_t t p_t), ``loop_exit_entropy`` (mean H, nats) and ``loop_p_last``
    (mean p_T).

    Either way a model with experts adds ``router_aux_coef`` x the
    load-balancing term and ``router_z_coef`` x the router z-loss, each a
    mean over the layers (and, under block diffusion, over both streams'
    rows). The metrics are {} for a dense next-token model, else
    ``router_aux``, ``router_z``, ``moe_max_load`` (worst layer),
    ``moe_dropped`` (assignments not computed, all layers), from
    layers that hold a share of their experts ``moe_held_share`` (the
    share of all assignments that landed on held experts), from a
    model whose step updates its selection biases the vector
    ``moe_load``, under block diffusion ``diffusion_masked_share``
    (masked data positions over data positions) and, sown by its
    attention, ``bd_kept_share``; from windowed attention layers
    ``swa_kept_share``; from gated-delta mixers
    ``gdn_state_abs_max``, ``gdn_decay_min`` and ``gdn_beta_mean``, from
    Kimi delta mixers the same three as ``kda_*``."""
    cfg = model.cfg
    B, S = inputs.shape
    C = min(_LOSS_CHUNK, S) if _LOSS_CHUNK > 0 else loss_chunk(
        B, S, cfg.vocab_size
    )
    mask_f = mask.astype(jnp.float32)
    denom = jnp.maximum(mask_f.sum(), 1.0)
    weights, extra = mask_f, {}
    two_streams = cfg.objective == "block_diffusion"
    if two_streams:
        targets = inputs
        inputs, weights, masked = diffusion_streams(cfg, inputs, mask)
        extra = {"diffusion_masked_share": (mask_f * masked).sum() / denom}
    elif cfg.objective != "next_token":
        raise ValueError(f"objective {cfg.objective!r}")
    if two_streams and cfg.mtp_layers:
        raise ValueError("prediction modules under block diffusion: not built")

    at = {} if positions is None else {"positions": positions}

    def with_router_terms(ce, sown):
        if not sown:
            # The zero term is the dense step's jaxpr as it always was: a
            # persistent compile cache keyed on it keeps hitting.
            return ce + cfg.router_aux_coef * jnp.zeros(()), extra
        metrics = {
            name: over_layers(sown[name])
            for name, over_layers in _SOWN_OVER_LAYERS if name in sown
        }
        loss = ce
        if "router_aux" in metrics:  # else: sown counters, no expert layer
            loss = ce + cfg.router_aux_coef * metrics["router_aux"]
            if cfg.router_z_coef:
                loss = loss + cfg.router_z_coef * metrics["router_z"]
        if "dsa_index_kl" in metrics:
            loss = loss + cfg.indexer_loss_coef * metrics["dsa_index_kl"]
        return loss, jax.lax.stop_gradient({**metrics, **extra})

    def data_rows(x):
        """The rows the loss is over: under block diffusion the noisy
        stream's, the first S of the trunk's 2S."""
        return x[:, :S] if two_streams else x

    def head_loss(h, targets, weights, chunk=C):
        w, head_dtype = _lm_head_projection(model, params)
        return _head_loss_sum(
            h.astype(head_dtype), w.astype(jnp.float32), targets, weights, chunk
        )

    if cfg.loop_steps > 1:
        if two_streams:
            raise ValueError("a looped stack under block diffusion: not built")
        (h, z), sown = _apply_with_aux(model, params, inputs, return_hidden=True, **at)
        T = cfg.loop_steps
        with jax.named_scope("loop/exit"):
            log_p, p = exit_distribution(z)
            entropy = -(p * log_p).sum(axis=0)
        chunk = loss_chunk(T * B, S, cfg.vocab_size) if _LOSS_CHUNK <= 0 else C
        w, head_dtype = _lm_head_projection(model, params)
        expected, rows = _head_loss_rows(
            h.reshape(T * B, S, -1).astype(head_dtype), w.astype(jnp.float32),
            jnp.tile(targets, (T, 1)), (p * mask_f).reshape(T * B, S),
            chunk if S % chunk == 0 else S,
        )
        with jax.named_scope("loop/exit"):
            over_data = lambda x: (x * mask_f).sum(axis=(-2, -1)) / denom  # noqa: E731
            mean_entropy = over_data(entropy)
            loss = expected / denom - cfg.loop_entropy_coef * mean_entropy
            step_ce = over_data(rows.reshape(T, B, S))
            extra = jax.lax.stop_gradient({
                **{f"loop_ce_{t + 1}": step_ce[t] for t in range(T)},
                "loop_exit_step_mean": over_data(
                    (p * jnp.arange(1, T + 1, dtype=p.dtype)[:, None, None]).sum(axis=0)
                ),
                "loop_exit_entropy": mean_entropy,
                "loop_p_last": over_data(p[-1]),
            })
        return with_router_terms(loss, sown)

    if cfg.mtp_layers:
        (h, *predicted), sown = _apply_with_aux(
            model, params, inputs, return_hidden=True, next_tokens=targets, **at
        )
        chunk = C if S % C == 0 else S  # an odd length: one chunk
        main, mtp = head_loss(h, targets, weights, chunk) / denom, 0.0
        for k, h_k in enumerate(predicted, start=1):
            # Module k's row p predicts targets[p + k]: the rows that span a
            # masked position or reach past the batch weigh nothing.
            weights = weights * jnp.roll(mask_f, -k, axis=1) * (jnp.arange(S) < S - k)
            mtp = mtp + head_loss(
                h_k, jnp.roll(targets, -k, axis=1), weights, chunk
            ) / jnp.maximum(weights.sum(), 1.0)
        mtp = mtp / len(predicted)
        extra = jax.lax.stop_gradient({"loss_main": main, "loss_mtp": mtp})
        return with_router_terms(main + cfg.mtp_loss_coef * mtp, sown)

    if S % C != 0:  # odd seq len: the plain full-logits path
        logits, sown = _apply_with_aux(model, params, inputs, **at)
        losses = optax.softmax_cross_entropy_with_integer_labels(
            data_rows(logits), targets
        )
        return with_router_terms((losses * weights).sum() / denom, sown)

    h, sown = _apply_with_aux(model, params, inputs, return_hidden=True, **at)
    return with_router_terms(head_loss(data_rows(h), targets, weights) / denom, sown)


def _loss_fn(model: Transformer, params, inputs, targets, mask, positions=None):
    return _loss_and_metrics(model, params, inputs, targets, mask, positions)[0]


def _batch_shardings(model: Transformer, mesh: Mesh):
    """The jit's shardings of a batch: the three [B,S] arrays by key; None
    for a model with ``mrope_section``, whose batch may carry
    "position_ids" [3,B,S] besides: ``_placed`` constrains what it has."""
    if model.cfg.mrope_section is not None:
        return None
    bsh = batch_sharding(mesh)
    return {"inputs": bsh, "targets": bsh, "mask": bsh}


def _placed(model: Transformer, mesh: Mesh, batch: dict) -> dict:
    """The batch as the step reads it: as handed in where the jit placed
    it (``_batch_shardings``), else each array constrained by its key."""
    if model.cfg.mrope_section is None:
        return batch
    bsh = batch_sharding(mesh)
    ids = NamedSharding(mesh, P(None, *bsh.spec))
    return {
        k: jax.lax.with_sharding_constraint(v, ids if k == "position_ids" else bsh)
        for k, v in batch.items()
    }


def make_train_step(
    model: Transformer,
    mesh: Mesh,
    shardings: TrainState,
    optimizer: Optional[optax.GradientTransformation] = None,
    donate: bool = True,
    accum_steps: int = 1,
) -> Callable[[TrainState, Any], Tuple[TrainState, Any]]:
    """batch = {"inputs": [B,S] i32, "targets": [B,S] i32, "mask": [B,S]}
    and, for a model with ``mrope_section``, optionally "position_ids"
    [3,B,S] i32 (a token's temporal, height and width ids; without them the
    model counts positions itself).
    Returns jitted (state, batch) -> (state, metrics): ``loss``,
    ``grad_norm`` and, for a model with experts, the router metrics of
    ``_loss_and_metrics`` (means over the microbatches when accumulating).
    A model with ``router_bias_update_rate`` > 0 gets its selection biases
    moved after the optimizer update (``update_router_bias``, from this
    step's ``moe_load``, summed over the microbatches) and
    ``router_bias_abs_max`` in the metrics in ``moe_load``'s place.

    ``accum_steps > 1`` runs gradient accumulation: the global batch is
    split into ``accum_steps`` microbatches along the batch dim and
    swept with ``lax.scan`` (ONE compiled microstep body — compile time
    and activation HBM stay those of a microbatch, which is how a large
    global batch fits a chip), accumulating fp32 gradients and applying
    the optimizer once.  Per-microbatch losses are normalized by their
    own mask counts and averaged, so with equal token counts per
    microbatch the result matches the unaccumulated step exactly (the
    usual data-parallel convention).  Requires B % accum_steps == 0.
    """
    optimizer = optimizer or _DEFAULT_OPT
    bsh = batch_sharding(mesh)
    batch_sh = _batch_shardings(model, mesh)

    def grads_and_loss(params, batch):
        inputs = jax.lax.with_sharding_constraint(batch["inputs"], bsh)
        (loss, router), grads = jax.value_and_grad(
            lambda p: _loss_and_metrics(
                model, p, inputs, batch["targets"], batch["mask"],
                batch.get("position_ids"),
            ),
            has_aux=True,
        )(params)
        return loss, router, grads

    def step_fn(state: TrainState, batch) -> Tuple[TrainState, Any]:
        batch = _placed(model, mesh, batch)
        if accum_steps <= 1:
            loss, router, grads = grads_and_loss(state.params, batch)
        else:
            B = batch["inputs"].shape[0]
            if B % accum_steps != 0:
                raise ValueError(
                    f"batch size {B} not divisible by "
                    f"accum_steps={accum_steps}"
                )
            # INTERLEAVED split (microbatch k = rows k::accum_steps):
            # under the contiguous (dp, fsdp) row sharding every shard
            # contributes the same fraction of each microbatch and the
            # rows land exactly where the microbatch sharding wants them
            # — a contiguous block split would leave each microbatch on
            # 1/accum_steps of the shards and force a cross-device
            # redistribution every scan iteration.
            micro = {
                k: jnp.moveaxis(
                    v.reshape(
                        B // accum_steps, accum_steps, *v.shape[1:]
                    ),
                    1,
                    0,
                )
                for k, v in batch.items()
                if k != "position_ids"
            }
            if "position_ids" in batch:  # [3,B,S]: the rows are its axis 1
                ids = batch["position_ids"]
                micro["position_ids"] = jnp.moveaxis(
                    ids.reshape(3, B // accum_steps, accum_steps, -1), 2, 0
                )
            g0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params
            )

            def body(carry, mb):
                acc_g, acc_loss = carry
                loss, router, grads = grads_and_loss(state.params, mb)
                acc_g = jax.tree_util.tree_map(
                    lambda a, g: a + g.astype(jnp.float32), acc_g, grads
                )
                return (acc_g, acc_loss + loss), router

            (gsum, loss_sum), routers = jax.lax.scan(
                body, (g0, jnp.zeros((), jnp.float32)), micro
            )
            inv = 1.0 / accum_steps
            grads = jax.tree_util.tree_map(lambda g: g * inv, gsum)
            loss = loss_sum * inv
            router = {
                k: v.sum(axis=0) if k == "moe_load" else v.mean()
                for k, v in routers.items()
            }
        loads = router.pop("moe_load", None)
        params, opt_state = apply_update(
            optimizer, model.cfg.router_bias_update_rate,
            state.params, state.opt_state, (grads, loads),
        )
        if loads is not None:
            router["router_bias_abs_max"] = router_bias_abs_max(params)
        gnorm = optax.global_norm(grads)
        new_state = TrainState(
            step=state.step + 1, params=params, opt_state=opt_state
        )
        return new_state, {"loss": loss, "grad_norm": gnorm, **router}

    return jax.jit(
        step_fn,
        in_shardings=(shardings, batch_sh),
        out_shardings=(shardings, None),
        donate_argnums=(0,) if donate else (),
    )


def make_grad_step(
    model: Transformer,
    mesh: Mesh,
    shardings: TrainState,
    with_metrics: bool = False,
) -> Callable[[Any, Any], Tuple[Any, Any]]:
    """(params, batch) -> (loss, grads): the bare gradient program
    (the batch as ``make_train_step``'s).
    ``with_metrics``: ((loss, router metrics), grads), the metrics those
    of ``_loss_and_metrics`` ({} for a dense model), ``moe_load`` among
    them; a loop takes ``make_split_grad_step`` and ``make_apply_step``.
    Without ``with_metrics`` no load leaves the step, so a loop built on
    it would train a model with ``router_bias_update_rate`` > 0 with its
    selection biases standing still: said once here, as a warning."""
    batch_sh = _batch_shardings(model, mesh)
    if model.cfg.router_bias_update_rate and not with_metrics:
        logger.warning(
            "make_grad_step(with_metrics=False) for a model with "
            "router_bias_update_rate=%g: the step returns no moe_load, so "
            "whatever applies its gradients cannot move the selection "
            "biases (build the loop on make_split_grad_step and "
            "make_apply_step; a caller that only compares loss and "
            "gradients loses nothing)",
            model.cfg.router_bias_update_rate,
        )

    def fn(params, batch):
        batch = _placed(model, mesh, batch)
        return jax.value_and_grad(
            lambda p: (_loss_and_metrics if with_metrics else _loss_fn)(
                model, p, batch["inputs"], batch["targets"], batch["mask"],
                batch.get("position_ids"),
            ),
            has_aux=with_metrics,
        )(params)

    return jax.jit(
        fn,
        in_shardings=(shardings.params, batch_sh),
        out_shardings=(None, shardings.params),
    )


def make_split_grad_step(model: Transformer, mesh: Mesh, shardings: TrainState):
    """The split step, its update *after* the Manager's outer-axis allreduce:
    (params, batch) -> (loss, metrics, to_reduce). ``to_reduce`` = (grads,
    loads) is all that rides that allreduce (the loads' mean over the replicas
    has the sign of their sum) and what ``make_apply_step`` takes whole."""
    bsh = batch_sharding(mesh)
    batch_sh = {"inputs": bsh, "targets": bsh, "mask": bsh}
    grad_step = make_grad_step(model, mesh, shardings, with_metrics=True)

    def fn(params, batch):
        (loss, metrics), grads = grad_step(params, batch)
        loads = metrics.pop("moe_load", None)
        return loss, metrics, (grads, loads)

    return jax.jit(
        fn,
        in_shardings=(shardings.params, batch_sh),
        out_shardings=(None, None, (shardings.params, None)),
    )


def make_apply_step(model: Transformer, shardings: TrainState, optimizer=None):
    """(params, opt_state, reduced) -> (params, opt_state):
    ``make_train_step``'s own update. Not donated (ROADMAP R2)."""
    return jax.jit(
        functools.partial(
            apply_update, optimizer or _DEFAULT_OPT,
            model.cfg.router_bias_update_rate,
        ),
        in_shardings=(
            shardings.params, shardings.opt_state, (shardings.params, None),
        ),
        out_shardings=(shardings.params, shardings.opt_state),
    )


def make_eval_step(model: Transformer, mesh: Mesh, shardings: TrainState):
    bsh = batch_sharding(mesh)
    batch_sh = {"inputs": bsh, "targets": bsh, "mask": bsh}

    def fn(params, batch):
        return _loss_fn(
            model, params, batch["inputs"], batch["targets"], batch["mask"]
        )

    return jax.jit(fn, in_shardings=(shardings.params, batch_sh))
