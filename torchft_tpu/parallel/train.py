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


# Tokens per chunked-loss slice. The [B,S,V] fp32 logits of a 32k-vocab
# model at B=8,S=1024 are >1 GB and their log_softmax + backward dlogits
# multiply that — the dominant HBM transient of the whole step. Chunking
# bounds it at [B,_LOSS_CHUNK,V] (~130 MB) with jax.checkpoint recompute.
# Env-tunable (TORCHFT_LOSS_CHUNK) so the on-chip MFU sweep can A/B chunk
# sizes without code edits — larger chunks = fewer scan iterations and
# bigger head matmuls at proportionally more transient HBM.
from torchft_tpu import knobs as _knobs

_LOSS_CHUNK = _knobs.get_int("TORCHFT_LOSS_CHUNK")


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
    """model.apply + what MoEMLP sows per layer, by name and stacked over
    the layers ({} for dense models): ``router_aux``, ``router_z``,
    ``moe_max_load``, ``moe_dropped``."""
    if model.cfg.num_experts <= 0:
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


def _loss_and_metrics(model: Transformer, params, inputs, targets, mask):
    """(loss, router metrics). The loss is the mean next-token
    cross-entropy plus, for a model with experts, ``router_aux_coef`` x
    the load-balancing term and ``router_z_coef`` x the router z-loss,
    each a mean over the layers. The metrics are {} for a dense model,
    else ``router_aux``, ``router_z``, ``moe_max_load`` (worst layer),
    ``moe_dropped`` (assignments not computed, all layers), from
    layers that hold a share of their experts ``moe_held_share`` (the
    share of all assignments that landed on held experts) and, from a
    model whose step updates its selection biases, the vector
    ``moe_load``."""
    B, S = inputs.shape
    C = min(_LOSS_CHUNK, S)
    mask_f = mask.astype(jnp.float32)
    denom = jnp.maximum(mask_f.sum(), 1.0)
    cfg = model.cfg

    def with_router_terms(ce, sown):
        if not sown:
            # The zero term is the dense step's jaxpr as it always was: a
            # persistent compile cache keyed on it keeps hitting.
            return ce + cfg.router_aux_coef * jnp.zeros(()), {}
        metrics = {
            name: over_layers(sown[name])
            for name, over_layers in _SOWN_OVER_LAYERS if name in sown
        }
        loss = ce + cfg.router_aux_coef * metrics["router_aux"]
        if cfg.router_z_coef:
            loss = loss + cfg.router_z_coef * metrics["router_z"]
        return loss, jax.lax.stop_gradient(metrics)

    if S % C != 0:  # odd seq len: the plain full-logits path
        logits, sown = _apply_with_aux(model, params, inputs)
        losses = optax.softmax_cross_entropy_with_integer_labels(
            logits, targets
        )
        return with_router_terms((losses * mask_f).sum() / denom, sown)

    h, sown = _apply_with_aux(model, params, inputs, return_hidden=True)
    w, head_dtype = _lm_head_projection(model, params)
    w = w.astype(head_dtype)
    n = S // C
    h_r = jnp.moveaxis(h.reshape(B, n, C, h.shape[-1]), 1, 0)  # [n,B,C,H]
    t_r = jnp.moveaxis(targets.reshape(B, n, C), 1, 0)
    m_r = jnp.moveaxis(mask_f.reshape(B, n, C), 1, 0)

    # A hand-written VJP for this scan (saved-lse + bf16 dlogits) is 2x
    # faster in isolation but 8% slower composed into the full step (XLA
    # overlaps this checkpointed scan's backward with the trunk backward;
    # a custom_vjp boundary defeats that) — measured on v5e, B=8 S=1024.
    def chunk(acc, xs):
        hc, tc, mc = xs
        logits = jnp.dot(
            hc.astype(head_dtype), w, preferred_element_type=jnp.float32
        )  # [B,C,V] fp32, exists only inside this chunk
        losses = optax.softmax_cross_entropy_with_integer_labels(logits, tc)
        return acc + (losses * mc).sum(), None

    total, _ = jax.lax.scan(
        jax.checkpoint(chunk), jnp.zeros((), jnp.float32), (h_r, t_r, m_r)
    )
    return with_router_terms(total / denom, sown)


def _loss_fn(model: Transformer, params, inputs, targets, mask):
    return _loss_and_metrics(model, params, inputs, targets, mask)[0]


def make_train_step(
    model: Transformer,
    mesh: Mesh,
    shardings: TrainState,
    optimizer: Optional[optax.GradientTransformation] = None,
    donate: bool = True,
    accum_steps: int = 1,
) -> Callable[[TrainState, Any], Tuple[TrainState, Any]]:
    """batch = {"inputs": [B,S] i32, "targets": [B,S] i32, "mask": [B,S]}.
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
    batch_sh = {"inputs": bsh, "targets": bsh, "mask": bsh}

    def grads_and_loss(params, batch):
        inputs = jax.lax.with_sharding_constraint(batch["inputs"], bsh)
        (loss, router), grads = jax.value_and_grad(
            lambda p: _loss_and_metrics(
                model, p, inputs, batch["targets"], batch["mask"]
            ),
            has_aux=True,
        )(params)
        return loss, router, grads

    def step_fn(state: TrainState, batch) -> Tuple[TrainState, Any]:
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
            }
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
        updates, opt_state = optimizer.update(
            grads, state.opt_state, state.params
        )
        params = optax.apply_updates(state.params, updates)
        if "moe_load" in router:
            params = update_router_bias(
                params, state.params, router.pop("moe_load"),
                model.cfg.router_bias_update_rate,
            )
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
    """(params, batch) -> (loss, grads): the DDP variant where the optimizer
    update is applied *after* the Manager's outer-axis gradient allreduce.
    ``with_metrics``: ((loss, router metrics), grads), the metrics those
    of ``_loss_and_metrics`` ({} for a dense model).

    A model with ``router_bias_update_rate`` > 0 hands its loads out in
    the metrics (``moe_load``, a vector): the loop takes them out, reduces
    them over the replicas with the gradients and gives them to
    ``update_router_bias`` after its own optimizer update, as
    ``train_hsdp.py`` does. Without ``with_metrics`` no load leaves the
    step, so a loop built on it would train that model with its selection
    biases standing still: said once here, as a warning."""
    bsh = batch_sharding(mesh)
    batch_sh = {"inputs": bsh, "targets": bsh, "mask": bsh}
    if model.cfg.router_bias_update_rate and not with_metrics:
        logger.warning(
            "make_grad_step(with_metrics=False) for a model with "
            "router_bias_update_rate=%g: the step returns no moe_load, so "
            "whatever applies its gradients cannot move the selection "
            "biases (ask for the metrics and call update_router_bias; a "
            "caller that only compares loss and gradients loses nothing)",
            model.cfg.router_bias_update_rate,
        )

    def fn(params, batch):
        return jax.value_and_grad(
            lambda p: (_loss_and_metrics if with_metrics else _loss_fn)(
                model, p, batch["inputs"], batch["targets"], batch["mask"]
            ),
            has_aux=with_metrics,
        )(params)

    return jax.jit(
        fn,
        in_shardings=(shardings.params, batch_sh),
        out_shardings=(None, shardings.params),
    )


def make_eval_step(model: Transformer, mesh: Mesh, shardings: TrainState):
    bsh = batch_sharding(mesh)
    batch_sh = {"inputs": bsh, "targets": bsh, "mask": bsh}

    def fn(params, batch):
        return _loss_fn(
            model, params, batch["inputs"], batch["targets"], batch["mask"]
        )

    return jax.jit(fn, in_shardings=(shardings.params, batch_sh))
