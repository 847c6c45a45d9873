"""Pipeline parallelism over a ``pp`` mesh axis (GPipe schedule).

The reference has NO pipeline engine — it only uses torch's pipelining
helper to *split* a model into DiLoCo fragments (reference:
train_diloco.py:162-165, SURVEY.md §2.3); actual PP is delegated to the
consuming trainer. This module exceeds that with a real TPU-native
schedule, designed the SPMD way rather than as a runtime of stage workers:

- the scan-stacked layer dim of the Transformer's params (leading
  ``[num_layers]`` axis, models/llama.py nn.scan) is sharded over ``pp``,
  so each stage device holds ``num_layers / pp`` layers — no parameter
  tree surgery, and FSDP-style rules still apply to the trailing dims;
- the schedule itself is a ``lax.scan`` over ticks inside ``shard_map``:
  each tick every stage applies its layer slice to its current microbatch
  and ``ppermute``\\ s the activation to the next stage. Reverse-mode AD
  through the loop IS pipeline backward (the transpose of ppermute is the
  reverse rotation), so one ``jax.grad`` gives the full bwd schedule with
  the same bubble;
- bubble fraction = (pp - 1) / (n_micro + pp - 1); activations of all
  in-flight ticks are the GPipe memory profile, reduced per-layer with
  ``jax.checkpoint`` when ``cfg.remat`` is set.

Stage-0 embedding and last-stage head/loss run on every pp rank (their
inputs are replicated; only the owning rank's result is consumed) — that
redundancy costs a few percent of FLOPs and keeps every collective a
static-shape ppermute XLA can schedule on ICI.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
# version-compat wrapper (check_rep/check_vma) shared with ring attention
from torchft_tpu.parallel.ring_attention import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from torchft_tpu.models.llama import (
    Block,
    LlamaConfig,
    RMSNorm,
    Transformer,
    rope_table,
)
from torchft_tpu.parallel.sharding import path_keys, tree_specs_like
from torchft_tpu.parallel.train import TrainState, apply_update, default_optimizer


def gpipe_loop(
    stage_fn: Callable[[jax.Array], jax.Array],
    x_all: jax.Array,
    axis: str = "pp",
) -> jax.Array:
    """The per-device GPipe tick loop; call INSIDE shard_map.

    ``x_all``: [n_micro, mb, ...] stage-0 inputs (replicated across the
    axis; only rank 0 consumes them). ``stage_fn`` must be shape-preserving
    (a homogeneous trunk). Returns [n_micro, mb, ...] outputs — valid on
    the LAST stage only; other ranks hold zeros/garbage.
    """
    n_micro = x_all.shape[0]
    n_stages = jax.lax.psum(1, axis)
    stage = jax.lax.axis_index(axis)
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def tick(carry, t):
        x_recv, out = carry
        feed = jax.lax.dynamic_index_in_dim(
            x_all, jnp.clip(t, 0, n_micro - 1), 0, keepdims=False
        )
        x_in = jnp.where(stage == 0, feed, x_recv)
        y = stage_fn(x_in)
        slot = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
        write = (stage == n_stages - 1) & (t >= n_stages - 1)
        cur = jax.lax.dynamic_index_in_dim(out, slot, 0, keepdims=False)
        out = jax.lax.dynamic_update_index_in_dim(
            out, jnp.where(write, y, cur), slot, 0
        )
        x_send = jax.lax.ppermute(y, axis, perm)
        return (x_send, out), None

    init = (jnp.zeros_like(x_all[0]), jnp.zeros_like(x_all))
    (_, out), _ = jax.lax.scan(
        tick, init, jnp.arange(n_micro + n_stages - 1)
    )
    return out


def pipeline_param_specs(params: Any) -> Any:
    """P('pp') on the stacked layer dim; everything else replicated (the
    pipeline composes with dp on the batch, not with fsdp/tp, in this v1)."""

    def spec(path, leaf):
        keys = path_keys(path)
        if "layers" in keys:
            return P(*(("pp",) + (None,) * (leaf.ndim - 1)))
        return P()

    return jax.tree_util.tree_map_with_path(spec, params)


def _check_cfg(cfg: LlamaConfig, n_stages: int) -> None:
    if cfg.num_layers % n_stages != 0:
        raise ValueError(
            f"num_layers {cfg.num_layers} not divisible by pp={n_stages}"
        )
    if cfg.tie_embeddings:
        raise ValueError("pipeline: tie_embeddings unsupported (head lives "
                         "on the last stage, embed on the first)")
    if cfg.num_experts > 0:
        raise ValueError("pipeline: MoE aux-loss sow is not plumbed "
                         "through shard_map; use the ep axis instead")
    if cfg.attn_impl in ("ring", "ulysses"):
        raise ValueError("pipeline: compose with sp later; use dense/flash")
    if cfg.loop_steps != 1:
        raise ValueError("pipeline: its copy of the stack visits a layer once; "
                         "a loop over the stack (loop_steps) is not built here")


def make_pipeline_loss(
    cfg: LlamaConfig, mesh: Mesh, n_micro: int
) -> Callable[[Any, Any], jax.Array]:
    """Returns loss(params, batch) where the layer stack is pipelined over
    mesh axis 'pp' and the batch is sharded over 'dp'. ``params`` is the
    standard Transformer param tree (layers stacked [num_layers, ...])."""
    n_stages = mesh.shape["pp"]
    _check_cfg(cfg, n_stages)
    block = Block(cfg)
    norm = RMSNorm(cfg.norm_eps, cfg.param_dtype)

    def device_fn(params, inputs, targets, mask):
        # params["layers"]: local [num_layers/pp, ...] slice.
        layers_local = params["layers"]
        B_loc, S = inputs.shape
        if B_loc % n_micro != 0:
            raise ValueError(
                f"local batch {B_loc} not divisible by n_micro {n_micro}"
            )
        mb = B_loc // n_micro

        embed_tab = params["embed"]["embedding"]  # [V, H] param_dtype
        x = jnp.take(embed_tab, inputs, axis=0).astype(cfg.dtype)
        x_all = x.reshape(n_micro, mb, S, cfg.hidden_size)
        positions = jnp.broadcast_to(jnp.arange(S), (mb, S))
        cos, sin = rope_table(
            positions, cfg.head_dim, cfg.rope_theta, cfg.dtype
        )

        def layer_step(h, layer_p):
            return block.apply({"params": layer_p}, h, cos, sin), None

        if cfg.remat:
            layer_step = jax.checkpoint(layer_step, prevent_cse=False)

        def stage_fn(h):
            out, _ = jax.lax.scan(layer_step, h, layers_local)
            return out

        h_all = gpipe_loop(stage_fn, x_all, axis="pp")  # last stage only

        # Head + loss on every rank; only the last stage's input is real.
        h = norm.apply(
            {"params": params["final_norm"]},
            h_all.reshape(B_loc, S, cfg.hidden_size),
        )
        w = params["lm_head"]["kernel"].astype(cfg.dtype)
        logits = jnp.dot(
            h.astype(cfg.dtype), w, preferred_element_type=jnp.float32
        )
        losses = optax.softmax_cross_entropy_with_integer_labels(
            logits, targets
        )
        mask_f = mask.astype(jnp.float32)
        stage = jax.lax.axis_index("pp")
        is_last = (stage == n_stages - 1).astype(jnp.float32)
        loss_sum = jax.lax.psum(
            jax.lax.psum((losses * mask_f).sum() * is_last, "pp"), "dp"
        )
        denom = jnp.maximum(jax.lax.psum(mask_f.sum(), "dp"), 1.0)
        return loss_sum / denom

    sharded = shard_map(
        device_fn,
        mesh=mesh,
        in_specs=(
            pipeline_param_specs_struct(cfg),
            P("dp", None),
            P("dp", None),
            P("dp", None),
        ),
        out_specs=P(),
    )

    def loss_fn(params, batch):
        return sharded(
            params, batch["inputs"], batch["targets"], batch["mask"]
        )

    return loss_fn


def pipeline_param_specs_struct(cfg: LlamaConfig) -> Any:
    """Spec pytree for the Transformer param structure (via eval_shape, so
    no FLOPs)."""
    model = Transformer(cfg)
    tokens = jnp.zeros((1, 8), jnp.int32)
    shape = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), tokens)["params"]
    )
    return pipeline_param_specs(shape)


def init_pipeline_state(
    cfg: LlamaConfig,
    mesh: Mesh,
    rng: jax.Array,
    sample_tokens_shape: Tuple[int, int],
    optimizer: Optional[optax.GradientTransformation] = None,
) -> Tuple[TrainState, TrainState]:
    """Born-sharded init: layers sharded over 'pp', rest replicated.
    Returns (state, shardings)."""
    optimizer = optimizer or default_optimizer()
    _check_cfg(cfg, mesh.shape["pp"])
    model = Transformer(cfg)

    def init_fn(rng):
        tokens = jnp.zeros(sample_tokens_shape, jnp.int32)
        params = model.init(rng, tokens)["params"]
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=optimizer.init(params),
        )

    shape = jax.eval_shape(init_fn, rng)
    p_specs = pipeline_param_specs(shape.params)
    # Path->spec dict so optimizer-state leaves (mu/nu mirror the params)
    # inherit their param's spec.
    spec_dict = {}

    def record(path, spec):
        spec_dict[path_keys(path)] = spec

    jax.tree_util.tree_map_with_path(
        record, p_specs, is_leaf=lambda x: isinstance(x, P)
    )
    opt_specs = tree_specs_like(shape.opt_state, spec_dict)
    to_sh = lambda s: NamedSharding(mesh, s)  # noqa: E731
    shardings = TrainState(
        step=to_sh(P()),
        params=jax.tree_util.tree_map(to_sh, p_specs),
        opt_state=jax.tree_util.tree_map(
            to_sh, opt_specs, is_leaf=lambda x: isinstance(x, P)
        ),
    )
    state = jax.jit(init_fn, out_shardings=shardings)(rng)
    return state, shardings


def make_pipeline_train_step(
    cfg: LlamaConfig,
    mesh: Mesh,
    shardings: TrainState,
    n_micro: int,
    optimizer: Optional[optax.GradientTransformation] = None,
):
    """Jitted (state, batch) -> (state, metrics) with the trunk pipelined
    over 'pp' and batch data-parallel over 'dp'."""
    optimizer = optimizer or default_optimizer()
    loss_fn = make_pipeline_loss(cfg, mesh, n_micro)
    batch_sh = NamedSharding(mesh, P("dp", None))

    def step_fn(state: TrainState, batch):
        loss, grads = jax.value_and_grad(loss_fn)(state.params, batch)
        params, opt_state = apply_update(
            optimizer, 0.0, state.params, state.opt_state, (grads, None)
        )
        return (
            TrainState(
                step=state.step + 1, params=params, opt_state=opt_state
            ),
            {"loss": loss, "grad_norm": optax.global_norm(grads)},
        )

    return jax.jit(
        step_fn,
        in_shardings=(
            shardings,
            {"inputs": batch_sh, "targets": batch_sh, "mask": batch_sh},
        ),
        out_shardings=(shardings, None),
        donate_argnums=(0,),
    )
