"""Parameter-path sharding rules for the Llama family.

The model stays mesh-agnostic; these rules map each parameter to a
PartitionSpec over the (dp, fsdp, sp, tp) mesh. The scan-stacked layer dim
(leading axis of every ``layers/*`` param) is unsharded — XLA scans over it;
a ``layer_pattern`` stack's ``layers_<i>/*`` params have no such axis.

Layout (standard HSDP+TP recipe, cf. the public scaling playbook):
- contraction-input dims shard over ``fsdp`` (all-gathered per layer),
- head/feature output dims shard over ``tp`` (ICI-adjacent),
- norms replicate; activations shard batch over (dp, fsdp) and sequence
  over ``sp``.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# name of innermost param container -> spec for the trailing dims
_RULES: Dict[Tuple[str, str], Tuple[Any, ...]] = {
    ("embed", "embedding"): ("tp", "fsdp"),
    ("wq", "kernel"): ("fsdp", "tp", None),
    ("wk", "kernel"): ("fsdp", "tp", None),
    ("wv", "kernel"): ("fsdp", "tp", None),
    ("wo", "kernel"): ("tp", None, "fsdp"),
    # Latent attention (models/mla.py): the two down-projections' input dim
    # shards over ``fsdp`` and their bottleneck stays whole (its norm needs
    # every channel); the two up-projections shard their heads over ``tp``
    # as wq/wk/wv do. The bottlenecks' norms (``q_norm``, ``kv_norm``)
    # replicate. ``eh_proj`` joins a prediction module's two inputs
    # [2H, H] and shards like an MLP's down-projection.
    ("wq_a", "kernel"): ("fsdp", None),
    ("wkv_a", "kernel"): ("fsdp", None),
    ("wq_b", "kernel"): ("fsdp", "tp", None),
    ("wkv_b", "kernel"): ("fsdp", "tp", None),
    ("eh_proj", "kernel"): ("tp", "fsdp"),
    ("gate", "kernel"): ("fsdp", "tp"),
    ("up", "kernel"): ("fsdp", "tp"),
    ("down", "kernel"): ("tp", "fsdp"),
    ("lm_head", "kernel"): ("fsdp", "tp"),
    # MoE: experts shard over 'ep'; within an expert the FFN shards like
    # the dense MLP. The fp32 router's [H, E] kernel shards H over fsdp
    # (gathered with the rest of the layer) and keeps E whole.
    ("mlp", "experts_gate"): ("ep", "fsdp", "tp"),
    ("mlp", "experts_up"): ("ep", "fsdp", "tp"),
    ("mlp", "experts_down"): ("ep", "tp", "fsdp"),
    ("router", "kernel"): ("fsdp", None),
    # The shared expert shards like the dense MLP. The Mamba-2 mixer's two
    # projections shard like an MLP's up and down; its convolution, decay,
    # step and norm parameters are per channel or per head and replicate,
    # as does the router's selection bias.
    ("shared_gate", "kernel"): ("fsdp", "tp"),
    ("shared_up", "kernel"): ("fsdp", "tp"),
    ("shared_down", "kernel"): ("tp", "fsdp"),
    ("in_proj", "kernel"): ("fsdp", "tp"),
    ("out_proj", "kernel"): ("tp", "fsdp"),
    # The gated-delta mixer (models/gated_delta.py): its five projections
    # out of the residual stream shard their heads over ``tp`` (a_proj's
    # and b_proj's [H, heads] one value a head), o_proj like an MLP's down;
    # the convolution over [q | k | v], A_log, dt_bias and the per-head
    # norm's vector replicate.
    ("q_proj", "kernel"): ("fsdp", "tp"),
    ("k_proj", "kernel"): ("fsdp", "tp"),
    ("v_proj", "kernel"): ("fsdp", "tp"),
    ("g_proj", "kernel"): ("fsdp", "tp"),
    ("a_proj", "kernel"): ("fsdp", "tp"),
    ("b_proj", "kernel"): ("fsdp", "tp"),
    ("o_proj", "kernel"): ("tp", "fsdp"),
    # The Kimi delta mixer (same file) has those names for its q, k, v,
    # beta and output projections; its two low-rank pairs go down to one
    # bottleneck for all heads (whole, like latent attention's) and up to
    # the heads over ``tp``; g_b_proj's bias, dt_bias (a key channel) and
    # the rest replicate. The attention's output gate shards as wq does.
    ("f_a_proj", "kernel"): ("fsdp", None),
    ("g_a_proj", "kernel"): ("fsdp", None),
    ("f_b_proj", "kernel"): (None, "tp"),
    ("g_b_proj", "kernel"): (None, "tp"),
    ("wg", "kernel"): ("fsdp", "tp", None),
    # The gated short-convolution mixer (``conv``) has the same two names:
    # its in_proj's [H, 3H] output splits into thirds that GSPMD re-shards
    # where ``tp`` cuts across them; its depthwise ``conv_kernel`` [taps, H]
    # is per channel and replicates, as the Mamba-2 mixer's does.
}


def _spec_for(path: Tuple[str, ...], ndim: int) -> P:
    key = tuple(path[-2:]) if len(path) >= 2 else tuple(path)
    rule = _RULES.get(key)  # type: ignore[arg-type]
    if rule is None:
        return P()  # norms / scalars: replicated
    pad = ndim - len(rule)
    return P(*((None,) * pad + tuple(rule)))


def path_keys(path) -> Tuple[str, ...]:
    keys = []
    for entry in path:
        if hasattr(entry, "key"):
            keys.append(str(entry.key))
        elif hasattr(entry, "idx"):
            keys.append(str(entry.idx))
        else:
            keys.append(str(entry))
    return tuple(keys)


def param_specs(params: Any) -> Any:
    """Pytree of PartitionSpec matching ``params`` (works on real arrays or
    ShapeDtypeStructs)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _spec_for(path_keys(path), leaf.ndim), params
    )


def param_shardings(params: Any, mesh: Mesh) -> Any:
    return jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), param_specs(params)
    )


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """[B, S]-shaped token batches: batch over (dp, fsdp), seq over sp."""
    return NamedSharding(mesh, P(("dp", "fsdp"), "sp"))


def tree_specs_like(tree: Any, params_spec_by_path: Dict[Tuple[str, ...], P]) -> Any:
    """Specs for an arbitrary pytree (e.g. optax state) whose leaves mirror
    parameter subtrees: a leaf whose path *ends with* a known param path gets
    that param's spec; everything else (counts, scalars) replicates."""

    def lookup(path, leaf):
        keys = path_keys(path)
        for start in range(len(keys)):
            suffix = keys[start:]
            if suffix in params_spec_by_path:
                return params_spec_by_path[suffix]
        return P()

    return jax.tree_util.tree_map_with_path(lookup, tree)


def params_spec_dict(params: Any) -> Dict[Tuple[str, ...], P]:
    out: Dict[Tuple[str, ...], P] = {}

    def record(path, leaf):
        out[path_keys(path)] = _spec_for(path_keys(path), leaf.ndim)
        return leaf

    jax.tree_util.tree_map_with_path(record, params)
    return out
