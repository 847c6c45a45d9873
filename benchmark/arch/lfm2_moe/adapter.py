"""A configuration file of an LFM2 mixture-of-experts decoder (the keys of
the published config.json of model_type "lfm2_moe") as the program's
model configuration. Every published layer is an operator and a
feed-forward, each between a pre-norm and its own residual add; the
program's stack spells a layer as two characters of its ``layer_pattern``:
'C' a gated short convolution or '*' rotary grouped-query attention with
per-head QK norms, then 'D' a dense SwiGLU feed-forward (the first
``num_dense_layers`` layers) or 'E' an expert layer (sigmoid router with
a selection bias over all the experts, gates renormalised over a sum plus
1e-6, three-matrix SiLU-gated experts, no shared expert). The head is
tied to the embedding.

The file describes one chip of a deployment: ``num_experts`` is the
number of experts HELD here, ``expert_parallel_chips`` over how many chips
a layer's experts lie (the router's width is their product) and
``expert_parallel_index`` which of them this chip is;
``vocab_parallel_chips`` says over how many the vocabulary lies, the
file's ``vocab_size`` being this chip's slice. ``layer_types`` and
``num_dense_layers`` are those of the layers run here. cells.py says what
an adapter provides. The parent loads this file: JAX and the program are
imported inside the functions.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark import cells

# What the program computes, by key: any other value is refused by name.
REQUIRED = {
    "model_type": "lfm2_moe",
    "conv_bias": False,
    "conv_L_cache": 3,  # the taps the program's mixer builds (SHORT_CONV_TAPS)
    "norm_topk_prob": True,
    "use_expert_bias": True,
    "tie_word_embeddings": True,
}
USED = frozenset({
    "layer_types", "num_hidden_layers", "num_dense_layers", "hidden_size",
    "intermediate_size", "moe_intermediate_size", "vocab_size",
    "num_attention_heads", "num_key_value_heads", "max_position_embeddings",
    "norm_eps", "rope_theta", "num_experts", "num_experts_per_tok",
    "routed_scaling_factor",
    # The deployment's layout and what the recipe leaves to the trainer
    # (`assumed` in the file).
    "expert_parallel_chips", "expert_parallel_index", "vocab_parallel_chips",
    "router_aux_loss_coef", "router_bias_update_rate",
})
KEYS = USED | frozenset(REQUIRED)
LAYER_KINDS = {"conv": "C", "full_attention": "*"}
sample_config = cells.arch_module("dense_decoder", "adapter").sample_config


def layout(config: Dict[str, Any]) -> Dict[str, int]:
    """The router's width and the experts held, from the deployment keys."""
    held, chips = config["num_experts"], config["expert_parallel_chips"]
    index = config["expert_parallel_index"]
    if chips < 1 or not 0 <= index < chips:
        raise cells.CellError(f"expert_parallel_index {index} of {chips} chips")
    return {"experts": held * chips, "first": index * held, "held": held}


def pattern(config: Dict[str, Any]) -> str:
    """Two characters a layer: the operator's, then the feed-forward's."""
    kinds, dense = config["layer_types"], config["num_dense_layers"]
    unknown = sorted(set(kinds) - set(LAYER_KINDS))
    if unknown or len(kinds) != config["num_hidden_layers"]:
        raise cells.CellError(
            f"layer_types {kinds!r}: {config['num_hidden_layers']} of 'conv' "
            "and 'full_attention' are what the stack is built from"
        )
    if not 0 <= dense <= len(kinds):
        raise cells.CellError(f"num_dense_layers {dense} of {len(kinds)} layers")
    return "".join(
        LAYER_KINDS[kind] + ("D" if i < dense else "E") for i, kind in enumerate(kinds)
    )


def model_config(config: Dict[str, Any], seq: int) -> Any:
    """Refuses what the program's stack does not compute, by name."""
    import jax.numpy as jnp

    from torchft_tpu.models.llama import LlamaConfig

    missing = sorted(k for k in KEYS if k not in config)
    if missing:
        raise cells.CellError(
            f"not a configuration of this architecture: it lacks {missing}"
        )
    for key, want in REQUIRED.items():
        if config[key] != want:
            raise cells.CellError(
                f"{key} = {config[key]!r}: the program computes {want!r} only"
            )
    if seq > config["max_position_embeddings"]:
        raise cells.CellError(f"sequence {seq} exceeds max_position_embeddings")
    if config["vocab_parallel_chips"] < 1:
        raise cells.CellError("vocab_parallel_chips counts the chips the vocabulary lies over")
    heads, hidden = config["num_attention_heads"], config["hidden_size"]
    if hidden % heads or heads % config["num_key_value_heads"]:
        raise cells.CellError("heads must divide the hidden size, key/value heads the heads")
    where = layout(config)
    if config["num_experts_per_tok"] > where["experts"]:
        raise cells.CellError("more experts per token than experts")
    run = config["run"]
    return LlamaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=hidden,
        intermediate_size=config["moe_intermediate_size"],
        dense_intermediate_size=config["intermediate_size"],
        num_layers=config["num_hidden_layers"],
        layer_pattern=pattern(config),
        num_heads=heads,
        num_kv_heads=config["num_key_value_heads"],
        head_dim=hidden // heads,
        max_seq_len=seq,
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["norm_eps"]),
        tie_embeddings=True,
        qk_norm="head",
        num_experts=where["experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        experts_held=(where["first"], where["held"]),
        expert_capacity_factor=None,  # dropless
        router_score="sigmoid",
        routed_scaling=float(config["routed_scaling_factor"]),
        gate_eps=1e-6,
        expert_act="swiglu",
        router_aux_coef=float(config["router_aux_loss_coef"]),
        router_z_coef=0.0,
        router_bias_update_rate=float(config["router_bias_update_rate"]),
        attn_impl=run["attn_impl"],
        dtype=jnp.dtype(run["compute_dtype"]),
        param_dtype=jnp.dtype(run["param_dtype"]),
    )
