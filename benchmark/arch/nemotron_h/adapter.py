"""A configuration file of a Nemotron-H hybrid (arXiv:2504.03624; the keys
of the published config.json of model_type "nemotron_h") as the
program's model configuration: a stack read from
``hybrid_override_pattern``, each layer ONE mixer between a pre-norm and
the residual add: 'M' a Mamba-2 mixer (arXiv:2405.21060), 'E' an expert
layer (sigmoid router with a selection bias over all the experts,
renormalised and scaled gates, two-matrix relu^2 experts, a shared
expert), '*' grouped-query attention without rotary embeddings.

The file describes one chip of a deployment: ``n_routed_experts`` is the
number of experts HELD here, ``expert_parallel_chips`` over how many
chips a layer's experts lie (the router's width is their product) and
``expert_parallel_index`` which of them this chip is;
``vocab_parallel_chips`` says over how many the vocabulary lies, the
file's ``vocab_size`` being this chip's slice. cells.py says what an
adapter provides. The parent loads this file: JAX and the program are
imported inside the functions.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark import cells

# What the program computes, by key: any other value is refused by name.
REQUIRED = {
    "model_type": "nemotron_h",
    "attention_bias": False, "mamba_proj_bias": False, "mlp_bias": False,
    "use_bias": False, "use_conv_bias": True,
    "mamba_hidden_act": "silu", "mlp_hidden_act": "relu2",
    "n_group": 1, "topk_group": 1,  # the group limit is then the identity
    "n_shared_experts": 1, "norm_topk_prob": True,
    "sliding_window": None, "tie_word_embeddings": False,
    "residual_in_fp32": False, "rescale_prenorm_residual": True,
}
# Keys of the published file that configure nothing here: `expand` (the
# mixer's inner width is mamba_num_heads x mamba_head_dim), the rotary
# keys (the attention takes no positional embedding), `intermediate_size`
# (the width of '-' layers, which are refused), and three of inference.
UNUSED = frozenset({
    "expand", "rope_theta", "partial_rotary_factor", "intermediate_size",
    "num_logits_to_keep", "use_mamba_kernels", "norm_eps",
})
USED = frozenset({
    "hybrid_override_pattern", "num_hidden_layers", "hidden_size", "vocab_size",
    "head_dim", "num_attention_heads", "num_key_value_heads",
    "max_position_embeddings", "layer_norm_epsilon",
    "mamba_num_heads", "mamba_head_dim", "n_groups", "ssm_state_size",
    "conv_kernel", "chunk_size", "time_step_min", "time_step_max",
    "time_step_floor",
    "n_routed_experts", "num_experts_per_tok", "moe_intermediate_size",
    "moe_shared_expert_intermediate_size", "routed_scaling_factor",
    # The deployment's layout and the one loss coefficient (`assumed`).
    "expert_parallel_chips", "expert_parallel_index", "vocab_parallel_chips",
    "router_aux_loss_coef",
})
KEYS = USED | UNUSED | frozenset(REQUIRED)
sample_config = cells.arch_module("dense_decoder", "adapter").sample_config


def layout(config: Dict[str, Any]) -> Dict[str, int]:
    """The router's width and the experts held, from the deployment keys."""
    held, chips = config["n_routed_experts"], config["expert_parallel_chips"]
    index = config["expert_parallel_index"]
    if not 0 <= index < chips:
        raise cells.CellError(f"expert_parallel_index {index} of {chips} chips")
    return {"experts": held * chips, "first": index * held, "held": held}


def model_config(config: Dict[str, Any], seq: int) -> Any:
    """Refuses what the program's stack does not compute, by name."""
    import jax.numpy as jnp

    from torchft_tpu.models.llama import LlamaConfig
    from torchft_tpu.models.mamba2 import Mamba2Config

    missing = sorted(k for k in KEYS if k not in config)
    if missing:
        raise cells.CellError(
            f"not a configuration of this architecture: it lacks {missing}"
        )
    for key in UNUSED:  # stated by the file, checked to be there, read by nothing
        config[key]
    for key, want in REQUIRED.items():
        if config[key] != want:
            raise cells.CellError(
                f"{key} = {config[key]!r}: the program computes {want!r} only"
            )
    pattern = config["hybrid_override_pattern"]
    if len(pattern) != config["num_hidden_layers"] or set(pattern) - set("ME*"):
        raise cells.CellError(
            f"pattern {pattern!r}: {config['num_hidden_layers']} layers of "
            "'M', 'E' and '*' are what the stack is built from ('-' layers are not)"
        )
    if config["norm_eps"] != config["layer_norm_epsilon"]:
        raise cells.CellError("norm_eps and layer_norm_epsilon differ")
    if seq > config["max_position_embeddings"]:
        raise cells.CellError(f"sequence {seq} exceeds max_position_embeddings")
    if config["vocab_parallel_chips"] < 1:
        raise cells.CellError("vocab_parallel_chips counts the chips the vocabulary lies over")
    where = layout(config)
    if config["num_experts_per_tok"] > where["experts"]:
        raise cells.CellError("more experts per token than experts")
    run = config["run"]
    return LlamaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["moe_intermediate_size"],
        num_layers=len(pattern),
        layer_pattern=pattern,
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        max_seq_len=seq,
        norm_eps=float(config["layer_norm_epsilon"]),
        rope=False,
        mamba=Mamba2Config(
            num_heads=config["mamba_num_heads"],
            head_dim=config["mamba_head_dim"],
            n_groups=config["n_groups"],
            state_size=config["ssm_state_size"],
            conv_kernel=config["conv_kernel"],
            chunk_size=config["chunk_size"],
            dt_min=float(config["time_step_min"]),
            dt_max=float(config["time_step_max"]),
            dt_floor=float(config["time_step_floor"]),
        ),
        num_experts=where["experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        experts_held=(where["first"], where["held"]),
        expert_capacity_factor=None,  # dropless
        router_score="sigmoid",
        routed_scaling=float(config["routed_scaling_factor"]),
        expert_act="relu2",
        shared_expert_size=config["moe_shared_expert_intermediate_size"],
        router_aux_coef=float(config["router_aux_loss_coef"]),
        router_z_coef=0.0,
        residual_init_scale=len(pattern) ** -0.5,
        attn_impl=run["attn_impl"],
        dtype=jnp.dtype(run["compute_dtype"]),
        param_dtype=jnp.dtype(run["param_dtype"]),
    )
