"""A configuration file of an SDAR mixture-of-experts decoder (the keys of
the published config.json of model_type "sdar_moe", which are Qwen3-MoE's)
as the program's model configuration. Every published layer is rotary
grouped-query attention with per-head QK norms and an expert layer, each
between a pre-norm and its own residual add: the program's stack spells a
layer as two characters of its ``layer_pattern``, "*E" (a
softmax router over all the experts, the chosen gates divided by their
sum, three-matrix SiLU-gated experts, no shared expert); the head is
untied. The model generates by masked diffusion inside blocks and is
TRAINED that way: the program's objective is ``block_diffusion``, two
streams through the trunk under the block-diffusion mask
(``LlamaConfig.objective``).

The file describes one chip of a deployment: ``num_experts`` is the
number of experts HELD here, ``expert_parallel_chips`` over how many chips
a layer's experts lie (the router's width is their product) and
``expert_parallel_index`` which of them this chip is;
``vocab_parallel_chips`` says over how many the vocabulary lies, the
file's ``vocab_size`` being this chip's slice. What the published file
does not give is the file's own, under ``assumed``: ``block_length``,
``mask_token_id``, ``diffusion_t_min``, ``diffusion_t_max``,
``router_aux_loss_coef``. cells.py says what an adapter provides. The
parent loads this file: JAX and the program are imported inside the
functions.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

from benchmark import cells

# What the program computes, by key: any other value is refused by name.
REQUIRED = {
    "model_type": "sdar_moe",
    "attention_bias": False,
    "hidden_act": "silu",
    "norm_topk_prob": True,
    "tie_word_embeddings": False,
    "decoder_sparse_step": 1,  # every layer an expert layer
    "mlp_only_layers": [],
    "rope_scaling": None,
    "sliding_window": None,
    "use_sliding_window": False,
    # Published and inert under the keys above: the dense width no layer
    # has (``mlp_only_layers`` is empty) and the window no layer uses. The
    # file carries the published values and no others.
    "intermediate_size": 6144,
    "max_window_layers": 48,
}
USED = frozenset({
    "num_hidden_layers", "hidden_size", "moe_intermediate_size", "vocab_size",
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "max_position_embeddings", "rms_norm_eps", "rope_theta", "num_experts",
    "num_experts_per_tok",
    # The deployment's layout and what the published file leaves open
    # (`assumed` in the file).
    "expert_parallel_chips", "expert_parallel_index", "vocab_parallel_chips",
    "block_length", "mask_token_id", "diffusion_t_min", "diffusion_t_max",
    "router_aux_loss_coef",
})
KEYS = USED | frozenset(REQUIRED)


# The router's width and the experts held, from the deployment keys: the
# layout keys are LFM2's file's, and so is their reading.
layout = cells.arch_module("lfm2_moe", "adapter").layout


def model_config(config: Dict[str, Any], seq: int) -> Any:
    """Refuses what the program does not compute, by name."""
    import jax.numpy as jnp

    from torchft_tpu.models.llama import LlamaConfig

    if "objective" not in {f.name for f in dataclasses.fields(LlamaConfig)}:
        raise cells.CellError(
            "this program has no block-diffusion objective "
            "(LlamaConfig.objective): it cannot train an sdar_moe configuration"
        )
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
    heads = config["num_attention_heads"]
    if heads % config["num_key_value_heads"]:
        raise cells.CellError("num_key_value_heads must divide num_attention_heads")
    where = layout(config)
    if config["num_experts_per_tok"] > where["experts"]:
        raise cells.CellError("num_experts_per_tok exceeds the router's width")
    block = config["block_length"]
    if block < 1 or seq % block:
        raise cells.CellError(f"block_length {block} does not divide the sequence {seq}")
    if not 0 <= config["mask_token_id"] < config["vocab_size"]:
        raise cells.CellError(
            f"mask_token_id {config['mask_token_id']} lies outside this "
            f"chip's {config['vocab_size']} rows"
        )
    if not 0.0 <= config["diffusion_t_min"] < config["diffusion_t_max"] <= 1.0:
        raise cells.CellError(
            "diffusion_t_min < diffusion_t_max are the ends of t's interval in [0, 1]"
        )
    run = config["run"]
    return LlamaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["moe_intermediate_size"],
        num_layers=config["num_hidden_layers"],
        # Two characters a published layer: its attention, its experts.
        layer_pattern="*E" * config["num_hidden_layers"],
        num_heads=heads,
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        max_seq_len=seq,
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=False,
        qk_norm="head",
        num_experts=where["experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        experts_held=(where["first"], where["held"]),
        expert_capacity_factor=None,  # dropless
        norm_topk_prob=True,
        router_aux_coef=float(config["router_aux_loss_coef"]),
        router_z_coef=0.0,
        objective="block_diffusion",
        block_length=block,
        mask_token_id=config["mask_token_id"],
        diffusion_t_min=float(config["diffusion_t_min"]),
        diffusion_t_max=float(config["diffusion_t_max"]),
        attn_impl=run["attn_impl"],
        dtype=jnp.dtype(run["compute_dtype"]),
        param_dtype=jnp.dtype(run["param_dtype"]),
    )


def sample_config(cfg: Any, seq: int) -> Any:
    """``cfg`` for the reference check's sample of ``seq`` tokens, which
    the trunk runs as 2 x ``seq`` rows: the block-diffusion kernels are
    taken wherever the cell takes them, also where the sample is shorter
    than the length from which the program prefers them (and by ``init``
    on the sample's one stream, which the trunk reads as two of half the
    length: no fallback is noted that no step takes)."""
    return dataclasses.replace(cfg, flash_min_seq=min(cfg.flash_min_seq, seq))
