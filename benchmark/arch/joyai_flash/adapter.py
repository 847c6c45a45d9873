"""A configuration file of a JoyAI-LLM-Flash decoder (the keys of the
published config.json of model_type "joyai_llm_flash", which are
DeepSeek-V3's name for name; arXiv:2412.19437 sections 2.1 and 2.2) as the
program's model configuration. Every published layer is latent attention
and a feed-forward, each between a pre-norm and its own residual add: the
program's stack spells a layer as two characters of its ``layer_pattern``,
'*' (``LlamaConfig.mla``: latent attention) then 'D' (a dense SwiGLU
feed-forward, the first ``first_k_dense_replace`` layers) or 'E' (sigmoid
router with a selection bias over all the experts, gates renormalised and
scaled, three-matrix SiLU-gated experts, shared experts as one of their
summed width). After the stack ``num_nextn_predict_layers``
multi-token-prediction modules, each a projection of [hidden | next
token's embedding] and one sparse block, through the shared final norm,
table and head; the head is untied.

The file describes one chip of a deployment: ``n_routed_experts`` is the
number of experts HELD here, ``expert_parallel_chips`` over how many chips
a layer's experts lie (the router's width is their product) and
``expert_parallel_index`` which of them this chip is;
``vocab_parallel_chips`` says over how many the vocabulary lies, the
file's ``vocab_size`` being this chip's slice. What the published file
does not give is the file's own, under ``assumed``:
``router_bias_update_rate``, ``mtp_loss_coef``. cells.py says what an
adapter provides. The parent loads this file: JAX and the program are
imported inside the functions.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

from benchmark import cells

# What the program computes, by key: any other value is refused by name.
REQUIRED = {
    "model_type": "joyai_llm_flash",
    "attention_bias": False,
    "hidden_act": "silu",
    "scoring_func": "sigmoid",
    "topk_method": "noaux_tc",  # the selection bias chooses, no balance loss
    "n_group": 1, "topk_group": 1,  # the group limit is then the identity
    "norm_topk_prob": True,
    "moe_layer_freq": 1,  # every layer after the leading dense ones has experts
    "ep_size": 1,  # the deployment's layout is this file's own keys below
    "rope_scaling": None,
    "rope_interleave": True,
    "tie_word_embeddings": False,
}
USED = frozenset({
    "num_hidden_layers", "first_k_dense_replace", "hidden_size",
    "intermediate_size", "moe_intermediate_size", "vocab_size",
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "q_lora_rank", "kv_lora_rank", "qk_head_dim", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "max_position_embeddings",
    "rms_norm_eps", "rope_theta", "n_routed_experts", "n_shared_experts",
    "num_experts_per_tok", "routed_scaling_factor", "num_nextn_predict_layers",
    # The deployment's layout and what the recipe leaves to the trainer
    # (`assumed` in the file).
    "expert_parallel_chips", "expert_parallel_index", "vocab_parallel_chips",
    "router_bias_update_rate", "mtp_loss_coef",
})
KEYS = USED | frozenset(REQUIRED)
sample_config = cells.arch_module("dense_decoder", "adapter").sample_config


def layout(config: Dict[str, Any]) -> Dict[str, int]:
    """The router's width and the experts held, from the deployment keys."""
    held, chips = config["n_routed_experts"], config["expert_parallel_chips"]
    index = config["expert_parallel_index"]
    if chips < 1 or not 0 <= index < chips:
        raise cells.CellError(f"expert_parallel_index {index} of {chips} chips")
    return {"experts": held * chips, "first": index * held, "held": held}


def pattern(config: Dict[str, Any]) -> str:
    """Two characters a layer: its attention, then its feed-forward."""
    layers, dense = config["num_hidden_layers"], config["first_k_dense_replace"]
    if not 0 <= dense <= layers:
        raise cells.CellError(f"first_k_dense_replace {dense} of {layers} layers")
    return "*D" * dense + "*E" * (layers - dense)


def model_config(config: Dict[str, Any], seq: int) -> Any:
    """Refuses what the program does not compute, by name."""
    import jax.numpy as jnp

    from torchft_tpu.models.llama import LlamaConfig

    if "mla" not in {f.name for f in dataclasses.fields(LlamaConfig)}:
        raise cells.CellError(
            "this program has no latent attention (LlamaConfig.mla) and no "
            "prediction module: it cannot train a joyai_llm_flash configuration"
        )
    from torchft_tpu.models.mla import MLAConfig

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
    heads, rope = config["num_attention_heads"], config["qk_rope_head_dim"]
    if config["num_key_value_heads"] != heads:
        raise cells.CellError(
            "num_key_value_heads: latent attention expands a key and a value "
            "for every head"
        )
    if config["qk_head_dim"] != config["qk_nope_head_dim"] + rope or config["head_dim"] != rope:
        raise cells.CellError(
            "qk_head_dim is the rope-free and the rotary part together, "
            "head_dim the rotary part"
        )
    where = layout(config)
    if config["num_experts_per_tok"] > where["experts"]:
        raise cells.CellError("num_experts_per_tok exceeds the router's width")
    if config["num_nextn_predict_layers"] < 0:
        raise cells.CellError("num_nextn_predict_layers counts the prediction modules")
    run = config["run"]
    return LlamaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["moe_intermediate_size"],
        dense_intermediate_size=config["intermediate_size"],
        num_layers=config["num_hidden_layers"],
        layer_pattern=pattern(config),
        num_heads=heads,
        num_kv_heads=heads,
        head_dim=rope,
        max_seq_len=seq,
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=False,
        mla=MLAConfig(
            q_lora_rank=config["q_lora_rank"],
            kv_lora_rank=config["kv_lora_rank"],
            qk_nope_head_dim=config["qk_nope_head_dim"],
            qk_rope_head_dim=rope,
            v_head_dim=config["v_head_dim"],
        ),
        num_experts=where["experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        experts_held=(where["first"], where["held"]),
        expert_capacity_factor=None,  # dropless
        router_score="sigmoid",
        routed_scaling=float(config["routed_scaling_factor"]),
        gate_eps=1e-20,
        expert_act="swiglu",
        shared_expert_size=config["n_shared_experts"] * config["moe_intermediate_size"],
        router_aux_coef=0.0,  # noaux_tc
        router_z_coef=0.0,
        router_bias_update_rate=float(config["router_bias_update_rate"]),
        mtp_layers=config["num_nextn_predict_layers"],
        mtp_loss_coef=float(config["mtp_loss_coef"]),
        attn_impl=run["attn_impl"],
        dtype=jnp.dtype(run["compute_dtype"]),
        param_dtype=jnp.dtype(run["param_dtype"]),
    )
