"""A configuration file of a Solar-Open2 model (the keys of the published
config.json of model_type "solar_open2"; Kimi Delta Attention
arXiv:2510.26692, whose ``linear_attn_config`` / ``kda_*`` keys the file
carries; the attention gate arXiv:2505.06708) as the program's model
configuration. Every published layer is a mixer and an expert layer, each
between a pre-norm and its own residual add: the program's stack spells a
layer as two characters of its ``layer_pattern``, '*' at the layers
``gqa_layers`` names (a rope-free grouped-query attention whose output is
gated, ``LlamaConfig.attn_gate``) and 'K' elsewhere (``LlamaConfig.kda``:
Kimi delta attention, a decay for every key channel), then 'E' (sigmoid
scores with a selection bias over all the experts, gates renormalised and
scaled, three-matrix SiLU-gated experts, the shared experts as one of
their summed width). The head is untied.

The file describes one chip of a deployment. ``num_attention_heads``,
``num_key_value_heads`` and ``linear_attn_config.num_heads`` are the heads
HELD here, ``head_parallel_chips`` over how many chips a layer's heads lie
(the published counts are their product) and ``head_parallel_index`` which
of them this chip is; ``n_routed_experts`` is the number of experts HELD,
``expert_parallel_chips`` over how many chips a layer's experts lie (the
router's width is their product) and ``expert_parallel_index`` which of
them this chip is; ``vocab_parallel_chips`` says over how many the
vocabulary lies, the file's ``vocab_size`` being this chip's slice. No
width is a share. cells.py says what an adapter provides.

The parent loads this file, and it is where a program that cannot train
the configuration is refused: at once, before JAX or the program is
imported and before any chip is asked for (``_program_has_kda``). JAX and
the program are imported inside the functions only.
"""

from __future__ import annotations

import os
from typing import Any, Dict

from benchmark import cells

# What the program computes, by key: any other value is refused by name.
REQUIRED = {
    "model_type": "solar_open2",
    "use_rope": False,  # the attention is rope-free; the delta layers carry the order
    "use_gqa_gate": True,
    "kda_use_full_proj": False,  # the decay's and the gate's projections are low-rank
    "first_k_dense_replace": 0,  # every layer has experts
    "norm_topk_prob": True,
    "tie_word_embeddings": False,
}
# Keys of the published file that configure nothing here: the rotary keys
# (``use_rope`` is false) and ``intermediate_size`` (the width of a dense
# feed-forward; with ``first_k_dense_replace`` 0 there is none).
UNUSED = frozenset({"partial_rotary_factor", "rope_theta", "intermediate_size"})
USED = frozenset({
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "moe_intermediate_size", "rms_norm_eps",
    "max_position_embeddings", "gqa_layers", "gqa_interval", "linear_attn_config",
    "kda_allow_neg_eigval", "n_routed_experts", "n_shared_experts",
    "routed_scaling_factor", "num_experts_per_tok",
    # The deployment's layout (`assumed`).
    "head_parallel_chips", "head_parallel_index", "expert_parallel_chips",
    "expert_parallel_index", "vocab_parallel_chips",
})
KEYS = USED | UNUSED | frozenset(REQUIRED)
LINEAR_KEYS = frozenset({"short_conv_kernel_size", "head_dim", "num_heads", "num_kv_heads"})
sample_config = cells.arch_module("dense_decoder", "adapter").sample_config


def _program_has_kda() -> bool:
    """Whether this checkout's program has the Kimi delta mixer, read from
    its source: importing ``torchft_tpu.models`` imports JAX."""
    path = os.path.join(cells.ROOT, "torchft_tpu", "models", "gated_delta.py")
    try:
        with open(path) as f:
            return "class KimiDeltaMixer" in f.read()
    except OSError:
        return False


if not _program_has_kda():
    raise cells.CellError(
        "this program has no Kimi delta attention (torchft_tpu/models/"
        "gated_delta.py: KimiDeltaMixer) and no gated attention: it cannot "
        "train a solar_open2 configuration"
    )


def layout(config: Dict[str, Any]) -> Dict[str, int]:
    """The router's width and the experts held, from the deployment keys."""
    held, chips = config["n_routed_experts"], config["expert_parallel_chips"]
    index = config["expert_parallel_index"]
    if chips < 1 or not 0 <= index < chips:
        raise cells.CellError(f"expert_parallel_index {index} of {chips} chips")
    return {"experts": held * chips, "first": index * held, "held": held}


def pattern(config: Dict[str, Any]) -> str:
    """Two characters a published layer: its mixer, its expert layer."""
    layers, gqa = config["num_hidden_layers"], config["gqa_layers"]
    if gqa != list(range(0, layers, config["gqa_interval"] + 1)):
        raise cells.CellError(
            f"gqa_layers {gqa!r}: an attention every gqa_interval + 1 = "
            f"{config['gqa_interval'] + 1} of {layers} layers, from layer 0, is "
            "what the stack is built from"
        )
    return "".join(("*" if i in gqa else "K") + "E" for i in range(layers))


def check(config: Dict[str, Any], seq: int) -> None:
    """Refuses what the program's stack does not compute, by name."""
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
    linear = config["linear_attn_config"]
    if set(linear) != LINEAR_KEYS:
        raise cells.CellError(
            f"linear_attn_config {sorted(linear)}: {sorted(LINEAR_KEYS)} are its keys"
        )
    if linear["num_kv_heads"] is not None:
        raise cells.CellError(
            "linear_attn_config.num_kv_heads: the mixer has one key head a value head"
        )
    if seq > config["max_position_embeddings"]:
        raise cells.CellError(f"sequence {seq} exceeds max_position_embeddings")
    chips, index = config["head_parallel_chips"], config["head_parallel_index"]
    if chips < 1 or not 0 <= index < chips:
        raise cells.CellError(f"head_parallel_index {index} of {chips} chips")
    if config["num_attention_heads"] % config["num_key_value_heads"]:
        raise cells.CellError(
            "num_key_value_heads does not divide num_attention_heads: a held "
            "key/value head is held with all the query heads that read it"
        )
    if config["vocab_parallel_chips"] < 1:
        raise cells.CellError("vocab_parallel_chips counts the chips the vocabulary lies over")
    if config["num_experts_per_tok"] > layout(config)["experts"]:
        raise cells.CellError("num_experts_per_tok exceeds the router's width")


def model_config(config: Dict[str, Any], seq: int) -> Any:
    import jax.numpy as jnp

    from torchft_tpu.models.gated_delta import KDAConfig
    from torchft_tpu.models.llama import LlamaConfig

    check(config, seq)
    where, linear, run = layout(config), config["linear_attn_config"], config["run"]
    return LlamaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["moe_intermediate_size"],
        num_layers=config["num_hidden_layers"],
        layer_pattern=pattern(config),
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        max_seq_len=seq,
        norm_eps=float(config["rms_norm_eps"]),
        rope=False,
        attn_gate=True,
        kda=KDAConfig(
            num_heads=linear["num_heads"],
            head_dim=linear["head_dim"],
            conv_kernel=linear["short_conv_kernel_size"],
            allow_neg_eigval=bool(config["kda_allow_neg_eigval"]),
        ),
        num_experts=where["experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        experts_held=(where["first"], where["held"]),
        expert_capacity_factor=None,  # dropless
        router_score="sigmoid",
        routed_scaling=float(config["routed_scaling_factor"]),
        expert_act="swiglu",
        shared_expert_size=config["n_shared_experts"] * config["moe_intermediate_size"],
        router_aux_coef=0.0,  # the selection bias balances, not a loss
        router_z_coef=0.0,
        router_bias_update_rate=0.0,  # the published file names no rate
        attn_impl=run["attn_impl"],
        dtype=jnp.dtype(run["compute_dtype"]),
        param_dtype=jnp.dtype(run["param_dtype"]),
    )
