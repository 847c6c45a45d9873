"""A configuration file of OLMoE's decoder (arXiv:2409.02060; the keys of
the published config.json of model_type "olmoe") as the program's model
configuration: the dense decoder's block with an RMSNorm over the whole
query and the whole key projection, and in place of the MLP a softmax
router over ``num_experts`` SwiGLU experts of width ``intermediate_size``,
``num_experts_per_tok`` of them a token, none dropped, the gates not
renormalised; a load-balancing term and a router z-loss in the loss.
Builds on ``dense_decoder``'s adapter for the keys the two share.
cells.py says what an adapter provides. The parent loads this file: JAX
and the program are imported inside the functions.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

from benchmark import cells

_dense = cells.arch_module("dense_decoder", "adapter")
# What the published config.json adds to the dense decoder's keys, and the
# two loss coefficients (the paper's; the file says so under `assumed`).
OWN_KEYS = frozenset({
    "model_type", "attention_bias", "clip_qkv", "rope_scaling",
    "norm_topk_prob", "num_experts", "num_experts_per_tok",
    "router_aux_loss_coef", "router_z_loss_coef",
})
KEYS = _dense.KEYS | OWN_KEYS
sample_config = _dense.sample_config


def model_config(config: Dict[str, Any], seq: int) -> Any:
    """Refuses what the program's dropless expert layer and full-width
    QK-norm do not compute, by name."""
    missing = sorted(k for k in OWN_KEYS if k not in config)
    if missing:
        raise cells.CellError(
            f"not a configuration of this architecture: it lacks {missing}"
        )
    if config["model_type"] != "olmoe":
        raise cells.CellError(f"model_type {config['model_type']!r} is not 'olmoe'")
    for key in ("attention_bias", "clip_qkv", "rope_scaling", "norm_topk_prob"):
        if config[key]:
            raise cells.CellError(
                f"{key} = {config[key]!r}: the program computes no biases, no "
                "clipping, plain rotary embeddings and gates that are not "
                "renormalised"
            )
    if config["num_experts_per_tok"] > config["num_experts"]:
        raise cells.CellError("more experts per token than experts")
    return dataclasses.replace(
        _dense.model_config(config, seq),
        qk_norm=True,
        num_experts=config["num_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        expert_capacity_factor=None,  # dropless
        router_aux_coef=float(config["router_aux_loss_coef"]),
        router_z_coef=float(config["router_z_loss_coef"]),
    )
