"""A configuration file of an Olmo-Hybrid model (the keys of the published
config.json of model_type "olmo_hybrid"; Gated DeltaNet arXiv:2412.06464,
OLMo 2's layer arXiv:2501.00656) as the program's model configuration: a
stack read from ``layer_types``, each published layer a mixer and a SwiGLU
feed-forward with the RMSNorm AFTER each (x + norm(mixer(x))):
"linear_attention" a gated-delta linear attention ('G'), "full_attention"
a rope-free attention with a whole-projection QK-norm ('*'), 'D' the
feed-forward.

The file describes one chip of a deployment: the four head counts
(``num_attention_heads``, ``num_key_value_heads``, ``linear_num_key_heads``,
``linear_num_value_heads``) are the heads HELD here,
``head_parallel_chips`` over how many chips a layer's heads lie (the
published counts are their product) and ``head_parallel_index`` which of
them this chip is; ``vocab_parallel_chips`` says over how many the
vocabulary lies, the file's ``vocab_size`` being this chip's slice. No
width is a share: a head is ``hidden_size`` / (held x chips) wide.
cells.py says what an adapter provides. The parent loads this file: JAX
and the program are imported inside the functions.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark import cells

# What the program computes, by key: any other value is refused by name.
REQUIRED = {
    "model_type": "olmo_hybrid",
    "hidden_act": "silu",
    "attention_bias": False,
    "tie_word_embeddings": False,
    "rope_parameters": {"rope_theta": None},  # the full attention is rope-free
}
USED = frozenset({
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "max_position_embeddings",
    "rms_norm_eps", "layer_types",
    "linear_num_key_heads", "linear_num_value_heads", "linear_key_head_dim",
    "linear_value_head_dim", "linear_conv_kernel_dim", "linear_allow_neg_eigval",
    # The deployment's layout (`assumed`).
    "head_parallel_chips", "head_parallel_index", "vocab_parallel_chips",
})
KEYS = USED | frozenset(REQUIRED)
KINDS = {"linear_attention": "G", "full_attention": "*"}
sample_config = cells.arch_module("dense_decoder", "adapter").sample_config


def check(config: Dict[str, Any], seq: int) -> None:
    """Refuses what the program's stack does not compute, by name."""
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
    kinds = config["layer_types"]
    if len(kinds) != config["num_hidden_layers"] or set(kinds) - set(KINDS):
        raise cells.CellError(
            f"layer_types {kinds!r}: {config['num_hidden_layers']} layers of "
            f"{sorted(KINDS)} are what the stack is built from"
        )
    if seq > config["max_position_embeddings"]:
        raise cells.CellError(f"sequence {seq} exceeds max_position_embeddings")
    chips, index = config["head_parallel_chips"], config["head_parallel_index"]
    if chips < 1 or not 0 <= index < chips:
        raise cells.CellError(f"head_parallel_index {index} of {chips} chips")
    if config["hidden_size"] % (config["num_attention_heads"] * chips):
        raise cells.CellError(
            f"head_parallel_chips {chips} x {config['num_attention_heads']} held "
            f"heads do not divide hidden_size {config['hidden_size']} into heads"
        )
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise cells.CellError(
            "a held share of grouped key/value heads: the program is told one "
            "key/value head a query head"
        )
    if config["linear_num_value_heads"] != config["linear_num_key_heads"]:
        raise cells.CellError(
            "linear_num_value_heads != linear_num_key_heads: the mixer has one "
            "value head a key head"
        )
    if config["vocab_parallel_chips"] < 1:
        raise cells.CellError("vocab_parallel_chips counts the chips the vocabulary lies over")


def head_dim(config: Dict[str, Any]) -> int:
    """hidden_size over the PUBLISHED number of heads (held x chips)."""
    return config["hidden_size"] // (
        config["num_attention_heads"] * config["head_parallel_chips"]
    )


def pattern(config: Dict[str, Any]) -> str:
    """Two characters a published layer: its mixer, its feed-forward."""
    return "".join(KINDS[kind] + "D" for kind in config["layer_types"])


def model_config(config: Dict[str, Any], seq: int) -> Any:
    import jax.numpy as jnp

    from torchft_tpu.models.gated_delta import GatedDeltaConfig
    from torchft_tpu.models.llama import LlamaConfig

    check(config, seq)
    run = config["run"]
    return LlamaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_layers=config["num_hidden_layers"],
        layer_pattern=pattern(config),
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=head_dim(config),
        max_seq_len=seq,
        norm_eps=float(config["rms_norm_eps"]),
        qk_norm=True,
        rope=False,
        gated_delta=GatedDeltaConfig(
            num_heads=config["linear_num_key_heads"],
            key_head_dim=config["linear_key_head_dim"],
            value_head_dim=config["linear_value_head_dim"],
            conv_kernel=config["linear_conv_kernel_dim"],
            allow_neg_eigval=bool(config["linear_allow_neg_eigval"]),
        ),
        norm_after_mixer=True,
        attn_impl=run["attn_impl"],
        dtype=jnp.dtype(run["compute_dtype"]),
        param_dtype=jnp.dtype(run["param_dtype"]),
    )
