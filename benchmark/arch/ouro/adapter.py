"""A configuration file of Ouro's looped language model (the keys of the
published config.json of ByteDance/Ouro-2.6B, model_type "ouro") as the
program's model configuration. Every published layer is a rotary
multi-head attention and a SwiGLU feed-forward, each between a norm before
AND a norm after it (``LlamaConfig.norm_after_mixer = "both"``) and its own
residual add: two characters of the program's ``layer_pattern``, "*D". The
WHOLE stack is applied ``total_ut_steps`` times on the same weights with
the final norm inside the loop (``LlamaConfig.loop_steps``), an exit gate
on every step's normed states turns the steps into a distribution over
depths, and the training loss is the expectation of the steps'
cross-entropies under it less ``loop_entropy_coef`` times its entropy
(``LlamaConfig.loop_entropy_coef``; a key of this repository's file, no key
of config.json: ``assumed`` there). The head is untied. cells.py says what
an adapter provides.

The parent loads this file, and it is where a program that cannot train
the configuration is refused: at once, before JAX or the program is
imported and before any chip is asked for (``_program_has_loop``). JAX
and the program are imported inside the functions only.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Dict

from benchmark import cells

# What the program computes, by key: any other value is refused by name.
REQUIRED = {
    "model_type": "ouro",
    "hidden_act": "silu",
    "rope_scaling": None,
    "use_sliding_window": False,
    "tie_word_embeddings": False,
}
USED = frozenset({
    "num_hidden_layers", "layer_types", "hidden_size", "intermediate_size",
    "vocab_size", "num_attention_heads", "num_key_value_heads", "head_dim",
    "max_position_embeddings", "rms_norm_eps", "rope_theta",
    "total_ut_steps", "early_exit_threshold",
    # A window's keys (``use_sliding_window`` false makes both say nothing).
    "sliding_window", "max_window_layers",
    # beta of the loss (`assumed` in the file).
    "loop_entropy_coef",
})
KEYS = USED | frozenset(REQUIRED)


def _program_has_loop() -> bool:
    """Whether this checkout's program can apply a stack more than once,
    read from its source: importing ``torchft_tpu.models`` imports JAX."""
    path = os.path.join(cells.ROOT, "torchft_tpu", "models", "llama.py")
    try:
        with open(path) as f:
            source = f.read()
    except OSError:
        return False
    return "loop_steps" in source and '"exit_gate"' in source


if not _program_has_loop():
    raise cells.CellError(
        "this program applies a layer's weights once a forward pass "
        "(torchft_tpu/models/llama.py has no loop_steps and no exit_gate): "
        "it cannot train an ouro configuration"
    )


def check(config: Dict[str, Any], seq: int) -> None:
    """Refuses what the program's looped stack does not compute, by name."""
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
    # ``use_sliding_window`` false (required above) makes the window's two
    # keys say nothing of the model; they are held to what such keys can be.
    window, windowed_from = config["sliding_window"], config["max_window_layers"]
    if (window is not None and window < 1) or windowed_from < 0:
        raise cells.CellError(
            f"sliding_window = {window!r}, max_window_layers = {windowed_from!r}: a "
            "window counts positions and a layer index is not negative"
        )
    kinds = config["layer_types"]
    if len(kinds) != config["num_hidden_layers"] or set(kinds) - {"full_attention"}:
        raise cells.CellError(
            f"layer_types {kinds!r}: {config['num_hidden_layers']} entries of "
            "'full_attention' are what the looped stack is built from"
        )
    if config["early_exit_threshold"] < 1:
        raise cells.CellError(
            f"early_exit_threshold = {config['early_exit_threshold']!r}: a token "
            "that leaves the loop early is not computed; every token runs "
            "total_ut_steps steps (threshold 1)"
        )
    if config["total_ut_steps"] < 2:
        raise cells.CellError(
            "total_ut_steps counts the loop's steps: at least 2 (one step has "
            "no exit gate and is a plain decoder)"
        )
    if seq > config["max_position_embeddings"]:
        raise cells.CellError(f"sequence {seq} exceeds max_position_embeddings")
    if config["num_attention_heads"] % config["num_key_value_heads"]:
        raise cells.CellError("num_key_value_heads must divide num_attention_heads")
    if not config["loop_entropy_coef"] >= 0:
        raise cells.CellError("loop_entropy_coef is the entropy term's weight, not negative")


def model_config(config: Dict[str, Any], seq: int) -> Any:
    import jax.numpy as jnp

    from torchft_tpu.models.llama import LlamaConfig

    check(config, seq)
    run = config["run"]
    return LlamaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_layers=config["num_hidden_layers"],
        layer_pattern="*D" * config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        max_seq_len=seq,
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=False,
        norm_after_mixer="both",
        embed_init_std=1.0,
        loop_steps=config["total_ut_steps"],
        loop_entropy_coef=float(config["loop_entropy_coef"]),
        attn_impl=run["attn_impl"],
        dtype=jnp.dtype(run["compute_dtype"]),
        param_dtype=jnp.dtype(run["param_dtype"]),
    )


def sample_config(cfg: Any, seq: int) -> Any:
    """``cfg`` for the reference check's sample of ``seq`` tokens: the
    flash kernel is taken wherever the cell takes it, also where the
    sample is shorter than the length from which the program prefers it."""
    from torchft_tpu.ops.flash_attention import supports

    if cfg.attn_impl == "flash" and supports(
        seq, cfg.flash_block_q, cfg.flash_block_k
    ):
        return dataclasses.replace(cfg, flash_min_seq=min(cfg.flash_min_seq, seq))
    return cfg
