"""A configuration file of a SmallThinker mixture-of-experts decoder (the
keys of the published config.json of PowerInfer/SmallThinker-21BA3B-Instruct;
arXiv:2507.20984) as the program's model configuration. Every published
layer is an attention and an expert layer, each between a pre-norm and its
own residual add: the program's stack spells a layer as two characters of
its ``layer_pattern``, '*' where ``sliding_window_layout`` and
``rope_layout`` say 0 (a global grouped-query attention WITHOUT rotary
embedding) and 'W' where they say 1 (a sliding window of
``sliding_window_size`` positions, with it), then 'E' (the top
``moe_num_active_primary_experts`` of the router's logits, a softmax over
those, three-matrix ReGLU experts, no shared expert). The router of a layer
reads the layer's normed input BEFORE its attention
(``LlamaConfig.router_ahead``); the head is untied.

The file describes one chip of a deployment: ``moe_num_primary_experts`` is
the number of experts HELD here, ``expert_parallel_chips`` over how many
chips a layer's experts lie (the router's width is their product) and
``expert_parallel_index`` which of them this chip is;
``vocab_parallel_chips`` says over how many the vocabulary lies, the file's
``vocab_size`` being this chip's slice. What the published file does not
give is the file's own, under ``assumed``: ``router_aux_loss_coef`` and
``embedding_init_std``. cells.py says what an adapter provides.

The reference check's sample (``sample_config``) is shorter than the
published window, and a windowed layer of the published model is then a
causal one: the comparison would never see the band. So a sample no longer
than the window is compared under a window of a quarter of its length (the
published proportion, 4,096 of 16,384) through the banded kernels at the
smallest tile the chip's compiler takes; ``reference.py`` states the same
rule for its side.

The parent loads this file, and it is where a program that cannot train
the configuration is refused: at once, before JAX or the program is
imported and before any chip is asked for (``_program_has_window``). JAX
and the program are imported inside the functions only.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Dict

from benchmark import cells

# What the program computes, by key: any other value is refused by name.
REQUIRED = {
    "model_name": "smallthinker_21b_instruct",
    "moe_primary_router_apply_softmax": True,
    "norm_topk_prob": True,
    "rope_scaling": None,
    "tie_word_embeddings": False,
}
USED = frozenset({
    "num_hidden_layers", "hidden_size", "moe_ffn_hidden_size", "vocab_size",
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "max_position_embeddings", "rms_norm_eps", "rope_theta", "rope_layout",
    "sliding_window_layout", "sliding_window_size", "moe_num_primary_experts",
    "moe_num_active_primary_experts",
    # The deployment's layout and what the published file leaves open
    # (`assumed` in the file).
    "expert_parallel_chips", "expert_parallel_index", "vocab_parallel_chips",
    "router_aux_loss_coef", "embedding_init_std",
})
KEYS = USED | frozenset(REQUIRED)
PERIOD = 4  # one global layer, then three windowed ones
# A sample no longer than the window keeps this share of itself: the
# published window's share of the published context.
SAMPLE_WINDOW_SHARE = 4
# The largest tile of such a sample: the smallest the chip's compiler takes
# (the row residuals' blocks are whole lane tiles), so that the band is
# several tiles wide and the sweeps skip tiles as the timed step's do.
SAMPLE_TILE = 128


def _program_has_window() -> bool:
    """Whether this checkout's program has the banded flash family, read
    from its source: importing ``torchft_tpu.ops`` imports JAX."""
    path = os.path.join(cells.ROOT, "torchft_tpu", "ops", "flash_attention.py")
    try:
        with open(path) as f:
            return "def flash_attention_window" in f.read()
    except OSError:
        return False


if not _program_has_window():
    raise cells.CellError(
        "this program has no sliding-window attention (torchft_tpu/ops/"
        "flash_attention.py: flash_attention_window; models/llama.py: the "
        "layer kind 'W') and no router ahead of the attention: it cannot "
        "train a smallthinker configuration"
    )


def layout(config: Dict[str, Any]) -> Dict[str, int]:
    """The router's width and the experts held, from the deployment keys."""
    held, chips = config["moe_num_primary_experts"], config["expert_parallel_chips"]
    index = config["expert_parallel_index"]
    if chips < 1 or not 0 <= index < chips:
        raise cells.CellError(f"expert_parallel_index {index} of {chips} chips")
    return {"experts": held * chips, "first": index * held, "held": held}


def pattern(config: Dict[str, Any]) -> str:
    """Two characters a published layer: its attention, its expert layer."""
    layers = config["num_hidden_layers"]
    window, rope = config["sliding_window_layout"], config["rope_layout"]
    if window != rope:
        raise cells.CellError(
            f"sliding_window_layout {window!r} and rope_layout {rope!r} disagree: "
            "a windowed layer is rotary and a global one is not, and the "
            "program has no other pairing"
        )
    if len(window) != layers or any(v not in (0, 1) for v in window):
        raise cells.CellError(
            f"sliding_window_layout {window!r}: one 0 or 1 for each of the "
            f"{layers} layers"
        )
    if any(v != window[i % PERIOD] for i, v in enumerate(window)):
        raise cells.CellError(
            f"sliding_window_layout {window!r} does not repeat with period {PERIOD}"
        )
    return "".join(("W" if v else "*") + "E" for v in window)


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
    if seq > config["max_position_embeddings"]:
        raise cells.CellError(f"sequence {seq} exceeds max_position_embeddings")
    if config["sliding_window_size"] < 1:
        raise cells.CellError("sliding_window_size counts the keys a row keeps")
    if not config["embedding_init_std"] > 0:
        raise cells.CellError("embedding_init_std is a standard deviation")
    if config["vocab_parallel_chips"] < 1:
        raise cells.CellError("vocab_parallel_chips counts the chips the vocabulary lies over")
    if config["num_attention_heads"] % config["num_key_value_heads"]:
        raise cells.CellError("num_key_value_heads must divide num_attention_heads")
    if config["moe_num_active_primary_experts"] > layout(config)["experts"]:
        raise cells.CellError(
            "moe_num_active_primary_experts exceeds the router's width"
        )


def model_config(config: Dict[str, Any], seq: int) -> Any:
    import jax.numpy as jnp

    from torchft_tpu.models.llama import LlamaConfig

    check(config, seq)
    where, run = layout(config), config["run"]
    return LlamaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["moe_ffn_hidden_size"],
        num_layers=config["num_hidden_layers"],
        layer_pattern=pattern(config),
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        max_seq_len=seq,
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=False,
        embed_init_std=float(config["embedding_init_std"]),
        rope=False,  # the global kind's; the windowed kind carries its own
        sliding_window=config["sliding_window_size"],
        router_ahead=True,
        num_experts=where["experts"],
        num_experts_per_tok=config["moe_num_active_primary_experts"],
        experts_held=(where["first"], where["held"]),
        expert_capacity_factor=None,  # dropless
        norm_topk_prob=True,
        expert_act="reglu",
        router_aux_coef=float(config["router_aux_loss_coef"]),
        router_z_coef=0.0,
        attn_impl=run["attn_impl"],
        dtype=jnp.dtype(run["compute_dtype"]),
        param_dtype=jnp.dtype(run["param_dtype"]),
    )


def sample_config(cfg: Any, seq: int) -> Any:
    """``cfg`` for the reference check's sample of ``seq`` tokens. A sample
    no longer than the window: a window of ``seq // SAMPLE_WINDOW_SHARE``
    under tiles of ``SAMPLE_TILE`` at most, so that the banded kernels, the
    band's edge and a sweep that skips tiles are inside what is compared
    (1,024 tokens: a window of 256, 21 of the causal 36 tiles of 128). A
    longer one (the builder's comparison at the cell's own length): the
    cell's own window and tiles. Either way the kernels are taken wherever
    the cell takes them, also below the length from which the program
    prefers them."""
    from torchft_tpu.ops.flash_attention import supports_window

    if seq <= cfg.sliding_window:
        cfg = dataclasses.replace(
            cfg, sliding_window=max(1, seq // SAMPLE_WINDOW_SHARE),
            flash_block_q=SAMPLE_TILE, flash_block_k=SAMPLE_TILE,
        )
    if cfg.attn_impl == "flash" and supports_window(
        seq, cfg.sliding_window, cfg.flash_block_q, cfg.flash_block_k
    ):
        return dataclasses.replace(cfg, flash_min_seq=min(cfg.flash_min_seq, seq))
    return cfg
