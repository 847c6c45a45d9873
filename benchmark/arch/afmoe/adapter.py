"""A configuration file of an AFMoE decoder (the keys of the published
config.json of arcee-ai/Trinity-Mini, model_type "afmoe") as the program's
model configuration. Every published layer is an attention and a
feed-forward, each between a norm before AND a norm after it
(``LlamaConfig.norm_after_mixer = "both"``) and its own residual add; the
program's stack spells a layer as two characters of its ``layer_pattern``:
'W' where ``layer_types`` says ``sliding_attention`` (a window of
``sliding_window`` positions, rotary) or '*' where it says
``full_attention`` (global, WITHOUT rotary embedding), both with per-head
QK norms and an elementwise sigmoid output gate; then 'D' a dense SwiGLU
feed-forward (the first ``num_dense_layers`` layers) or 'E' an expert layer
(sigmoid scores plus a selection bias choose ``num_experts_per_tok``, the
scores without it weigh, renormalised and times ``route_scale``; SiLU-gated
experts; one shared expert). The embedded rows are multiplied by
sqrt(hidden_size) where ``mup_enabled``; the head is untied.

The file describes one chip of a deployment: ``num_experts`` is the number
of experts HELD here, ``expert_parallel_chips`` over how many chips a
layer's experts lie (the router's width is their product) and
``expert_parallel_index`` which of them this chip is;
``vocab_parallel_chips`` says over how many the vocabulary lies, the file's
``vocab_size`` being this chip's slice. ``layer_types`` and
``num_dense_layers`` are those of the layers run here: a layer's kind is
its ENTRY of ``layer_types``, not its index modulo
``global_attn_every_n_layers`` (a kept run need not start at a period's
first layer). cells.py says what an adapter provides.

The reference check's sample (``sample_config``) is shorter than the
published window, and a sliding layer of the published model is then a
causal one: the comparison would never see the band. So a sample no longer
than the window is compared under a window of half its length through the
banded kernels at the smallest tile the chip's compiler takes (1,024
tokens: a window of 512 at tiles of 128, a query tile's sweep five key
tiles, as the timed shape's is at 2,048 under the tiles of 512 the band's
rule takes);
``reference.py`` states the same rule for its side.

The parent loads this file, and it is where a program that cannot train
the configuration is refused: at once, before JAX or the program is
imported and before any chip is asked for (``_program_has_sandwich``). JAX
and the program are imported inside the functions only.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Dict

from benchmark import cells

# What the program computes, by key: any other value is refused by name.
REQUIRED = {
    "model_type": "afmoe",
    "hidden_act": "silu",
    "rope_scaling": None,
    "n_group": 1,
    "topk_group": 1,
    "num_expert_groups": 1,
    "num_limited_groups": 1,
    "num_shared_experts": 1,
    "score_func": "sigmoid",
    "route_norm": True,
    "tie_word_embeddings": False,
}
USED = frozenset({
    "num_hidden_layers", "layer_types", "global_attn_every_n_layers",
    "num_dense_layers", "hidden_size", "intermediate_size",
    "moe_intermediate_size", "vocab_size", "num_attention_heads",
    "num_key_value_heads", "head_dim", "max_position_embeddings",
    "rms_norm_eps", "rope_theta", "sliding_window", "num_experts",
    "num_experts_per_tok", "route_scale", "mup_enabled",
    # The rate of the step's selection-bias move (`assumed` in the file).
    "load_balance_coeff",
    # How the model's own code multiplies its experts (one grouped matmul
    # over the sorted rows, which is what the program's held dispatch does):
    # either value is the same mathematics.
    "use_grouped_mm",
    # The deployment's layout (`assumed` in the file).
    "expert_parallel_chips", "expert_parallel_index", "vocab_parallel_chips",
})
KEYS = USED | frozenset(REQUIRED)
LAYER_KINDS = {"sliding_attention": "W", "full_attention": "*"}
# A sample no longer than the window keeps this share of itself: four of its
# tiles at 1,024 tokens, as the published window is four of the timed tiles.
SAMPLE_WINDOW_SHARE = 2
# The largest tile of such a sample: the smallest the chip's compiler takes
# (the row residuals' blocks are whole lane tiles), so that the band is
# tiles wide and the sweeps skip tiles as the timed step's do.
SAMPLE_TILE = 128


def _program_has_sandwich() -> bool:
    """Whether this checkout's program has the norm before AND after a
    sub-layer and the scale on the embedded rows, read from its source:
    importing ``torchft_tpu.models`` imports JAX."""
    path = os.path.join(cells.ROOT, "torchft_tpu", "models", "llama.py")
    try:
        with open(path) as f:
            source = f.read()
    except OSError:
        return False
    return '"post_norm"' in source and "embed_scale" in source


if not _program_has_sandwich():
    raise cells.CellError(
        "this program has no norm before AND after a sub-layer "
        "(torchft_tpu/models/llama.py: norm_after_mixer = 'both', the norm "
        "'post_norm') and no scale on the embedded rows (embed_scale): it "
        "cannot train an afmoe configuration"
    )


def layout(config: Dict[str, Any]) -> Dict[str, int]:
    """The router's width and the experts held, from the deployment keys."""
    held, chips = config["num_experts"], config["expert_parallel_chips"]
    index = config["expert_parallel_index"]
    if chips < 1 or not 0 <= index < chips:
        raise cells.CellError(f"expert_parallel_index {index} of {chips} chips")
    return {"experts": held * chips, "first": index * held, "held": held}


def pattern(config: Dict[str, Any]) -> str:
    """Two characters a published layer: its attention, its feed-forward."""
    kinds, dense = config["layer_types"], config["num_dense_layers"]
    period = config["global_attn_every_n_layers"]
    unknown = sorted(set(kinds) - set(LAYER_KINDS))
    if unknown or len(kinds) != config["num_hidden_layers"]:
        raise cells.CellError(
            f"layer_types {kinds!r}: {config['num_hidden_layers']} of "
            "'sliding_attention' and 'full_attention' are what the stack is "
            "built from"
        )
    if period < 1 or any(k != kinds[i % period] for i, k in enumerate(kinds)):
        raise cells.CellError(
            f"layer_types {kinds!r} does not repeat with period "
            f"global_attn_every_n_layers = {period}"
        )
    if kinds[:period].count("full_attention") > 1:
        raise cells.CellError(
            f"layer_types {kinds!r}: more than one full_attention in a period "
            f"of global_attn_every_n_layers = {period}"
        )
    if not 0 <= dense <= len(kinds):
        raise cells.CellError(f"num_dense_layers {dense} of {len(kinds)} layers")
    return "".join(
        LAYER_KINDS[kind] + ("D" if i < dense else "E") for i, kind in enumerate(kinds)
    )


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
    if config["sliding_window"] < 1:
        raise cells.CellError("sliding_window counts the keys a row keeps")
    if config["vocab_parallel_chips"] < 1:
        raise cells.CellError("vocab_parallel_chips counts the chips the vocabulary lies over")
    if config["num_attention_heads"] % config["num_key_value_heads"]:
        raise cells.CellError("num_key_value_heads must divide num_attention_heads")
    if config["num_experts_per_tok"] > layout(config)["experts"]:
        raise cells.CellError("num_experts_per_tok exceeds the router's width")
    if not isinstance(config["use_grouped_mm"], bool):
        raise cells.CellError("use_grouped_mm says how the experts are multiplied: true or false")
    if not config["load_balance_coeff"] >= 0:
        raise cells.CellError("load_balance_coeff is the selection bias's rate, not negative")


def model_config(config: Dict[str, Any], seq: int) -> Any:
    import jax.numpy as jnp

    from torchft_tpu.models.llama import LlamaConfig

    check(config, seq)
    where, run = layout(config), config["run"]
    return LlamaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["moe_intermediate_size"],
        dense_intermediate_size=config["intermediate_size"],
        num_layers=config["num_hidden_layers"],
        layer_pattern=pattern(config),
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        max_seq_len=seq,
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=False,
        embed_scale=float(config["hidden_size"]) ** 0.5 if config["mup_enabled"] else 1.0,
        rope=False,  # the global kind's; the windowed kind carries its own
        sliding_window=config["sliding_window"],
        qk_norm="head",
        attn_gate=True,
        norm_after_mixer="both",
        num_experts=where["experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        experts_held=(where["first"], where["held"]),
        expert_capacity_factor=None,  # dropless
        router_score="sigmoid",
        routed_scaling=float(config["route_scale"]),
        gate_eps=1e-20,
        expert_act="swiglu",
        shared_expert_size=config["moe_intermediate_size"] * config["num_shared_experts"],
        router_aux_coef=0.0,  # no balance term in the loss: the bias balances
        router_z_coef=0.0,
        router_bias_update_rate=float(config["load_balance_coeff"]),
        attn_impl=run["attn_impl"],
        dtype=jnp.dtype(run["compute_dtype"]),
        param_dtype=jnp.dtype(run["param_dtype"]),
    )


def sample_config(cfg: Any, seq: int) -> Any:
    """``cfg`` for the reference check's sample of ``seq`` tokens. A sample
    no longer than the window: a window of ``seq // SAMPLE_WINDOW_SHARE``
    under tiles of ``SAMPLE_TILE`` at most, so that the banded kernels, the
    band's edge and a sweep that skips tiles are inside what is compared
    (1,024 tokens: a window of 512, 30 of the causal 36 tiles of 128, five
    key tiles a query tile). A longer one (the builder's comparison at the
    cell's own length): the cell's own window and tiles. Either way the
    kernels are taken wherever the cell takes them, also below the length
    from which the program prefers them."""
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
