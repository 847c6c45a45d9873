"""A configuration file of Keye-VL-2.0's language model (the keys of the
published config.json of model_type "KeyeVL2", which are Qwen3-MoE's plus
``rope_scaling.mrope_section`` and ``sa_config``) as the program's model
configuration. Every published layer is a rotary grouped-query attention
with per-head QK norms whose keys an indexer SELECTS (``sa_config``:
DeepSeek Sparse Attention; ``LlamaConfig.sparse_topk``) and an expert
layer, each between a pre-norm and its own residual add: the program's
stack spells a layer as two characters of its ``layer_pattern``, "*E" (a
softmax router over all the experts, the chosen gates divided by their
sum, three-matrix SiLU-gated experts, no shared expert); the head is
untied. The vision tower is no part of the file (the catalog's row gives
no key of it); what it asks of the language model, positions of three ids
a token, is (``LlamaConfig.mrope_section``).

The file describes one chip of a deployment: ``num_experts`` is the
number of experts HELD here, ``expert_parallel_chips`` over how many chips
a layer's experts lie (the router's width, the published
``num_local_experts``, is their product) and ``expert_parallel_index``
which of them this chip is; ``vocab_parallel_chips`` says over how many
the vocabulary lies, the file's ``vocab_size`` being this chip's slice.
What the published file does not give is the file's own, under
``assumed``: ``router_aux_loss_coef``, ``indexer_loss_coef``,
``embedding_init_std`` and the indexer's equations. cells.py says what an adapter provides.

The reference check's sample (``sample_config``) is shorter than the
published ``topk``, and every query of the published model then keeps every
earlier key: the comparison would never see the selection. So a sample no
longer than ``topk`` is compared under a ``topk`` of a quarter of its
length through the selected kernels at the smallest tile the chip's
compiler takes; ``reference.py`` states the same rule for its side.

The parent loads this file, and it is where a program that cannot train
the configuration is refused: at once, before JAX or the program is
imported and before any chip is asked for (``_program_has_selection``).
JAX and the program are imported inside the functions only.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Dict

from benchmark import cells

# What the program computes, by key: any other value is refused by name.
REQUIRED = {
    "model_type": "KeyeVL2",
    "attention_bias": False,
    "hidden_act": "silu",
    "norm_topk_prob": True,
    "tie_word_embeddings": False,
    "decoder_sparse_step": 1,  # every layer an expert layer
    "mlp_only_layers": [],
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
    "max_position_embeddings", "rms_norm_eps", "rope_theta", "rope_scaling",
    "sa_config", "num_experts", "num_local_experts", "num_experts_per_tok",
    # The deployment's layout and what the published file leaves open
    # (`assumed` in the file).
    "expert_parallel_chips", "expert_parallel_index", "vocab_parallel_chips",
    "router_aux_loss_coef", "indexer_loss_coef", "embedding_init_std",
})
KEYS = USED | frozenset(REQUIRED)
SA_KEYS = frozenset({
    "indexer_head_dim", "indexer_num_heads", "indexer_num_kv_heads",
    "kv_chunk_size", "q_chunk_size", "topk",
})
ROPE_KEYS = frozenset({"mrope_section", "rope_type", "type"})
# The tile of the program's score pass (ops/sparse_index.py CHUNK): the
# published chunk sizes, read as that tile.
CHUNK = 512
# A sample no longer than ``topk`` keeps this share of itself a query.
SAMPLE_TOPK_SHARE = 4
# The largest tile of such a sample: the smallest the chip's compiler takes
# (the row residuals' blocks are whole lane tiles), so that the causal
# triangle is several tiles wide, as the timed step's is.
SAMPLE_TILE = 128


def _program_has_selection() -> bool:
    """Whether this checkout's program has the selected flash family and
    the indexer's passes, read from its source: importing
    ``torchft_tpu.ops`` imports JAX."""
    ops = os.path.join(cells.ROOT, "torchft_tpu", "ops")
    try:
        with open(os.path.join(ops, "flash_attention.py")) as f:
            return "def flash_attention_selected" in f.read() and os.path.exists(
                os.path.join(ops, "sparse_index.py")
            )
    except OSError:
        return False


if not _program_has_selection():
    raise cells.CellError(
        "this program has no learned sparse attention (torchft_tpu/ops/"
        "sparse_index.py; ops/flash_attention.py: flash_attention_selected; "
        "models/llama.py: LlamaConfig.sparse_topk): it cannot train a "
        "keye_vl2 configuration"
    )


# The router's width and the experts held, from the deployment keys: the
# layout keys are LFM2's file's, and so is their reading.
layout = cells.arch_module("lfm2_moe", "adapter").layout


def check(config: Dict[str, Any], seq: int) -> None:
    """Refuses what the program does not compute, by name."""
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
    if not config["embedding_init_std"] > 0:
        raise cells.CellError("embedding_init_std is a standard deviation")
    heads = config["num_attention_heads"]
    if heads % config["num_key_value_heads"]:
        raise cells.CellError("num_key_value_heads must divide num_attention_heads")
    where = layout(config)
    if where["experts"] != config["num_local_experts"]:
        raise cells.CellError(
            f"num_local_experts = {config['num_local_experts']}: the router's "
            f"width is num_experts x expert_parallel_chips = {where['experts']}"
        )
    if config["num_experts_per_tok"] > where["experts"]:
        raise cells.CellError("num_experts_per_tok exceeds the router's width")
    rope, sa = config["rope_scaling"], config["sa_config"]
    if not isinstance(rope, dict) or set(rope) != ROPE_KEYS:
        raise cells.CellError(f"rope_scaling {rope!r}: the keys {sorted(ROPE_KEYS)}")
    if rope["rope_type"] != "default" or rope["type"] != "default":
        raise cells.CellError(
            f"rope_scaling type {rope['type']!r}/{rope['rope_type']!r}: the "
            "program scales no frequency ('default' only)"
        )
    sections = rope["mrope_section"]
    if len(sections) != 3 or sum(sections) != config["head_dim"] // 2:
        raise cells.CellError(
            f"mrope_section {sections!r}: three sections that add up to the "
            f"head's {config['head_dim'] // 2} frequency pairs"
        )
    if not isinstance(sa, dict) or set(sa) != SA_KEYS:
        raise cells.CellError(f"sa_config {sa!r}: the keys {sorted(SA_KEYS)}")
    if sa["indexer_num_kv_heads"] != 1:
        raise cells.CellError(
            f"indexer_num_kv_heads = {sa['indexer_num_kv_heads']}: the program's "
            "indexer has one index key a position"
        )
    for key in ("q_chunk_size", "kv_chunk_size"):
        if sa[key] != CHUNK:
            raise cells.CellError(
                f"sa_config.{key} = {sa[key]}: the program's score pass works "
                f"in tiles of {CHUNK}"
            )
    if min(sa["topk"], sa["indexer_num_heads"], sa["indexer_head_dim"]) < 1:
        raise cells.CellError("sa_config: topk and the indexer's sizes count from 1")
    if sa["indexer_head_dim"] % 2:
        raise cells.CellError("indexer_head_dim is rotated in pairs")


def model_config(config: Dict[str, Any], seq: int) -> Any:
    import jax.numpy as jnp

    from torchft_tpu.models.llama import LlamaConfig

    from torchft_tpu.ops.flash_attention import supports_selected

    check(config, seq)
    where, run, sa = layout(config), config["run"], config["sa_config"]
    cfg = LlamaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["moe_intermediate_size"],
        num_layers=config["num_hidden_layers"],
        # Two characters a published layer: its attention, its experts.
        layer_pattern="*E" * config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        max_seq_len=seq,
        rope_theta=float(config["rope_theta"]),
        mrope_section=tuple(config["rope_scaling"]["mrope_section"]),
        norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=False,
        embed_init_std=float(config["embedding_init_std"]),
        qk_norm="head",
        sparse_topk=sa["topk"],
        indexer_heads=sa["indexer_num_heads"],
        indexer_head_dim=sa["indexer_head_dim"],
        indexer_loss_coef=float(config["indexer_loss_coef"]),
        num_experts=where["experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        experts_held=(where["first"], where["held"]),
        expert_capacity_factor=None,  # dropless
        norm_topk_prob=True,
        router_aux_coef=float(config["router_aux_loss_coef"]),
        router_z_coef=0.0,
        attn_impl=run["attn_impl"],
        dtype=jnp.dtype(run["compute_dtype"]),
        param_dtype=jnp.dtype(run["param_dtype"]),
    )
    if (
        cfg.attn_impl == "flash" and seq >= cfg.flash_min_seq
        and not supports_selected(seq, cfg.flash_block_q, cfg.flash_block_k)
    ):
        raise cells.CellError(
            f"a sequence of {seq} under topk = {sa['topk']}: the selected "
            "kernels' tiles cannot pack its selection (whole lane tiles of 128 "
            "columns), and dense attention under it would hold every head's "
            f"[{seq}, {seq}] scores"
        )
    return cfg


def sample_config(cfg: Any, seq: int) -> Any:
    """``cfg`` for the reference check's sample of ``seq`` tokens. A sample
    no longer than ``topk``: a ``topk`` of ``seq // SAMPLE_TOPK_SHARE``
    under tiles of ``SAMPLE_TILE`` at most, so that rows really select and
    the selected kernels, their packed mask and their table of tile pairs
    are inside what is compared (1,024 tokens: 256 keys a query, 36 causal
    tile pairs of 128). A longer one (the builder's comparison at the
    cell's own length): the cell's own ``topk`` and tiles. Either way the
    kernels are taken wherever the cell takes them, also below the length
    from which the program prefers them."""
    from torchft_tpu.ops.flash_attention import supports_selected

    if seq <= cfg.sparse_topk:
        cfg = dataclasses.replace(
            cfg, sparse_topk=max(1, seq // SAMPLE_TOPK_SHARE),
            flash_block_q=SAMPLE_TILE, flash_block_k=SAMPLE_TILE,
        )
    if cfg.attn_impl == "flash" and supports_selected(
        seq, cfg.flash_block_q, cfg.flash_block_k
    ):
        return dataclasses.replace(cfg, flash_min_seq=min(cfg.flash_min_seq, seq))
    return cfg
