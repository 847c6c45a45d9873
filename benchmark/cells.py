"""Finds a cell's data files by the names in BENCHMARK.json.

Pure Python, no JAX: the parent process (run.py) imports this and must
never touch an accelerator. A later PR adds a configuration, a traffic
mix, a cell or a per-layer metric as a new file plus an entry in
BENCHMARK.json; nothing here, in run.py or in worker.py names one.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class CellError(Exception):
    """A cell that cannot be run as described; the run exits non-zero."""


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]  # the configuration file, as it is run
    mix: Dict[str, Any]  # the traffic file
    end_to_end: List[Dict[str, Any]]  # metric entries this cell reports
    per_layer: List[Dict[str, Any]]


def _for_cell(metrics: List[Dict[str, Any]], name: str) -> List[Dict[str, Any]]:
    return [m for m in metrics if name in m.get("workloads", [name])]


def load_cell(name: str, table_path: str = "") -> Cell:
    """The cell ``name`` of the table at ``table_path`` (BENCHMARK.json at
    the root of the checkout by default; tests pass a table of their own,
    whose ``file`` and traffic paths are relative to the table)."""
    table_path = table_path or os.path.join(ROOT, "BENCHMARK.json")
    base = os.path.dirname(os.path.abspath(table_path))
    table = load_json(table_path)
    cells = {w["name"]: w for w in table["workloads"]}
    if name not in cells:
        raise CellError(f"no workload {name!r} in {table_path}: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in table["configs"]}
    cfg_entry = configs[w["config"]]
    traffic_dir = os.path.join(
        base, table.get("traffic_dir", os.path.join("benchmark", "traffic"))
    )
    mix_path = os.path.join(traffic_dir, w["traffic"] + ".json")
    mix = load_json(mix_path)
    need = mix["groups"] * mix["chips_per_group"]
    if need != w["chips"]:
        raise CellError(
            f"cell {name!r} asks for {w['chips']} chip(s) but its mix "
            f"{w['traffic']!r} places {mix['groups']} group(s) of "
            f"{mix['chips_per_group']}"
        )
    return Cell(
        name=name,
        chips=w["chips"],
        config=load_json(os.path.join(base, cfg_entry["file"])),
        mix=mix,
        end_to_end=_for_cell(table["end_to_end"], name),
        per_layer=_for_cell(table["per_layer"], name),
    )


def model_kwargs(config: Dict[str, Any], seq: int) -> Dict[str, Any]:
    """The configuration file's published keys (those of the model's own
    config.json) as keyword arguments of ``models.llama.LlamaConfig``.
    Refuses what the block does not compute."""
    window = config.get("sliding_window")
    if window is not None and seq > window:
        raise CellError(
            f"sequence {seq} exceeds the sliding window {window}: the block "
            "has no window mask, so this would not be the published model"
        )
    if seq > config["max_position_embeddings"]:
        raise CellError(f"sequence {seq} exceeds max_position_embeddings")
    if config.get("hidden_act", "silu") != "silu" or config.get("bias"):
        raise CellError("the block computes SwiGLU without biases only")
    heads = config["num_attention_heads"]
    return dict(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=heads,
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config.get("head_dim") or config["hidden_size"] // heads,
        max_seq_len=seq,
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        attn_impl=config["run"]["attn_impl"],
    )
