"""Finds a cell's files by the names in BENCHMARK.json.

Pure Python, no JAX: the parent process (run.py) imports this and must
never touch an accelerator. A later PR adds a configuration, a traffic
mix, a cell, a per-layer metric or an architecture as new files plus an
entry in BENCHMARK.json; nothing here, in run.py or in worker.py names
one.

The end-to-end metrics of every cell are run.py's (``join``), from the
groups' result files: ``setup_s``, the parent's start to the first
step of the earliest group's window; ``tok_s_chip``, the committed
tokens of all groups from the first window's start to the last one's
end, over the cell's chips; ``peak_hbm_gib``, the SMALLEST over the
cell's groups of the group's largest ``peak_bytes_in_use``. The smallest,
because an undonated step loop races the next gradient against the
update and a group's process-lifetime peak lands on one of three levels
by no rule (``join`` has the account); the largest over four groups
flipped between them and refused PRs that ran no code of the cell. The
largest and every group's reading stay on the line under ``device``.

An architecture is a directory of three files, ``arch/<name>/`` beside
the table or else under ``benchmark/``, named by the configuration
file's ``"arch"`` key (``DEFAULT_ARCH`` where the file has none):

- ``adapter.py``: ``KEYS``, every key of a configuration file it reads
  or checks; ``model_config(config, seq)``, the program's model
  configuration for sequences of ``seq`` under the file's ``run`` group
  (``build_model`` takes it; the traffic draws tokens below its
  ``vocab_size``), raising ``CellError`` for what the program does not
  compute; and ``sample_config(cfg, seq)``, the same for the reference
  check's shorter sample. The parent loads it: JAX and the program are
  imported inside its functions only.
- ``reference.py``: ``loss_and_grads(params, batch, config)`` in float32
  at the highest matmul precision, sharing no code with the program,
  and the check's tolerances ``GRAD_REL_L2_TOL`` and ``LOSS_REL_TOL``
  with the reason for each.
- ``flops.py``: ``model_flops_per_token(config, seq)``,
  ``total_params(config)`` and, for each kernel the architecture runs,
  the function of its operations or bytes that the kernel's roofline
  metric asks for by name (a metric reads None where there is none).
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import os
from types import ModuleType
from typing import Any, Dict, List

DEFAULT_ARCH = "dense_decoder"
# Keys of a configuration file that describe it and configure no model.
# Any other key has to be one its architecture's adapter reads: a file
# that says more than the adapter understands is another model, not this
# one with a key to spare.
DOC_KEYS = frozenset(
    {"arch", "source", "run", "reduced", "assumed", "stands_for", "distortions"}
)

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class CellError(Exception):
    """A cell that cannot be run as described; the run exits non-zero."""


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def find_file(table_path: str, *parts: str) -> str:
    """``parts`` joined beside the table (a test's own) if it is there,
    else under benchmark/; "" where neither has it."""
    dirs = [HERE]
    if table_path:
        dirs.insert(0, os.path.dirname(os.path.abspath(table_path)))
    return next(
        (p for d in dirs if os.path.exists(p := os.path.join(d, *parts))), ""
    )


def load_module(path: str) -> ModuleType:
    """The Python file at ``path``, run as a module of its own."""
    name = os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(f"benchmark_file_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def arch_module(name: str, part: str, table_path: str = "") -> ModuleType:
    """``adapter``, ``reference`` or ``flops`` of the architecture ``name``
    (an architecture that builds on another's files asks for them here)."""
    return load_module(os.path.join(find_file(table_path, "arch", name), part + ".py"))


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]  # the configuration file, as it is run
    mix: Dict[str, Any]  # the traffic file
    end_to_end: List[Dict[str, Any]]  # metric entries this cell reports
    per_layer: List[Dict[str, Any]]
    arch_dir: str  # the three files of the configuration's architecture

    @functools.cached_property
    def adapter(self) -> ModuleType:
        return load_module(os.path.join(self.arch_dir, "adapter.py"))

    @functools.cached_property
    def reference(self) -> ModuleType:
        return load_module(os.path.join(self.arch_dir, "reference.py"))

    @functools.cached_property
    def flops(self) -> ModuleType:
        return load_module(os.path.join(self.arch_dir, "flops.py"))


def _for_cell(metrics: List[Dict[str, Any]], name: str) -> List[Dict[str, Any]]:
    return [m for m in metrics if name in m.get("workloads", [name])]


def load_cell(name: str, table_path: str = "") -> Cell:
    """The cell ``name`` of the table at ``table_path`` (BENCHMARK.json at
    the root of the checkout by default; tests pass a table of their own,
    whose ``file`` and traffic paths are relative to the table)."""
    path = table_path or os.path.join(ROOT, "BENCHMARK.json")
    base = os.path.dirname(os.path.abspath(path))
    table = load_json(path)
    cells = {w["name"]: w for w in table["workloads"]}
    if name not in cells:
        raise CellError(f"no workload {name!r} in {path}: {sorted(cells)}")
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
    config = load_json(os.path.join(base, cfg_entry["file"]))
    arch = config.get("arch", DEFAULT_ARCH)
    arch_dir = find_file(table_path, "arch", arch)
    if not arch_dir:
        raise CellError(
            f"configuration {w['config']!r} names the architecture {arch!r}: "
            f"no directory arch/{arch}"
        )
    cell = Cell(
        name=name,
        chips=w["chips"],
        config=config,
        mix=mix,
        end_to_end=_for_cell(table["end_to_end"], name),
        per_layer=_for_cell(table["per_layer"], name),
        arch_dir=arch_dir,
    )
    unread = sorted(set(config) - DOC_KEYS - set(cell.adapter.KEYS))
    if unread:
        raise CellError(
            f"configuration {w['config']!r} has keys the {arch!r} adapter "
            f"does not read: {unread}. It would run as another model."
        )
    return cell
