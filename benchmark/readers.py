"""What the per-layer metric files share: each reader in ``metrics/``
takes the run (worker.py builds it after a ``--trace 1`` window) and
returns one number, or None when there is nothing to read — the harness
then leaves the metric out of the line.

``run`` holds: ``cell``, ``records`` (one per window step: wall times,
the worker's spans by name in seconds, whether it committed, and under
``counters`` what the step's program counted beside its loss),
``journal`` (the program's journal events inside the window),
``trace`` (trace_reduce.Trace of the traced steps, or None),
``traced_steps``, ``programs_reloaded`` (programs traced again and fetched
from the compile cache inside the window), ``setup`` (the parts of the set-up in seconds),
``tok_s_chip``, ``window_s``, ``device_kind``, ``memory_stats`` (one
dict per device of the group) and ``peaks``.
"""

from __future__ import annotations

import statistics
from typing import Any, Dict, List, Optional


def span_median_ms(run: Dict[str, Any], name: str) -> Optional[float]:
    """Median over the window's steps of the worker span ``name``."""
    vals = [r["spans"][name] for r in run["records"] if name in r["spans"]]
    return statistics.median(vals) * 1e3 if vals else None


def counter_median(run: Dict[str, Any], name: str) -> Optional[float]:
    """Median over the window's steps of the step program's own counter
    ``name``; None where no step carries it (a trainer that hands none
    over, a model whose step does not count it)."""
    vals = [r["counters"][name] for r in run["records"]
            if name in r.get("counters", {})]
    return statistics.median(vals) if vals else None


def journal_median_ms(run: Dict[str, Any], event: str) -> Optional[float]:
    """Median of ``attrs.elapsed_s`` over the window's events of a kind."""
    vals = [
        e["attrs"]["elapsed_s"] for e in run["journal"]
        if e.get("event") == event and "elapsed_s" in e.get("attrs", {})
    ]
    return statistics.median(vals) * 1e3 if vals else None


def host_annotation_p50_ms(run: Dict[str, Any], name: str) -> Optional[float]:
    """Median duration of the program's own trace span ``name`` over the
    traced steps (the program's histogram only has power-of-two buckets)."""
    trace = run["trace"]
    vals: List[float] = trace.host.get(name, []) if trace else []
    return statistics.median(vals) * 1e3 if vals else None


def kernel_ms_per_step(run: Dict[str, Any], pattern: str) -> Optional[float]:
    """Device self time per traced step of the operations matching
    ``pattern`` in the trace."""
    trace = run["trace"]
    if trace is None or not run["traced_steps"]:
        return None
    secs = trace.ops_matching(pattern)
    return secs * 1e3 / run["traced_steps"] if secs > 0 else None


def kernel_work(run: Dict[str, Any], name: str, *args: Any) -> Optional[float]:
    """``name(config, *args)`` of the flops.py of the cell's architecture:
    the operations or bytes a kernel's step requires, from shapes. None
    where the architecture has no such kernel."""
    cell = run["cell"]
    fn = getattr(cell.flops, name, None)
    return None if fn is None else fn(cell.config, *args)


def peak(run: Dict[str, Any], key: str) -> float:
    """The published peak of the device the run was on; a device that is
    not in peaks.json is an error, never a default."""
    kind = run["device_kind"]
    if kind not in run["peaks"]:
        raise KeyError(f"no published peaks for device kind {kind!r} in peaks.json")
    return float(run["peaks"][kind][key])
