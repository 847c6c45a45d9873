"""Reads the program's own span tree out of the journal.

At every commit gate the Manager journals ONE ``step_spans`` event: the
``trace_span``s closed since the last gate, ``[name, t0, t1, id, parent,
thread, attrs]`` each, times in seconds on the journal's clock
(``time.time()``). A metric file takes ``steps(run)`` and sums, unions or
takes medians over it; a program that journals no such event (every
commit before the one that added it) gives no steps, the readers below
then return None and the harness leaves the metric out of the line.
"""

from __future__ import annotations

import dataclasses
import statistics
from typing import Any, Callable, Dict, List, Optional

from benchmark.trace_reduce import union_seconds

ROOT = "torchft::ddp::allreduce_grads"


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    t0: float
    t1: float
    id: int
    parent: Optional[int]
    thread: int
    attrs: Dict[str, Any]

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def steps(run: Dict[str, Any]) -> List[List[Span]]:
    """The spans of each ``step_spans`` event inside the window, one list
    per step."""
    return [
        [Span(*s[:7]) for s in e["attrs"]["spans"]]
        for e in run["journal"]
        if e.get("event") == "step_spans"
    ]


def named(step: List[Span], *names: str) -> List[Span]:
    return [s for s in step if s.name in names]


def median_per_step(
    run: Dict[str, Any], value: Callable[[List[Span]], Optional[float]]
) -> Optional[float]:
    """Median over the window's steps of ``value(step)``; a step for which
    it is None (nothing of the kind ran in it) is left out."""
    vals = [v for v in map(value, steps(run)) if v is not None]
    return statistics.median(vals) if vals else None


def sum_ms(run: Dict[str, Any], *names: str) -> Optional[float]:
    """Median over the steps of the summed durations of the spans called
    ``names``, in milliseconds."""

    def value(step: List[Span]) -> Optional[float]:
        spans = named(step, *names)
        return sum(s.seconds for s in spans) * 1e3 if spans else None

    return median_per_step(run, value)


def union_ms(run: Dict[str, Any], name: str) -> Optional[float]:
    """Median over the steps of the time at least one span called ``name``
    was open, in milliseconds."""

    def value(step: List[Span]) -> Optional[float]:
        spans = named(step, name)
        if not spans:
            return None
        return union_seconds([(s.t0, s.t1) for s in spans]) * 1e3

    return median_per_step(run, value)


def p50_ms(run: Dict[str, Any], name: str) -> Optional[float]:
    """Median duration of the spans called ``name`` over all the steps
    (one per bucket a step), in milliseconds."""
    vals = [s.seconds for step in steps(run) for s in named(step, name)]
    return statistics.median(vals) * 1e3 if vals else None
