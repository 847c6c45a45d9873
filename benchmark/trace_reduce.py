"""From a profiler trace (``*.xplane.pb``) to numbers.

The reduction every PR uses, kept with the benchmark so that no PR that
claims a gain can change it. Reads the file with ``jax.profiler.
ProfileData`` and nothing else.

What a TPU trace holds (looked at by hand, PR 22): one plane per chip,
``/device:TPU:<n>``, whose line ``XLA Ops`` carries one event per
executed HLO operation (fusions, custom calls — Pallas kernels among
them — copies, and the ``while``/``conditional`` wrappers whose
interval covers their body's operations); and the host plane
``/host:CPU`` with one line per thread, carrying the worker's
``TraceAnnotation`` spans (``bench::<name>``) and the program's own
(``torchft::...``). Device and host events share one clock.

- window: from the start of the first ``bench::step`` annotation to the
  end of the last (the traced steps);
- busy: the union of the ``XLA Ops`` intervals inside the window, per
  chip, then the mean over the chips — a wrapper and its body count
  once;
- an operation's time: its self time (its interval less its children's)
  so that a ``while`` does not own its body;
- a gap: a maximal idle interval of a chip inside the window, named for
  the innermost worker or program span open on the host at its middle.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]  # start, end in seconds on the trace's clock

OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
STEP_SPAN = "step"


def union_seconds(intervals: List[Interval]) -> float:
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def gaps_in(intervals: List[Interval], window: Interval) -> List[Interval]:
    """Maximal sub-intervals of ``window`` that no interval covers."""
    out, cursor = [], window[0]
    for a, b in sorted(intervals):
        if a > cursor:
            out.append((cursor, min(a, window[1])))
        cursor = max(cursor, b)
        if cursor >= window[1]:
            break
    if cursor < window[1]:
        out.append((cursor, window[1]))
    return [(a, b) for a, b in out if b > a]


def self_times(events: List[Tuple[str, float, float]]) -> Dict[str, float]:
    """Per name, the seconds of each event not covered by events nested
    inside it (events of one line nest or are disjoint)."""
    out: Dict[str, float] = {}
    stack: List[List] = []  # [name, end, child_seconds, start]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, end, child, start = stack.pop()
            out[name] = out.get(name, 0.0) + (end - start) - child
            if stack:
                stack[-1][2] += end - start

    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        close(a)
        stack.append([name, b, 0.0, a])
    close(float("inf"))
    return out


@dataclasses.dataclass
class Trace:
    window: Interval
    chips: int
    busy_s: float  # mean over chips of the busy union inside the window
    op_seconds: Dict[str, float]  # self time per operation name, mean over chips
    gaps: List[Tuple[str, float]]  # (host span open at the gap's middle, seconds)
    host: Dict[str, List[float]]  # durations of each host annotation, by name

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def ops_matching(self, pattern: str) -> float:
        """Seconds of the operations whose name matches ``pattern``."""
        rx = re.compile(pattern)
        return sum(s for n, s in self.op_seconds.items() if rx.search(n))

    def top_ops(self, n: int) -> List[List]:
        top = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:n]
        return [[name, secs] for name, secs in top]

    def top_gaps(self, n: int) -> List[List]:
        """The idle seconds by what the host was doing, largest first."""
        by: Dict[str, float] = {}
        for name, secs in self.gaps:
            by[name] = by.get(name, 0.0) + secs
        return [[k, v] for k, v in sorted(by.items(), key=lambda kv: -kv[1])[:n]]


def short_name(hlo: str) -> str:
    """An ``XLA Ops`` event is named by its whole HLO instruction,
    ``%fusion.99 = (f32[...], ...) fusion(...), kind=...``: keep the
    instruction's own name and the start of its result type."""
    name, _, rest = hlo.partition(" = ")
    return f"{name.lstrip('%')} {rest[:40]}".strip()


def _events(line, rename=lambda n: n) -> List[Tuple[str, float, float]]:
    return [
        (rename(e.name), e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
        for e in line.events
    ]


def reduce(path: str, span_prefix: str) -> Optional[Trace]:
    """The trace at ``path``, or None when it holds no traced step or no
    device plane (a CPU run): then there is nothing to report."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    host_spans: List[Tuple[str, float, float]] = []
    device_lines = []
    for plane in data.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                host_spans += [
                    e for e in _events(line)
                    if e[0].startswith(span_prefix) or e[0].startswith("torchft::")
                ]
        elif DEVICE_PLANE.match(plane.name):
            device_lines += [ln for ln in plane.lines if ln.name == OPS_LINE]
    steps = [e for e in host_spans if e[0] == span_prefix + STEP_SPAN]
    if not steps or not device_lines:
        return None
    window = (min(e[1] for e in steps), max(e[2] for e in steps))

    host: Dict[str, List[float]] = {}
    for name, a, b in host_spans:
        if a >= window[0] and b <= window[1]:
            host.setdefault(name, []).append(b - a)

    def open_at(t: float) -> str:
        """The innermost (shortest) span covering ``t``, the step aside."""
        covering = [
            (b - a, name) for name, a, b in host_spans
            if a <= t <= b and name != span_prefix + STEP_SPAN
        ]
        return min(covering)[1] if covering else "(between spans)"

    busy, ops, gaps = 0.0, {}, []
    for line in device_lines:
        evs = [
            (n, max(a, window[0]), min(b, window[1]))
            for n, a, b in _events(line, short_name)
            if b > window[0] and a < window[1]
        ]
        spans = [(a, b) for _, a, b in evs]
        busy += union_seconds(spans)
        for name, secs in self_times(evs).items():
            ops[name] = ops.get(name, 0.0) + secs
        gaps += [(open_at((a + b) / 2), b - a) for a, b in gaps_in(spans, window)]
    n = len(device_lines)
    return Trace(
        window=window,
        chips=n,
        busy_s=busy / n,
        op_seconds={k: v / n for k, v in ops.items()},
        gaps=[(name, secs / n) for name, secs in gaps],
        host=host,
    )
