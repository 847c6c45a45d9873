"""Reads what the host waited for out of the journal: the socket
collectives' own accounts, and the ``getrusage`` of the commit gates.

**Per step, from ``pg_collective``.** A collective that moved messages
over the Python sockets journals, beside ``elapsed_s``: ``tx_bytes``,
``rx_bytes`` (payload each way), ``send_s``, ``send_cpu_s`` (wall and
thread-CPU seconds inside the sends), ``peer_wait_s`` (a receive's wait
until the first of its message was here: the peer had not sent, or the
reader thread was not running), ``recv_s`` (the rest of the receives'
waits: the bytes landing, and the queue's hand-off) and ``recv_cpu_s``
(the reader threads' CPU for the messages it consumed). ``per_step``
sums fields over a step's collectives, grouped by the step-scoped trace
id as ``wire_bytes_step`` groups them, and takes the median over steps.

**Per gate, from ``commit_gate``.** Every gate carries the cumulative
``cpu_user_s``, ``cpu_sys_s``, ``minflt`` and ``nivcsw`` of its process.
``per_gate`` takes the difference between consecutive gates of the
window (the first gives none) and the median of those; ``per_second``
divides each difference by that of the two events' ``ts``. A cumulative
that reads 0 at every gate was not counted (``runsc``, the kernel of the
machines the chip tool hands out, fills neither ``ru_minflt`` nor
``ru_nivcsw``; on a kernel that counts no process gets as far as a gate
with either at 0) and gives None, not a 0 that would read as a best.

A program whose events carry no such field (every commit before the one
that added them, the native engine's collectives, a cell with no
Manager) gives None from every reader here, never 0, and the harness
leaves the metric out of the line. A metric of a cell with several
groups is the mean over the groups (run.py's ``join``).
"""

from __future__ import annotations

import statistics
from typing import Any, Dict, List, Optional, Tuple

# The wire stage's socket collectives: what ``wire_sock_ms`` times.
WIRE_OPS = ("alltoall", "allgather")


def per_step(run: Dict[str, Any], *fields: str, scale: float = 1) -> Optional[float]:
    """Median over the window's steps of ``fields`` summed over the step's
    completed ``WIRE_OPS`` collectives that carry every one of them, times
    ``scale`` (1e3 for seconds as ms)."""
    steps: Dict[str, float] = {}
    for e in run["journal"]:
        if e.get("event") != "pg_collective" or not e.get("trace"):
            continue
        a = e.get("attrs", {})
        if a.get("op") in WIRE_OPS and a.get("ok", True) and all(f in a for f in fields):
            steps[e["trace"]] = steps.get(e["trace"], 0) + sum(a[f] for f in fields)
    return statistics.median(steps.values()) * scale if steps else None


def _gate_pairs(run: Dict[str, Any], fields: Tuple[str, ...]) -> List[Tuple[float, float]]:
    """(difference of summed ``fields``, difference of ``ts``) of every two
    consecutive gates of the window that both carry every field; none
    where the fields read 0 at every gate (the kernel does not count)."""
    gates = [
        (e.get("ts", 0.0), e.get("attrs", {})) for e in run["journal"]
        if e.get("event") == "commit_gate"
    ]
    if not any(a.get(f) for _, a in gates for f in fields):
        return []
    return [
        (sum(b[f] - a[f] for f in fields), tb - ta)
        for (ta, a), (tb, b) in zip(gates, gates[1:])
        if all(f in a and f in b for f in fields)
    ]


def per_gate(run: Dict[str, Any], *fields: str, scale: float = 1) -> Optional[float]:
    """Median over consecutive gates of the growth of summed ``fields``,
    times ``scale``."""
    pairs = _gate_pairs(run, fields)
    return statistics.median(d for d, _ in pairs) * scale if pairs else None


def per_second(run: Dict[str, Any], *fields: str) -> Optional[float]:
    """Median over consecutive gates of the growth of summed ``fields``
    over the seconds between the two events."""
    rates = [d / dt for d, dt in _gate_pairs(run, fields) if dt > 0]
    return statistics.median(rates) if rates else None
