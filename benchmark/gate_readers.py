"""Reads the commit gates out of the journal.

At every commit gate the Manager journals ONE ``commit_gate`` event.
Since the PR that made a dropped step explain itself it carries, beside
``committed`` and ``elapsed_s``: ``local_vote``, ``cause``, ``quorum_id``,
``participants``, the manager server's view of its own heartbeats since
the previous gate (``hb_rounds``, ``hb_gap_max_ms``, ``hb_rtt_max_ms``,
``hb_late``) and ``rss_peak_bytes``. A program whose gates carry no such
field (every commit before that one) gives None from every reader here,
never 0, and the harness leaves the metric out of the line.

A metric of a cell with several groups is the mean over the groups
(run.py's ``join``): each group's worker reads its own journal.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional


def gates(run: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The ``attrs`` of the window's ``commit_gate`` events, in order."""
    return [
        e.get("attrs", {}) for e in run["journal"]
        if e.get("event") == "commit_gate"
    ]


def field(run: Dict[str, Any], name: str) -> List[Any]:
    """``name`` of every gate of the window that carries it."""
    return [g[name] for g in gates(run) if name in g]


def largest(run: Dict[str, Any], name: str) -> Optional[float]:
    """The largest ``name`` over the window's gates; None where no gate
    carries the field."""
    vals = field(run, name)
    return max(vals) if vals else None
