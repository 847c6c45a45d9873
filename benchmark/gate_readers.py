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

``explain`` is for a run that lost a step: the group's refused gates and
the lighthouse's evictions of it, one line each, which the worker puts in
its result file and run.py writes to standard error (the driver keeps no
run directory, so a ``cause`` that is not printed reaches nobody).
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, List, Optional

# What a line of ``explain`` carries, in this order, where the record has it.
GATE_FIELDS = ("cause", "error_class", "quorum_id", "participants",
               "hb_gap_max_ms", "hb_rtt_max_ms")
EVICTED_FIELDS = ("seq", "gap_ms", "budget_ms", "out_ms", "sender_gap_ms",
                  "sender_rtt_ms")


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


def explain(events: Iterable[Dict[str, Any]]) -> List[str]:
    """One line for every ``commit_gate`` of ``events`` that did not
    commit (prefix ``refused-gate:``) and one for every ``lh_evicted``
    (``lh-evicted:``), in the journal's order; [] where there is neither.
    A field the record lacks (a program from before it) is left out."""
    lines = []
    for e in events:
        attrs = e.get("attrs", {})
        if e.get("event") == "commit_gate" and attrs.get("committed") is False:
            prefix, fields = "refused-gate:", GATE_FIELDS
        elif e.get("event") == "lh_evicted":
            prefix, fields = "lh-evicted:", EVICTED_FIELDS
        else:
            continue
        said = [f"{k}={_short(attrs[k])}" for k in fields if k in attrs]
        lines.append(" ".join([prefix, f"step={e.get('step')}", *said]))
    return lines


def _short(value: Any) -> str:
    """A participant is ``<name>:<uuid>``: the name says which group."""
    if isinstance(value, list):
        return "[" + ",".join(str(v).split(":")[0] for v in value) + "]"
    return str(value)
