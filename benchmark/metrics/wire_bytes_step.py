"""Bytes this group handed its process group per step: the journal's
`pg_collective.nbytes` summed over the step-scoped trace id, median over
the window's steps. A count; it repeats exactly."""

import statistics


def read(run):
    per_step = {}
    for e in run["journal"]:
        if e.get("event") == "pg_collective" and e.get("trace"):
            a = e.get("attrs", {})
            if a.get("ok", True):
                per_step[e["trace"]] = per_step.get(e["trace"], 0) + int(a["nbytes"])
    return statistics.median(per_step.values()) if per_step else None
