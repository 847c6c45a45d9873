"""The largest absolute entry of a gated-delta mixer's state at a
sequence's end: the LARGEST over the window's steps of the step program's
``gdn_state_abs_max`` (itself the largest over the step's mixers, heads
and sequences). With beta up to 2 a state's eigenvalues reach down to -1:
a state that grows step after step is the failure this is there to see,
so the window's worst step is what counts, not its median. None on a
program whose step counts no such thing."""


def read(run):
    vals = [r["counters"]["gdn_state_abs_max"] for r in run["records"]
            if "gdn_state_abs_max" in r.get("counters", {})]
    return max(vals) if vals else None
