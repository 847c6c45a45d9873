"""The largest absolute entry of a Kimi delta mixer's state at a
sequence's end: the LARGEST over the window's steps of the step program's
``kda_state_abs_max`` (itself the largest over the step's mixers, heads
and sequences). With beta up to 2 a state's eigenvalues reach down to -1:
a state that grows step after step is the failure this is there to see,
so the window's worst step is what counts, not its median
(``gdn_state_abs_max`` reads the same of a gated-delta mixer). None on a
program whose step counts no such thing."""


def read(run):
    vals = [r["counters"]["kda_state_abs_max"] for r in run["records"]
            if "kda_state_abs_max" in r.get("counters", {})]
    return max(vals) if vals else None
