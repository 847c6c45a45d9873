"""The allocator's `peak_bytes_reserved` on the fullest device: memory
held apart from the live buffers that `peak_hbm_gib` counts. It is about
the size of the compiled programs' temporaries as `memory_analysis()`
gives them (PERF.md §7), which `peak_bytes_in_use` does not contain."""


def read(run):
    vals = [s.get("peak_bytes_reserved") for s in run["memory_stats"]]
    vals = [v for v in vals if v]
    return max(vals) / 2**30 if vals else None
