"""Device time per step of the Pallas flash-attention kernels (forward,
dq and dkv; remat runs the forward twice). PATTERN is the name the trace
shows for them (PERF.md: the program gives them no stable name yet)."""

from benchmark import readers

PATTERN = r"^flash_attention"


def read(run):
    return readers.kernel_ms_per_step(run, PATTERN)
