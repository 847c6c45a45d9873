"""The banded flash kernels' share of their roofline: the least time the
chip needs for the windowed layers' attention a step requires (the
architecture's flops.py: ``swa_flops_per_step`` over the entries the band
KEEPS, w(w+1)/2 + (S-w)w a head and sequence, never the tiles the kernels
run, nothing recomputed; ``swa_bytes_per_step``; compute-bound, the bytes
are far below) over the kernels' measured device time (``swa_ms``). What
the tiles' masked entries and remat's second forward cost is the distance
to 100: at tiles of 1,024 the band keeps 0.800 of what the kernels compute
(``swa_kept_share``)."""

from benchmark import readers
from benchmark.metrics import swa_ms


def read(run):
    ms = swa_ms.read(run)
    mix = run["cell"].mix
    b, s = int(mix["batch"]), int(mix["seq"])
    ops = readers.kernel_work(run, "swa_flops_per_step", b, s)
    nbytes = readers.kernel_work(run, "swa_bytes_per_step", b, s)
    if ms is None or ops is None or nbytes is None:
        return None
    least = max(
        ops / readers.peak(run, "bf16_flops_per_s"),
        nbytes / readers.peak(run, "hbm_bytes_per_s"),
    )
    return 100.0 * least * 1e3 / ms
