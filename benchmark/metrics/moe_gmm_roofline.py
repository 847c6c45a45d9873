"""The grouped matmuls' share of their roofline: the least time the chip
needs for the expert matmuls a step requires (the architecture's
flops.py: three matmuls of every assignment, forward and backward,
nothing recomputed; compute-bound at 2,048 rows an expert) over the
kernels' measured device time."""

from benchmark import readers
from benchmark.metrics import moe_gmm_ms


def read(run):
    ms = moe_gmm_ms.read(run)
    mix = run["cell"].mix
    b, s = int(mix["batch"]), int(mix["seq"])
    ops = readers.kernel_work(run, "gmm_flops_per_step", b, s)
    nbytes = readers.kernel_work(run, "gmm_bytes_per_step", b, s)
    if ms is None or ops is None or nbytes is None:
        return None
    least = max(
        ops / readers.peak(run, "bf16_flops_per_s"),
        nbytes / readers.peak(run, "hbm_bytes_per_s"),
    )
    return 100.0 * least * 1e3 / ms
