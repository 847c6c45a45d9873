"""The flash-attention kernels' share of their roofline: the least time
the chip needs for the attention a step requires (flops.py, causal,
nothing recomputed; compute-bound, the bytes are far below) over the
kernels' measured device time."""

from benchmark import flops, readers
from benchmark.metrics import flash_ms


def read(run):
    ms = flash_ms.read(run)
    if ms is None:
        return None
    cell, mix = run["cell"], run["cell"].mix
    b, s = int(mix["batch"]), int(mix["seq"])
    least = max(
        flops.flash_flops_per_step(cell.config, b, s) / readers.peak(run, "bf16_flops_per_s"),
        flops.flash_bytes_per_step(cell.config, b, s) / readers.peak(run, "hbm_bytes_per_s"),
    )
    return 100.0 * least * 1e3 / ms
