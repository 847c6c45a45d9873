"""The chunked state-space scan's share of its roofline: the least time
the chip needs for the scans a step requires (the architecture's
flops.py: ``ssd_flops_per_step``, the chunked algorithm's multiply-adds
over the causal half of a chunk, forward and backward, nothing
recomputed; ``ssd_bytes_per_step``, x, B, C, dt in and y out and their
gradients, once; the bytes bound it on a v5e) over the measured device
time of the scan's operations (``ssm_ms.scan_patterns``: what the trace
can name of the scan alone, without the convolution and the gated norm).
The program's scan is plain XLA: it writes its decay matrices and chunk
states to memory and remat runs it twice, so the share is small; a fused
kernel is what would raise it."""

from benchmark import readers
from benchmark.metrics import ssm_ms


def read(run):
    d = ssm_ms.dims(run)
    if d is None:
        return None
    ms = readers.kernel_ms_per_step(run, ssm_ms.any_of(ssm_ms.scan_patterns(d)))
    ops = readers.kernel_work(run, "ssd_flops_per_step", d["b"], d["s"])
    nbytes = readers.kernel_work(run, "ssd_bytes_per_step", d["b"], d["s"])
    if ms is None or ops is None or nbytes is None:
        return None
    least = max(
        ops / readers.peak(run, "bf16_flops_per_s"),
        nbytes / readers.peak(run, "hbm_bytes_per_s"),
    )
    return 100.0 * least * 1e3 / ms
