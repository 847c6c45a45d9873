"""The quantize kernels' share of their roofline: the HBM bytes a step's
quantize and dequantize must move (flops.py: fp32 gradient in, payload
and scales out, and back; bandwidth-bound) over the published HBM
bandwidth, over the kernels' measured device time."""

from benchmark import flops, readers
from benchmark.metrics import quant_kernel_ms


def read(run):
    ms = quant_kernel_ms.read(run)
    if ms is None:
        return None
    cell = run["cell"]
    least = flops.quant_bytes_per_step(
        cell.config, int(cell.mix["quantize_bits"])
    ) / readers.peak(run, "hbm_bytes_per_s")
    return 100.0 * least * 1e3 / ms
