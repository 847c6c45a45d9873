"""The chunked delta rule's share of its roofline in the Kimi delta mixers:
the least time the chip needs for the delta rules a step requires (the
architecture's flops.py: ``kda_flops_per_step``, the chunked algorithm's
multiply-adds forward and backward, nothing recomputed;
``kda_bytes_per_step``, q, k, v, a log-decay a key channel and beta in and o
out and their gradients, once; the bytes bound it on a v5e) over the
measured device time of the chunk algebra's and the scan's operations
(``kda_ms.scan_patterns``: what the trace can name of them, without the
convolution, the gates and the norm). The counts are the algorithm's and
stay right whatever implements it. The program's form is plain XLA: it
writes its decayed operands, the sub-blocks' products, T, W, U and every
chunk's entering state to memory, and remat runs it twice, so the share is
small; a fused kernel is what would raise it."""

from benchmark import readers
from benchmark.metrics import kda_ms


def read(run):
    d = kda_ms.dims(run)
    if d is None:
        return None
    ms = readers.kernel_ms_per_step(run, kda_ms.any_of(kda_ms.scan_patterns(d)))
    ops = readers.kernel_work(run, "kda_flops_per_step", d["b"], d["s"])
    nbytes = readers.kernel_work(run, "kda_bytes_per_step", d["b"], d["s"])
    if ms is None or ops is None or nbytes is None:
        return None
    least = max(
        ops / readers.peak(run, "bf16_flops_per_s"),
        nbytes / readers.peak(run, "hbm_bytes_per_s"),
    )
    return 100.0 * least * 1e3 / ms
