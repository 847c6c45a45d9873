"""The gated short convolutions' share of their roofline: the least time
the chip needs for what lies between the mixers' two projections in a step
(the architecture's flops.py: ``short_conv_bytes_per_step``, in_proj's
3H-wide output read once and the H-wide product written once, forward, and
their gradients, in bf16, nothing recomputed; ``short_conv_flops_per_step``,
the two gates and the taps; the bytes bound it on a v5e by two orders) over
the device time ``short_conv_ms`` can name. That time leaves out what XLA
fuses into the neighbouring matmuls (``short_conv_ms`` says what), so the
share is an upper bound of the plain form's: the stage is memory-bound,
XLA writes float32 temporaries of the residual stream's size and remat
runs the forward twice. A fused kernel named ``short_conv...`` is timed
whole, and is what would raise it."""

from benchmark import readers
from benchmark.metrics import short_conv_ms


def read(run):
    ms = short_conv_ms.read(run)
    mix = run["cell"].mix
    b, s = int(mix["batch"]), int(mix["seq"])
    ops = readers.kernel_work(run, "short_conv_flops_per_step", b, s)
    nbytes = readers.kernel_work(run, "short_conv_bytes_per_step", b, s)
    if ms is None or ops is None or nbytes is None:
        return None
    least = max(
        ops / readers.peak(run, "bf16_flops_per_s"),
        nbytes / readers.peak(run, "hbm_bytes_per_s"),
    )
    return 100.0 * least * 1e3 / ms
