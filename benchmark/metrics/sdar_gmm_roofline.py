"""The grouped matmuls' share of their roofline in SDAR's expert layer, as
``gated_gmm_roofline`` reads it: the least time the chip needs for the
matmuls of the rows the traced steps really filled (this architecture's
flops.py, which counts both streams' rows, at the mean ``moe_held_share``
those steps counted) over the device time of XLA's ``ragged-dot``
kernels (which times remat's second forward too)."""

from benchmark.metrics.gated_gmm_roofline import read  # noqa: F401
