"""The grouped matmuls' share of their roofline in Solar-Open2's expert
layers, as ``gated_gmm_roofline`` reads it: the least time the chip needs
for the matmuls of the rows the traced steps really filled (this
architecture's flops.py, four expert layers, at the mean ``moe_held_share``
those steps counted) over the device time of XLA's ``ragged-dot`` kernels
(which times remat's second forward too). At about 410 rows an expert the
matmuls stand at the chip's ridge: the operations' time is a fifth above
the bytes'."""

from benchmark.metrics.gated_gmm_roofline import read  # noqa: F401
