"""The grouped matmuls' share of their roofline in Keye-VL-2.0's expert
layers, as ``gated_gmm_roofline`` reads it: the least time the chip needs
for the matmuls of the rows the traced steps really filled (this
architecture's flops.py, six expert layers of three-matrix SiLU-gated
experts, at the mean ``moe_held_share`` those steps counted) over the
device time of XLA's ``ragged-dot`` kernels (which times remat's second
forward too). At about 1,024 rows an expert of [2048, 768] weights the
matmuls are compute-bound."""

from benchmark.metrics.gated_gmm_roofline import read  # noqa: F401
