"""The share of all of a step's assignments of a token to an expert that
landed on the experts this chip holds, in Keye-VL-2.0's expert layers: the
median over the window's steps of the step program's ``moe_held_share``,
as ``gated_held_share`` reads it. A uniform router over 128 experts of
which 16 are held reads 0.125; it sizes the rows the grouped matmuls really
fill (1,024 an expert at 16,384 tokens) against their static buffer."""

from benchmark.metrics.gated_held_share import read  # noqa: F401
