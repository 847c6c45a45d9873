"""The share of all of a step's assignments of a token to an expert that
landed on the experts this chip holds, in SmallThinker's expert layers: the
median over the window's steps of the step program's ``moe_held_share``,
as ``gated_held_share`` reads it. A uniform router over 64 experts of which
8 are held reads 0.125; it sizes the rows the grouped matmuls really fill
(1,536 an expert at 16,384 tokens) against their static buffer (four times
the uniform share). The router reads the layer's input before its
attention; on the harness's random tokens its choices drift inside a
window, so read this before the grouped matmuls' numbers."""

from benchmark.metrics.gated_held_share import read  # noqa: F401
