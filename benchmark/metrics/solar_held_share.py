"""The share of all of a step's assignments of a token to an expert that
landed on the experts this chip holds, in Solar-Open2's expert layers: the
median over the window's steps of the step program's ``moe_held_share``,
as ``gated_held_share`` reads it. A uniform router over 320 experts of
which 8 are held reads 0.025; it sizes the rows the grouped matmuls really
fill against their static buffer (four times the uniform share). Nothing
moves this model's selection bias (the published file names no rate), so
over a window the share says where the router's own scores drift."""

from benchmark.metrics.gated_held_share import read  # noqa: F401
