"""The share of all of a step's assignments of a token to an expert that
landed on the experts this chip holds, in JoyAI-LLM-Flash's expert layers
(the prediction module's among them): the median over the window's steps
of the step program's ``moe_held_share``, as ``gated_held_share`` reads it.
A uniform router over 256 experts of which 8 are held reads 0.03125; it
sizes the rows the grouped matmuls really fill against their static
buffer (four times the uniform share)."""

from benchmark.metrics.gated_held_share import read  # noqa: F401
