"""The share of all of a step's assignments of a row to an expert that
landed on the experts this chip holds, in SDAR's expert layer: the median
over the window's steps of the step program's ``moe_held_share``, as
``gated_held_share`` reads it (over both streams' rows here). A uniform
router over 128 experts of which 16 are held reads 0.125; it sizes the
rows the grouped matmuls really fill against their static buffer."""

from benchmark.metrics.gated_held_share import read  # noqa: F401
