"""Of the held dispatch's static row buffer in Keye-VL-2.0's expert layers
(65,536 rows: four times the uniform share of 16,384 x 8 assignments), the
share its row-tile loops ran over: the median over the window's steps of
the step program's ``moe_held_run_share``, as ``held_run_share`` reads it.
About ``keye_held_share`` x 2 rounded up to a tile of 512 rows a layer; 1
means the loops walk the whole buffer."""

from benchmark.metrics.held_run_share import read  # noqa: F401
