"""Assignments to an expert held here that the step did not compute
because the held dispatch's static row buffer (four times a uniform
router's share) was full, all of Keye-VL-2.0's expert layers together: the
median over the window's steps of the step program's ``moe_dropped``, as
``gated_held_dropped`` reads it. 0 is the contract."""

from benchmark.metrics.gated_held_dropped import read  # noqa: F401
