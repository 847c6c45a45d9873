"""Median per step of the blocked grad_step program (forward, loss and backward)."""

from benchmark import readers


def read(run):
    return readers.span_median_ms(run, "grad")
