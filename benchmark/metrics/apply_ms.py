"""Median per step of the blocked, undonated apply_step program (AdamW)."""

from benchmark import readers


def read(run):
    return readers.span_median_ms(run, "apply")
