"""Median per step of the blocked fused program of make_train_step (forward, backward and AdamW)."""

from benchmark import readers


def read(run):
    return readers.span_median_ms(run, "step")
