"""Median per step of the worker's span around mm.allreduce_grads(...), entered after the gradients are ready on the device: pull, host passes, wire and push."""

from benchmark import readers


def read(run):
    return readers.span_median_ms(run, "allreduce")
