"""Median per step of the summed `torchft::manager::allreduce_scale`
spans: the in-place `*= scale` over every reduced bucket."""

from benchmark import span_readers


def read(run):
    return span_readers.sum_ms(run, "torchft::manager::allreduce_scale")
