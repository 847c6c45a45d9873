"""Median per step of the summed `torchft::manager::allreduce_wait`
spans: the time the trainer's thread is blocked in `work.wait()`, the
in-place scaling of the host path included."""

from benchmark import span_readers


def read(run):
    return span_readers.sum_ms(run, "torchft::manager::allreduce_wait")
