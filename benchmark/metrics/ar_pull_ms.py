"""Median per step of `torchft::ddp::pull`: `np.asarray` of every
gradient leaf, device to host, after the gradients are ready."""

from benchmark import span_readers


def read(run):
    return span_readers.sum_ms(run, "torchft::ddp::pull")
