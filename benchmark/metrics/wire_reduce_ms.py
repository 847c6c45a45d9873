"""Median per step of the summed `torchft::collectives::wire_reduce`
spans: the wire stage's numpy work between and after its socket
operations (dequantize-accumulate of every rank's chunk, requantize of
the sum, joining the gathered chunks into one payload)."""

from benchmark import span_readers


def read(run):
    return span_readers.sum_ms(run, "torchft::collectives::wire_reduce")
