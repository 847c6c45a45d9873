"""Median per step of the union of the `torchft::collectives::wire`
spans: the time the serialised wire was busy with some bucket. Against
`allreduce_ms` it says how much of the allreduce the wire bounds."""

from benchmark import span_readers


def read(run):
    return span_readers.union_ms(run, "torchft::collectives::wire")
