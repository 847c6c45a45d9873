"""Median per step of the time, between the start of the step's first
`torchft::collectives::wire` span and the end of its last, in which no
`wire` span is open: the serialised wire had started and then stood
still because the next bucket's payload was not on the host yet (its
pull, or the dispatch of its quantize kernels, was outrun). With
`wire_start_ms` and `wire_busy_ms` it tiles the allreduce up to the last
bucket's push. A step with no `wire` span is left out; none at all
reads None, not 0."""

from benchmark import span_readers
from benchmark.trace_reduce import union_seconds

WIRE = "torchft::collectives::wire"


def read(run):
    def value(step):
        wires = span_readers.named(step, WIRE)
        if not wires:
            return None
        whole = max(w.t1 for w in wires) - min(w.t0 for w in wires)
        return (whole - union_seconds([(w.t0, w.t1) for w in wires])) * 1e3

    return span_readers.median_per_step(run, value)
