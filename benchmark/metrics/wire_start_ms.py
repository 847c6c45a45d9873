"""Median per step of the time from the start of the program's
`torchft::ddp::allreduce_grads` span to the start of the step's first
`torchft::collectives::wire` span: how long the allreduce ran before any
bucket was on the wire. It is the first bucket's dispatch, its pull off
the device and whatever that pull waited for, so it says whether the
schedule of the buckets (smallest first, pulls one at a time) engages.
A step with no root or no `wire` span (the host fp32 path, a program
that journals no span tree) is left out; none at all reads None."""

from benchmark import span_readers

WIRE = "torchft::collectives::wire"


def read(run):
    def value(step):
        roots = span_readers.named(step, span_readers.ROOT)
        wires = span_readers.named(step, WIRE)
        if not roots or not wires:
            return None
        return (min(w.t0 for w in wires) - roots[-1].t0) * 1e3

    return span_readers.median_per_step(run, value)
