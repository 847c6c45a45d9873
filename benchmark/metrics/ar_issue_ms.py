"""Median per step of the time from the start of the program's
`torchft::ddp::allreduce_grads` span to the moment the last bucket was
handed to `Manager.allreduce` (the end of the last
`torchft::manager::allreduce` span under it): what the caller's thread
does before it starts to wait. On the host path that is the pull and the
bucket copies; on the device path concatenate, snapshot and the quantize
kernels' dispatch."""

from benchmark import span_readers


def read(run):
    def value(step):
        roots = span_readers.named(step, span_readers.ROOT)
        if not roots:
            return None
        root = roots[-1]
        issued = [
            s.t1 for s in span_readers.named(step, "torchft::manager::allreduce")
            if s.parent == root.id
        ]
        return (max(issued) - root.t0) * 1e3 if issued else None

    return span_readers.median_per_step(run, value)
