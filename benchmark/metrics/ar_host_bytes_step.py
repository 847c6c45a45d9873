"""Bytes the host touched per step on the fp32 path, from the spans'
own counts: pulled (`torchft::ddp::pull.nbytes`) + copied
(`torchft::ddp::pack.nbytes`, the concatenated buckets, and
`torchft::manager::host_copy.copied_bytes`, 0 when the bucket was
already writable) + scaled (`torchft::manager::allreduce_scale.nbytes`).
Median over the window's steps. A count; it repeats exactly."""

from benchmark import span_readers

COUNTS = {
    "torchft::ddp::pull": "nbytes",
    "torchft::ddp::pack": "nbytes",
    "torchft::manager::host_copy": "copied_bytes",
    "torchft::manager::allreduce_scale": "nbytes",
}


def read(run):
    def value(step):
        spans = span_readers.named(step, *COUNTS)
        if not spans:
            return None
        return sum(int(s.attrs.get(COUNTS[s.name], 0)) for s in spans)

    return span_readers.median_per_step(run, value)
