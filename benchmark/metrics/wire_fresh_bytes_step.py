"""Bytes of host memory the quantized wire stage had to allocate per
step: the summed ``fresh_bytes`` of the step's
`torchft::collectives::wire_reduce` spans (scratch growth and joined
payloads that found no free buffer), median over the window's steps. 0
when every stage wrote into buffers it already had; the first steps of a
run, which size them, lie in the warm-up. A program whose spans carry no
such count (every commit before the one that reuses the buffers) gives
None, not 0."""

from benchmark import span_readers

SPAN = "torchft::collectives::wire_reduce"


def read(run):
    def value(step):
        counts = [s.attrs["fresh_bytes"] for s in span_readers.named(step, SPAN)
                  if "fresh_bytes" in s.attrs]
        return sum(counts) if counts else None

    return span_readers.median_per_step(run, value)
