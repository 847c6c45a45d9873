"""Bytes of host memory the DDP wrapper's ``pack`` had to allocate per
step: the summed ``fresh_bytes`` of the step's `torchft::ddp::pack` spans
(a bucket buffer made this call: the wrapper's first call, a new bucket
layout, the call after a failed step; the compensated copy under error
feedback), median over the window's steps. 0 when every bucket was
copied into a buffer the wrapper already had; the first step of a run,
which sizes them, lies in the warm-up. A program whose spans carry no
such count (every commit before the one that keeps the buffers) gives
None, not 0."""

from benchmark import span_readers

SPAN = "torchft::ddp::pack"


def read(run):
    def value(step):
        counts = [s.attrs["fresh_bytes"] for s in span_readers.named(step, SPAN)
                  if "fresh_bytes" in s.attrs]
        return sum(counts) if counts else None

    return span_readers.median_per_step(run, value)
