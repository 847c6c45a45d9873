"""Median per step of the summed `torchft::ddp::push` and
`torchft::ddp::push_wait` spans: what the caller's thread spends sending
the reduced buckets back to the device on the fp32 host path. A `push`
is the dispatch of one bucket's `jax.device_put`s (asynchronous), the
one `push_wait` the wait for all of them to land; the transfer itself
runs under the next buckets' pulls and packs. A program that has no such
spans (the host gradient goes up inside `apply_step`'s own transfer:
every commit before the one that pushes) gives None."""

from benchmark import span_readers


def read(run):
    return span_readers.sum_ms(
        run, "torchft::ddp::push", "torchft::ddp::push_wait"
    )
