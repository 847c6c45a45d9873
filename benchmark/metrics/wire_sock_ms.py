"""Median per step of the summed `torchft::collectives::wire_alltoall`
and `torchft::collectives::wire_allgather` spans: the socket part of the
wire stage (two alltoalls and one allgather a bucket, each waiting for
the slowest peer)."""

from benchmark import span_readers


def read(run):
    return span_readers.sum_ms(
        run,
        "torchft::collectives::wire_alltoall",
        "torchft::collectives::wire_allgather",
    )
