"""Payload bytes the step's `alltoall` and `allgather` collectives moved
over the sockets, sent plus received (`pg_collective.tx_bytes +
rx_bytes`), median over the window's steps: the measured traffic, where
`wire_bytes_step` is what the caller handed the allgathers. A count; it
repeats exactly. Leaves out the frames' headers (tens of bytes a
message); None where the events carry no account."""

from benchmark import wait_readers


def read(run):
    return wait_readers.per_step(run, "tx_bytes", "rx_bytes")
