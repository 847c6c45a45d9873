"""Median per step of the seconds the step's `alltoall` and `allgather`
collectives waited while their messages' bytes were landing, the
queue's hand-off included (`pg_collective.recv_s`), ms.
`wire_send_ms + wire_peer_wait_ms + wire_recv_ms` is `wire_sock_ms` less
the own-chunk copies. None where the events carry no account."""

from benchmark import wait_readers


def read(run):
    return wait_readers.per_step(run, "recv_s", scale=1e3)
