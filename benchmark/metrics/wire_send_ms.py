"""Median per step of the seconds the step's `alltoall` and `allgather`
collectives spent inside their sends (`pg_collective.send_s`: header and
frame of every message, the wait for the socket's buffer, which is the
peer's reader draining it, in it), ms.
With `wire_peer_wait_ms` and `wire_recv_ms` it tiles `wire_sock_ms` less
the own-chunk copies. Leaves out every other collective; None where the
events carry no account (the native engine, an older program)."""

from benchmark import wait_readers


def read(run):
    return wait_readers.per_step(run, "send_s", scale=1e3)
