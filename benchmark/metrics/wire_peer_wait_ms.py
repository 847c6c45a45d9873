"""Median per step of the seconds the step's `alltoall` and `allgather`
collectives waited for a message of which nothing had arrived
(`pg_collective.peer_wait_s`), ms. An upper bound on the ranks' skew,
not the skew: a peer sends to its peers one after another, so the
message's header is written only after the peer's `sendall` to the
ranks before this one, and that transfer time is in here beside the
skew and the reader thread's starvation (`host_nivcsw_step`, where the
kernel counts, is the hint for the last). Only the skew's share is out
of a faster copy's reach. None where the events carry no account."""

from benchmark import wait_readers


def read(run):
    return wait_readers.per_step(run, "peer_wait_s", scale=1e3)
