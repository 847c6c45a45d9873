"""Median per step of the thread-CPU seconds of the step's `alltoall`
and `allgather` collectives' socket work (`pg_collective.send_cpu_s +
recv_cpu_s`), ms: the cores the sockets burnt, in the kernel's copies
and in the faults of each receive's fresh buffer. The receive part is
the reader threads', so it runs beside the collective and is no share
of `wire_sock_ms`. Each message's share is a difference of
`time.thread_time()`, which ticks at 10 ms on `runsc`: a sum of 0s and
10s there. None where the events carry no account."""

from benchmark import wait_readers


def read(run):
    return wait_readers.per_step(run, "send_cpu_s", "recv_cpu_s", scale=1e3)
