"""Cores the group's trainer process keeps busy through a step: median
over consecutive `commit_gate` events of the growth of `cpu_user_s +
cpu_sys_s` over the growth of the events' `ts`. All the process's
threads, XLA's and the sockets' among them; the manager server and the
lighthouse are other processes and are left out. The harness takes the
mean over a cell's groups; four such processes share the host in
`mistral-ft4`. None where the gates carry no such field."""

from benchmark import wait_readers


def read(run):
    return wait_readers.per_second(run, "cpu_user_s", "cpu_sys_s")
