"""Kernel time of the group's trainer process a step, ms: median growth
of `commit_gate.cpu_sys_s` between consecutive gates (page faults,
socket copies, every thread's). None where the gates carry no such
field."""

from benchmark import wait_readers


def read(run):
    return wait_readers.per_gate(run, "cpu_sys_s", scale=1e3)
