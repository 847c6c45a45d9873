"""Times a step that a runnable thread of the group's trainer process
was taken off its core: median growth of `commit_gate.nivcsw` between
consecutive gates. A thread that blocked switched voluntarily and is
left out. None where the gates carry no such field, and None where it
reads 0 at every gate: that kernel does not count switches (`runsc`, on
the machines the chip tool hands out). No cell of BENCHMARK.json lists
this metric while the benchmark's machines run that kernel."""

from benchmark import wait_readers


def read(run):
    return wait_readers.per_gate(run, "nivcsw")
