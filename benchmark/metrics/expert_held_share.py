"""The share of all of a step's assignments of a token to an expert that
landed on the experts this chip holds: the median over the window's steps
of the step program's ``moe_held_share`` (the mean over the expert layers
of what each sows). A uniform router over E experts of which n are held
reads n/E; it sizes the rows the grouped matmuls really fill against
their static buffer (``MoEMLP._sorted_held``). None on a cell whose
layers hold all their experts or whose step counts no such thing."""

from benchmark import readers


def read(run):
    return readers.counter_median(run, "moe_held_share")
