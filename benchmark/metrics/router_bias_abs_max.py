"""The largest absolute selection bias over the expert layers after the
step's out-of-gradient update of it: the median over the window's steps of
the step program's ``router_bias_abs_max``. The biases start at 0 and move
by the update's rate a step, so a number above 0 says that the update
runs, and its size how far it has gone (at most rate x steps done). None
on a program whose step has no such update."""

from benchmark import readers


def read(run):
    return readers.counter_median(run, "router_bias_abs_max")
