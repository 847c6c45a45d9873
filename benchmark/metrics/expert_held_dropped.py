"""Assignments to an expert held here that the step did not compute
because the held dispatch's static row buffer was full, all layers
together: the median over the window's steps of the step program's
``moe_dropped``. 0 is the contract; anything else says the buffer's
bound (``MoEMLP._sorted_held``) no longer holds for this routing. None,
not 0, on a cell whose step counts no such thing."""

from benchmark import readers


def read(run):
    return readers.counter_median(run, "moe_dropped")
