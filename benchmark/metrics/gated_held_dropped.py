"""Assignments to a SiLU-gated expert held here that the step did not
compute because the held dispatch's static row buffer was full, all layers
together: the median over the window's steps of the step program's
``moe_dropped``. 0 is the contract (in ``lfm2-raw`` the buffer holds every
assignment of the step, so nothing can be dropped). None, not 0, on a step
that counts no such thing."""

from benchmark import readers


def read(run):
    return readers.counter_median(run, "moe_dropped")
