"""How uneven the routing is: the median over the window's steps of the
step program's ``moe_max_load``, the largest expert's assignments over
the mean expert's, in the worst layer (1 is perfectly even, the number
of experts is one expert taking every token). It bounds the grouped
matmuls' largest group and, under a capacity factor, what is dropped.
None on a cell whose step counts no such thing."""

from benchmark import readers


def read(run):
    return readers.counter_median(run, "moe_max_load")
