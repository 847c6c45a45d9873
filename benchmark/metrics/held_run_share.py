"""Of the held dispatch's static row buffer, the share its row-tile loops
ran over: the median over the window's steps of the step program's
``moe_held_run_share`` (the mean over the expert layers of what each sows:
the rows of the tiles run, ceil(rows filled / ``HELD_ROW_TILE``) tiles,
over the buffer's R rows; ``MoEMLP._sorted_held``). It is the engagement
of the rows-filled dispatch: the gathers into the buffer, the activation
between the grouped matmuls, their gradients and the combine's transpose
cost this share of what they cost over all R rows. It should read
``*_held_share`` x T*K / R rounded up to a tile a layer; 1 means the loops
walk the whole buffer again (a router that floods the held experts, or a
buffer of one tile).

Not in it: the grouped matmuls (they stop at the rows filled by their
group sizes, whatever this reads), the two argsorts over the T*K
assignments and the K gathers that bring a token's rows back (they walk
T rows whatever the buffer holds). None where the step counts no such
thing: a cell whose layers hold all their experts or have none, a trainer
that hands no counters over, a program from before the loops."""

from benchmark import readers


def read(run):
    return readers.counter_median(run, "moe_held_run_share")
