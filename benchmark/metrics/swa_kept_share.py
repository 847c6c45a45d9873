"""Of the score entries the windowed attention layers compute, the share
the band keeps: the step program's own ``swa_kept_share``, a constant of
its compiled tile schedule (kept entries w(w+1)/2 + (S-w)w over the entries
of the tiles the banded flash kernels run; over the whole S x S square
where the program fell back to dense attention). At S = 16,384, w = 4,096:
0.800 at tiles of 1,024 (70 tiles a head), 0.889 at 512 (252), so it also
says which tiles a step compiled. One minus it is the part of ``swa_ms``
spent on entries that are masked away inside kept tiles. None on a program
whose step counts no such thing."""

from benchmark import readers


def read(run):
    return readers.counter_median(run, "swa_kept_share")
