"""The share of a step's data positions that block diffusion's noise
masked: the median over the window's steps of the step program's
``diffusion_masked_share``. Under t ~ U(t_min, 1) a block it is near
(1 + t_min) / 2 (0.725 at the cell's 0.45); it says the schedule runs on the timed path, and it sizes
the rows that carry a loss (the head still runs over every row of the
noisy stream). None on a program whose step counts no such thing."""

from benchmark import readers


def read(run):
    return readers.counter_median(run, "diffusion_masked_share")
