"""The share of all of a step's assignments of a token to an expert that
landed on the SiLU-gated experts this chip holds: the median over the
window's steps of the step program's ``moe_held_share`` (the mean over the
expert layers of what each sows). A uniform router over E experts of which
n are held reads n/E (0.25 in ``lfm2-raw``); it sizes the rows the grouped
matmuls really fill against their static buffer, and says whether the
step's selection-bias update keeps the routing on the held experts over a
window of random tokens. None on a step that counts no such thing."""

from benchmark import readers


def read(run):
    return readers.counter_median(run, "moe_held_share")
