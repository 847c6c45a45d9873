"""Of the causal tile pairs of the selected flash kernels, the share that
held a selected entry and so ran: the median over the window's steps of the
step program's ``dsa_tiles_run_share`` (the mean over the layers of the
``runs`` table's causal ones over the causal tile pairs). 1.0 under seeded
random weights, whose selection is scattered: the cell measures what the
mechanism costs. A trained selection clusters and skips
(tests/test_keye.py shows one). None on a step that counts no such thing."""

from benchmark import readers


def read(run):
    return readers.counter_median(run, "dsa_tiles_run_share")
