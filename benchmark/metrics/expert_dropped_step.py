"""Assignments of a token to an expert that the step did not compute,
all layers together: the median over the window's steps of the step
program's ``moe_dropped`` (``parallel/train.py`` ``_loss_and_metrics``
sums what each expert layer sows). A dropless dispatch reads 0; a
capacity factor that cuts reads what it cut. None, not 0, on a cell
whose step counts no such thing (a dense model, a trainer that hands the
step's metrics to nobody)."""

from benchmark import readers


def read(run):
    return readers.counter_median(run, "moe_dropped")
