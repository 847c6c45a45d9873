"""Of the causal score entries of the selected attention layers, the share
their indexers selected: the median over the window's steps of the step
program's ``dsa_kept_share`` (the mean over the layers of the packed
selection's set bits over B * S (S + 1) / 2). Row t keeps min(topk, t + 1)
keys, so it is a constant of the shapes, 0.2344 at S = 16,384 under
topk = 2,048, read from the selection itself: a selection that kept too
many or too few keys a row would show here. None on a step that counts no
such thing."""

from benchmark import readers


def read(run):
    return readers.counter_median(run, "dsa_kept_share")
