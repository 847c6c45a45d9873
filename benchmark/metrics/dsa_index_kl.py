"""The indexer's loss L_I, nats a token: the median over the window's steps
of the step program's ``dsa_index_kl`` (the mean over the layers and rows
of KL(p || softmax_{S_t}(I)), p the main attention's head-summed
probabilities over the selection). The indexer's leaves learn from it
alone, so a value that does not fall over a window of steps, or 0, is a
dead indexer. None on a step that counts no such thing."""

from benchmark import readers


def read(run):
    return readers.counter_median(run, "dsa_index_kl")
