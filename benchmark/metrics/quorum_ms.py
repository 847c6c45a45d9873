"""Median per step of the program's own clock around the quorum RPC
(journal event `quorum_ready`, `elapsed_s`)."""

from benchmark import readers


def read(run):
    return readers.journal_median_ms(run, "quorum_ready")
