"""Median per step of the program's own clock around the commit RPC
(journal event `commit_gate`, `elapsed_s`)."""

from benchmark import readers


def read(run):
    return readers.journal_median_ms(run, "commit_gate")
