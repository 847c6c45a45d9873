"""Peak resident set of the group's trainer process, GiB: the largest
``rss_peak_bytes`` over the window's ``commit_gate`` events
(``getrusage(RUSAGE_SELF).ru_maxrss``, a process-lifetime peak, so
set-up is in it). The harness takes the mean over a cell's groups; four
groups share one host in ``mistral-ft4``. None, not 0, where the gates
carry no such field."""

from benchmark import gate_readers


def read(run):
    peak = gate_readers.largest(run, "rss_peak_bytes")
    return None if peak is None else peak / 2**30
