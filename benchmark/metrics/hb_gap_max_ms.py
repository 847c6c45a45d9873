"""Largest gap between two heartbeats this group's manager server SENT
to the active lighthouse inside the window, on the sender's steady
clock: the largest ``hb_gap_max_ms`` over the window's ``commit_gate``
events (each holds the largest since the gate before it). The interval
is 100 ms; the lighthouse evicts a participant whose gap, as it ARRIVES,
passes max(1000, 12 x interval) ms. A late heartbeat is late by this or
by ``hb_rtt_max_ms``. The harness takes the mean over a cell's groups.
None, not 0, where the gates carry no such field."""

from benchmark import gate_readers


def read(run):
    return gate_readers.largest(run, "hb_gap_max_ms")
