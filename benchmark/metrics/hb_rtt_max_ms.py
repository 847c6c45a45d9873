"""Largest round trip of one heartbeat of this group's manager server to
the active lighthouse inside the window (send of the frame to the ack
read, the sender's steady clock): the largest ``hb_rtt_max_ms`` over the
window's ``commit_gate`` events. The loop sends its next heartbeat one
interval after the ack, so a long round trip is a long gap at the
lighthouse too. The harness takes the mean over a cell's groups. None,
not 0, where the gates carry no such field."""

from benchmark import gate_readers


def read(run):
    return gate_readers.largest(run, "hb_rtt_max_ms")
