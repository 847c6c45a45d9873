"""Times the quorum this group's steps were judged under changed inside
the window: consecutive ``commit_gate`` events whose ``quorum_id``
differ. 0 in a quiet run (the lighthouse keeps the id while membership
stands and no member reports a failed commit); a dropped or late group,
or a refused step, is at least one. The harness takes the mean over a
cell's groups. None, not 0, where the gates carry no such field."""

from benchmark import gate_readers


def read(run):
    ids = gate_readers.field(run, "quorum_id")
    if not ids:
        return None
    return sum(a != b for a, b in zip(ids, ids[1:]))
