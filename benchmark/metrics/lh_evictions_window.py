"""Times the lighthouse's evidence plane evicted THIS group inside the
window, as the group's own journal has it: ``lh_evicted`` events (the
lighthouse's word in the ack of the heartbeat that brought the group
back) and ``failure_signal`` events with source ``hb_lapse`` whose
subject is the group itself (the signal as peers see it, in the group's
own acks), one eviction counted once by the signal's ``seq``. The
harness takes the mean over a cell's groups, so one eviction in
``mistral-ft4`` reads 0.25. None, not 0, where the program journals no
such thing: no gate of the window carries the heartbeat counters."""

from benchmark import gate_readers


def read(run):
    if not gate_readers.field(run, "hb_rounds"):
        return None
    seqs, unnumbered = set(), 0
    for e in run["journal"]:
        attrs = e.get("attrs", {})
        mine = e.get("event") == "lh_evicted" or (
            e.get("event") == "failure_signal"
            and attrs.get("source") == "hb_lapse"
            and attrs.get("subject") == e.get("replica_id")
        )
        if not mine:
            continue
        if attrs.get("seq") is None:
            unnumbered += 1
        else:
            seqs.add(attrs["seq"])
    return len(seqs) + unnumbered
