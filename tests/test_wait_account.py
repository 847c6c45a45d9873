"""What a stage was doing while it held the step: every socket
collective's ``pg_collective`` says its bytes each way and its send,
peer-late and receive seconds, and every ``commit_gate`` carries what
has a reader of the ``getrusage`` it makes. Two (or three) ``ProcessGroupSocket``
ranks over loopback in threads of one process share one journal; each
rank's events are told apart by the trace id its group was given."""

import json
import socket
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from torchft_tpu import _native, process_group, telemetry
from torchft_tpu.process_group import (
    ProcessGroupDummy,
    ProcessGroupNative,
    ProcessGroupSocket,
    ReduceOp,
    _PeerConn,
    _WireAccount,
)
from torchft_tpu.store import TCPStoreServer

WIRE_FIELDS = ("tx_bytes", "rx_bytes", "send_s", "send_cpu_s", "peer_wait_s",
               "recv_s", "recv_cpu_s", "rx_fresh_bytes")
RUSAGE_FIELDS = ("cpu_user_s", "cpu_sys_s", "minflt", "nivcsw")
# The three waits are on time.time(), elapsed_s on time.monotonic(): two
# clocks may disagree by what the system clock was slewed meanwhile.
CLOCKS = 1e-4


@pytest.fixture
def store():
    server = TCPStoreServer()
    yield server
    server.shutdown()


@pytest.fixture
def journal(tmp_path, monkeypatch):
    """A configured journal; yields a reader of rank ``r``'s
    ``pg_collective`` attrs (all ranks' where ``r`` is None)."""
    path = str(tmp_path / "journal.jsonl")
    monkeypatch.setenv("TORCHFT_JOURNAL_FILE", path)
    monkeypatch.delenv("TORCHFT_JOURNAL_DIR", raising=False)
    telemetry.reset_event_log()

    def collectives(rank=None):
        with open(path) as f:
            evs = [json.loads(line) for line in f]
        return [e["attrs"] for e in evs if e["event"] == "pg_collective"
                and (rank is None or e.get("trace") == f"rank{rank}")]

    yield collectives
    telemetry.reset_event_log()


def _parallel(fns):
    with ThreadPoolExecutor(max_workers=len(fns)) as pool:
        return [f.result(timeout=60) for f in [pool.submit(fn) for fn in fns]]


def _group(store, cls, world, prefix):
    groups = [cls(timeout=10.0) for _ in range(world)]
    _parallel([lambda r=r: groups[r].configure(f"{store.address()}/{prefix}", r, world)
               for r in range(world)])
    for r, g in enumerate(groups):
        g.set_trace_id(f"rank{r}")
    return groups


def _run(store, cls, world, prefix, body):
    groups = _group(store, cls, world, prefix)
    try:
        return _parallel([lambda r=r: body(groups[r], r) for r in range(world)])
    finally:
        for g in groups:
            g.shutdown()


# Each case: (what every rank runs, payload bytes a rank sends = receives).
N = 4096  # float32 elements of one array


def _alltoall(g, r):
    w = g.size()
    g.alltoall([[np.full(N, r, np.float32), np.full(8, r, np.int8)]
                for _ in range(w)]).wait(timeout=30)


def _allgather(g, r):
    g.allgather([np.full(N, r, np.float32), np.full(8, r, np.int8)]).wait(timeout=30)


def _allreduce(g, r):
    g.allreduce(np.full(N, r, np.float32), ReduceOp.SUM).wait(timeout=30)


def _reduce_scatter(g, r):
    g.reduce_scatter([np.full(N, r, np.float32) for _ in range(g.size())]).wait(timeout=30)


def _ring_bytes(world):
    # 2 (world - 1) chunks of N / world elements each way
    return 2 * (world - 1) * (N // world) * 4


EXACT = [
    ("alltoall", _alltoall, 2, (N * 4 + 8) * 1),
    ("alltoall", _alltoall, 3, (N * 4 + 8) * 2),
    ("allgather", _allgather, 2, (N * 4 + 8) * 1),
    ("allgather", _allgather, 3, (N * 4 + 8) * 2),
    ("allreduce", _allreduce, 2, _ring_bytes(2)),
    ("allreduce", _allreduce, 4, _ring_bytes(4)),
    ("reduce_scatter", _reduce_scatter, 2, N * 4),
]


@pytest.mark.parametrize("op,body,world,want", EXACT,
                         ids=[f"{c[0]}-{c[2]}" for c in EXACT])
def test_bytes_each_way_are_exact_and_the_seconds_tile(store, journal, op, body, world, want):
    _run(store, ProcessGroupSocket, world, f"x_{op}{world}", body)
    for rank in range(world):
        (ev,) = journal(rank)
        assert ev["op"] == op and ev["ok"]
        assert (ev["tx_bytes"], ev["rx_bytes"]) == (want, want)
        for k in WIRE_FIELDS[2:]:
            assert ev[k] >= 0.0, k
        assert ev["send_s"] + ev["peer_wait_s"] + ev["recv_s"] <= ev["elapsed_s"] + CLOCKS
        assert "messages" not in ev
    # nbytes is what it always was: what the caller handed in, 0 for alltoall
    handed = {"alltoall": 0, "allgather": N * 4 + 8, "allreduce": N * 4,
              "reduce_scatter": N * 4 * world}[op]
    assert {ev["nbytes"] for ev in journal()} == {handed}


def test_a_late_peer_shows_as_peer_wait_on_the_early_rank_only(store, journal):
    def body(g, r):
        if r == 1:
            time.sleep(0.2)
        g.alltoall([np.full(N, r, np.float32) for _ in range(2)]).wait(timeout=30)

    _run(store, ProcessGroupSocket, 2, "late", body)
    (early,), (late,) = journal(0), journal(1)
    assert early["peer_wait_s"] >= 0.15
    assert late["peer_wait_s"] < 0.05
    for ev in (early, late):
        assert ev["nbytes"] == 0 and ev["tx_bytes"] == ev["rx_bytes"] == N * 4
        assert ev["send_s"] + ev["peer_wait_s"] + ev["recv_s"] <= ev["elapsed_s"] + CLOCKS
    # the early rank's collective lasted as long as it waited
    assert early["elapsed_s"] + CLOCKS >= early["peer_wait_s"]


def test_point_to_point_and_broadcast_account_one_way(store, journal):
    def body(g, r):
        if r == 0:
            g.send([np.zeros(N, np.float32)], dst=1, tag="t").wait(timeout=30)
        else:
            g.recv(src=0, tag="t").wait(timeout=30)
        g.broadcast([np.zeros(N, np.float32)], root=0).wait(timeout=30)

    _run(store, ProcessGroupSocket, 2, "p2p", body)
    for ev in journal(0):
        assert (ev["tx_bytes"], ev["rx_bytes"]) == (N * 4, 0)
        assert ev["peer_wait_s"] == ev["recv_s"] == ev["recv_cpu_s"] == 0.0
    for ev in journal(1):
        assert (ev["tx_bytes"], ev["rx_bytes"]) == (0, N * 4)
        assert ev["send_s"] == ev["send_cpu_s"] == 0.0
    assert [ev["op"] for ev in journal(0)] == ["send", "broadcast"]
    assert [ev["op"] for ev in journal(1)] == ["recv", "broadcast"]


def test_each_collective_has_an_account_of_its_own(store, journal):
    """Three collectives queued on one pg-exec thread: each event holds
    its own bytes, not a running total."""
    def body(g, r):
        works = [g.allgather([np.zeros(N * k, np.float32)]) for k in (1, 2, 3)]
        for w in works:
            w.wait(timeout=30)

    _run(store, ProcessGroupSocket, 2, "own", body)
    for rank in range(2):
        assert [ev["rx_bytes"] for ev in journal(rank)] == [N * 4, N * 8, N * 12]


def test_a_collective_with_no_peer_carries_none_of_the_fields(store, journal):
    _run(store, ProcessGroupSocket, 1, "alone", _allreduce)
    (ev,) = journal(0)
    assert ev["nbytes"] == N * 4 and not set(WIRE_FIELDS) & set(ev)


@pytest.mark.skipif(not _native.is_available(), reason="native engine unavailable")
def test_the_native_engines_collectives_carry_none_of_the_fields(store, journal):
    def body(g, r):
        _allreduce(g, r)
        _allgather(g, r)
        g.broadcast([np.zeros(N, np.float32)], root=0).wait(timeout=30)

    _run(store, ProcessGroupNative, 2, "native", body)
    evs = journal()
    assert len(evs) == 6 and {ev["backend"] for ev in evs} == {"torchft-native"}
    for ev in evs:
        assert not set(WIRE_FIELDS) & set(ev)


def test_the_dummy_group_journals_no_collective(journal):
    pg = ProcessGroupDummy()
    pg.allreduce(np.zeros(4, np.float32)).wait()
    pg.alltoall([np.zeros(4, np.float32)]).wait()
    telemetry.get_event_log().emit("marker")  # the journal file exists
    assert journal() == []


@pytest.fixture
def no_journal(monkeypatch):
    monkeypatch.delenv("TORCHFT_JOURNAL_FILE", raising=False)
    monkeypatch.delenv("TORCHFT_JOURNAL_DIR", raising=False)
    telemetry.reset_event_log()
    assert telemetry.get_event_log() is None
    yield
    telemetry.reset_event_log()


@pytest.mark.parametrize("body", [_alltoall, _allgather, _allreduce],
                         ids=["alltoall", "allgather", "allreduce"])
def test_no_journal_no_account_and_the_collective_still_runs(store, no_journal,
                                                             monkeypatch, body):
    """Tracing that is off does no work on the hot path: no account is
    opened, so no send or receive reads a clock for one."""
    opened = []
    real = process_group._WireAccount
    monkeypatch.setattr(process_group, "_WireAccount",
                        lambda: opened.append(1) or real())
    _run(store, ProcessGroupSocket, 2, "nolog", body)
    assert opened == []


def _one_message(left, right):
    """Send one array left to right; the reader's queue item, then the
    array as ``recv`` gives it."""
    left.send("t", np.arange(N, dtype=np.float32))
    deadline = time.time() + 10
    while "t" not in right._queues and time.time() < deadline:
        time.sleep(0.005)
    item = right._queues["t"].queue[0]
    return item, right.recv("t", 5.0)


def test_a_bare_connection_stamps_its_messages_and_needs_no_account(journal):
    """A ``_PeerConn`` driven outside any collective (no account on the
    thread): send and recv work, and with a journal configured the
    queue's items carry the reader's stamp and CPU."""
    a, b = socket.socketpair()
    left, right = _PeerConn(a, peer=1), _PeerConn(b, peer=0)
    try:
        t0 = time.time()
        (_, payload, t_hdr, cpu_s, _fresh), got = _one_message(left, right)
        assert len(payload) == N * 4 and t0 <= t_hdr <= time.time() and cpu_s >= 0.0
        np.testing.assert_array_equal(got, np.arange(N, dtype=np.float32))
    finally:
        left.close()
        right.close()


def test_with_no_journal_the_reader_takes_no_stamp(no_journal, monkeypatch):
    a, b = socket.socketpair()
    left, right = _PeerConn(a, peer=1), _PeerConn(b, peer=0)
    monkeypatch.setattr(process_group.time, "thread_time",
                        lambda: pytest.fail("a clock was read with tracing off"))
    try:
        (_, payload, t_hdr, cpu_s, _fresh), got = _one_message(left, right)
        assert len(payload) == N * 4 and (t_hdr, cpu_s) == (0.0, 0.0)
        np.testing.assert_array_equal(got, np.arange(N, dtype=np.float32))
    finally:
        left.close()
        right.close()


def test_a_message_that_was_here_before_the_call_is_all_recv(journal):
    """``peer_wait_s`` counts only the time before the first of the
    message was here: a receive of a message that had landed adds its
    whole (short) wait to ``recv_s``."""
    a, b = socket.socketpair()
    left, right = _PeerConn(a, peer=1), _PeerConn(b, peer=0)
    acct = process_group._wire.account = _WireAccount()
    try:
        left.send("t", np.zeros(N, np.float32))
        deadline = time.time() + 10
        while "t" not in right._queues and time.time() < deadline:
            time.sleep(0.005)
        right.recv("t", 5.0)
    finally:
        process_group._wire.account = None
        left.close()
        right.close()
    assert (acct.tx_bytes, acct.rx_bytes, acct.messages) == (N * 4, N * 4, 2)
    assert acct.peer_wait_s == 0.0 and 0.0 < acct.recv_s < 1.0
    assert acct.send_s > 0.0 and acct.fields().keys() == set(WIRE_FIELDS)
    assert _WireAccount().fields() == {}


# ---------------------------------------------------------------------------
# The gate's getrusage
# ---------------------------------------------------------------------------


def _three_gates(tmp_path, monkeypatch):
    from tests.test_manager import _journaled

    def drive(m):
        for _ in range(3):
            m.start_quorum()
            m.wait_quorum()
            assert m.should_commit()

    events = _journaled(tmp_path, monkeypatch, drive)
    return [e for e in events if e["event"] == "commit_gate"]


@pytest.mark.parametrize("field", RUSAGE_FIELDS)
def test_the_gate_carries_its_getrusage_cumulative(tmp_path, monkeypatch, field):
    gates = _three_gates(tmp_path, monkeypatch)
    vals = [g["attrs"][field] for g in gates]
    assert len(vals) == 3 and vals == sorted(vals) and vals[0] >= 0
    assert all("rss_peak_bytes" in g["attrs"] for g in gates)
    kind = float if field.startswith("cpu_") else int
    assert all(isinstance(v, kind) for v in vals)
    if field == "cpu_user_s":
        assert vals[0] > 0  # a process that got this far has burnt some


@pytest.mark.parametrize("field", ["majflt", "nvcsw"])
def test_the_gate_carries_no_field_without_a_reader(tmp_path, monkeypatch, field):
    assert all(field not in g["attrs"] for g in _three_gates(tmp_path, monkeypatch))


def test_one_getrusage_a_gate(tmp_path, monkeypatch):
    import resource

    from torchft_tpu import manager

    calls = []
    real = resource.getrusage

    def counting(who):
        calls.append(who)
        return real(who)

    monkeypatch.setattr(manager.resource, "getrusage", counting)
    assert len(_three_gates(tmp_path, monkeypatch)) == 3
    assert len(calls) == 3
