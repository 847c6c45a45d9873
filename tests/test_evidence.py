"""Tests for the failure-evidence plane: the lighthouse signal bus
(piggyback ingest, proc_death leaves, cadence-aware hb_lapse eviction,
ring overflow accounting), wire back-compat in both directions, the
manager's evidence RPCs, the detect drill's seeded determinism, and the
detection-latency attribution report."""

import json
import os
import re
import sys
import time
import urllib.request

import pytest

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(__file__)), "tools")
)

from torchft_tpu.coordination import (
    LighthouseClient,
    LighthouseServer,
    ManagerClient,
    ManagerServer,
)
from torchft_tpu.telemetry import SIGNAL_SOURCES, EventLog


@pytest.fixture
def lighthouse():
    server = LighthouseServer(
        min_replicas=2, join_timeout_ms=200, quorum_tick_ms=20,
        fleet_snap_ms=0,
    )
    yield server
    server.shutdown()


def _dg(step, rate, cf=0):
    d = {"v": 1, "step": step, "rate": rate, "gp": 1.0, "err": 0}
    if cf:
        d["cf"] = cf
    return d


def _sig(source, subject="", site="", detail=None):
    s = {"source": source}
    if subject:
        s["replica_id"] = subject
    if site:
        s["site"] = site
    if detail is not None:
        s["detail"] = detail
    return s


# ---------------------------------------------------------------------------
# Signal bus: ingest, attribution fields, enum closure
# ---------------------------------------------------------------------------


def test_heartbeat_piggyback_signal_ingested(lighthouse):
    """Evidence riding a survivor's heartbeat frame lands in the ring
    with source, subject, observation site and a monotone seq; the
    subject's fleet row carries it in its SIGNAL cell."""
    c = LighthouseClient(lighthouse.address())
    c.heartbeat("alive", digest=_dg(1, 1.0), hb_interval_ms=60000)
    c.heartbeat("victim", digest=_dg(1, 1.0), hb_interval_ms=60000)
    c.heartbeat(
        "alive", digest=_dg(2, 1.0), hb_interval_ms=60000,
        signals=[_sig("native_abort", subject="victim",
                      site="manager:alive", detail={"msg": "abort"})],
    )
    fleet = c.fleet()
    assert fleet["signal_seq"] == 1
    [rec] = fleet["signals"]
    assert rec["source"] == "native_abort"
    assert rec["replica_id"] == "victim"
    assert rec["site"] == "manager:alive"
    assert rec["seq"] == 1
    assert rec["ts_ms"] > 0
    assert fleet["signal_counts"] == {"native_abort": 1}
    # Attribution lands on the SUBJECT's row, not the reporter's.
    assert fleet["replicas"]["victim"]["signal"] == "native_abort"
    assert fleet["replicas"]["victim"]["signal_age_ms"] >= 0
    assert fleet["replicas"]["alive"]["signal"] is None
    # Ingested evidence must NOT evict the subject (a healer's self-
    # signal or a flaky reporter must never kill a live survivor).
    assert "victim" in fleet["replicas"]
    c.close()


def test_unknown_signal_source_dropped(lighthouse):
    """The source enum is closed: an unknown source is dropped at ingest
    instead of poisoning the ring (and the py/cc enums agree)."""
    c = LighthouseClient(lighthouse.address())
    c.heartbeat(
        "r0", digest=_dg(1, 1.0), hb_interval_ms=60000,
        signals=[_sig("made_up_source", subject="r0"),
                 _sig("rpc_error", subject="r0")],
    )
    fleet = c.fleet()
    assert fleet["signal_seq"] == 1
    assert [r["source"] for r in fleet["signals"]] == ["rpc_error"]
    assert set(fleet["signal_counts"]) <= set(SIGNAL_SOURCES)
    c.close()


def test_dead_leave_signals_proc_death_planned_drain_does_not(lighthouse):
    """A leave filed on a corpse's behalf (reason="trainer died") is
    failure evidence; a planned drain stays signal-free."""
    c = LighthouseClient(lighthouse.address())
    c.heartbeat("planned", digest=_dg(1, 1.0), hb_interval_ms=60000)
    c.heartbeat("corpse", digest=_dg(1, 1.0), hb_interval_ms=60000)
    c.leave("planned")  # planned drain: no evidence
    fleet = c.fleet()
    assert fleet["signal_seq"] == 0
    c.leave("corpse", reason="trainer died")
    fleet = c.fleet()
    assert fleet["signal_seq"] == 1
    [rec] = fleet["signals"]
    assert rec["source"] == "proc_death"
    assert rec["replica_id"] == "corpse"
    assert rec["site"] == "lighthouse.leave"
    # Both are gone from the tables either way.
    assert "corpse" not in fleet["replicas"]
    assert "planned" not in fleet["replicas"]
    c.close()


# ---------------------------------------------------------------------------
# Cadence-aware eviction + wire back-compat (old client direction)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_hb_lapse_evicts_declared_cadence_only(monkeypatch):
    """A replica that DECLARED a heartbeat cadence and blew the evidence
    budget is evicted with an hb_lapse signal; an old client that never
    declared one (pre-signal wire format) keeps the timeout path — the
    back-compat contract for old senders."""
    monkeypatch.setenv("TORCHFT_LH_EVICT_FLOOR_MS", "400")
    server = LighthouseServer(
        min_replicas=2, join_timeout_ms=200, quorum_tick_ms=20,
        heartbeat_timeout_ms=60000, fleet_snap_ms=0,
    )
    try:
        c = LighthouseClient(server.address())
        # New client declares 50ms cadence; old client declares nothing.
        c.heartbeat("modern", digest=_dg(1, 1.0), hb_interval_ms=50)
        c.heartbeat("legacy", digest=_dg(1, 1.0))
        deadline = time.time() + 10.0
        fleet = c.fleet()
        while time.time() < deadline:
            fleet = c.fleet()
            if any(r["source"] == "hb_lapse"
                   for r in fleet.get("signals") or []):
                break
            time.sleep(0.05)
        lapse = [r for r in fleet["signals"] if r["source"] == "hb_lapse"]
        assert [r["replica_id"] for r in lapse] == ["modern"]
        assert lapse[0]["site"] == "lighthouse.fleet_scan"
        assert lapse[0]["detail"]["gap_ms"] > lapse[0]["detail"]["budget_ms"]
        # The fleet row survives eviction as detection forensics, wearing
        # the evidence that killed its quorum entry; the legacy row keeps
        # no evidence — only the (here 60s) heartbeat timeout may reap it.
        assert fleet["replicas"]["modern"]["signal"] == "hb_lapse"
        assert fleet["replicas"]["legacy"]["signal"] is None
        # Rise-edge-only: once the quorum-plane entry is gone the scan
        # must not re-signal the same lapse every tick.
        time.sleep(0.5)
        fleet = c.fleet()
        assert [r["replica_id"] for r in fleet["signals"]
                if r["source"] == "hb_lapse"] == ["modern"]
        c.close()
    finally:
        server.shutdown()


# ---------------------------------------------------------------------------
# Ring overflow surfaced like the anomaly ring
# ---------------------------------------------------------------------------


def test_signal_ring_overflow_is_counted(lighthouse):
    """Overflowing the 64-record signal ring surfaces a drop counter in
    /fleet.json and /metrics instead of silently losing evidence, and
    the ring keeps the NEWEST records."""
    c = LighthouseClient(lighthouse.address())
    c.heartbeat("rep", digest=_dg(1, 1.0), hb_interval_ms=60000)
    for i in range(70):
        c.heartbeat(
            "rep", digest=_dg(i + 2, 1.0), hb_interval_ms=60000,
            signals=[_sig("rpc_error", subject="rep",
                          site=f"client:{i}")],
        )
    fleet = c.fleet()
    assert fleet["signal_seq"] == 70
    assert len(fleet["signals"]) == 64
    assert fleet["agg"]["signals_dropped"] == 6
    assert fleet["signals"][-1]["seq"] == 70
    assert fleet["signals"][0]["seq"] == 7
    assert fleet["signal_counts"]["rpc_error"] == 70
    with urllib.request.urlopen(
        f"http://{lighthouse.address()}/metrics", timeout=5
    ) as resp:
        metrics = resp.read().decode()
    assert "torchft_lighthouse_signals_total 70" in metrics
    assert 'torchft_lighthouse_signals_total{source="rpc_error"} 70' \
        in metrics
    assert "torchft_lighthouse_signals_dropped 6" in metrics
    c.close()


def test_obs_export_signal_gauges_and_overflow_journal(tmp_path):
    """The exporter mirrors the signal plane the way it mirrors the
    anomaly plane: per-source gauges, a dropped gauge, seq-cursor
    failure_signal journaling, and rise-edge signal_overflow events."""
    import obs_export

    fleet = {
        "job": "default", "signal_seq": 3,
        "agg": {"n": 2, "stragglers": 0, "anomalies_dropped": 0,
                "signals_dropped": 2},
        "replicas": {}, "anomalies": [],
        "signal_counts": {"proc_death": 1, "rpc_error": 2},
        "signals": [
            {"seq": 2, "source": "rpc_error", "replica_id": "r1",
             "site": "client:heartbeat", "ts_ms": 100},
            {"seq": 3, "source": "proc_death", "replica_id": "r0",
             "site": "lighthouse.leave", "ts_ms": 200},
        ],
    }
    text = obs_export.render_fleet_prometheus(fleet, max_replicas=64)
    assert 'torchft_exporter_fleet_signals_total{job="default"} 3' in text
    assert ('torchft_exporter_fleet_signals_dropped{job="default"} 2'
            in text)
    assert ('torchft_exporter_fleet_signals_by_source{job="default",'
            'source="proc_death"} 1' in text)
    assert ('torchft_exporter_fleet_signals_by_source{job="default",'
            'source="rpc_error"} 2' in text)

    path = str(tmp_path / "sig.jsonl")
    log = EventLog(path, replica_id="exporter")
    cursor = obs_export.journal_signals(log, fleet, 0)
    assert cursor == 3
    # Cursor advanced: re-journaling is a no-op (restart semantics).
    assert obs_export.journal_signals(log, fleet, cursor) == 3
    mark = obs_export.journal_signal_overflow(log, fleet, 0)
    assert mark == 2
    assert obs_export.journal_signal_overflow(log, fleet, mark) == 2
    fleet["agg"]["signals_dropped"] = 5
    assert obs_export.journal_signal_overflow(log, fleet, mark) == 5
    log.close()
    lines = [json.loads(line) for line in open(path)]
    kinds = [ln["event"] for ln in lines]
    assert kinds == ["failure_signal", "failure_signal",
                     "signal_overflow", "signal_overflow"]
    assert lines[0]["attrs"]["source"] == "rpc_error"
    assert lines[1]["attrs"]["subject"] == "r0"
    assert lines[2]["attrs"]["new_drops"] == 2
    assert lines[3]["attrs"]["new_drops"] == 3
    # No fleet / no journal: safe no-ops.
    assert obs_export.journal_signals(None, None, 7) == 7
    assert obs_export.journal_signal_overflow(None, None, 7) == 7


def test_obs_top_signal_column_checked():
    """The SIGNAL column and recent-signals tail render and are covered
    by --once --check's frame validation."""
    import obs_top

    fleet = {
        "job": "default", "anomaly_seq": 0, "signal_seq": 2,
        "agg": {"n": 2, "n_digest": 2, "stragglers": 0,
                "quorum_world": 2, "joins_total": 0, "leaves_total": 0,
                "epoch": 1, "median_rate": 1.0, "median_step": 5,
                "anomalies_dropped": 0, "signals_dropped": 1},
        "replicas": {
            "r0": {"straggler": False, "flags": [],
                   "digest": {"step": 5, "rate": 1.0, "gp": 1.0},
                   "last_hb_age_ms": 40,
                   "signal": "proc_death", "signal_age_ms": 120},
            "r1": {"straggler": False, "flags": [],
                   "digest": {"step": 5, "rate": 1.0, "gp": 1.0},
                   "last_hb_age_ms": 40},
        },
        "anomalies": [],
        "signals": [
            {"seq": 1, "source": "rpc_error", "replica_id": "r0",
             "site": "client:x", "ts_ms": 1},
            {"seq": 2, "source": "proc_death", "replica_id": "r0",
             "site": "lighthouse.leave", "ts_ms": 2},
        ],
    }
    frame = obs_top.render(fleet, color=False)
    assert obs_top.check_frame(fleet, frame) == []
    head = frame.splitlines()[0]
    assert "signals=2" in head and "sig_dropped=1" in head
    r0 = next(ln for ln in frame.splitlines() if ln.startswith("r0"))
    assert "proc_death" in r0
    assert "recent signals:" in frame
    assert "#2 proc_death subject=r0 site=lighthouse.leave" in frame
    # Dropping the SIGNAL cell or a tail line fails the check.
    broken = frame.replace("proc_death", "-")
    assert any("SIGNAL column" in p or "recent-signals" in p
               for p in obs_top.check_frame(fleet, broken))


# ---------------------------------------------------------------------------
# Manager evidence RPCs + back-compat (old lighthouse direction)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_manager_signal_rpc_relays_to_lighthouse(lighthouse):
    """A trainer-filed signal flows through the manager's bounded outbox
    onto the heartbeat frame and into the lighthouse ring, and the ACK
    feeds the manager's evidence_status cursor back up."""
    mgr = ManagerServer(
        replica_id="g0", lighthouse_addr=lighthouse.address(),
        store_address="127.0.0.1:0", world_size=1,
        heartbeat_interval_ms=50,
    )
    try:
        mc = ManagerClient(mgr.address())
        st = mc.evidence_status()
        assert st["signal_seq"] == 0
        assert st["outbox"] == 0
        mc.signal("native_abort", replica_id="g1",
                  site="trainer:g0", detail={"msg": "wedged"})
        deadline = time.time() + 10.0
        while time.time() < deadline:
            st = mc.evidence_status()
            if st["signal_seq"] >= 1 and st["outbox"] == 0:
                break
            time.sleep(0.05)
        assert st["signal_seq"] >= 1
        assert st["outbox"] == 0  # delivered and acked, not just queued
        assert (st.get("signal") or {}).get("source") == "native_abort"
        lc = LighthouseClient(lighthouse.address())
        fleet = lc.fleet()
        assert any(
            r["source"] == "native_abort" and r["replica_id"] == "g1"
            for r in fleet["signals"]
        )
        lc.close()
        # Empty source is refused, not silently queued.
        with pytest.raises(Exception):
            mc.signal("")
        mc.close()
    finally:
        mgr.shutdown()


def test_evidence_watcher_tolerates_pre_signal_acks():
    """Old-lighthouse direction of wire back-compat: an evidence_status
    shaped like a pre-signal server (no signal_seq, no signal) must
    neither fire nor crash the watcher; a hard signal about a CURRENT
    quorum peer fires exactly once; soft, self, and non-member signals
    (a relaunched peer's evicted previous incarnation) only advance the
    cursor."""
    from torchft_tpu.manager import _EvidenceWatcher

    class FakeManager:
        _replica_id = "self"
        _evidence_peers = {"self", "peer"}

        def __init__(self):
            self.aborts = 0
            self.journal = []

        def _journal(self, event, **attrs):
            self.journal.append((event, attrs))

        class _logger:  # noqa: N801 - attribute shim
            @staticmethod
            def info(msg):
                pass

        def _abort_pg_on_stall(self):
            self.aborts += 1

    class FakeClient:
        def __init__(self, responses):
            self.responses = list(responses)

        def evidence_status(self, timeout=1.0):
            return self.responses.pop(0)

    mgr = FakeManager()
    w = _EvidenceWatcher.__new__(_EvidenceWatcher)
    w._manager = mgr
    w._poll_s = 0.01
    w._base_seq = None
    w._fired = False
    w._client = FakeClient([
        {"ok": True},                                  # old server: no keys
        {"ok": True},                                  # still nothing
        {"ok": True, "signal_seq": 1,
         "signal": {"source": "rpc_error", "replica_id": "peer"}},  # soft
        {"ok": True, "signal_seq": 2,
         "signal": {"source": "proc_death", "replica_id": "self"}},  # self
        {"ok": True, "signal_seq": 3,
         "signal": {"source": "hb_lapse",
                    "replica_id": "peer:dead-uuid"}},  # hard, NON-member
        {"ok": True, "signal_seq": 4,
         "signal": {"source": "proc_death", "replica_id": "peer"}},  # HARD
        {"ok": True, "signal_seq": 5,
         "signal": {"source": "native_abort", "replica_id": "peer"}},
    ])
    w._poll_once()  # baselines at seq 0
    assert w._base_seq == 0
    w._poll_once()  # old server again: no rise, no fire
    assert mgr.aborts == 0
    w._poll_once()  # soft source: cursor advances only
    assert (w._base_seq, mgr.aborts) == (1, 0)
    w._poll_once()  # hard but SELF: cursor advances only
    assert (w._base_seq, mgr.aborts) == (2, 0)
    w._poll_once()  # hard but about a replica OUTSIDE the quorum
    assert (w._base_seq, mgr.aborts) == (3, 0)
    w._poll_once()  # hard peer evidence: abort fired
    assert mgr.aborts == 1
    assert [e for e, _ in mgr.journal] == ["failure_signal"]
    assert mgr.journal[0][1]["reaction"] == "pg_abort"
    w._poll_once()  # latched: one reaction per arming
    assert mgr.aborts == 1


# ---------------------------------------------------------------------------
# Detect drill determinism + attribution report
# ---------------------------------------------------------------------------


def test_detect_drill_schedule_deterministic():
    import detect_drill

    a = detect_drill.fault_schedule(4242, 8)
    b = detect_drill.fault_schedule(4242, 8)
    assert a == b
    assert detect_drill.fault_schedule(7, 8) != a
    # Every fault kind appears, every victim is unique, and every kind
    # maps to its documented first source.
    kinds = {f["kind"] for f in a}
    assert kinds == set(detect_drill.EXPECTED_SOURCE)
    assert len({f["victim"] for f in a}) == len(a)
    for f in a:
        assert f["expected_source"] == \
            detect_drill.EXPECTED_SOURCE[f["kind"]]


def test_detect_report_tiles_and_attributes():
    import detect_report

    base = 1000.0
    events = [
        {"event": "chaos_inject", "ts": base, "replica_id": "drill",
         "attrs": {"kind": "hb_stop", "plane": "detect", "site": "r1",
                   "expected_source": "hb_lapse"}},
        {"event": "failure_signal", "ts": base + 0.6,
         "replica_id": "exporter",
         "attrs": {"source": "hb_lapse", "subject": "r1",
                   "site": "lighthouse.fleet_scan", "seq": 1}},
        {"event": "quorum_ready", "ts": base + 0.9, "replica_id": "r0",
         "attrs": {"quorum_id": 2}},
        {"event": "heal_attempt", "ts": base + 1.4, "replica_id": "r1",
         "attrs": {}},
        # Second injection: never detected.
        {"event": "chaos_inject", "ts": base + 10.0,
         "replica_id": "drill",
         "attrs": {"kind": "digest_stall", "plane": "detect",
                   "site": "r2", "expected_source": "digest_anomaly"}},
    ]
    report = detect_report.analyze(events)
    row = report["rows"][0]
    assert row["source"] == "hb_lapse"
    assert row["signal_s"] == pytest.approx(0.6)
    assert row["quorum_s"] == pytest.approx(0.3)
    assert row["react_s"] == pytest.approx(0.5)
    assert row["total_s"] == pytest.approx(1.4)
    assert report["rows"][1]["source"] is None
    assert report["summary"]["matrix"]["hb_stop.hb_lapse"]["n"] == 1
    assert detect_report.check(report) == []
    # --require-detected flags the undetected injection.
    assert any("never detected" in e
               for e in detect_report.check(report, require_detected=True))
    # A first signal from the WRONG source fails the attribution check.
    events[1]["attrs"]["source"] = "rpc_error"
    bad = detect_report.analyze(events)
    assert any("expected 'hb_lapse'" in e for e in detect_report.check(bad))


def test_recovery_report_detect_attribution_split():
    """recovery_report splits the detect phase by winning signal source
    without disturbing the tiling invariant."""
    import recovery_report

    episodes = [
        {"id": "e0", "open": False, "t_start": 100.0, "t_end": 106.0,
         "ttr_s": 6.0, "primary": "r1",
         "replicas": {"r1": {"t_start": 100.0, "t_end": 106.0,
                             "ttr_s": 6.0, "attempts": [],
                             "phases": {"detect": 1.0, "quorum": 2.0,
                                        "transfer": 1.0, "rebuild": 1.0,
                                        "catchup": 1.0}}},
         "root_cause": {"kind": "chaos", "replica": "r1"}, "cascade": []},
    ]
    events = [
        {"event": "failure_signal", "ts": 99.5, "replica_id": "runner",
         "attrs": {"source": "proc_death", "subject": "r1",
                   "site": "runner.monitor"}},
    ]
    recovery_report.attribute_detect(events, episodes)
    ds = episodes[0]["detect_signal"]
    assert ds["source"] == "proc_death"
    assert ds["lead_s"] == pytest.approx(0.5)
    # A signal far before the window does not attach.
    episodes[0]["detect_signal"] = None
    recovery_report.attribute_detect(
        [{"event": "failure_signal", "ts": 10.0,
          "attrs": {"source": "hb_lapse", "subject": "r1"}}],
        episodes,
    )
    assert episodes[0]["detect_signal"] is None


# ---------------------------------------------------------------------------
# A dropped group explains itself: the eviction on both clocks
# ---------------------------------------------------------------------------

_LH_LINE = re.compile(r"^(\d{13}) \[lighthouse\] (.*)$")


def _lighthouse_lines(capfd):
    """What the lighthouse printed (it inherits this process's stderr):
    (wall-clock ms, text) of every line; a line without a clock fails."""
    out = []
    for line in capfd.readouterr().err.splitlines():
        if "[lighthouse]" not in line:
            continue
        m = _LH_LINE.match(line)
        assert m, f"a lighthouse line without a clock: {line!r}"
        out.append((int(m.group(1)), m.group(2)))
    return out


def _field(text, name):
    return int(re.search(rf"{name}=(-?\d+)", text).group(1))


def test_evicted_parked_quorum_request_is_put_back(monkeypatch, capfd):
    """ROADMAP S9(a), the thing itself: a live replica whose heartbeats
    lapse while its quorum request is parked is evicted with that request
    erased. Its next heartbeat re-admits it, tells it so in the ack (what
    was erased, the gap, how long it was out), and the parked request is
    registered again, so the peer's quorum forms at once instead of
    waiting out the evicted replica's quorum timeout."""
    import threading
    from concurrent.futures import ThreadPoolExecutor

    monkeypatch.setenv("TORCHFT_LH_EVICT_FLOOR_MS", "400")
    server = LighthouseServer(
        min_replicas=2, join_timeout_ms=60000, quorum_tick_ms=20,
        heartbeat_timeout_ms=60000, fleet_snap_ms=0,
    )
    stop = threading.Event()
    pool = ThreadPoolExecutor(max_workers=2)
    clients = [LighthouseClient(server.address()) for _ in range(4)]
    a, b, a_quorum, b_quorum = clients
    try:
        def beat_b():
            while not stop.is_set():
                b.heartbeat("b", hb_interval_ms=50)
                time.sleep(0.05)

        t_wall0 = time.time()
        a.heartbeat("a", hb_interval_ms=50)
        pool.submit(beat_b)
        parked = pool.submit(a_quorum.quorum, "a", timeout=15.0)
        # a's heartbeats stop (budget: max(400, 12 x 50) ms) while its
        # request is parked; b stays live and has not asked yet.
        watch = LighthouseClient(server.address())
        deadline = time.time() + 10.0
        lapse = []
        while time.time() < deadline and not lapse:
            lapse = [r for r in watch.fleet().get("signals") or []
                     if r["source"] == "hb_lapse"]
            time.sleep(0.02)
        watch.close()
        assert [r["replica_id"] for r in lapse] == ["a"]
        assert not parked.done()
        ack = a.heartbeat("a", hb_interval_ms=50)
        ev = ack["evicted"]
        assert ev["erased"] == "heartbeat+participant+quorum_request"
        assert ev["via"] == "heartbeat"
        assert ev["seq"] == lapse[0]["seq"]
        assert ev["budget_ms"] == 600
        assert ev["gap_ms"] >= ev["open_gap_ms"] > ev["budget_ms"]
        assert 0 <= ev["out_ms"] <= ev["gap_ms"]
        # Told once.
        assert "evicted" not in a.heartbeat("a", hb_interval_ms=50)
        # The repair: b's request completes the quorum with a's parked
        # one, long before either would time out.
        t0 = time.monotonic()
        q = b_quorum.quorum("b", timeout=5.0)
        assert time.monotonic() - t0 < 3.0
        assert sorted(m.replica_id for m in q.participants) == ["a", "b"]
        assert parked.result(timeout=5.0).quorum_id == q.quorum_id
    finally:
        stop.set()
        pool.shutdown(wait=True)
        for c in clients:
            c.close()
        server.shutdown()

    lines = _lighthouse_lines(capfd)
    assert all(t_wall0 * 1e3 - 5 <= ms <= time.time() * 1e3 + 5 for ms, _ in lines)

    def one(needle):
        (hit,) = [(ms, text) for ms, text in lines if needle in text]
        return hit

    t_sig, sig = one("hb_lapse on a")
    assert '"gap_ms"' in sig and '"budget_ms"' in sig
    t_ev, evicted = one("evicted a on evidence")
    assert "erased=heartbeat+participant+quorum_request" in evicted
    assert _field(evicted, "gap_ms") == ev["open_gap_ms"]
    t_re, readmit = one("re-admitted a by its heartbeat")
    assert _field(readmit, "out_ms") == ev["out_ms"]
    assert t_re - t_ev == pytest.approx(ev["out_ms"], abs=3)
    _, back = one("heartbeat of evicted a back")
    assert _field(back, "gap_ms") == ev["gap_ms"]
    one("re-registered the parked quorum request of a")
    assert t_sig <= t_ev <= t_re


def test_a_stalled_heartbeat_is_in_both_journals(tmp_path, monkeypatch, capfd):
    """Two live Managers in lockstep; the chaos plane stalls ONE heartbeat
    of the victim's manager server past the lighthouse's budget. The
    lighthouse's log has the lapse, the eviction and the re-admission with
    a clock; the victim's journal has ``lh_evicted`` with the lighthouse's
    gap beside its own sender's numbers; the peer's journal has the
    ``failure_signal``; every gate has the heartbeat counters, and they
    were reset at the gate."""
    import threading

    from torchft_tpu import chaos, telemetry
    from torchft_tpu.manager import Manager
    from torchft_tpu.process_group import ProcessGroupDummy

    path = str(tmp_path / "journal.jsonl")
    monkeypatch.setenv("TORCHFT_JOURNAL_FILE", path)
    telemetry.reset_event_log()
    server = LighthouseServer(
        min_replicas=2, join_timeout_ms=60000, quorum_tick_ms=20,
        heartbeat_timeout_ms=60000,
    )
    managers = []

    def make(name, chaos_spec=None):
        if chaos_spec:
            monkeypatch.setenv("TORCHFT_CHAOS", chaos_spec)
        try:
            m = Manager(
                pg=ProcessGroupDummy(), state_dict=lambda: {},
                load_state_dict=lambda s: None, min_replica_size=2,
                timeout=20.0, quorum_timeout=20.0, connect_timeout=10.0,
                replica_id=name, lighthouse_addr=server.address(),
                group_rank=0, group_world_size=1, init_sync=False,
            )
        finally:
            monkeypatch.delenv("TORCHFT_CHAOS", raising=False)
        managers.append(m)
        return m

    def loop(m, t_end):
        while time.monotonic() < t_end:
            m.start_quorum()
            m.should_commit()
            time.sleep(0.05)

    try:
        # The 31st heartbeat of the victim's server, ~3 s after it starts,
        # takes 1.5 s longer; the budget is max(1000, 12 x 100) ms.
        victim = make(
            "victim",
            "seed:7,spec:stall@ctrl:match=heartbeat:ms=1500:after=30:count=1",
        )
        peer = make("peer")
        t_end = time.monotonic() + 6.5
        threads = [threading.Thread(target=loop, args=(m, t_end))
                   for m in (victim, peer)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        for m in managers:
            m.shutdown()
        server.shutdown()
        telemetry.reset_event_log()
        chaos.reset()

    with open(path) as fh:
        events = [json.loads(line) for line in fh]

    def of(who, kind):
        return [e for e in events if e["event"] == kind
                and str(e["replica_id"]).startswith(who)]

    # Every gate says under what it was judged and how the heartbeats went.
    for who in ("victim", "peer"):
        gates = of(who, "commit_gate")
        assert len(gates) > 20
        for g in gates:
            a = g["attrs"]
            assert {"local_vote", "cause", "quorum_id", "participants",
                    "hb_rounds", "hb_gap_max_ms", "hb_rtt_max_ms", "hb_late",
                    "rss_peak_bytes"} <= set(a)
            assert (a["cause"] == "ok") == a["committed"]
            assert a["rss_peak_bytes"] > 2**20
        # Reset at the gate: steps take ~60 ms, so no gate after the first
        # can have counted more than a few 100 ms rounds, and the rounds of
        # all gates are the heartbeats of the run.
        assert max(g["attrs"]["hb_rounds"] for g in gates[1:]) <= 20
        assert sum(g["attrs"]["hb_rounds"] for g in gates) >= 30

    evicted = of("victim", "lh_evicted")
    assert evicted, "the victim never learned that it was evicted"
    ev = evicted[0]["attrs"]
    assert ev["budget_ms"] == 1200 and ev["gap_ms"] > 1200
    assert "heartbeat" in ev["erased"]
    own_gap = max(g["attrs"]["hb_gap_max_ms"] for g in of("victim", "commit_gate"))
    own_rtt = max(g["attrs"]["hb_rtt_max_ms"] for g in of("victim", "commit_gate"))
    assert own_gap > 1200 and own_rtt > 1200  # a stalled send is a long trip
    assert ev["gap_ms"] >= own_gap - 100
    assert ev["sender_rtt_ms"] == pytest.approx(own_rtt, abs=1.0)
    assert max(g["attrs"]["hb_late"] for g in of("victim", "commit_gate")) >= 1
    # The peer ran quietly, and read of the lapse in its own acks.
    assert max(g["attrs"]["hb_gap_max_ms"] for g in of("peer", "commit_gate")) < 1000
    seen = [e["attrs"] for e in of("peer", "failure_signal")
            if e["attrs"]["source"] == "hb_lapse"]
    assert seen and all(s["subject"].startswith("victim") for s in seen)
    assert seen[0]["site"] == "manager.gate"
    assert seen[0]["detail"]["gap_ms"] > seen[0]["detail"]["budget_ms"] == 1200
    assert len({s["seq"] for s in seen}) == len(seen)  # each journaled once
    assert not of("peer", "lh_evicted")

    lines = _lighthouse_lines(capfd)
    texts = [text for _, text in lines]
    assert any("hb_lapse on victim" in t for t in texts)
    assert any("evicted victim" in t and "erased=heartbeat" in t for t in texts)
    assert any("re-admitted victim" in t and "out_ms=" in t for t in texts)
    backs = [t for t in texts if "heartbeat of evicted victim" in t]
    assert _field(backs[0], "gap_ms") == ev["gap_ms"]
