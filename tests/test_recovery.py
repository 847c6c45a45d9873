"""Recovery forensics plane: the failure-episode detector
(telemetry.detect_episodes), heal-transfer accounting (``heal_xfer``
events from both checkpoint transports), the episode report / Chrome
trace overlay (tools/recovery_report.py, tools/obs_trace.py) and the
obs_top TTR-budget column. (The recovery drill's budgets:
tests/test_drill_budgets.py.)

The synthetic journals pin EXACT ground truth: a kill+heal fixture
whose phase windows are known by construction (including an aborted
first heal attempt with a latched cause), so TTR decomposition, primary
election, root-cause attribution, and cascade edges are asserted to
equality — and the committed CHAOS_SOAK.json schedule (benign chaos, no
kills) doubles as the no-false-positive guard."""

import json
import os
import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from torchft_tpu import telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import obs_report  # noqa: E402
import obs_top  # noqa: E402
import obs_trace  # noqa: E402
import recovery_report  # noqa: E402


# ---------------------------------------------------------------------------
# Synthetic journals (ts in absolute seconds)
# ---------------------------------------------------------------------------


def _ev(event, ts, step=None, rid="0", trace=None, **attrs):
    return {
        "ts": ts, "event": event, "step": step, "replica_id": rid,
        "trace": trace, "attrs": attrs,
    }


def kill_heal_fixture():
    """Replica 1 is SIGKILLed around t=100 and relaunches: its journal
    resumes at the quorum_start of the healing incarnation. Replica 0
    survives, latches the fallout (failed allreduce, pg_abort, failed
    gate), waits out the re-quorum, and donates the checkpoint. The
    first heal attempt is chaos-aborted (cause latched), the second
    succeeds with full transfer accounting. Both replicas commit at
    t=107."""
    r0 = [
        _ev("commit_gate", 99.0, step=11, rid="0", elapsed_s=0.05,
            committed=True),
        _ev("allreduce_complete", 100.2, step=12, rid="0", elapsed_s=0.1,
            ok=False),
        _ev("pg_abort", 100.3, rid="0"),
        _ev("commit_gate", 100.4, step=12, rid="0", elapsed_s=0.05,
            committed=False),
        _ev("quorum_start", 102.0, rid="0"),
        _ev("quorum_ready", 104.0, rid="0", elapsed_s=2.0, heal=False,
            quorum_id=7, max_step=12),
        _ev("pg_configure", 104.5, rid="0", elapsed_s=0.4),
        _ev("heal_send_start", 105.0, rid="0"),
        _ev("heal_send_done", 106.5, rid="0", elapsed_s=1.5,
            nbytes=1 << 26),
        _ev("commit_gate", 107.0, step=12, rid="0", elapsed_s=0.05,
            committed=True),
    ]
    r1 = [
        _ev("quorum_start", 103.0, rid="1"),
        _ev("quorum_ready", 104.0, rid="1", elapsed_s=1.0, heal=True,
            quorum_id=7, max_step=12, trace="q7.s12"),
        _ev("pg_configure", 104.4, rid="1", elapsed_s=0.3),
        _ev("chaos_inject", 104.55, rid="1", kind="abort_heal",
            plane="heal", site="recv"),
        _ev("heal_failed", 104.6, rid="1", cause="ChaosError",
            phase="plan", error="chaos: abort_heal@heal:recv"),
        _ev("commit_gate", 104.8, step=12, rid="1", elapsed_s=0.05,
            committed=False),
        _ev("quorum_ready", 105.4, rid="1", elapsed_s=0.4, heal=True,
            quorum_id=8, max_step=12),
        _ev("heal_start", 105.6, rid="1", max_step=12),
        _ev("heal_xfer", 106.5, step=12, rid="1", dir="recv",
            transport="http", nbytes=1 << 26, elapsed_s=0.8, wire_s=0.7,
            ser_s=0.05, lock_s=0.0, retries=2),
        _ev("heal_done", 106.6, rid="1", elapsed_s=1.0, peer=0,
            max_step=12),
        _ev("commit_gate", 107.0, step=12, rid="1", elapsed_s=0.05,
            committed=True),
    ]
    return r0 + r1


# ---------------------------------------------------------------------------
# Episode detector
# ---------------------------------------------------------------------------


def test_detector_kill_heal_fixture():
    eps = telemetry.detect_episodes(kill_heal_fixture())
    assert len(eps) == 1
    ep = eps[0]
    # The relaunched healer is the primary; the kill is the root cause,
    # dated at the first fleet-wide evidence (survivor's failed step).
    assert ep["primary"] == "1"
    assert ep["root_cause"]["kind"] == "process_loss"
    assert ep["root_cause"]["replica"] == "1"
    assert ep["root_cause"]["ts"] == pytest.approx(100.2)
    assert not ep["open"]
    assert ep["t_start"] == pytest.approx(100.2)
    assert ep["t_end"] == pytest.approx(107.0)
    assert ep["ttr_s"] == pytest.approx(6.8)
    # Cascade: fallout on the survivor, never before the root cause.
    assert [(c["from"], c["to"]) for c in ep["cascade"]] == [("1", "0")]
    assert ep["cascade"][0]["dt_s"] >= 0.0
    # The donor's send spans are attributed.
    assert any(d["replica"] == "0" for d in ep["donors"])
    # Survivor decomposition: 1.8 detect (failure -> quorum_start),
    # 2.0 quorum, 0.4 rebuild, the rest catchup.
    p0 = ep["replicas"]["0"]["phases"]
    assert p0["detect"] == pytest.approx(1.8)
    assert p0["quorum"] == pytest.approx(2.0)
    assert p0["rebuild"] == pytest.approx(0.4)
    assert p0["transfer"] == pytest.approx(0.0)
    assert p0["catchup"] == pytest.approx(2.6)
    # Healer decomposition: two quorum waits, one transfer, one rebuild.
    p1 = ep["replicas"]["1"]["phases"]
    assert p1["quorum"] == pytest.approx(1.4)
    assert p1["transfer"] == pytest.approx(1.0)
    assert p1["rebuild"] == pytest.approx(0.3)
    assert p1["detect"] == pytest.approx(0.0)
    assert ep["replicas"]["1"]["ttr_s"] == pytest.approx(4.0)


def test_phases_tile_ttr_exactly():
    eps = telemetry.detect_episodes(kill_heal_fixture())
    for ep in eps:
        for row in ep["replicas"].values():
            total = row["t_end"] - row["t_start"]
            assert sum(row["phases"].values()) == pytest.approx(
                total, abs=1e-9
            )
    report = recovery_report.analyze(kill_heal_fixture())
    assert recovery_report.check(report) == []


def test_failed_attempt_latches_cause_and_phase():
    ep = telemetry.detect_episodes(kill_heal_fixture())[0]
    attempts = ep["replicas"]["1"]["attempts"]
    assert [a["ok"] for a in attempts] == [False, True]
    assert attempts[0]["cause"] == "ChaosError"
    assert attempts[0]["phase"] == "plan"
    assert attempts[1]["peer"] == 0
    assert ep["replicas"]["1"]["failed_attempts"] == 1


def test_xfer_accounting_and_bandwidth():
    ep = telemetry.detect_episodes(kill_heal_fixture())[0]
    x = ep["replicas"]["1"]["xfer"]
    assert x["nbytes"] == 1 << 26
    assert x["transport"] == "http"
    assert x["retries"] == 2
    # 64 MiB in 0.8 s = 0.078125 GiB/s.
    assert x["gib_s"] == pytest.approx((1 / 16) / 0.8)
    summ = recovery_report.analyze(kill_heal_fixture())["summary"]
    assert summ["heal_gib_s"]["http"]["n"] == 1
    assert summ["heal_gib_s"]["http"]["bytes"] == 1 << 26


def test_chaos_root_cause_without_relaunch():
    # No kill: a survivor latches a failure right after an injection.
    evs = [
        _ev("chaos_inject", 10.0, rid="0", kind="reset", plane="data",
            site="allreduce"),
        _ev("allreduce_complete", 10.5, step=3, rid="0", elapsed_s=0.1,
            ok=False),
        _ev("pg_abort", 10.6, rid="0"),
        _ev("quorum_start", 10.7, rid="0"),
        _ev("quorum_ready", 12.0, rid="0", elapsed_s=1.0, heal=False),
        _ev("commit_gate", 12.5, step=3, rid="0", elapsed_s=0.05,
            committed=True),
    ]
    eps = telemetry.detect_episodes(evs)
    assert len(eps) == 1
    root = eps[0]["root_cause"]
    assert root["kind"] == "chaos"
    assert root["chaos"]["kind"] == "reset"
    assert root["ts"] == pytest.approx(10.0)


def test_open_episode_at_journal_end():
    evs = [
        _ev("quorum_start", 50.0, rid="1"),
        _ev("quorum_ready", 51.0, rid="1", elapsed_s=1.0, heal=True),
        _ev("heal_failed", 51.5, rid="1", cause="TimeoutError",
            phase="transfer", error="recv timed out"),
    ]
    eps = telemetry.detect_episodes(evs)
    assert len(eps) == 1 and eps[0]["open"]
    report = recovery_report.analyze(evs)
    assert report["summary"]["num_open"] == 1
    # Tiling holds for in-progress episodes too.
    assert recovery_report.check(report) == []


def test_committed_commits_without_impact_are_not_episodes():
    evs = [
        _ev("quorum_start", 1.0, rid="0"),
        _ev("quorum_ready", 1.2, rid="0", elapsed_s=0.2, heal=False),
        _ev("commit_gate", 2.0, step=1, rid="0", elapsed_s=0.05,
            committed=True),
        _ev("commit_gate", 3.0, step=2, rid="0", elapsed_s=0.05,
            committed=True),
    ]
    assert telemetry.detect_episodes(evs) == []


def test_committed_chaos_soak_schedule_is_not_an_episode():
    """The committed CHAOS_SOAK.json fired benign control/data-plane
    faults (no kills, no heal kinds) and every step still committed —
    replaying its injection schedule through the detector must find
    ZERO episodes (the false-positive guard)."""
    with open(os.path.join(REPO, "CHAOS_SOAK.json")) as f:
        soak = json.load(f)
    assert soak["kills"] == 0
    evs = []
    for g, injs in soak["injections"].items():
        for inj in injs:
            evs.append(_ev(
                "chaos_inject", float(inj["ts"]), step=inj.get("step"),
                rid=str(g), kind=inj["kind"], plane=inj["plane"],
                site=inj["site"],
            ))
            # The soak's I3 invariant: a commit follows every injection.
            evs.append(_ev(
                "commit_gate", float(inj["ts"]) + 0.5,
                step=inj.get("step"), rid=str(g), elapsed_s=0.05,
                committed=True,
            ))
    assert len(evs) > 20
    assert telemetry.detect_episodes(evs) == []


def test_check_catches_broken_tiling_and_unlatched_cause():
    report = recovery_report.analyze(kill_heal_fixture())
    row = report["episodes"][0]["replicas"]["1"]
    row["phases"]["catchup"] += 0.5
    row["attempts"][0]["cause"] = None
    errs = recovery_report.check(report)
    assert any("phases sum" in e for e in errs)
    assert any("without a latched cause" in e for e in errs)


# ---------------------------------------------------------------------------
# Rotation-aware journal loading
# ---------------------------------------------------------------------------


def test_load_events_reads_rotated_segment_first(tmp_path):
    live = tmp_path / "journal_replica0_rank0.jsonl"
    old = tmp_path / "journal_replica0_rank0.jsonl.1"
    old.write_text(
        json.dumps({"ts": 1.0, "event": "quorum_start",
                    "replica_id": "0"}) + "\n"
        + json.dumps({"ts": 2.0, "event": "quorum_ready",
                      "replica_id": "0"}) + "\n"
    )
    live.write_text(
        json.dumps({"ts": 3.0, "event": "commit_gate",
                    "replica_id": "0"}) + "\n"
    )
    # Directory scan and explicit live-file path both pull in the `.1`
    # segment, rotated events first.
    for paths in ([str(tmp_path)], [str(live)]):
        evs = obs_report.load_events(paths)
        assert [e["ts"] for e in evs] == [1.0, 2.0, 3.0]
    # An explicitly-listed `.1` file is not read twice.
    evs = obs_report.load_events([str(old), str(live)])
    assert [e["ts"] for e in evs] == [1.0, 2.0, 3.0]


# ---------------------------------------------------------------------------
# heal_xfer emission from the real transports
# ---------------------------------------------------------------------------


def _sample_state():
    return {
        "model": {
            "w": np.arange(4096, dtype=np.float32).reshape(64, 64),
            "b": np.zeros(64, dtype=np.float32),
        },
        "step": 7,
    }


def _read_journal(path):
    out = []
    with open(path) as f:
        for line in f:
            out.append(json.loads(line))
    return out


def test_http_transport_emits_heal_xfer(tmp_path, monkeypatch):
    from torchft_tpu.checkpointing.http_transport import HTTPTransport

    journal = tmp_path / "j.jsonl"
    monkeypatch.setenv("TORCHFT_JOURNAL_FILE", str(journal))
    telemetry.reset_event_log()
    sender = HTTPTransport(num_chunks=2)
    receiver = HTTPTransport()
    try:
        state = _sample_state()
        sender.send_checkpoint([1], step=7, state_dict=state, timeout=10)
        got = receiver.recv_checkpoint(
            src_rank=0, metadata=sender.metadata(), step=7, timeout=10
        )
        np.testing.assert_array_equal(
            got["model"]["w"], state["model"]["w"]
        )
    finally:
        sender.shutdown()
        receiver.shutdown()
        telemetry.reset_event_log()
    xfers = [e for e in _read_journal(journal)
             if e["event"] == "heal_xfer"]
    by_dir = {}
    for e in xfers:
        by_dir.setdefault(e["attrs"]["dir"], []).append(e["attrs"])
    # Staging on the donor, one send per served request, one recv total.
    assert set(by_dir) == {"stage", "send", "recv"}
    recv = by_dir["recv"][0]
    assert recv["transport"] == "http"
    assert recv["nbytes"] > 0
    assert recv["elapsed_s"] > 0
    assert recv["wire_s"] >= 0 and recv["ser_s"] >= 0
    assert recv["retries"] == 0
    assert recv["chunks"] and all(
        c["nbytes"] > 0 for c in recv["chunks"]
    )
    # Bytes served == bytes received (same wire).
    assert sum(s["nbytes"] for s in by_dir["send"]) == recv["nbytes"]


def test_pg_transport_emits_heal_xfer(tmp_path, monkeypatch):
    from torchft_tpu.checkpointing.pg_transport import PGTransport
    from torchft_tpu.process_group import ProcessGroupSocket
    from torchft_tpu.store import TCPStoreServer

    journal = tmp_path / "j.jsonl"
    monkeypatch.setenv("TORCHFT_JOURNAL_FILE", str(journal))
    telemetry.reset_event_log()
    store = TCPStoreServer()
    pgs = [ProcessGroupSocket(timeout=10.0) for _ in range(2)]

    def configure(rank):
        pgs[rank].configure(f"{store.address()}/ckpt", rank, 2)

    try:
        with ThreadPoolExecutor(max_workers=2) as pool:
            list(pool.map(configure, range(2)))
        state = _sample_state()
        sender = PGTransport(pgs[0], timeout=10.0)
        receiver = PGTransport(pgs[1], timeout=10.0)
        with ThreadPoolExecutor(max_workers=2) as pool:
            fs = pool.submit(
                sender.send_checkpoint, [1], 2, state, 10
            )
            fr = pool.submit(receiver.recv_checkpoint, 0, "<n/a>", 2, 10)
            fs.result(timeout=30)
            got = fr.result(timeout=30)
        np.testing.assert_array_equal(
            got["model"]["w"], state["model"]["w"]
        )
    finally:
        for pg in pgs:
            pg.shutdown()
        store.shutdown()
        telemetry.reset_event_log()
    xfers = {e["attrs"]["dir"]: e["attrs"]
             for e in _read_journal(journal)
             if e["event"] == "heal_xfer"}
    assert set(xfers) == {"send", "recv"}
    assert xfers["send"]["transport"] == "pg"
    assert xfers["recv"]["nbytes"] == xfers["send"]["nbytes"] > 0
    assert xfers["recv"]["wire_s"] >= 0
    assert xfers["recv"]["ser_s"] >= 0


# ---------------------------------------------------------------------------
# Report artifacts: emit, trace overlay, obs_top column, ledger gate
# ---------------------------------------------------------------------------


def test_emit_recovery_episode_events(tmp_path):
    report = recovery_report.analyze(kill_heal_fixture())
    out = tmp_path / "episodes.jsonl"
    n = recovery_report.emit_episodes(report, str(out))
    assert n == 1
    evs = _read_journal(out)
    assert [e["event"] for e in evs] == ["recovery_episode"]
    a = evs[0]["attrs"]
    assert evs[0]["replica_id"] == "1"
    assert a["ttr_ms"] == pytest.approx(6800.0)
    assert a["root_cause"] == "process_loss"
    # The emitted phase decomposition is the primary's and re-tiles.
    phase_ms = sum(
        a[f"{ph}_ms"] for ph in telemetry.RECOVERY_PHASES
    )
    assert phase_ms == pytest.approx(4000.0, abs=1e-3)


def test_obs_trace_episode_overlay_validates():
    trace = obs_trace.build_trace(kill_heal_fixture())
    assert obs_trace.validate_trace(trace) == []
    evs = trace["traceEvents"]
    phase_spans = [e for e in evs if e.get("cat") == "episode"
                   and e["ph"] == "X"]
    assert {e["name"] for e in phase_spans} <= set(
        telemetry.RECOVERY_PHASES
    )
    # Both replicas got a recovery track, the root cause is marked, and
    # the episode flow chain binds marker -> primary phases.
    assert len({e["pid"] for e in phase_spans}) == 2
    assert any(e["ph"] == "i" and e["name"] == "root_cause:process_loss"
               for e in evs)
    flow = [e for e in evs if e.get("cat") == "episode-flow"]
    assert [e["ph"] for e in flow][:1] == ["s"]
    assert [e["ph"] for e in flow][-1:] == ["f"]
    assert len({e["id"] for e in flow}) == 1


def test_obs_top_ttr_budget_column():
    fleet = {
        "replicas": {
            "r0": {"digest": {"step": 10, "rate": 1.0,
                              "ph": {"h": [50.0, 70.0]}},
                   "flags": [], "last_hb_age_ms": 100},
            "r1": {"digest": {"step": 12, "rate": 1.1,
                              "ph": {"h": [1.0, 4.2]}},
                   "flags": [], "last_hb_age_ms": 90},
        },
        "agg": {"n": 2, "n_digest": 2, "stragglers": 0,
                "median_step": 12},
    }
    frame = obs_top.render(fleet, top=0, ttr_budget_s=60.0)
    r0 = next(ln for ln in frame.splitlines() if ln.startswith("r0"))
    r1 = next(ln for ln in frame.splitlines() if ln.startswith("r1"))
    assert "70.0/60" in r0 and "TTR_BUDGET" in r0
    assert "4.2/60" in r1 and "TTR_BUDGET" not in r1
    assert obs_top.check_frame(fleet, frame, ttr_budget_s=60.0) == []
    # A frame that drops the over-budget tag must fail the check.
    bad = frame.replace(" TTR_BUDGET", "")
    assert obs_top.check_frame(fleet, bad, ttr_budget_s=60.0)
