"""Perf attribution plane: interval-overlap math (telemetry), the
critical-path profiler (tools/perf_report.py) and the trainers' MFU
module (torchft_tpu/perf.py).

The synthetic journals pin EXACT ground truth: each fixture constructs
events whose phase windows are known by construction (fully-hidden,
fully-exposed, partial overlap, multi-replica skew), so the attribution
numbers are asserted to equality, not plausibility."""

import json
import os
import sys

import pytest

from torchft_tpu import perf, telemetry

sys.path.insert(
    0,
    os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools",
    ),
)

import perf_report  # noqa: E402


# ---------------------------------------------------------------------------
# Synthetic journals (ts in absolute seconds; replica r0 unless noted)
# ---------------------------------------------------------------------------


def _ev(event, ts, step=0, rid="r0", **attrs):
    return {
        "ts": ts, "event": event, "step": step, "replica_id": rid,
        "attrs": attrs,
    }


def _step_events(
    t0, *, rid="r0", step=0, quorum_s=0.1, compute_s=0.0,
    issue_at=None, complete_at=None, wait_s=0.0, commit_s=0.05,
):
    """One step's journal: quorum wait, optional async allreduce window
    [issue_at, complete_at] whose final ``wait_s`` was blocked, then a
    commit gate. Times are offsets from t0."""
    evs = [
        _ev("quorum_start", t0, step=step, rid=rid),
        _ev("quorum_ready", t0 + quorum_s, step=step, rid=rid,
            elapsed_s=quorum_s),
    ]
    if issue_at is not None:
        evs.append(_ev("allreduce_issue", t0 + issue_at, step=step, rid=rid))
        evs.append(
            _ev("allreduce_complete", t0 + complete_at, step=step, rid=rid,
                elapsed_s=wait_s)
        )
        t_end = t0 + complete_at + commit_s
    else:
        t_end = t0 + quorum_s + compute_s + commit_s
    evs.append(
        _ev("commit_gate", t_end, step=step, rid=rid, elapsed_s=commit_s,
            committed=True)
    )
    return evs


def test_fully_hidden_allreduce():
    # quorum [0, 0.1]; allreduce in flight [0.1, 1.0] with ZERO blocked
    # wait (completion observed instantly at 1.0); commit [1.0, 1.05].
    # Compute = everything between quorum and commit = [0.1, 1.0].
    evs = _step_events(
        100.0, quorum_s=0.1, issue_at=0.1, complete_at=1.0, wait_s=0.0,
        commit_s=0.05,
    )
    attr = telemetry.comm_attribution(telemetry.step_phase_windows(evs))
    assert attr["total_s"] == pytest.approx(1.05)
    assert attr["quorum_s"] == pytest.approx(0.1)
    assert attr["allreduce_s"] == pytest.approx(0.0)  # nothing exposed
    assert attr["comm_inflight_s"] == pytest.approx(0.9)
    assert attr["comm_hidden_s"] == pytest.approx(0.9)
    assert attr["compute_s"] == pytest.approx(0.9)
    assert attr["overlap_frac"] == pytest.approx(1.0)
    assert attr["exposed_frac"] == pytest.approx(0.0)


def _blocked_steps():
    """Four steps on two replicas that do little but wait for a socket
    allreduce: per step 0.97 ms quorum, 1.65 ms grad compute, 190.44 ms
    blocked allreduce, 0.45+0.83 ms commit/apply."""
    evs = []
    t = 1000.0
    for step in range(4):
        for rid in ("r0", "r1"):
            q, g, ar, cm = 0.97e-3, 1.65e-3, 190.44e-3, (0.45 + 0.83) * 1e-3
            evs += [
                _ev("quorum_start", t, step=step, rid=rid),
                _ev("quorum_ready", t + q, step=step, rid=rid, elapsed_s=q),
                _ev("allreduce_issue", t + q + g, step=step, rid=rid),
                _ev("allreduce_complete", t + q + g + ar, step=step,
                    rid=rid, elapsed_s=ar),
                _ev("commit_gate", t + q + g + ar + cm, step=step, rid=rid,
                    elapsed_s=cm, committed=True),
            ]
        t += 0.2
    return evs


@pytest.mark.parametrize("evs, total_s, allreduce_s, compute_s, fingerprint", [
    # The trainer blocked for the ENTIRE in-flight window: issue at 0.1,
    # complete at 1.0, wait_s=0.9. No compute anywhere.
    # 86% allreduce, 10% quorum, 5% commit (rounded).
    pytest.param(
        _step_events(100.0, quorum_s=0.1, issue_at=0.1, complete_at=1.0,
                     wait_s=0.9, commit_s=0.05),
        1.05, 0.9, 0.0, "a86>q10>m5", id="one_step"),
    # A sliver of compute before a blocked allreduce 115 times its length:
    # the whole run reads ~0.98 exposed, on every step of both replicas.
    pytest.param(_blocked_steps(), 194.34e-3, 190.44e-3, 1.65e-3, "a98",
                 id="four_steps_two_replicas"),
])
def test_fully_exposed_allreduce(evs, total_s, allreduce_s, compute_s,
                                 fingerprint):
    first = [e for e in evs if e["step"] == 0 and e["replica_id"] == "r0"]
    attr = telemetry.comm_attribution(telemetry.step_phase_windows(first))
    assert attr["total_s"] == pytest.approx(total_s)
    assert attr["allreduce_s"] == pytest.approx(allreduce_s)
    assert attr["comm_hidden_s"] == pytest.approx(0.0)
    assert attr["compute_s"] == pytest.approx(compute_s)
    assert attr["overlap_frac"] == pytest.approx(0.0)
    assert attr["exposed_frac"] == pytest.approx(allreduce_s / total_s)
    assert telemetry.dominant_exposed(attr) == (
        "allreduce", pytest.approx(allreduce_s)
    )
    report = perf_report.analyze(evs)
    assert perf_report.check(report) == []
    assert report["summary"]["exposed_allreduce_frac"] == pytest.approx(
        allreduce_s / total_s)
    assert report["summary"]["dominant_exposed"] == "allreduce"
    # Every step's critical-path fingerprint leads with exposed allreduce.
    for srec in report["steps"].values():
        assert srec["fingerprint"].startswith(fingerprint)


def test_partial_overlap_allreduce():
    # In flight [0.1, 1.0] (0.9 s); the last 0.3 s were blocked wait →
    # 0.6 s hidden under the [0.1, 0.7] compute span.
    evs = _step_events(
        100.0, quorum_s=0.1, issue_at=0.1, complete_at=1.0, wait_s=0.3,
        commit_s=0.05,
    )
    attr = telemetry.comm_attribution(telemetry.step_phase_windows(evs))
    assert attr["allreduce_s"] == pytest.approx(0.3)
    assert attr["comm_hidden_s"] == pytest.approx(0.6)
    assert attr["compute_s"] == pytest.approx(0.6)
    assert attr["overlap_frac"] == pytest.approx(0.6 / 0.9)
    # Tiling invariant: phases cover the step window exactly.
    tiled = sum(
        attr[k] for k in
        ("quorum_s", "heal_s", "allreduce_s", "commit_s", "compute_s")
    )
    assert tiled == pytest.approx(attr["total_s"], abs=1e-9)


def test_heal_window_and_priority_deoverlap():
    # A heal window overlapping the exposed allreduce must not double
    # count: heal has priority, allreduce keeps only its own remainder.
    evs = [
        _ev("quorum_start", 0.0),
        _ev("quorum_ready", 0.1, elapsed_s=0.1),
        _ev("heal_done", 0.5, elapsed_s=0.4, max_step=0),
        _ev("allreduce_issue", 0.3),
        _ev("allreduce_complete", 0.8, elapsed_s=0.5),
        _ev("commit_gate", 0.9, elapsed_s=0.1, committed=True),
    ]
    attr = telemetry.comm_attribution(telemetry.step_phase_windows(evs))
    assert attr["heal_s"] == pytest.approx(0.4)
    # Exposed wait [0.3, 0.8] minus heal [0.1, 0.5] = [0.5, 0.8].
    assert attr["allreduce_s"] == pytest.approx(0.3)
    assert attr["compute_s"] == pytest.approx(0.0)
    tiled = sum(
        attr[k] for k in
        ("quorum_s", "heal_s", "allreduce_s", "commit_s", "compute_s")
    )
    assert tiled == pytest.approx(attr["total_s"], abs=1e-9)


def test_late_shutdown_event_does_not_stretch_step():
    # A goodput event seconds after the last phase event must not inflate
    # compute (the step window is bounded by phase events only).
    evs = _step_events(
        100.0, quorum_s=0.1, issue_at=0.1, complete_at=1.0, wait_s=0.9,
    )
    evs.append(_ev("goodput", 170.0, committed_steps=1))
    attr = telemetry.comm_attribution(telemetry.step_phase_windows(evs))
    assert attr["total_s"] == pytest.approx(1.05)
    assert attr["compute_s"] == pytest.approx(0.0)


def test_multi_replica_skew_critical_path():
    # r0 compute-bound and fast; r1 allreduce-blocked and 3x slower →
    # r1 is the critical replica and the run-level dominant exposed
    # interval is its allreduce.
    evs = _step_events(
        100.0, rid="r0", quorum_s=0.05, issue_at=0.05, complete_at=0.4,
        wait_s=0.0, commit_s=0.05,
    ) + _step_events(
        100.0, rid="r1", quorum_s=0.05, issue_at=0.05, complete_at=1.3,
        wait_s=1.25, commit_s=0.05,
    )
    report = perf_report.analyze(evs)
    assert perf_report.check(report) == []
    srec = report["steps"][0]
    assert srec["critical_replica"] == "r1"
    assert srec["dominant_exposed"] == "allreduce"
    assert srec["replicas"]["r0"]["critical"] is False
    assert srec["replicas"]["r1"]["allreduce_s"] == pytest.approx(1.25)
    # Run-level exposed fraction: 1.25 exposed out of (0.45 + 1.35) wall.
    assert report["summary"]["exposed_allreduce_frac"] == pytest.approx(
        1.25 / 1.80
    )


def test_perf_report_emit_round_trip(tmp_path):
    evs = _step_events(
        100.0, quorum_s=0.1, issue_at=0.1, complete_at=1.0, wait_s=0.3,
    )
    report = perf_report.analyze(evs)
    out = tmp_path / "perf_steps.jsonl"
    n = perf_report.emit_perf_steps(report, str(out))
    assert n == 1
    lines = [json.loads(x) for x in out.read_text().splitlines() if x]
    recs = [e for e in lines if e.get("event") == "perf_step"]
    assert len(recs) == 1
    a = recs[0]["attrs"]
    assert a["allreduce_ms"] == pytest.approx(300.0, abs=0.01)
    assert a["fingerprint"] == report["steps"][0]["replicas"]["r0"][
        "fingerprint"
    ]


def test_interval_algebra():
    assert telemetry.merge_intervals([(0, 1), (0.5, 2), (3, 4)]) == [
        (0, 2), (3, 4)
    ]
    assert telemetry.union_s([(0, 1), (0.5, 2)]) == pytest.approx(2.0)
    assert telemetry.intersect_intervals([(0, 2)], [(1, 3)]) == [(1, 2)]
    assert telemetry.subtract_intervals([(0, 3)], [(1, 2)]) == [
        (0, 1), (2, 3)
    ]


def test_lane_exposed_attribution_sole_runner():
    # Two lanes: peer1 [0, 10us], peer2 [5, 25us]. peer2 runs alone for
    # the 15us after peer1 finishes (the tail the collective's completion
    # actually waited on); peer1's sole time is the 5us head start.
    evs = [_ev(
        "native_collective", 1.0, op="allreduce", status="completed",
        lanes=[
            {"peer": 1, "stripe": 0, "dir": "send", "t0_ns": 0,
             "t1_ns": 10_000, "bytes": 1000},
            {"peer": 2, "stripe": 0, "dir": "send", "t0_ns": 5_000,
             "t1_ns": 25_000, "bytes": 2000},
        ],
    )]
    lanes = telemetry.lane_exposed_attribution(evs)
    assert lanes[(2, 0, "send")]["sole_s"] == pytest.approx(15e-6)
    assert lanes[(1, 0, "send")]["sole_s"] == pytest.approx(5e-6)
    assert lanes[(2, 0, "send")]["busy_s"] == pytest.approx(20e-6)


# ---------------------------------------------------------------------------
# MFU module
# ---------------------------------------------------------------------------


def test_peak_tables_substring_match():
    assert perf.peak_tflops("TPU v5p") == 459
    assert perf.peak_tflops("TPU v5 lite") == 197
    assert perf.peak_tflops("cpu") is None
    assert perf.peak_hbm_gbps("TPU v4") == 1228


def test_roofline_cpu_is_honest():
    r = perf.roofline(1e12, 1e9, 1.0, "cpu", 1)
    assert r["tflops_per_s"] == pytest.approx(1.0)
    assert r["mfu"] is None  # no invented peak for a CPU
    assert r["roofline_frac"] is None
    assert r["ai"] == pytest.approx(1000.0)


def test_roofline_tpu_fractions():
    # 1 chip of v4 (275 bf16 TFLOPs, 1228 GB/s): compute-bound AI.
    r = perf.roofline(275e12, 1e12, 1.0, "TPU v4", 1)
    assert r["mfu"] == pytest.approx(1.0)
    assert r["roofline_frac"] == pytest.approx(1.0)


def test_record_jit_cost_and_step_metrics():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    perf.reset_step_costs()
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((32, 32), jnp.float32)
    rec = perf.record_jit_cost("toy", f, x, force=True)
    assert rec is not None and rec["flops"] > 0
    m = perf.step_metrics("toy", 0.01)
    assert m["tflops_per_s"] == pytest.approx(rec["flops"] / 0.01 / 1e12)
    assert m["mfu"] is None  # CPU device: no peak
    s = perf.format_step_metrics(m)
    assert s.startswith(" perf[") and "TF/s" in s
    assert perf.format_step_metrics(None) == ""
    perf.reset_step_costs()
    assert perf.step_metrics("toy", 0.01) is None


def test_record_jit_cost_noop_when_knob_off(monkeypatch):
    monkeypatch.delenv("TORCHFT_PERF", raising=False)
    perf.reset_step_costs()
    assert perf.record_jit_cost("toy2", None) is None
    assert perf.get_step_cost("toy2") is None
