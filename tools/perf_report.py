#!/usr/bin/env python
"""Cross-replica critical-path profiler over event journals.

Where ``obs_report.py`` names the slowest replica per step, this names
the dominant *exposed* interval on the step critical path — the stall a
speed PR should attack first — using interval-overlap math over the span
windows the journal already carries (``telemetry.step_phase_windows`` /
``comm_attribution``), not phase-duration sums:

* per (step, replica): quorum | heal | compute | allreduce | commit as
  *tiling* intervals, exposed-comm seconds vs comm hidden under compute,
  an ``overlap_frac``, and a deterministic perf fingerprint (``a98>c2``
  = 98% exposed allreduce);
* per step: the critical (slowest) replica and its dominant exposed
  phase;
* run-level: the exposed-allreduce fraction of total step wall and, when
  the native engine's flight-recorder lanes are present, per-(peer,
  stripe, dir) sole-runner exposure — the lane tail each collective's
  completion actually waited on;
* MFU next to ms when a ``perf_model`` event is present (trainers under
  ``TORCHFT_PERF``, see torchft_tpu/perf.py).

``--emit PATH`` re-journals the analysis as ``perf_step`` events (one
per step+replica) so downstream tools consume attribution without
re-deriving it. ``--check`` asserts the tiling invariant (phases sum to
the step window exactly), fraction sanity, and optionally
``--expect-exposed-allreduce F --tol T`` against a known ground truth.

Journals are loaded through ``obs_report.load_events``, which is
rotation-aware: when a journal has been size-rotated
(``TORCHFT_JOURNAL_MAX_MB``), the ``.1`` segment is read before the
live file so long-run analysis sees the full event stream in order.

Usage::

    python tools/perf_report.py /tmp/journal/          # dir of *.jsonl
    python tools/perf_report.py a.jsonl b.jsonl --json
    python tools/perf_report.py /tmp/journal --check \
        --expect-exposed-allreduce 0.98 --tol 0.10
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, List, Optional, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

import obs_report  # noqa: E402
from torchft_tpu import perf as perf_mod  # noqa: E402
from torchft_tpu import telemetry  # noqa: E402

# Phase tiling must cover the step window exactly (construction
# guarantees it; drift beyond float noise means the math broke).
TILE_EPS_S = 1e-6


def analyze(events: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Full report dict from a merged event list."""
    grouped: Dict[Tuple[int, str], List[Dict[str, Any]]] = {}
    for ev in events:
        step = obs_report._event_step(ev)
        if step is None:
            continue
        grouped.setdefault((step, obs_report._replica_key(ev)), []).append(ev)

    rows: Dict[int, Dict[str, Dict[str, Any]]] = {}
    for (step, rid), evs in sorted(grouped.items()):
        win = telemetry.step_phase_windows(evs)
        attr = telemetry.comm_attribution(win)
        if attr["total_s"] <= 0:
            continue
        attr["fingerprint"] = telemetry.perf_fingerprint(attr)
        phase, sec = telemetry.dominant_exposed(attr)
        attr["dominant_exposed"] = phase
        attr["dominant_exposed_s"] = sec
        rows.setdefault(step, {})[rid] = attr

    steps: Dict[int, Dict[str, Any]] = {}
    for step, by_rid in rows.items():
        crit = max(by_rid, key=lambda r: by_rid[r]["total_s"])
        for rid in by_rid:
            by_rid[rid]["critical"] = rid == crit
        steps[step] = {
            "replicas": by_rid,
            "critical_replica": crit,
            "dominant_exposed": by_rid[crit]["dominant_exposed"],
            "fingerprint": by_rid[crit]["fingerprint"],
        }

    all_rows = [a for by_rid in rows.values() for a in by_rid.values()]
    total_s = sum(a["total_s"] for a in all_rows)
    sums = {
        k: sum(a[k] for a in all_rows)
        for k in (
            "quorum_s", "heal_s", "compute_s", "allreduce_s", "commit_s",
            "comm_inflight_s", "comm_hidden_s",
        )
    }
    exposed_allreduce_frac = (
        sums["allreduce_s"] / total_s if total_s > 0 else None
    )
    overlap_frac = (
        sums["comm_hidden_s"] / sums["comm_inflight_s"]
        if sums["comm_inflight_s"] > 0
        else None
    )
    dominant = max(
        ("quorum", "heal", "allreduce", "commit"),
        key=lambda p: sums[f"{p}_s"],
    ) if all_rows else None

    lanes = telemetry.lane_exposed_attribution(events)
    lane_rows = sorted(
        (
            {
                "peer": k[0], "stripe": k[1], "dir": k[2],
                "sole_s": round(v["sole_s"], 6),
                "busy_s": round(v["busy_s"], 6),
                "bytes": int(v["bytes"]),
                "count": int(v["count"]),
            }
            for k, v in lanes.items()
        ),
        key=lambda r: -r["sole_s"],
    )

    models = {}
    for ev in events:
        if ev.get("event") == "perf_model":
            a = ev.get("attrs") or {}
            models[a.get("name", "?")] = a
    mfu = None
    if models and all_rows:
        # Mean committed-step wall across replicas vs the registered cost
        # of the (single) step program — coarse but honest: compile-time
        # FLOPs over measured wall.
        mean_dt = total_s / len(all_rows)
        a = next(iter(models.values()))
        mfu = perf_mod.roofline(
            float(a.get("flops") or 0.0),
            float(a.get("bytes_accessed") or 0.0),
            mean_dt,
            str(a.get("device_kind") or ""),
            int(a.get("n_devices") or 1),
        )
        mfu["mean_step_s"] = mean_dt

    return {
        "steps": steps,
        "summary": {
            "num_steps": len(steps),
            "num_rows": len(all_rows),
            "total_step_s": round(total_s, 6),
            "exposed_allreduce_frac": exposed_allreduce_frac,
            "overlap_frac": overlap_frac,
            "dominant_exposed": dominant,
            **{k: round(v, 6) for k, v in sums.items()},
        },
        "lane_exposure": lane_rows,
        "perf_models": models,
        "mfu": mfu,
    }


def check(report: Dict[str, Any]) -> List[str]:
    """Internal-consistency violations (empty list = clean)."""
    errs: List[str] = []
    if not report["steps"]:
        errs.append("no analyzable steps in the journal")
    for step, srec in report["steps"].items():
        for rid, a in srec["replicas"].items():
            tiled = (
                a["quorum_s"] + a["heal_s"] + a["allreduce_s"]
                + a["commit_s"] + a["compute_s"]
            )
            if abs(tiled - a["total_s"]) > max(
                TILE_EPS_S, 1e-9 * a["total_s"]
            ):
                errs.append(
                    f"step {step} replica {rid}: phases sum {tiled:.9f}s "
                    f"!= step window {a['total_s']:.9f}s (tiling broke)"
                )
            for key in ("overlap_frac", "exposed_frac"):
                v = a.get(key)
                if v is not None and not (-1e-9 <= v <= 1.0 + 1e-9):
                    errs.append(
                        f"step {step} replica {rid}: {key}={v} out of [0,1]"
                    )
            if a["comm_hidden_s"] - a["comm_inflight_s"] > TILE_EPS_S:
                errs.append(
                    f"step {step} replica {rid}: hidden "
                    f"{a['comm_hidden_s']}s > in-flight "
                    f"{a['comm_inflight_s']}s"
                )
    return errs


def emit_perf_steps(report: Dict[str, Any], path: str) -> int:
    """Re-journal the analysis as ``perf_step`` events; returns count."""
    log = telemetry.EventLog(path, replica_id="perf_report")
    n = 0
    try:
        for step in sorted(report["steps"]):
            srec = report["steps"][step]
            for rid, a in srec["replicas"].items():
                log.emit(
                    "perf_step",
                    step=step,
                    replica_id=rid,
                    total_ms=round(a["total_s"] * 1e3, 3),
                    quorum_ms=round(a["quorum_s"] * 1e3, 3),
                    heal_ms=round(a["heal_s"] * 1e3, 3),
                    compute_ms=round(a["compute_s"] * 1e3, 3),
                    allreduce_ms=round(a["allreduce_s"] * 1e3, 3),
                    commit_ms=round(a["commit_s"] * 1e3, 3),
                    comm_inflight_ms=round(a["comm_inflight_s"] * 1e3, 3),
                    comm_hidden_ms=round(a["comm_hidden_s"] * 1e3, 3),
                    overlap_frac=a["overlap_frac"],
                    exposed_frac=a["exposed_frac"],
                    fingerprint=a["fingerprint"],
                    dominant_exposed=a["dominant_exposed"],
                    critical=a["critical"],
                )
                n += 1
    finally:
        log.close()
    return n


def render_text(report: Dict[str, Any]) -> str:
    out: List[str] = []
    s = report["summary"]
    out.append(
        f"{'step':>6} {'replica':>10} {'quorum':>8} {'heal':>8} "
        f"{'compute':>8} {'exposed-ar':>10} {'hidden':>8} {'commit':>8} "
        f"{'total':>8} {'ovl%':>5}  fingerprint"
    )
    for step in sorted(report["steps"]):
        srec = report["steps"][step]
        for rid in sorted(srec["replicas"]):
            a = srec["replicas"][rid]
            ovl = (
                f"{a['overlap_frac'] * 100:4.0f}%"
                if a["overlap_frac"] is not None
                else "    -"
            )
            marker = (
                f"<- critical ({a['dominant_exposed']})"
                if a["critical"] and len(srec["replicas"]) > 1
                else ""
            )
            out.append(
                f"{step:>6} {rid:>10} {a['quorum_s']:>8.3f} "
                f"{a['heal_s']:>8.3f} {a['compute_s']:>8.3f} "
                f"{a['allreduce_s']:>10.3f} {a['comm_hidden_s']:>8.3f} "
                f"{a['commit_s']:>8.3f} {a['total_s']:>8.3f} {ovl}  "
                f"{a['fingerprint']} {marker}"
            )
    out.append("")
    if s["exposed_allreduce_frac"] is not None:
        out.append(
            f"critical path: dominant exposed interval = "
            f"{s['dominant_exposed']} "
            f"(exposed allreduce {s['exposed_allreduce_frac'] * 100:.1f}% "
            f"of step wall; comm overlap "
            + (
                f"{s['overlap_frac'] * 100:.1f}%"
                if s["overlap_frac"] is not None
                else "n/a"
            )
            + ")"
        )
    if report["lane_exposure"]:
        out.append("")
        out.append("native lane exposure (sole-runner tail per "
                   "(peer, stripe, dir)):")
        for r in report["lane_exposure"][:8]:
            out.append(
                f"  peer {r['peer']} stripe {r['stripe']} ({r['dir']}): "
                f"sole {r['sole_s'] * 1e3:.2f} ms over {r['count']} "
                f"collectives ({r['bytes'] / (1 << 20):.1f} MiB)"
            )
    if report["mfu"]:
        m = report["mfu"]
        out.append("")
        out.append(
            "mfu: "
            + (
                f"{m['tflops_per_s']:.4g} TF/s"
                if m.get("tflops_per_s") is not None
                else "n/a"
            )
            + (
                f", mfu={m['mfu'] * 100:.2f}%"
                if m.get("mfu") is not None
                else ", mfu=n/a (no TPU peak for this device)"
            )
            + (
                f", roofline={m['roofline_frac'] * 100:.1f}%"
                if m.get("roofline_frac") is not None
                else ""
            )
            + f" @ mean step {m['mean_step_s'] * 1e3:.1f} ms"
        )
    return "\n".join(out)


def main(argv: Optional[list] = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("paths", nargs="+",
                   help="journal files or directories of *.jsonl")
    p.add_argument("--json", action="store_true",
                   help="emit the full report as JSON")
    p.add_argument("--emit", metavar="PATH", default=None,
                   help="append perf_step events (JSONL journal) here")
    p.add_argument("--check", action="store_true",
                   help="assert tiling/fraction invariants; exit 1 on "
                   "violation")
    p.add_argument("--expect-exposed-allreduce", type=float, default=None,
                   help="with --check: run-level exposed-allreduce "
                   "fraction must match this ground truth")
    p.add_argument("--tol", type=float, default=0.10,
                   help="absolute tolerance for "
                   "--expect-exposed-allreduce (default 0.10)")
    args = p.parse_args(argv)

    events = obs_report.load_events(args.paths)
    if not events:
        print("no journal events found", file=sys.stderr)
        return 1
    report = analyze(events)

    n_emitted = 0
    if args.emit:
        n_emitted = emit_perf_steps(report, args.emit)

    if args.json:
        json.dump(report, sys.stdout, indent=1, default=str)
        print()
    else:
        print(render_text(report))

    if args.check:
        errs = check(report)
        frac = report["summary"]["exposed_allreduce_frac"]
        if args.expect_exposed_allreduce is not None:
            if frac is None:
                errs.append("no exposed-allreduce fraction to compare")
            elif abs(frac - args.expect_exposed_allreduce) > args.tol:
                errs.append(
                    f"exposed-allreduce fraction {frac:.4f} not within "
                    f"{args.tol} of expected "
                    f"{args.expect_exposed_allreduce:.4f}"
                )
        if args.emit and n_emitted == 0:
            errs.append("--emit produced no perf_step events")
        if errs:
            for e in errs:
                print(f"CHECK FAIL: {e}", file=sys.stderr)
            return 1
        print(
            f"perf_report check OK: {report['summary']['num_rows']} rows, "
            f"{n_emitted} perf_step events emitted"
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
