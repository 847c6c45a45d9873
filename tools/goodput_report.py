#!/usr/bin/env python
"""Goodput ledger forensics: replica-second accounting from journals.

Where ``recovery_report.py`` decomposes individual failure episodes,
this audits the **time-accounting plane**: every committed manager
journals a ``goodput_window`` event per commit gate, carrying the
closed-classification split (``telemetry.BADPUT_KINDS``) of the wall-clock
window since the previous gate. This tool stitches those windows into
per-replica and fleet accounts and proves the central invariant:

  tiling — within each window the splits sum to the window's duration,
  and within each incarnation the window durations sum to the ledger's
  cumulative total, both to ``TILE_EPS_S``. Accounted time provably
  covers wall clock with nothing double-counted and nothing dropped.

On top of the audited accounts it reports:

* per-replica and fleet seconds by badput kind, with ``down`` derived
  from inter-incarnation journal gaps (a killed incarnation's ledger
  dies with it; the next one restarts at zero — the hole between them
  is time the replica was not even accounting);
* per-fault-kind cost: each ``chaos_inject`` / kill is joined to its
  recovery episode (``telemetry.detect_episodes``) and the episode
  window is intersected with the goodput windows it overlaps, yielding
  seconds lost by badput kind **per fault kind** — what a given fault
  class actually costs the fleet;
* the headline: fleet goodput fraction and **goodput retention** —
  1 - fault_badput / (accounted - init_compile), the share of
  steady-state capacity that survived the faults. This is the number
  ``goodput_soak.py`` holds to its 0.95 budget.

Usage::

    python tools/goodput_report.py /tmp/journal/       # dir of *.jsonl
    python tools/goodput_report.py a.jsonl b.jsonl --json
    python tools/goodput_report.py --from-bench BENCH_GOODPUT.json --check
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, List, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

import obs_report  # noqa: E402
from torchft_tpu import telemetry  # noqa: E402
from torchft_tpu.telemetry import (  # noqa: E402
    BADPUT_KINDS,
    FAULT_BADPUT_KINDS,
)

# Tiling must hold to this absolute epsilon (the manager journals every
# goodput_window figure at 9 decimals, so honest accounts land orders of
# magnitude inside it; drift beyond it means the ledger math broke).
TILE_EPS_S = 1e-6


def _zero_accounts() -> Dict[str, float]:
    return {k: 0.0 for k in BADPUT_KINDS}


def _replica_key(replica_id: Any) -> str:
    """Stable per-slot key: a relaunched replica gets a fresh uuid suffix
    (``train_ddp_0:<uuid>``) but keeps its slot prefix, and ``down`` time
    is only derivable when both incarnations land in one stream."""
    return str(replica_id).split(":", 1)[0]


def _windows_by_replica(
    events: List[Dict[str, Any]],
) -> Dict[str, List[Dict[str, Any]]]:
    """``goodput_window`` events grouped per replica slot, time order."""
    out: Dict[str, List[Dict[str, Any]]] = {}
    for ev in events:
        if ev.get("event") != "goodput_window":
            continue
        out.setdefault(_replica_key(ev.get("replica_id")), []).append(ev)
    for wins in out.values():
        wins.sort(key=lambda ev: float(ev.get("ts", 0.0)))
    return out


def _audit_replica(
    rid: str, wins: List[Dict[str, Any]], problems: List[str]
) -> Dict[str, Any]:
    """Audits one replica's window stream: per-window tiling, per-segment
    cumulative tiling, incarnation segmentation (a ledger restart shows
    as ``total_s`` falling back toward zero), and the ``down`` seconds
    between incarnations. Returns the replica's account row."""
    acct = _zero_accounts()
    segments: List[Dict[str, Any]] = []
    seg: Optional[Dict[str, Any]] = None
    prev_total = None
    for ev in wins:
        a = ev.get("attrs") or {}
        ts = float(ev.get("ts", 0.0))
        dur = float(a.get("dur_s", 0.0))
        total = float(a.get("total_s", 0.0))
        splits = a.get("splits") or {}
        residual = a.get("residual")
        if residual not in BADPUT_KINDS:
            problems.append(
                f"{rid}: window @{ts:.3f} has residual {residual!r} "
                f"outside BADPUT_KINDS")
        bad_keys = [k for k in splits if k not in BADPUT_KINDS]
        if bad_keys:
            problems.append(
                f"{rid}: window @{ts:.3f} splits carry unknown kind(s) "
                f"{bad_keys}")
        if dur < -TILE_EPS_S:
            problems.append(f"{rid}: window @{ts:.3f} negative dur_s {dur}")
        ssum = sum(float(v) for v in splits.values())
        if abs(ssum - dur) > TILE_EPS_S:
            problems.append(
                f"{rid}: window @{ts:.3f} splits sum {ssum:.9f}s != "
                f"dur_s {dur:.9f}s")
        if prev_total is not None and total < prev_total - TILE_EPS_S:
            segments.append(seg)
            seg = None
        if seg is None:
            seg = {
                # Ledger origin (process start) reconstructed from the
                # first window: it closed at ts and the ledger had
                # accounted total seconds by then.
                "t_origin": ts - total,
                "t_first": ts,
                "t_last": ts,
                "dur_sum": 0.0,
                "last_total": 0.0,
                "n": 0,
                "committed": 0,
            }
        seg["t_last"] = ts
        seg["dur_sum"] += dur
        seg["last_total"] = total
        seg["n"] += 1
        if a.get("committed"):
            seg["committed"] += 1
        prev_total = total
        for k in BADPUT_KINDS:
            if k in splits:
                acct[k] += float(splits[k])
    if seg is not None:
        segments.append(seg)
    down_s = 0.0
    for i, s in enumerate(segments):
        # Cumulative tiling per incarnation: the windows' durations must
        # sum to the ledger total (per-window figures are journaled at
        # 9 decimals, so allow the rounding to accumulate but stay well
        # under TILE_EPS_S for any realistic window count).
        err = abs(s["dur_sum"] - s["last_total"])
        if err > max(TILE_EPS_S, 1e-9 * s["last_total"]):
            problems.append(
                f"{rid}: incarnation {i} windows sum {s['dur_sum']:.9f}s "
                f"!= ledger total {s['last_total']:.9f}s")
        if i > 0:
            gap = s["t_origin"] - segments[i - 1]["t_last"]
            down_s += max(gap, 0.0)
    acct["down"] += down_s
    total_s = sum(acct.values())
    return {
        "windows": sum(s["n"] for s in segments),
        "committed_windows": sum(s["committed"] for s in segments),
        "incarnations": len(segments),
        "down_s": round(down_s, 6),
        "accounted_s": round(total_s, 6),
        "goodput_frac": (
            round(acct["compute"] / total_s, 6) if total_s > 0 else None
        ),
        "badput_s": {k: round(v, 6) for k, v in acct.items()},
    }


def _fault_kind(episode: Dict[str, Any]) -> str:
    """Stable label for the fault class behind an episode: the injected
    chaos kind when the root cause was an injection, else the root-cause
    kind itself (``process_loss`` for a kill, ``latch`` for an organic
    error)."""
    rc = episode.get("root_cause") or {}
    if rc.get("kind") == "chaos" and rc.get("chaos"):
        return f"chaos:{rc['chaos'].get('kind')}"
    return str(rc.get("kind", "unknown"))


def attribute_fault_cost(
    events: List[Dict[str, Any]],
    episodes: List[Dict[str, Any]],
    slack_s: float = 5.0,
) -> Dict[str, Dict[str, Any]]:
    """Seconds lost by badput kind, per fault kind. Each goodput window
    spans ``[ts - dur_s, ts]``; its non-compute splits are attributed to
    an episode pro-rata to the window's overlap with the episode window
    (padded by ``slack_s`` on the right — the discarded/replayed step
    after a heal commits just past the episode's closing gate)."""
    wins = []
    for ev in events:
        if ev.get("event") != "goodput_window":
            continue
        a = ev.get("attrs") or {}
        ts = float(ev.get("ts", 0.0))
        dur = float(a.get("dur_s", 0.0))
        if dur <= 0:
            continue
        wins.append((ts - dur, ts, dur, a.get("splits") or {}))
    out: Dict[str, Dict[str, Any]] = {}
    for e in episodes:
        kind = _fault_kind(e)
        row = out.setdefault(
            kind, {"episodes": 0, "cost_s": {}, "total_cost_s": 0.0}
        )
        row["episodes"] += 1
        lo, hi = float(e["t_start"]), float(e["t_end"]) + slack_s
        for w_lo, w_hi, dur, splits in wins:
            overlap = min(hi, w_hi) - max(lo, w_lo)
            if overlap <= 0:
                continue
            frac = min(overlap / dur, 1.0)
            for k, v in splits.items():
                if k == "compute" or k not in BADPUT_KINDS:
                    continue
                v = float(v) * frac
                if v <= 0:
                    continue
                row["cost_s"][k] = row["cost_s"].get(k, 0.0) + v
                row["total_cost_s"] += v
    for row in out.values():
        row["cost_s"] = {k: round(v, 6) for k, v in sorted(
            row["cost_s"].items())}
        row["total_cost_s"] = round(row["total_cost_s"], 6)
    return out


def analyze(events: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Full goodput report dict from a merged event list."""
    problems: List[str] = []
    by_replica = _windows_by_replica(events)
    replicas = {
        rid: _audit_replica(rid, wins, problems)
        for rid, wins in sorted(by_replica.items())
    }
    fleet = _zero_accounts()
    for row in replicas.values():
        for k in BADPUT_KINDS:
            fleet[k] += row["badput_s"][k]
    total_s = sum(fleet.values())
    fault_badput_s = sum(fleet[k] for k in FAULT_BADPUT_KINDS)
    # Retention denominator excludes init_compile: paying the one-time
    # startup cost is not a fault, and counting it would let long warmups
    # mask real fault badput.
    steady_s = total_s - fleet["init_compile"]
    episodes = telemetry.detect_episodes(events)
    fault_cost = attribute_fault_cost(events, episodes)
    return {
        "replicas": replicas,
        "problems": problems,
        "summary": {
            "num_replicas": len(replicas),
            "num_windows": sum(r["windows"] for r in replicas.values()),
            "num_incarnations": sum(
                r["incarnations"] for r in replicas.values()),
            "accounted_s": round(total_s, 6),
            "badput_s": {k: round(v, 6) for k, v in fleet.items()},
            "goodput_frac": (
                round(fleet["compute"] / total_s, 6) if total_s > 0
                else None),
            "fault_badput_s": round(fault_badput_s, 6),
            "goodput_retention": (
                round(1.0 - fault_badput_s / steady_s, 6)
                if steady_s > 0 else None),
            "num_episodes": len(episodes),
            "fault_cost": fault_cost,
        },
    }


def check(report: Dict[str, Any]) -> List[str]:
    """Invariant violations (empty = pass): every tiling problem from the
    audit, plus account sanity (no negative kinds, classification closure)."""
    errs = list(report["problems"])
    for rid, row in report["replicas"].items():
        for k, v in row["badput_s"].items():
            if v < -TILE_EPS_S:
                errs.append(f"{rid}: negative account {k}={v}")
        if set(row["badput_s"]) != set(BADPUT_KINDS):
            errs.append(f"{rid}: account keys are not BADPUT_KINDS")
    s = report["summary"]
    gp = s.get("goodput_frac")
    if gp is not None and not (0.0 <= gp <= 1.0):
        errs.append(f"fleet goodput fraction {gp} outside [0, 1]")
    return errs


def render_text(report: Dict[str, Any]) -> str:
    out: List[str] = []
    s = report["summary"]
    out.append(
        f"{'replica':>24} {'inc':>4} {'wins':>5} {'good%':>7} "
        f"{'acct_s':>9} {'down_s':>8}  worst badput")
    for rid, row in report["replicas"].items():
        worst = max(
            ((k, v) for k, v in row["badput_s"].items() if k != "compute"),
            key=lambda kv: kv[1], default=(None, 0.0))
        gp = row["goodput_frac"]
        out.append(
            f"{rid:>24} {row['incarnations']:>4} {row['windows']:>5} "
            f"{(gp * 100 if gp is not None else 0.0):>7.2f} "
            f"{row['accounted_s']:>9.2f} {row['down_s']:>8.2f}  "
            + (f"{worst[0]} {worst[1]:.2f}s" if worst[1] > 0 else "-"))
    out.append("")
    out.append("fleet seconds by badput kind:")
    for k in BADPUT_KINDS:
        v = s["badput_s"][k]
        if v > 0:
            out.append(f"  {k:>16} {v:>10.3f}s")
    if s["fault_cost"]:
        out.append("")
        out.append("cost by fault kind (episode-joined):")
        for kind in sorted(s["fault_cost"]):
            row = s["fault_cost"][kind]
            split = ", ".join(
                f"{k} {v:.2f}s" for k, v in row["cost_s"].items())
            out.append(
                f"  {kind:>20} x{row['episodes']}: "
                f"{row['total_cost_s']:.3f}s ({split or 'no overlap'})")
    out.append("")
    gp = s["goodput_frac"]
    ret = s["goodput_retention"]
    out.append(
        f"{s['num_replicas']} replica(s), {s['num_incarnations']} "
        f"incarnation(s), {s['num_windows']} window(s), "
        f"{s['accounted_s']:.2f}s accounted"
    )
    out.append(
        "fleet goodput "
        + (f"{gp * 100:.2f}%" if gp is not None else "n/a")
        + ", retention "
        + (f"{ret * 100:.2f}%" if ret is not None else "n/a")
        + f" ({s['fault_badput_s']:.2f}s fault badput over "
        f"{s['num_episodes']} episode(s))"
    )
    return "\n".join(out)


def main(argv: Optional[list] = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("paths", nargs="*",
                   help="journal files or directories of *.jsonl")
    p.add_argument("--from-bench", metavar="FILE", default=None,
                   help="read the journal dir from a BENCH_GOODPUT.json "
                   "artifact (its journal_dir field)")
    p.add_argument("--json", action="store_true",
                   help="emit the full report as JSON")
    p.add_argument("--check", action="store_true",
                   help="assert the tiling/account invariants; exit 1 on "
                   "violation")
    p.add_argument("--min-windows", type=int, default=0,
                   help="with --check: at least this many goodput windows")
    args = p.parse_args(argv)

    paths = list(args.paths)
    if args.from_bench:
        with open(args.from_bench) as f:
            doc = json.load(f)
        jd = doc.get("journal_dir")
        if not jd:
            print(f"{args.from_bench} has no journal_dir", file=sys.stderr)
            return 1
        paths.append(jd)
    if not paths:
        p.error("give journal paths or --from-bench")

    events = obs_report.load_events(paths)
    if not events:
        print("no journal events found", file=sys.stderr)
        return 1
    report = analyze(events)

    if args.json:
        json.dump(report, sys.stdout, indent=1, default=str)
        print()
    else:
        print(render_text(report))

    if args.check:
        errs = check(report)
        n_wins = report["summary"]["num_windows"]
        if args.min_windows and n_wins < args.min_windows:
            errs.append(
                f"{n_wins} goodput window(s) < --min-windows "
                f"{args.min_windows}")
        if errs:
            for e in errs:
                print(f"CHECK FAIL: {e}", file=sys.stderr)
            return 1
        print(
            f"goodput_report check OK: {n_wins} window(s) tile to "
            f"{report['summary']['accounted_s']:.2f}s accounted across "
            f"{report['summary']['num_replicas']} replica(s)"
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
