#!/usr/bin/env python
"""The benchmark ledger: one append-only JSONL trajectory for every
BENCH writer.

The repo's BENCH_* artifacts are each a one-off schema (bench.py's
result line, bench_pg's backend table, fleet_load's budget report,
wan_drill's drill record). This module normalizes the *headline metrics*
out of all of them into ``BENCH_LEDGER.jsonl`` — one record per metric
sample::

    {"schema": 1, "ts": ..., "metric": "pg.allreduce.native.gib_s",
     "value": 2.11, "unit": "GiB/s", "direction": "higher",
     "family": "pg", "source": "tools/bench_pg.py",
     "git_rev": "337d037", "env": {...fingerprint...}, "extra": {...}}

``direction`` says which way is better, so tools/perf_gate.py can
compare head-of-ledger against pinned baselines without per-metric
special cases. ``env`` fingerprints the box (host, platform, cpu count,
python/jax versions) so a regression can be told apart from a machine
change. Writers call :func:`record` (never raises into the bench — a
ledger I/O failure must not fail a measurement run); readers use
:func:`load`/:func:`head`.

CLI::

    python tools/perf_ledger.py --list            # trajectory per metric
    python tools/perf_ledger.py --check           # schema-validate all
    python tools/perf_ledger.py --import-legacy   # backfill BENCH_*.json
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from torchft_tpu import knobs  # noqa: E402

SCHEMA = 1
LEDGER_DEFAULT = os.path.join(REPO, "BENCH_LEDGER.jsonl")
REQUIRED = (
    "schema", "ts", "metric", "value", "unit", "direction", "family",
    "source", "git_rev", "env",
)
DIRECTIONS = ("higher", "lower")


def ledger_path(path: Optional[str] = None) -> str:
    return path or knobs.get_str("TORCHFT_PERF_LEDGER") or LEDGER_DEFAULT


def git_rev() -> str:
    try:
        return subprocess.run(
            ["git", "-C", REPO, "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10, check=True,
        ).stdout.strip() or "unknown"
    except Exception:  # noqa: BLE001 - no git, detached dir, ...
        return "unknown"


def env_fingerprint() -> Dict[str, Any]:
    fp: Dict[str, Any] = {
        "host": platform.node(),
        "platform": platform.platform(terse=True),
        "machine": platform.machine(),
        "python": platform.python_version(),
        "cpu_count": os.cpu_count(),
    }
    try:
        import jax

        fp["jax"] = jax.__version__
    except Exception:  # noqa: BLE001 - ledger must work without jax
        pass
    return fp


def make_record(
    metric: str,
    value: float,
    unit: str,
    direction: str,
    family: str,
    source: str,
    extra: Optional[Dict[str, Any]] = None,
    ts: Optional[float] = None,
) -> Dict[str, Any]:
    rec: Dict[str, Any] = {
        "schema": SCHEMA,
        "ts": time.time() if ts is None else float(ts),
        "metric": metric,
        "value": float(value),
        "unit": unit,
        "direction": direction,
        "family": family,
        "source": source,
        "git_rev": git_rev(),
        "env": env_fingerprint(),
    }
    if extra:
        rec["extra"] = extra
    errs = validate(rec)
    if errs:
        raise ValueError(f"invalid ledger record: {errs}")
    return rec


def record(
    metric: str,
    value: Any,
    unit: str,
    direction: str,
    family: str,
    source: str,
    extra: Optional[Dict[str, Any]] = None,
    path: Optional[str] = None,
    ts: Optional[float] = None,
) -> Optional[Dict[str, Any]]:
    """Append one sample; returns the record, or None when it could not
    be written (non-numeric value, read-only checkout). Benches call
    this after their own artifact write — it must never turn a good
    measurement run into a failure."""
    try:
        rec = make_record(
            metric, value, unit, direction, family, source,
            extra=extra, ts=ts,
        )
        line = json.dumps(rec, sort_keys=True) + "\n"
        with open(ledger_path(path), "a") as f:
            f.write(line)
        return rec
    except Exception as e:  # noqa: BLE001
        print(f"[perf_ledger] skipped {metric}: {e}", file=sys.stderr)
        return None


def record_report(
    kind: str,
    doc: Dict[str, Any],
    source: str,
    path: Optional[str] = None,
) -> int:
    """Append a live tool report's headline metrics, reusing the same
    extractors as the legacy-artifact importer so live runs extend the
    backfilled trajectories under identical metric names. ``kind`` is
    one of bench|pg|fleet|wan|recovery|elastic|control|detect|goodput.
    Returns
    the number of records
    appended;
    never raises into the calling bench."""
    try:
        extract = _REPORT_EXTRACTORS[kind]
        rows = extract("live", doc)
    except Exception as e:  # noqa: BLE001 - the measurement already ran
        print(f"[perf_ledger] {kind} extract skipped: {e}",
              file=sys.stderr)
        return 0
    n = 0
    for metric, value, unit, direction, family, _src, extra in rows:
        if record(metric, value, unit, direction, family, source,
                  extra=extra, path=path):
            n += 1
    return n


def validate(rec: Any) -> List[str]:
    errs: List[str] = []
    if not isinstance(rec, dict):
        return ["record is not an object"]
    for k in REQUIRED:
        if k not in rec:
            errs.append(f"missing field {k}")
    if rec.get("direction") not in DIRECTIONS:
        errs.append(f"direction must be one of {DIRECTIONS}")
    v = rec.get("value")
    if not isinstance(v, (int, float)) or isinstance(v, bool):
        errs.append("value must be numeric")
    elif v != v:  # NaN
        errs.append("value is NaN")
    if not isinstance(rec.get("env"), dict):
        errs.append("env must be an object")
    return errs


def load(path: Optional[str] = None) -> List[Dict[str, Any]]:
    """All parseable records, in file (= time-appended) order."""
    p = ledger_path(path)
    out: List[Dict[str, Any]] = []
    try:
        fh = open(p)
    except OSError:
        return out
    with fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if isinstance(rec, dict) and "metric" in rec:
                out.append(rec)
    return out


def head(records: List[Dict[str, Any]]) -> Dict[str, Dict[str, Any]]:
    """Latest record per metric (file order wins ties)."""
    out: Dict[str, Dict[str, Any]] = {}
    for rec in records:
        out[rec["metric"]] = rec
    return out


def history(
    records: List[Dict[str, Any]], metric: str
) -> List[Dict[str, Any]]:
    return [r for r in records if r["metric"] == metric]


# ----------------------------------------------------------------------
# Legacy backfill: the nine one-off BENCH_* schemas -> ledger records
# ----------------------------------------------------------------------


def _bench_round_records(
    fn: str, doc: Dict[str, Any], prefix: str = "", family: str = "ddp",
) -> List[Dict[str, Any]]:
    """bench.py supervisor artifacts (BENCH_r0N.json): the result line
    lands in ``parsed``; r5's got truncated into ``tail``, so fall back
    to scraping the known scalar fields out of the tail text. The TPU
    artifact gets a ``tpu.`` prefix so on-chip numbers never share a
    trajectory (or a gate baseline) with the CPU-proxy runs."""
    parsed = doc.get("parsed")
    if parsed is None:
        tail = doc.get("tail") or ""
        start = tail.find('"diloco_ft_ms_per_step"')
        if start < 0:
            return []
        try:
            parsed = json.loads("{" + tail[start:].rstrip())
        except ValueError:
            return []
    src = f"bench.py ({os.path.basename(fn)})"
    out = []

    def add(metric, value, unit, direction, extra=None):
        if value is None:
            return
        out.append((prefix + metric, float(value), unit, direction,
                    family, src, extra))

    add("ddp.ms_per_step", parsed.get("ddp_ft_ms_per_step"), "ms", "lower")
    add("ddp.tokens_per_sec", parsed.get("tokens_per_sec"), "tokens/s",
        "higher")
    add("ddp.mfu", parsed.get("mfu_est"), "frac", "higher")
    add("diloco.ms_per_step", parsed.get("diloco_ft_ms_per_step"), "ms",
        "lower")
    add("diloco.ft_ratio", parsed.get("value")
        if parsed.get("metric") == "diloco_ft_throughput_ratio_vs_nofault"
        else None, "ratio", "higher")
    parts = parsed.get("ddp_per_step_parts_ms") or {}
    add("ddp.exposed_allreduce_ms", parts.get("allreduce"), "ms", "lower")
    qb = parsed.get("quorum_bench") or {}
    add("quorum.p95_ms", qb.get("p95_ms"), "ms", "lower")
    return out


def _pg_records(fn: str, doc: Dict[str, Any]) -> List[Dict[str, Any]]:
    src = f"tools/bench_pg.py ({os.path.basename(fn)})"
    largest = doc.get("largest_size_mib")
    out = []
    for backend, rows in (doc.get("backends") or {}).items():
        for row in rows:
            if row.get("size_mib") == largest:
                out.append((
                    f"pg.allreduce.{backend}.gib_s",
                    float(row["gib_per_s"]), "GiB/s", "higher", "pg", src,
                    {"size_mib": largest},
                ))
    if doc.get("native_over_socket") is not None:
        out.append(("pg.native_over_socket",
                    float(doc["native_over_socket"]), "ratio", "higher",
                    "pg", src, None))
    fr = doc.get("fr_overhead") or {}
    if fr.get("overhead_pct") is not None:
        out.append(("pg.fr_overhead_pct", float(fr["overhead_pct"]), "%",
                    "lower", "pg", src, None))
    return out


def _fleet_records(fn: str, doc: Dict[str, Any]) -> List[Dict[str, Any]]:
    src = f"tools/fleet_load.py ({os.path.basename(fn)})"
    out = []
    for n, res in (doc.get("fleets") or {}).items():
        hb = (res.get("heartbeat") or {}).get("p95_us")
        fj = ((res.get("http") or {}).get("fleet_json") or {}).get("p95_us")
        qr = res.get("quorum") or {}
        if hb is not None:
            out.append((f"fleet.hb_p95_us.n{n}", float(hb), "us", "lower",
                        "fleet", src, None))
        if fj is not None:
            out.append((f"fleet.fleet_json_p95_us.n{n}", float(fj), "us",
                        "lower", "fleet", src, None))
        # Incremental-quorum headline: wall time from the first register
        # to the broadcast, the number the delta-driven gate + shared
        # broadcast payload cut from ~4 s to sub-second at N=1024.
        if qr.get("formation_ms") is not None:
            out.append((f"fleet.quorum_formation_ms.n{n}",
                        float(qr["formation_ms"]), "ms", "lower", "fleet",
                        src, {"rpc_p95_us": qr.get("p95_us")}))
        if qr.get("p95_us") is not None:
            out.append((f"fleet.quorum_rpc_p95_us.n{n}",
                        float(qr["p95_us"]), "us", "lower", "fleet",
                        src, None))
    # --multijob scenario: M jobs x N replicas across a district->root
    # federation with a seeded churn storm in one job. Pins the per-job
    # formation tail, the sibling-job heartbeat tail DURING the storm
    # (cross-job hot-path isolation), and the isolation violation count
    # (bit-exact sibling control-plane state; must stay 0).
    mj = doc.get("multijob") or {}
    if mj:
        mtag = f"m{mj.get('m_jobs')}x{mj.get('n_per_job')}"
        extra = {"storm_job": mj.get("storm_job"), "seed": mj.get("seed")}
        if mj.get("formation_p95_ms") is not None:
            out.append((f"fleet.multijob_formation_p95_ms.{mtag}",
                        float(mj["formation_p95_ms"]), "ms", "lower",
                        "fleet", src, extra))
        sib = (mj.get("sibling_heartbeat") or {}).get("p95_us")
        if sib is not None:
            out.append((f"fleet.multijob_sibling_hb_p95_us.{mtag}",
                        float(sib), "us", "lower", "fleet", src, None))
        viol = (mj.get("isolation") or {}).get("violations")
        if viol is not None:
            out.append((f"fleet.multijob_isolation_violations.{mtag}",
                        float(len(viol)), "count", "lower", "fleet", src,
                        {"siblings": (mj.get("isolation") or {}).get(
                            "siblings")}))
    # --restart-lighthouse scenario: warm-restart re-register storm (time
    # for all N conns to heartbeat-ack against the restarted process) and
    # /fleet.json aggregate repopulation (agg.n back to N).
    rst = doc.get("restart") or {}
    n = rst.get("n")
    if n is not None:
        if rst.get("reregister_s") is not None:
            out.append((f"fleet.restart_reregister_s.n{n}",
                        float(rst["reregister_s"]), "s", "lower", "fleet",
                        src, {"restart_s": rst.get("restart_s")}))
        if rst.get("repopulate_s") is not None:
            out.append((f"fleet.restart_repopulate_s.n{n}",
                        float(rst["repopulate_s"]), "s", "lower", "fleet",
                        src, None))
    return out


def _wan_records(fn: str, doc: Dict[str, Any]) -> List[Dict[str, Any]]:
    src = f"tools/wan_drill.py ({os.path.basename(fn)})"
    out = []
    recs = doc.get("recoveries") or []
    if recs:
        vals = sorted(float(r.get("recovery_s", r))
                      if isinstance(r, dict) else float(r) for r in recs)
        out.append(("wan.recovery_max_s", vals[-1], "s", "lower", "wan",
                    src, {"n": len(vals)}))
    elif doc.get("max_recovery_s") is not None:
        out.append(("wan.recovery_max_s", float(doc["max_recovery_s"]),
                    "s", "lower", "wan", src, None))
    if doc.get("wall_s") is not None:
        out.append(("wan.drill_wall_s", float(doc["wall_s"]), "s", "lower",
                    "wan", src, None))
    return out


def _elastic_records(fn: str, doc: Dict[str, Any]) -> List[Dict[str, Any]]:
    """BENCH_ELASTIC.json (tools/elastic_drill.py): time-to-join, heal
    bandwidth of the join transfers (PR-10 heal_xfer accounting), and
    goodput retention vs the static 2-replica baseline — the numbers the
    elastic gate pins (goodput_retention carries the 0.80 budget)."""
    src = f"tools/elastic_drill.py ({os.path.basename(fn)})"
    summ = doc.get("summary") or {}
    out = []
    n_j = summ.get("num_joins")
    extra = {"joins": n_j} if n_j is not None else None
    if summ.get("time_to_join_p95_s") is not None:
        out.append(("elastic.time_to_join_s",
                    float(summ["time_to_join_p95_s"]), "s", "lower",
                    "elastic", src, extra))
    if summ.get("heal_gib_s") is not None:
        out.append(("elastic.heal_gib_s", float(summ["heal_gib_s"]),
                    "GiB/s", "higher", "elastic", src,
                    {"bytes": summ.get("heal_bytes")}))
    if summ.get("goodput_retention") is not None:
        # Goodput is aggregate committed samples/s (world_size x batch x
        # step rate), not raw step cadence: scaling 2->8 groups on a
        # shared-core CI box slows every group's cadence while the fleet
        # still trains MORE examples per second — samples/s is the number
        # the resize is supposed to keep monotone.
        out.append(("elastic.goodput_retention",
                    float(summ["goodput_retention"]), "ratio", "higher",
                    "elastic", src,
                    {"baseline_samples_per_s": summ.get(
                        "baseline_samples_per_s"),
                     "elastic_samples_per_s": summ.get(
                         "elastic_samples_per_s")}))
    return out


def _recovery_records(fn: str, doc: Dict[str, Any]) -> List[Dict[str, Any]]:
    """BENCH_RECOVERY.json (tools/recovery_drill.py): TTR percentiles,
    the per-phase p95 decomposition, and per-transport heal bandwidth —
    the numbers the recovery gate pins."""
    src = f"tools/recovery_drill.py ({os.path.basename(fn)})"
    summ = doc.get("summary") or {}
    out = []
    n_ep = summ.get("num_episodes")
    extra = {"episodes": n_ep} if n_ep is not None else None
    if summ.get("ttr_p50_s") is not None:
        out.append(("recovery.ttr_p50_s", float(summ["ttr_p50_s"]), "s",
                    "lower", "recovery", src, extra))
    if summ.get("ttr_p95_s") is not None:
        out.append(("recovery.ttr_p95_s", float(summ["ttr_p95_s"]), "s",
                    "lower", "recovery", src, extra))
    for ph, row in (summ.get("phases") or {}).items():
        if isinstance(row, dict) and row.get("p95_s") is not None:
            out.append((f"recovery.phase_p95_s.{ph}", float(row["p95_s"]),
                        "s", "lower", "recovery", src, None))
    for transport, row in (summ.get("heal_gib_s") or {}).items():
        if isinstance(row, dict) and row.get("p50") is not None:
            out.append((f"recovery.heal_gib_s.{transport}",
                        float(row["p50"]), "GiB/s", "higher", "recovery",
                        src, {"n": row.get("n"), "bytes": row.get("bytes")}))
    if summ.get("goodput_during_heal_p50") is not None:
        # Healthy-fleet compute share while one replica heals, from the
        # goodput ledger's windows intersected with each episode window —
        # the per-episode cut of the ROADMAP "goodput-during-heal" gate.
        out.append(("recovery.goodput_during_heal",
                    float(summ["goodput_during_heal_p50"]), "ratio",
                    "higher", "recovery", src, extra))
    return out


def _detect_records(fn: str, doc: Dict[str, Any]) -> List[Dict[str, Any]]:
    """BENCH_DETECT.json (tools/detect_drill.py): detection latency of
    the failure-evidence bus, overall and per (fault kind x first signal
    source) — the numbers the detect gate pins with absolute budgets."""
    src = f"tools/detect_drill.py ({os.path.basename(fn)})"
    summ = doc.get("summary") or {}
    out = []
    n_f = summ.get("num_faults")
    extra = {"faults": n_f} if n_f is not None else None
    if summ.get("detect_p50_s") is not None:
        out.append(("detect.p50_s", float(summ["detect_p50_s"]), "s",
                    "lower", "detect", src, extra))
    if summ.get("detect_p95_s") is not None:
        out.append(("detect.p95_s", float(summ["detect_p95_s"]), "s",
                    "lower", "detect", src, extra))
    for pair, row in (summ.get("detect") or {}).items():
        if isinstance(row, dict) and row.get("p95_s") is not None:
            out.append((f"detect.{pair}.p95_s", float(row["p95_s"]), "s",
                        "lower", "detect", src,
                        {"n": row.get("n"), "budget_s": row.get("budget_s")}))
    return out


def _control_records(fn: str, doc: Dict[str, Any]) -> List[Dict[str, Any]]:
    """BENCH_CONTROL.json (tools/lighthouse_drill.py): control-plane TTR
    after killing the active lighthouse — failover detection latency,
    quorum-service gap (longest step-visible stall), stale quorums the
    fence let through (must be 0) — the numbers the control gate pins
    with absolute budgets."""
    src = f"tools/lighthouse_drill.py ({os.path.basename(fn)})"
    summ = doc.get("summary") or {}
    out = []
    n_f = summ.get("num_failovers")
    extra = {"failovers": n_f} if n_f is not None else None
    if summ.get("failover_p50_s") is not None:
        out.append(("control.failover_p50_s",
                    float(summ["failover_p50_s"]), "s", "lower", "control",
                    src, extra))
    if summ.get("failover_p95_s") is not None:
        out.append(("control.failover_p95_s",
                    float(summ["failover_p95_s"]), "s", "lower", "control",
                    src, extra))
    if summ.get("quorum_gap_s") is not None:
        out.append(("control.quorum_gap_s", float(summ["quorum_gap_s"]),
                    "s", "lower", "control", src, None))
    if summ.get("stale_quorums_accepted") is not None:
        out.append(("control.stale_quorums_accepted",
                    float(summ["stale_quorums_accepted"]), "count",
                    "lower", "control", src,
                    {"demotions": summ.get("demotions")}))
    return out


def _goodput_records(fn: str, doc: Dict[str, Any]) -> List[Dict[str, Any]]:
    """BENCH_GOODPUT.json (tools/goodput_soak.py): the audited
    time-accounting headline — fleet goodput fraction, fault badput
    seconds, and goodput retention at 1 kill/100 steps (retention
    carries the absolute 0.95 budget: the paper's <5% throughput-loss
    claim)."""
    src = f"tools/goodput_soak.py ({os.path.basename(fn)})"
    summ = doc.get("summary") or {}
    out = []
    extra = {
        "windows": summ.get("num_windows"),
        "episodes": summ.get("num_episodes"),
        "kills": doc.get("kills"),
        "steps": doc.get("steps"),
    }
    if summ.get("goodput_retention") is not None:
        out.append(("goodput.retention",
                    float(summ["goodput_retention"]), "ratio", "higher",
                    "goodput", src, extra))
    if summ.get("goodput_frac") is not None:
        out.append(("goodput.fleet_fraction",
                    float(summ["goodput_frac"]), "ratio", "higher",
                    "goodput", src, extra))
    if summ.get("fault_badput_s") is not None:
        out.append(("goodput.fault_badput_s",
                    float(summ["fault_badput_s"]), "s", "lower",
                    "goodput", src,
                    {"badput_s": summ.get("badput_s")}))
    return out


# Live benches reuse the same extractors via record_report(), so one
# metric name has exactly one extraction path (import-time and run-time).
_REPORT_EXTRACTORS = {
    "bench": _bench_round_records,
    "pg": _pg_records,
    "fleet": _fleet_records,
    "wan": _wan_records,
    "recovery": _recovery_records,
    "elastic": _elastic_records,
    "control": _control_records,
    "detect": _detect_records,
    "goodput": _goodput_records,
}


def import_legacy(path: Optional[str] = None) -> int:
    """One-shot backfill of the legacy BENCH_*.json artifacts, in
    round/file order so the trajectory reads oldest-first. Skips any
    (metric, source) pair already present — safe to re-run."""
    existing = {
        (r["metric"], r.get("source")) for r in load(path)
    }
    plans = [
        (sorted(
            f for f in os.listdir(REPO)
            if f.startswith("BENCH_r0") and f.endswith(".json")
        ), _bench_round_records),
        (["BENCH_PG_allreduce.json"], _pg_records),
        (["BENCH_FLEET.json", "BENCH_FLEET_quick.json"], _fleet_records),
        (["BENCH_WAN.json"], _wan_records),
    ]
    n = 0
    for files, fn_records in plans:
        for fn in files:
            full = os.path.join(REPO, fn)
            if not os.path.exists(full):
                continue
            try:
                with open(full) as f:
                    doc = json.load(f)
            except (OSError, ValueError) as e:
                print(f"[perf_ledger] skip {fn}: {e}", file=sys.stderr)
                continue
            ts = os.path.getmtime(full)
            for tup in fn_records(fn, doc):
                metric, value, unit, direction, family, src, extra = tup
                if (metric, src) in existing:
                    continue
                if record(metric, value, unit, direction, family, src,
                          extra=extra, path=path, ts=ts) is not None:
                    existing.add((metric, src))
                    n += 1
    return n


def main(argv: Optional[list] = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--ledger", default=None,
                   help="ledger path (default BENCH_LEDGER.jsonl, or "
                   "TORCHFT_PERF_LEDGER)")
    p.add_argument("--list", action="store_true",
                   help="print the trajectory per metric")
    p.add_argument("--check", action="store_true",
                   help="schema-validate every record; exit 1 on errors")
    p.add_argument("--import-legacy", action="store_true",
                   help="backfill records from the legacy BENCH_*.json "
                   "artifacts")
    args = p.parse_args(argv)

    if args.import_legacy:
        n = import_legacy(args.ledger)
        print(f"imported {n} records into {ledger_path(args.ledger)}")

    records = load(args.ledger)
    if args.check:
        bad = 0
        for i, rec in enumerate(records):
            errs = validate(rec)
            if errs:
                bad += 1
                print(f"record {i} ({rec.get('metric')}): {errs}",
                      file=sys.stderr)
        families = {r.get("family") for r in records}
        print(
            f"ledger check: {len(records)} records, "
            f"{len(head(records))} metrics, "
            f"{len(families)} families, {bad} invalid"
        )
        return 1 if bad or not records else 0

    if args.list or not args.import_legacy:
        bym: Dict[str, List[Dict[str, Any]]] = {}
        for r in records:
            bym.setdefault(r["metric"], []).append(r)
        for metric in sorted(bym):
            hist = bym[metric]
            latest = hist[-1]
            arrow = "^" if latest["direction"] == "higher" else "v"
            vals = " -> ".join(f"{r['value']:g}" for r in hist[-6:])
            print(f"{metric:<34} [{arrow}] {vals} {latest['unit']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
