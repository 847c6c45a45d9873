"""Detection-latency drill: seeded ground-truth faults vs the signal bus.

Boots a real C++ lighthouse (evidence plane on) plus a small fleet of
synthetic heartbeaters, then injects a seeded schedule of faults — each
with a known *expected first signal source* — and measures how long the
unified failure-evidence bus takes to surface each one in the fleet
signal ring:

  fault kind        injection                          expected source
  ----------        ---------                          ---------------
  hb_stop           victim stops heartbeating          hb_lapse
  digest_stall      victim's digest reports cf>=3      digest_anomaly
  dead_leave        leave on the corpse's behalf       proc_death
                    (reason="trainer died")
  abort_piggyback   native-abort evidence rides a      native_abort
                    survivor's heartbeat frame

The injection timestamps are the drill's own (it IS the chaos plane
here), so detection latency needs no cross-process clock games: it is
``first matching ring signal observed - injection``, sampled by polling
the ``fleet`` RPC with a ``signal_seq`` cursor at poll cadence. Ground
truth (``chaos_inject``) and every observed signal (``failure_signal``)
are journaled, so ``tools/detect_report.py`` can re-derive the same
attribution offline from the journal alone.

The outcome is ONE JSON line plus a ``BENCH_DETECT.json`` artifact with
per-(fault kind x signal source) detection p50/p95. The drill checks
``BUDGETS`` below against that report itself: a broken or unmeasured
budget is listed under ``budget_problems`` and fails the run.
``--replay`` re-derives the fault schedule from the artifact's seed and
asserts it reproduces the recorded multiset.

``--quick`` is the ``suite_gate.sh detect`` lane shape: 6 replicas,
8 faults (every kind at least once), fixed seed.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

from torchft_tpu.coordination import (  # noqa: E402
    LighthouseClient,
    LighthouseServer,
)
from torchft_tpu.telemetry import EventLog  # noqa: E402

import obs_export  # noqa: E402
from drills import check_budgets  # noqa: E402

QUICK_SEED = 4242
HB_INTERVAL_MS = 50
TICK_MS = 50
# Drill-speed cadence eviction: budget = max(floor, 12 x 50ms) = 600ms.
EVICT_FLOOR_MS = 600

# fault kind -> the signal source that must observe it first.
EXPECTED_SOURCE = {
    "hb_stop": "hb_lapse",
    "digest_stall": "digest_anomaly",
    "dead_leave": "proc_death",
    "abort_piggyback": "native_abort",
}

# The drill's budgets: (metric, direction, bound, why), in seconds, with
# shared-core headroom on top of each mechanism's own latency — these
# are detection-wedge tripwires, not latency targets.
BUDGETS = (
    ("detect.p95_s", "lower", 5.0,
     "over all faults; the slowest source (hb_lapse) sets it"),
    ("detect.hb_stop.hb_lapse.p95_s", "lower", 5.0,
     "evict budget (600 ms at drill cadence) + scan tick + poll cadence"),
    ("detect.digest_stall.digest_anomaly.p95_s", "lower", 2.0,
     "surfaces on the victim's next heartbeat frame"),
    ("detect.dead_leave.proc_death.p95_s", "lower", 2.0,
     "surfaces on the leave RPC itself"),
    ("detect.abort_piggyback.native_abort.p95_s", "lower", 2.0,
     "rides the survivor's next heartbeat frame"),
)
# Signal source -> the bound one fault of it is held to (and, x4, how
# long the drill waits for it before calling it undetected).
DETECT_BUDGET_S = {m.split(".")[2]: b for m, _d, b, _w in BUDGETS
                   if m.count(".") == 3}
POLL_S = 0.02
FAULT_GAP_S = 0.25  # settle time between injections


def budget_values(report: Dict[str, Any]) -> Dict[str, Any]:
    summ = report.get("summary") or {}
    vals = {"detect.p95_s": summ.get("detect_p95_s")}
    for pair, row in (summ.get("detect") or {}).items():
        vals[f"detect.{pair}.p95_s"] = row.get("p95_s")
    return vals


def fault_schedule(seed: int, n_faults: int) -> List[Dict[str, Any]]:
    """Seeded fault plan, a pure function of (seed, n_faults): every
    fault kind appears at least once (n_faults >= 4), the rest are drawn
    by the rng, and the order is a seeded shuffle. Victim i is the
    dedicated replica ``det<i>`` so no victim is reused — a stopped or
    left heartbeater stays down. --replay re-derives this plan to prove
    the injection multiset reproduces."""
    rng = random.Random(seed)
    kinds = list(EXPECTED_SOURCE)
    plan = kinds * (n_faults // len(kinds))
    plan += [rng.choice(kinds) for _ in range(n_faults - len(plan))]
    rng.shuffle(plan)
    return [
        {"kind": kind, "victim": f"det{i}",
         "expected_source": EXPECTED_SOURCE[kind]}
        for i, kind in enumerate(plan)
    ]


class Heartbeater:
    """One synthetic replica: heartbeats at a declared cadence with a
    healthy digest until told to misbehave."""

    def __init__(self, addr: str, replica_id: str) -> None:
        self.replica_id = replica_id
        self._addr = addr
        self._stop = threading.Event()
        self._muted = threading.Event()
        self._cf = 0
        self._signals: List[Dict[str, Any]] = []
        self._lock = threading.Lock()
        self._step = 0
        self._thread = threading.Thread(
            target=self._run, name=f"hb-{replica_id}", daemon=True
        )
        self._thread.start()

    def _run(self) -> None:
        client = LighthouseClient(self._addr, connect_timeout=10.0)
        try:
            while not self._stop.is_set():
                if not self._muted.is_set():
                    with self._lock:
                        cf = self._cf
                        sigs = self._signals
                        self._signals = []
                    self._step += 1
                    digest = {
                        "v": 1, "step": self._step, "rate": 1.0,
                        "gp": 1.0, "err": 0,
                    }
                    if cf:
                        digest["cf"] = cf
                    try:
                        client.heartbeat(
                            self.replica_id,
                            timeout=2.0,
                            digest=digest,
                            hb_interval_ms=HB_INTERVAL_MS,
                            signals=sigs or None,
                        )
                    except Exception:  # noqa: BLE001 - keep cadence
                        pass
                self._stop.wait(HB_INTERVAL_MS / 1000.0)
        finally:
            client.close()

    def mute(self) -> None:
        """hb_stop: the thread stays alive but no frame ever leaves —
        indistinguishable from a hung process on the wire."""
        self._muted.set()

    def set_commit_failures(self, cf: int) -> None:
        with self._lock:
            self._cf = cf

    def attach_signal(self, signal: Dict[str, Any]) -> None:
        """abort_piggyback: the signal rides this replica's next frame."""
        with self._lock:
            self._signals.append(signal)

    def leave_dead(self) -> None:
        """dead_leave: stop heartbeating, then file the corpse's leave
        (what the manager binary's parent-death watchdog does)."""
        self._muted.set()
        client = LighthouseClient(self._addr, connect_timeout=10.0)
        try:
            client.leave(self.replica_id, timeout=5.0,
                         reason="trainer died")
        finally:
            client.close()

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=5.0)


def _pct(vals: List[float], q: float) -> Optional[float]:
    if not vals:
        return None
    s = sorted(vals)
    return s[min(len(s) - 1, int(q * len(s)))]


def _await_signal(client: LighthouseClient, cursor: int, source: str,
                  subject: str, deadline_s: float) -> Optional[Dict[str, Any]]:
    """Polls the fleet signal ring until a signal newer than ``cursor``
    matches (source, subject); returns it (with observation wall time)
    or None at the deadline."""
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        try:
            fleet = client.fleet(timeout=2.0)
        except Exception:  # noqa: BLE001 - poll through transient faults
            time.sleep(POLL_S)
            continue
        for rec in fleet.get("signals") or []:
            if int(rec.get("seq", 0)) <= cursor:
                continue
            if (str(rec.get("source")) == source
                    and str(rec.get("replica_id")) == subject):
                rec = dict(rec)
                rec["t_observed"] = time.time()
                return rec
        time.sleep(POLL_S)
    return None


def inject(fault: Dict[str, Any], hbs: Dict[str, Heartbeater],
           survivor: Heartbeater) -> None:
    kind, victim = fault["kind"], fault["victim"]
    if kind == "hb_stop":
        hbs[victim].mute()
    elif kind == "digest_stall":
        hbs[victim].set_commit_failures(5)
    elif kind == "dead_leave":
        hbs[victim].leave_dead()
    elif kind == "abort_piggyback":
        # A SURVIVOR reports the victim's native-engine abort — evidence
        # about a peer always arrives via someone else's frame.
        survivor.attach_signal({
            "source": "native_abort",
            "replica_id": victim,
            "site": f"manager:{survivor.replica_id}",
            "detail": {"msg": "collective abort latched"},
        })
    else:  # pragma: no cover - schedule only emits known kinds
        raise ValueError(f"unknown fault kind {kind!r}")


def run_drill(args) -> dict:
    plan = fault_schedule(args.seed, args.faults)
    workdir = tempfile.mkdtemp(prefix="detect_drill_")
    journal_dir = os.path.join(workdir, "journal")
    os.makedirs(journal_dir, exist_ok=True)
    n_hb = args.faults + args.survivors

    os.environ["TORCHFT_LH_EVICT_FLOOR_MS"] = str(EVICT_FLOOR_MS)
    lh = LighthouseServer(
        bind="127.0.0.1:0",
        min_replicas=2,
        join_timeout_ms=30000,
        quorum_tick_ms=TICK_MS,
        heartbeat_timeout_ms=30000,  # the EVIDENCE path must win, not this
    )
    addr = lh.address()
    journal = EventLog(
        os.path.join(journal_dir, "detect_drill.jsonl"),
        replica_id="detect_drill",
    )
    t0 = time.time()
    rows: List[Dict[str, Any]] = []
    try:
        hbs = {
            f"det{i}": Heartbeater(addr, f"det{i}") for i in range(n_hb)
        }
        survivor = hbs[f"det{n_hb - 1}"]  # never a victim
        poller = LighthouseClient(addr, connect_timeout=10.0)
        try:
            # Let the fleet table populate (every replica has a row and a
            # declared cadence) before the first injection.
            fleet: Dict[str, Any] = {}
            deadline = time.time() + 30.0
            while time.time() < deadline:
                try:
                    fleet = poller.fleet(timeout=2.0)
                    if len(fleet.get("replicas") or {}) >= n_hb:
                        break
                except Exception:  # noqa: BLE001 - still booting
                    pass
                time.sleep(0.05)
            cursor = int(fleet.get("signal_seq", 0))

            for fault in plan:
                time.sleep(FAULT_GAP_S)
                expected = fault["expected_source"]
                budget = DETECT_BUDGET_S[expected]
                t_inject = time.time()
                journal.emit(
                    "chaos_inject",
                    kind=fault["kind"],
                    plane="detect",
                    site=fault["victim"],
                    expected_source=expected,
                )
                inject(fault, hbs, survivor)
                sig = _await_signal(
                    poller, cursor, expected, fault["victim"],
                    deadline_s=max(budget * 4, 10.0),
                )
                row = {
                    **fault,
                    "t_inject": t_inject,
                    "detected": sig is not None,
                    "budget_s": budget,
                }
                if sig is not None:
                    cursor = int(sig["seq"])
                    row.update({
                        "detect_s": round(sig["t_observed"] - t_inject, 4),
                        "seq": int(sig["seq"]),
                        "site": str(sig.get("site", "")),
                    })
                    journal.emit(
                        "failure_signal",
                        seq=int(sig["seq"]),
                        source=expected,
                        subject=fault["victim"],
                        site=str(sig.get("site", "")),
                        ts_ms=int(sig.get("ts_ms", 0)),
                        detect_s=row["detect_s"],
                    )
                rows.append(row)

            # Final ring drain through the SAME journaling path the live
            # exporter uses, so the journal carries every signal (not just
            # the per-fault winners) for offline attribution.
            fleet = poller.fleet(timeout=2.0)
            obs_export.journal_signal_overflow(journal, fleet, 0)
            signal_counts = fleet.get("signal_counts") or {}
        finally:
            poller.close()
            for hb in hbs.values():
                hb.stop()
    finally:
        journal.close()
        lh.shutdown()
        os.environ.pop("TORCHFT_LH_EVICT_FLOOR_MS", None)
    wall_s = time.time() - t0

    # Per-(fault kind x source) detection percentiles.
    by_pair: Dict[str, List[float]] = {}
    for row in rows:
        if row.get("detect_s") is None:
            continue
        key = f"{row['kind']}.{row['expected_source']}"
        by_pair.setdefault(key, []).append(row["detect_s"])
    detect = {
        key: {
            "n": len(v),
            "p50_s": round(_pct(v, 0.50), 4),
            "p95_s": round(_pct(v, 0.95), 4),
            "budget_s": DETECT_BUDGET_S[key.rsplit(".", 1)[1]],
        }
        for key, v in sorted(by_pair.items())
    }
    all_lat = [row["detect_s"] for row in rows
               if row.get("detect_s") is not None]
    undetected = [r for r in rows if not r["detected"]]
    over_budget = [r for r in rows
                   if r.get("detect_s") is not None
                   and r["detect_s"] > r["budget_s"]]
    summ = {
        "num_faults": len(rows),
        "num_detected": len(rows) - len(undetected),
        "detect_p50_s": _pct(all_lat, 0.50),
        "detect_p95_s": _pct(all_lat, 0.95),
        "detect": detect,
        "signal_counts": signal_counts,
    }
    result = {
        "drill": "detect",
        "seed": args.seed,
        "faults": len(plan),
        "fault_plan": [[f["kind"], f["victim"]] for f in plan],
        "hb_interval_ms": HB_INTERVAL_MS,
        "evict_floor_ms": EVICT_FLOOR_MS,
        "summary": summ,
        "budgets_s": DETECT_BUDGET_S,
        "wall_s": round(wall_s, 1),
        "journal_dir": journal_dir,
    }
    problems = check_budgets(budget_values(result), BUDGETS)
    result["budget_problems"] = problems
    result["ok"] = not undetected and not over_budget and not problems
    artifact = {**result, "rows": rows}
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=1, default=str)
    return result


def replay_check(args) -> dict:
    """Re-derives the fault plan from the artifact's recorded seed and
    asserts it reproduces the recorded injection multiset — the drill's
    determinism contract, checkable without a second run."""
    with open(args.out) as f:
        art = json.load(f)
    derived = [[f["kind"], f["victim"]]
               for f in fault_schedule(art["seed"], art["faults"])]
    recorded = [list(p) for p in art.get("fault_plan") or []]
    ok = sorted(map(tuple, derived)) == sorted(map(tuple, recorded))
    return {"drill": "detect", "replay": True, "seed": art["seed"],
            "derived": derived, "recorded": recorded, "ok": ok}


def main() -> int:
    import signal as _signal

    def _term(_signum, _frame):
        raise SystemExit(143)

    _signal.signal(_signal.SIGTERM, _term)
    os.chdir(REPO)
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--quick", action="store_true",
                   help="suite_gate lane: 8 faults, 2 extra survivors, "
                   "fixed seed")
    p.add_argument("--seed", type=int, default=QUICK_SEED)
    p.add_argument("--faults", type=int, default=8,
                   help="injections (>= 4 so every kind appears)")
    p.add_argument("--survivors", type=int, default=2,
                   help="extra never-killed heartbeaters (the last one "
                   "carries piggyback evidence)")
    p.add_argument("--replay", action="store_true",
                   help="verify the fault plan in --out reproduces from "
                   "its recorded seed, without re-running")
    p.add_argument("--out", type=str,
                   default=os.path.join(REPO, "BENCH_DETECT.json"))
    args = p.parse_args()
    if args.faults < len(EXPECTED_SOURCE):
        p.error(f"--faults must be >= {len(EXPECTED_SOURCE)}")
    report = replay_check(args) if args.replay else run_drill(args)
    print(json.dumps(report), flush=True)
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
