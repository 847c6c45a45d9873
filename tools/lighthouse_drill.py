"""Control-plane-loss drill: lighthouse HA measured end to end.

Launches a real 2-replica DDP run against an ordered lighthouse list
(primary + warm standby, both with durable state dirs), then at a
seeded step SIGKILLs the ACTIVE lighthouse. The managers' heartbeat
lease lapses, they fail over down the list, and the standby takes over
with a bumped fencing epoch. Once the fleet demonstrably trains on the
standby, the old primary is resurrected on its original port with its
stale state dir — the classic split-brain setup — and must be fenced
out (demoted by the fleet's epoch-carrying heartbeats, zero of its
quorums accepted).

Asserted invariants:

  C1 no-wedge      — the run finishes every step within the deadline
                     and both groups commit the SAME final params
                     (bit-exact sha over the weights).
  C2 one owner     — from the journals: every quorum_id maps to exactly
                     one fencing epoch across all replicas, and no
                     replica ever accepts an epoch below one it has
                     seen (zero stale quorums).
  C3 fenced out    — the resurrected primary reports role=standby with
                     demotions >= 1 (it observed the successor's epoch
                     and stepped aside) after re-absorbing the fleet's
                     heartbeats.
  C4 bounded TTR   — failover latency (kill -> first quorum served by
                     the successor, from ``lh_failover`` journal
                     events) and the step-visible quorum-service gap
                     stay inside absolute budgets.

The outcome is ONE JSON line plus a ``BENCH_CONTROL.json`` artifact
(failover p50/p95, quorum-service gap, re-register time, the seeded
kill schedule). The drill checks ``BUDGETS`` below against that
report itself: a broken or unmeasured budget is listed under
``budget_problems`` and fails the run. ``--replay`` re-derives the
kill schedule from the artifact's seed and asserts it reproduces the
recorded injection multiset.

``--quick`` is the ``suite_gate.sh control`` lane shape: 2 replicas,
2 lighthouses, one kill cycle, fixed seed.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

from torchft_tpu.coordination import (  # noqa: E402
    LighthouseClient,
    LighthouseServer,
)
from torchft_tpu.orchestration import (  # noqa: E402
    ReplicaGroupRunner,
    render_topology,
)

import obs_report  # noqa: E402
from drills import check_budgets  # noqa: E402

QUICK_SEED = 4242

# The drill's budgets: (metric, direction, bound, why). Failover latency
# is measured to the first post-failover quorum the trainer journals, so
# it includes up to one step of trainer cadence on a shared core — the
# two times are wedge tripwires, not latency targets.
BUDGETS = (
    ("control.failover_p95_s", "lower", 20.0,
     "lease 1.5 s + one trainer step; beyond 20 s the fleet is wedged"),
    ("control.quorum_gap_s", "lower", 30.0,
     "longest step-visible stall across the kill; a quorum timeout's worth"),
    ("control.stale_quorums_accepted", "lower", 0.0,
     "the fence: no replica accepts an epoch below one it has seen"),
)
LEASE_MS = 1500


def budget_values(report: Dict[str, Any]) -> Dict[str, Any]:
    summ = report.get("summary") or {}
    return {
        "control.failover_p95_s": summ.get("failover_p95_s"),
        "control.quorum_gap_s": summ.get("quorum_gap_s"),
        "control.stale_quorums_accepted": summ.get("stale_quorums_accepted"),
    }


def kill_schedule(seed: int, steps: int, kills: int) -> List[int]:
    """Seeded kill steps, spaced through the first 2/3 of the run so
    every cycle leaves room for failover + resurrection + training.
    The schedule is a pure function of (seed, steps, kills): --replay
    re-derives it to prove the injection multiset reproduces."""
    rng = random.Random(seed)
    marks = []
    span = max(2, (2 * steps) // (3 * (kills + 1)))
    for k in range(kills):
        lo = max(1, (k + 1) * span)
        marks.append(rng.randint(lo, lo + span - 1))
    return marks


def _specs(cmd, n_groups, lighthouse_addr, result_dir, journal_dir):
    env = {
        "JAX_PLATFORMS": "cpu",
        "PYTHONUNBUFFERED": "1",
        "TORCHFT_QUORUM_TIMEOUT_SEC": "120",
        "TORCHFT_TIMEOUT_SEC": "10",
        # Short lease so failover fires at drill (not production) speed.
        "TORCHFT_LH_LEASE_MS": str(LEASE_MS),
    }
    os.makedirs(journal_dir, exist_ok=True)
    return render_topology(
        list(cmd) + ["--result-dir", result_dir],
        num_replica_groups=n_groups,
        lighthouse_addr=lighthouse_addr,
        env=env,
        journal_dir=journal_dir,
    )


def _wait_step_mark(runner, log_dir, group, marks, deadline_s):
    deadline = time.time() + deadline_s
    path = os.path.join(log_dir, f"replica{group}_rank0.r0.log")
    markers = [f"- step {s}]" for s in marks]
    while time.time() < deadline:
        runner.monitor_once()
        try:
            text = open(path).read()
        except OSError:
            time.sleep(0.3)
            continue
        for m in markers:
            if m in text:
                return True
        time.sleep(0.3)
    return False


def _mk_lighthouse(bind: str, state_dir: str, standby: bool) -> LighthouseServer:
    return LighthouseServer(
        bind=bind,
        min_replicas=2,
        join_timeout_ms=30000,
        quorum_tick_ms=50,
        heartbeat_timeout_ms=5000,
        state_dir=state_dir,
        standby=standby,
    )


def _await_fenced(addr: str, n_replicas: int,
                  deadline_s: float) -> Dict[str, Any]:
    """Polls a resurrected lighthouse until the fleet's heartbeats have
    both re-registered (row count back to n) and demoted it (the fence).
    Returns observation timings + the final status snapshot."""
    t0 = time.time()
    cli = LighthouseClient(addr)
    out: Dict[str, Any] = {"reregister_s": None, "demote_s": None}
    try:
        deadline = t0 + deadline_s
        status: Dict[str, Any] = {}
        while time.time() < deadline:
            try:
                status = cli.status(timeout=2.0)
            except Exception:  # noqa: BLE001 - still booting
                time.sleep(0.1)
                continue
            hb = len(status.get("heartbeat_ages_ms") or {})
            if hb >= n_replicas and out["reregister_s"] is None:
                out["reregister_s"] = round(time.time() - t0, 3)
            if status.get("role") == "standby" and out["demote_s"] is None:
                out["demote_s"] = round(time.time() - t0, 3)
            if out["reregister_s"] is not None and out["demote_s"] is not None:
                break
            time.sleep(0.1)
        out["role"] = status.get("role")
        out["epoch"] = status.get("epoch")
        out["observed_epoch"] = status.get("observed_epoch")
        out["demotions"] = status.get("demotions", 0)
    finally:
        cli.close()
    return out


def _pct(vals: List[float], q: float) -> Optional[float]:
    if not vals:
        return None
    s = sorted(vals)
    return s[min(len(s) - 1, int(q * len(s)))]


def run_drill(args) -> dict:
    marks = kill_schedule(args.seed, args.steps, args.kills)
    workdir = tempfile.mkdtemp(prefix="lighthouse_drill_")
    result_dir = os.path.join(workdir, "results")
    log_dir = os.path.join(workdir, "logs")
    journal_dir = os.path.join(workdir, "journal")
    state_dirs = [os.path.join(workdir, f"lh{i}_state") for i in range(2)]

    # Primary (active) + one warm standby, both durable.
    lh = [
        _mk_lighthouse("127.0.0.1:0", state_dirs[0], standby=False),
        _mk_lighthouse("127.0.0.1:0", state_dirs[1], standby=True),
    ]
    addrs = [s.address() for s in lh]
    runner = ReplicaGroupRunner(
        _specs(
            [
                sys.executable, "train_ddp.py", "--model", "cnn",
                "--steps", str(args.steps), "--batch-size", "8",
                "--min-replicas", "2",
                # Pace the toy steps (~ms each on CPU) so the lease-based
                # failover window actually lands mid-run.
                "--step-min-s", str(args.step_min_s),
            ],
            args.replicas, ",".join(addrs), result_dir, journal_dir,
        ),
        max_restarts=1,
        log_dir=log_dir,
    )
    t0 = time.time()
    runner.start()
    active = 0
    kills: List[Dict[str, Any]] = []
    resurrections: List[Dict[str, Any]] = []
    try:
        for mark in marks:
            assert _wait_step_mark(
                runner, log_dir, 0, range(mark, mark + 4), args.deadline
            ), f"fleet never reached kill step {mark}"
            # SIGKILL the ACTIVE lighthouse (no goodbye, port vanishes).
            proc = lh[active]._server._proc
            t_kill = time.time()
            proc.kill()
            proc.wait()
            kills.append({"step": mark, "t_kill": t_kill,
                          "addr": addrs[active], "index": active})
            stale, active = active, (active + 1) % len(lh)

            # Proof of takeover: training advances past the kill mark,
            # which requires quorums served by the successor.
            assert _wait_step_mark(
                runner, log_dir, 0, range(mark + 4, mark + 10),
                args.deadline,
            ), f"fleet wedged after lighthouse kill at step {mark}"

            # Resurrect the stale primary: same port, same (now stale)
            # state dir, booting ACTIVE at the old epoch — the fleet's
            # epoch-carrying heartbeats must fence it out.
            port = addrs[stale].rsplit(":", 1)[1]
            lh[stale] = _mk_lighthouse(
                f"127.0.0.1:{port}", state_dirs[stale], standby=False)
            fenced = _await_fenced(addrs[stale], args.replicas, 60.0)
            fenced["index"] = stale
            resurrections.append(fenced)
        wedge_free = runner.run_until_done(timeout=args.deadline)
    finally:
        runner.stop()
        for s in lh:
            s.shutdown()
    wall_s = time.time() - t0

    # -- harvest: journals + result files ---------------------------------
    events = obs_report.load_events([journal_dir])
    qr = [e for e in events if e.get("event") == "quorum_ready"]
    failover_ev = [e for e in events if e.get("event") == "lh_failover"]
    epoch_ev = [e for e in events if e.get("event") == "lh_epoch"]

    # C2: exactly one epoch owner per quorum_id, epochs never decrease.
    owners: Dict[int, set] = {}
    stale_accepted = 0
    per_replica: Dict[str, List[Dict[str, Any]]] = {}
    for e in qr:
        a = e.get("attrs") or {}
        owners.setdefault(a.get("quorum_id"), set()).add(a.get("epoch"))
        per_replica.setdefault(e.get("replica_id") or "?", []).append(e)
    for rows in per_replica.values():
        rows.sort(key=lambda e: e["ts"])
        hi = 0
        for e in rows:
            ep = int((e.get("attrs") or {}).get("epoch") or 0)
            if ep < hi:
                stale_accepted += 1
            hi = max(hi, ep)
    multi_owner = {qid: sorted(eps) for qid, eps in owners.items()
                   if len(eps) > 1}

    # C4: failover latency (kill -> first lh_failover journaled by each
    # replica) and the quorum-service gap (consecutive quorum_ready
    # events straddling the kill instant).
    failover_s: List[float] = []
    for k in kills:
        per: Dict[str, float] = {}
        for e in failover_ev:
            dt = e["ts"] - k["t_kill"]
            rid = e.get("replica_id") or "?"
            if 0 <= dt <= 120 and (rid not in per or dt < per[rid]):
                per[rid] = dt
        failover_s += sorted(per.values())
    gaps: List[float] = []
    for k in kills:
        for rows in per_replica.values():
            for prev, nxt in zip(rows, rows[1:]):
                if prev["ts"] <= k["t_kill"] <= nxt["ts"]:
                    gaps.append(nxt["ts"] - prev["ts"])
    quorum_gap_s = max(gaps) if gaps else None

    # C1: every group finished every step with bit-exact params.
    results: Dict[int, Optional[Dict[str, Any]]] = {}
    for g in range(args.replicas):
        try:
            with open(os.path.join(result_dir, f"group{g}.json")) as f:
                results[g] = json.load(f)
        except (OSError, ValueError):
            results[g] = None
    shas = {(r or {}).get("param_sha256") for r in results.values()}
    final_steps = {(r or {}).get("final_step") for r in results.values()}
    c1 = (bool(wedge_free) and None not in results.values()
          and len(shas) == 1 and None not in shas
          and final_steps == {args.steps})
    c2 = not multi_owner and stale_accepted == 0
    c3 = all(r.get("role") == "standby" and int(r.get("demotions") or 0) >= 1
             for r in resurrections)

    epochs = sorted({int((e.get("attrs") or {}).get("epoch") or 0)
                     for e in epoch_ev})
    summ = {
        "failover_p50_s": _pct(failover_s, 0.50),
        "failover_p95_s": _pct(failover_s, 0.95),
        "quorum_gap_s": quorum_gap_s,
        "reregister_s": max(
            (r["reregister_s"] for r in resurrections
             if r.get("reregister_s") is not None), default=None),
        "stale_quorums_accepted": stale_accepted,
        "demotions": sum(int(r.get("demotions") or 0)
                         for r in resurrections),
        "num_failovers": len(failover_ev),
        "epochs_accepted": epochs,
    }
    result = {
        "drill": "control",
        "seed": args.seed,
        "steps": args.steps,
        "replicas": args.replicas,
        "kills": len(kills),
        "kill_steps": marks,
        "lease_ms": LEASE_MS,
        "wedge_free": bool(wedge_free),
        "summary": summ,
        "wall_s": round(wall_s, 1),
        "journal_dir": journal_dir,
    }
    problems = check_budgets(budget_values(result), BUDGETS)
    c4 = len(failover_s) >= args.replicas * len(kills) and not problems
    result["invariants"] = {
        "bit_exact_no_wedge": bool(c1),
        "one_epoch_owner": bool(c2),
        "stale_primary_fenced": bool(c3),
        "bounded_ttr": bool(c4),
    }
    result["budget_problems"] = problems
    result["ok"] = bool(c1 and c2 and c3 and c4)
    artifact = {
        **result,
        "failover_samples_s": [round(v, 3) for v in failover_s],
        "kills_detail": kills,
        "resurrections": resurrections,
        "multi_owner_quorums": multi_owner,
        "results": results,
    }
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=1, default=str)
    return result


def replay_check(args) -> dict:
    """Re-derives the kill schedule from the artifact's recorded seed
    and asserts it reproduces the recorded injection multiset — the
    drill's determinism contract, checkable without a second run."""
    with open(args.out) as f:
        art = json.load(f)
    derived = kill_schedule(art["seed"], art["steps"], art["kills"])
    recorded = art.get("kill_steps") or []
    ok = sorted(derived) == sorted(recorded)
    return {"drill": "control", "replay": True, "seed": art["seed"],
            "derived": derived, "recorded": recorded, "ok": ok}


def main() -> int:
    import signal as _signal

    # Driver SIGTERM must run the finally blocks (runner.stop/lighthouse
    # shutdown) or the spawned trainers orphan-spin on quorum retries.
    def _term(_signum, _frame):
        raise SystemExit(143)

    _signal.signal(_signal.SIGTERM, _term)
    os.chdir(REPO)
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--quick", action="store_true",
                   help="suite_gate lane: 2 replicas, 2 lighthouses, "
                   "1 kill cycle, fixed seed")
    p.add_argument("--seed", type=int, default=QUICK_SEED)
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--replicas", type=int, default=2)
    p.add_argument("--kills", type=int, default=1,
                   help="active-lighthouse SIGKILL cycles (each is "
                   "kill -> failover -> resurrect-and-fence)")
    p.add_argument("--deadline", type=float, default=600.0)
    p.add_argument("--step-min-s", type=float, default=0.3,
                   help="per-step pacing handed to train_ddp.py; must "
                   "comfortably exceed (lease / steps-remaining) so the "
                   "failover fires while steps remain")
    p.add_argument("--replay", action="store_true",
                   help="verify the kill schedule in --out reproduces "
                   "from its recorded seed, without re-running")
    p.add_argument("--out", type=str,
                   default=os.path.join(REPO, "BENCH_CONTROL.json"))
    args = p.parse_args()
    report = replay_check(args) if args.replay else run_drill(args)
    print(json.dumps(report), flush=True)
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
