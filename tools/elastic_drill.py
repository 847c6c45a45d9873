"""Seeded elastic-membership drill: grow/shrink a live DDP fleet.

Walks the replica-group world size 2 -> 8 -> 3 on a running job,
resizing every K steps:

  grow   (step ~K)  six replica groups launch mid-run, discover the
                    live quorum, heal in via the streaming checkpoint
                    transport (``elastic_join`` journaled), and enter
                    lockstep;
  shrink (step ~2K) a seeded chaos ``preempt`` plan picks WHICH five
                    of the eight groups get the eviction SIGTERM (the
                    bit-identical decision function both chaos
                    implementations share); each victim finishes its
                    step, commits, leaves the quorum (``elastic_leave``)
                    and exits 0 inside the grace window — the window
                    k8s grants via ``terminationGracePeriodSeconds``,
                    both driven by ``TORCHFT_DRAIN_GRACE_S``. A victim
                    that overruns the window is hard-killed (SIGKILL)
                    and counted: a passing drill has zero hard kills.

A separate static 2-replica leg of the same length is the goodput
baseline. Goodput is aggregate committed samples/s (world x batch x
step rate summed over every group's own step stamps), NOT raw step
cadence: on a shared-core CI box eight groups slow each other's cadence
while the fleet still trains more examples per second — samples/s is
what a goodput-monotone resize must retain.

Asserted invariants:

  E1 joins      — every joiner journaled ``elastic_join`` and committed
                  steps mid-run (time-to-join measured per group).
  E2 drains     — every victim exited 0 with the drain markers logged
                  and ``elastic_leave`` journaled; zero hard kills.
  E3 agreement  — the three survivors finish at the full step count
                  with bitwise-identical parameters; no wedge.
  E4 goodput    — elastic-leg samples/s >= ``--goodput-floor`` x the
                  static baseline, and the 0.80 of ``BUDGETS`` below
                  whatever the option says.
  E5 replay     — ``--replay BENCH_ELASTIC.json`` re-derives the
                  preemption plan from the recorded seed and asserts
                  the injection multiset is identical.

The outcome is ONE JSON line plus a ``BENCH_ELASTIC.json`` artifact
(time_to_join_p95_s, heal GiB/s from the joiners' receiver-side
``heal_xfer`` accounting, goodput_retention). The drill checks
``BUDGETS`` against that report itself: a broken or unmeasured budget
is listed under ``budget_problems`` and fails the run.

``--quick`` is the suite_gate lane shape: the full 2 -> 8 -> 3 walk at
a short step count with a fixed seed.
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import re
import signal
import sys
import tempfile
import time
from typing import Dict, List, Optional, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

from torchft_tpu import chaos, knobs  # noqa: E402
from torchft_tpu.coordination import LighthouseServer  # noqa: E402
from torchft_tpu.orchestration import (  # noqa: E402
    ReplicaGroupRunner,
    render_topology,
)

import obs_report  # noqa: E402
from drills import check_budgets  # noqa: E402

# The drill's budgets: (metric, direction, bound, why).
BUDGETS = (
    ("elastic.goodput_retention", "higher", 0.80,
     "committed samples/s through the 2->8->3 walk against the static "
     "2-replica leg: a resize may cost a fifth of goodput, not more"),
)


def budget_values(report: dict) -> dict:
    summ = report.get("summary") or {}
    return {"elastic.goodput_retention": summ.get("goodput_retention")}


# p < 1 makes the seed pick WHICH groups get the eviction notice (the
# plan sweeps the fleet until enough victims fired, so the count is
# exact while the membership stays seed-dependent); grace is the
# SIGTERM->SIGKILL drain window in ms.
QUICK_SPEC = "preempt@any:p=0.65:grace=90000"
QUICK_SEED = 6814

_STEP_RE = re.compile(r"step=(\d+) .*?t=([0-9.]+)")


# -- seeded preemption plan (shared by the live run and --replay) ----------


def plan_preemptions(
    seed: int, spec: str, candidates: List[int], n_victims: int
) -> Tuple[List[int], List[Dict[str, int]]]:
    """Which ``n_victims`` of ``candidates`` the seed evicts, plus the
    injection records that prove it. Pure function of (seed, spec,
    candidates, n_victims): sweeps the remaining groups in order,
    consulting the chaos decision hash once per (group, pass) visit,
    until exactly ``n_victims`` rules fired — the same multiset falls
    out of every replay."""
    _, rules = chaos.parse_spec(f"seed:{seed},spec:{spec}")
    st = chaos.Chaos(seed, rules)
    victims: List[int] = []
    injections: List[Dict[str, int]] = []
    remaining = list(candidates)
    for _sweep in range(64):
        if len(victims) >= n_victims:
            break
        for g in list(remaining):
            if len(victims) >= n_victims:
                break
            inj = st.pick("preempt", "any", f"elastic_drill/group{g}")
            if inj is None:
                continue
            victims.append(g)
            remaining.remove(g)
            injections.append(
                {
                    "group": g,
                    "site": inj.site,
                    "rule": inj.rule,
                    "visit": inj.visit,
                    "seq": inj.seq,
                    "grace_ms": inj.grace,
                }
            )
    if len(victims) < n_victims:
        raise RuntimeError(
            f"preempt plan starved: {len(victims)}/{n_victims} fired in 64 "
            f"sweeps (spec {spec!r} — count= caps or p too low?)"
        )
    return victims, injections


def _inj_multiset(injections: List[Dict[str, int]]) -> List[Tuple]:
    return sorted(
        (i["site"], i["rule"], i["visit"], i["seq"]) for i in injections
    )


# -- harness helpers -------------------------------------------------------


def _specs(cmd, n_groups, lighthouse, result_dir, journal_dir):
    env = {
        "JAX_PLATFORMS": "cpu",
        "PYTHONUNBUFFERED": "1",  # live join/step detection reads logs
        "TORCHFT_QUORUM_TIMEOUT_SEC": "120",
    }
    os.makedirs(journal_dir, exist_ok=True)
    return render_topology(
        list(cmd) + ["--result-dir", result_dir],
        num_replica_groups=n_groups,
        lighthouse_addr=lighthouse.address(),
        env=env,
        journal_dir=journal_dir,
    )


def _lighthouse() -> LighthouseServer:
    return LighthouseServer(
        bind="127.0.0.1:0",
        min_replicas=2,
        join_timeout_ms=30000,
        quorum_tick_ms=50,
        heartbeat_timeout_ms=5000,
    )


def _pump(runners) -> bool:
    alive = False
    for r in runners:
        alive = r.monitor_once() or alive
    return alive


def _group_text(log_dir: str, group: int) -> str:
    """Every incarnation's log for one group, concatenated."""
    text = []
    for path in sorted(
        glob.glob(os.path.join(log_dir, f"replica{group}_rank0.r*.log"))
    ):
        try:
            text.append(open(path).read())
        except OSError:
            continue
    return "\n".join(text)


def _wait_step_mark(runners, log_dir, group, marks, deadline_s) -> bool:
    """Group reached one of ``marks`` (manager's flushed step lines)."""
    deadline = time.time() + deadline_s
    markers = [f"- step {s}]" for s in marks]
    while time.time() < deadline:
        _pump(runners)
        text = _group_text(log_dir, group)
        if any(m in text for m in markers):
            return True
        time.sleep(0.5)
    return False


def _wait_joined(runners, log_dir, groups, deadline_s) -> List[int]:
    """Waits until every group in ``groups`` committed a step (its first
    trainer step stamp = it healed in and entered lockstep); returns the
    still-missing groups (empty = all joined)."""
    deadline = time.time() + deadline_s
    missing = set(groups)
    while time.time() < deadline and missing:
        _pump(runners)
        for g in list(missing):
            if _STEP_RE.search(_group_text(log_dir, g)):
                missing.discard(g)
        if missing:
            time.sleep(0.5)
    return sorted(missing)


def _stamps(log_dir: str) -> List[Tuple[int, int, float]]:
    """(group, step, unix_time) for every committed-step stamp in every
    incarnation log (train_ddp stamps each step print for this)."""
    out = []
    for path in glob.glob(os.path.join(log_dir, "replica*_rank0.r*.log")):
        m = re.search(r"replica(\d+)_rank0", os.path.basename(path))
        if not m:
            continue
        g = int(m.group(1))
        try:
            text = open(path).read()
        except OSError:
            continue
        for sm in _STEP_RE.finditer(text):
            out.append((g, int(sm.group(1)), float(sm.group(2))))
    return out


def _samples_per_s(
    stamps: List[Tuple[int, int, float]], batch: int
) -> Optional[float]:
    """Aggregate committed samples/s over the leg's steady window: every
    stamp is one group committing one step of ``batch`` examples. Steps
    < 3 are warmup (compile lands in the first stamps' gaps)."""
    ts = sorted(t for (_g, step, t) in stamps if step >= 3)
    if len(ts) < 6 or ts[-1] <= ts[0]:
        return None
    return batch * (len(ts) - 1) / (ts[-1] - ts[0])


def _p95(vals: List[float]) -> Optional[float]:
    s = sorted(vals)
    if not s:
        return None
    return s[max(0, math.ceil(0.95 * len(s)) - 1)]


def _read_results(result_dir, groups) -> Dict[int, Optional[dict]]:
    out: Dict[int, Optional[dict]] = {}
    for g in groups:
        try:
            with open(os.path.join(result_dir, f"group{g}.json")) as f:
                out[g] = json.load(f)
        except (OSError, ValueError):
            out[g] = None
    return out


def _journal_file(journal_dir: str, group: int) -> str:
    return os.path.join(
        journal_dir, f"journal_replica{group}_rank0.jsonl"
    )


# -- legs ------------------------------------------------------------------


def _baseline_leg(args, workdir: str) -> Optional[float]:
    """Static 2-replica run of the same length; returns samples/s."""
    result_dir = os.path.join(workdir, "baseline_results")
    log_dir = os.path.join(workdir, "baseline_logs")
    journal_dir = os.path.join(workdir, "baseline_journal")
    lighthouse = _lighthouse()
    runner = ReplicaGroupRunner(
        _specs(
            [
                sys.executable, "train_ddp.py", "--model", "cnn",
                "--steps", str(args.steps),
                "--batch-size", str(args.batch_size),
                "--min-replicas", "2",
            ],
            2, lighthouse, result_dir, journal_dir,
        ),
        max_restarts=1,
        log_dir=log_dir,
    )
    runner.start()
    try:
        ok = runner.run_until_done(timeout=args.deadline)
    finally:
        runner.stop()
        lighthouse.shutdown()
    if not ok:
        return None
    return _samples_per_s(_stamps(log_dir), args.batch_size)


def _elastic_leg(args, workdir: str, victims, injections) -> dict:
    peak, final = args.peak, args.final_world
    grow_at = args.resize_every
    result_dir = os.path.join(workdir, "results")
    log_dir = os.path.join(workdir, "logs")
    journal_dir = os.path.join(workdir, "journal")
    lighthouse = _lighthouse()
    specs = _specs(
        [
            sys.executable, "train_ddp.py", "--model", "cnn",
            "--steps", str(args.steps),
            "--batch-size", str(args.batch_size),
            "--min-replicas", "2",
        ],
        peak, lighthouse, result_dir, journal_dir,
    )
    base = ReplicaGroupRunner(specs[:2], max_restarts=2, log_dir=log_dir)
    late = ReplicaGroupRunner(specs[2:], max_restarts=2, log_dir=log_dir)
    runners = [base, late]

    def _loc(g: int) -> Tuple[ReplicaGroupRunner, int]:
        return (base, g) if g < 2 else (late, g - 2)

    joiners = list(range(2, peak))
    survivors = sorted(set(range(peak)) - set(victims))
    leg: dict = {
        "victims": victims,
        "survivors": survivors,
        "hard_kills": 0,
        "join_missing": joiners,
        "t_join_s": {},
        "journal_dir": journal_dir,
    }
    t0 = time.time()
    base.start()
    try:
        # -- world 2: reach the grow boundary --------------------------
        assert _wait_step_mark(
            [base], log_dir, 0, range(grow_at, grow_at + 5), args.deadline
        ), f"fleet never reached the grow mark (step {grow_at})"

        # -- grow 2 -> peak: launch the joiners ------------------------
        t_grow = time.time()
        late.start()
        leg["join_missing"] = _wait_joined(
            runners, log_dir, joiners, args.deadline
        )
        assert not leg["join_missing"], (
            f"groups {leg['join_missing']} never entered lockstep"
        )
        # First committed step per joiner = launch -> lockstep latency.
        for (g, _step, t) in sorted(
            _stamps(log_dir), key=lambda s: s[2]
        ):
            if g in joiners and g not in leg["t_join_s"]:
                leg["t_join_s"][g] = round(t - t_grow, 2)

        # -- full world: run to the shrink boundary --------------------
        # The join window consumes an unpredictable number of incumbent
        # steps (6 trainers pre-warm while 2 keep stepping full speed),
        # so the shrink boundary is K steps after the LAST join landed —
        # resizes stay K steps apart in fleet time, and the full-world
        # phase is a real K-step lockstep phase, not a race.
        fleet_now = max(
            (step for (_g, step, _t) in _stamps(log_dir)), default=0
        )
        shrink_at = fleet_now + args.resize_every
        leg["shrink_at"] = shrink_at
        assert shrink_at + args.resize_every <= args.steps, (
            f"horizon too short: joins landed at fleet step {fleet_now}, "
            f"shrink at {shrink_at} leaves < {args.resize_every} post-"
            f"shrink steps of {args.steps} (raise --steps)"
        )
        assert _wait_step_mark(
            runners, log_dir, 0, range(shrink_at, shrink_at + 5),
            args.deadline,
        ), f"fleet never reached the shrink mark (step {shrink_at})"

        # -- shrink peak -> final: deliver the seeded evictions --------
        for inj in injections:
            g = inj["group"]
            runner, idx = _loc(g)
            runner.retire_group(idx)  # a botched drain must stay gone
            assert runner.kill_group(idx, signal.SIGTERM), (
                f"group {g} was not running at its eviction"
            )
            time.sleep(0.3)  # stagger the wave like a real reclaim sweep
        grace_s = max(
            (
                inj["grace_ms"] / 1000.0
                if inj["grace_ms"] > 0
                else knobs.get_float("TORCHFT_DRAIN_GRACE_S")
            )
            for inj in injections
        )
        deadline = time.time() + grace_s
        pending = list(victims)
        while time.time() < deadline and pending:
            _pump(runners)
            pending = [
                g for g in pending if not _loc(g)[0].clean_exit(_loc(g)[1])
            ]
            if pending:
                time.sleep(0.5)
        for g in pending:  # grace exhausted: the k8s hard-kill analog
            runner, idx = _loc(g)
            if runner.kill_group(idx, signal.SIGKILL):
                leg["hard_kills"] += 1

        # -- final world: survivors run out the job --------------------
        fleet_deadline = time.time() + args.deadline
        while time.time() < fleet_deadline:
            if not _pump(runners):
                break
            time.sleep(1.0)
        leg["wedge_free"] = base.run_until_done(timeout=5) and (
            late.run_until_done(timeout=5)
        )
    finally:
        base.stop()
        late.stop()
        lighthouse.shutdown()
    leg["wall_s"] = round(time.time() - t0, 1)

    # -- harvest -----------------------------------------------------------
    res = _read_results(result_dir, range(peak))
    shas = {
        g: (res[g] or {}).get("param_sha256") for g in survivors
    }
    leg["survivor_final_steps"] = [
        (res[g] or {}).get("final_step") for g in survivors
    ]
    leg["agreement"] = (
        None not in shas.values()
        and len(set(shas.values())) == 1
        and all(
            (res[g] or {}).get("final_step") == args.steps
            for g in survivors
        )
    )
    drains_ok = True
    leg["victim_drains"] = {}
    for g in victims:
        runner, idx = _loc(g)
        text = _group_text(log_dir, g)
        row = {
            "exit_clean": runner.clean_exit(idx),
            "drain_logged": "draining at step" in text
            and "left the quorum" in text,
            "elastic_leave_journaled": any(
                e.get("event") == "elastic_leave"
                for e in obs_report.load_events(
                    [_journal_file(journal_dir, g)]
                )
            ),
        }
        leg["victim_drains"][g] = row
        drains_ok = drains_ok and all(row.values())
    leg["drains_ok"] = drains_ok and leg["hard_kills"] == 0

    joins_ok = True
    heal_bytes, heal_secs = 0, 0.0
    for g in joiners:
        evs = obs_report.load_events([_journal_file(journal_dir, g)])
        if not any(e.get("event") == "elastic_join" for e in evs):
            joins_ok = False
        for e in evs:
            attrs = e.get("attrs") or {}
            if e.get("event") == "heal_xfer" and attrs.get("dir") == "recv":
                heal_bytes += int(attrs.get("nbytes", 0))
                heal_secs += float(attrs.get("elapsed_s", 0.0))
    leg["joins_ok"] = joins_ok and len(leg["t_join_s"]) == len(joiners)
    leg["heal_bytes"] = heal_bytes
    leg["heal_gib_s"] = (
        round(heal_bytes / (1 << 30) / heal_secs, 6)
        if heal_secs > 0
        else None
    )
    leg["samples_per_s"] = _samples_per_s(
        _stamps(log_dir), args.batch_size
    )
    return leg


# -- entry points ----------------------------------------------------------


def run_drill(args) -> dict:
    candidates = list(range(args.peak))
    n_victims = args.peak - args.final_world
    if not (2 < args.final_world <= args.peak):
        raise SystemExit("need 2 < final world <= peak")
    if args.steps < 2 * args.resize_every + 8:
        raise SystemExit("need steps >= 2*resize_every + 8 for a real "
                         "post-shrink phase")
    victims, injections = plan_preemptions(
        args.seed, args.spec, candidates, n_victims
    )
    workdir = tempfile.mkdtemp(prefix="elastic_drill_")
    t0 = time.time()
    baseline = _baseline_leg(args, workdir)
    leg = _elastic_leg(args, workdir, victims, injections)

    retention = None
    if baseline and leg.get("samples_per_s"):
        retention = round(leg["samples_per_s"] / baseline, 4)
    t_joins = sorted(leg["t_join_s"].values())
    summary = {
        "time_to_join_p95_s": _p95(t_joins),
        "time_to_join_s": leg["t_join_s"],
        "num_joins": len(leg["t_join_s"]),
        "heal_gib_s": leg["heal_gib_s"],
        "heal_bytes": leg["heal_bytes"],
        "goodput_retention": retention,
        "baseline_samples_per_s": (
            round(baseline, 3) if baseline else None
        ),
        "elastic_samples_per_s": (
            round(leg["samples_per_s"], 3)
            if leg.get("samples_per_s")
            else None
        ),
    }
    result = {
        "drill": "elastic",
        "seed": args.seed,
        "spec": args.spec,
        "walk": [2, args.peak, args.final_world],
        "resize_every": args.resize_every,
        "steps": args.steps,
        "batch_size": args.batch_size,
        "candidates": candidates,
        "n_victims": n_victims,
        "victims": victims,
        "survivors": leg["survivors"],
        "hard_kills": leg["hard_kills"],
        "wedge_free": bool(leg.get("wedge_free")),
        "invariants": {
            "joins": bool(leg["joins_ok"]),
            "drains": bool(leg["drains_ok"]),
            "agreement": bool(leg["agreement"]),
            "goodput": bool(
                retention is not None
                and retention >= args.goodput_floor
            ),
        },
        "goodput_floor": args.goodput_floor,
        "summary": summary,
        "victim_drains": leg["victim_drains"],
        "survivor_final_steps": leg["survivor_final_steps"],
        "wall_s": round(time.time() - t0, 1),
        "journal_dir": leg["journal_dir"],
    }
    result["budget_problems"] = check_budgets(budget_values(result), BUDGETS)
    result["ok"] = bool(
        result["wedge_free"] and all(result["invariants"].values())
        and not result["budget_problems"]
    )
    artifact = {
        **result,
        # The seeded eviction plan: --replay re-derives this multiset
        # from (seed, spec, candidates, n_victims) and asserts equality.
        "injections": injections,
        "replay_cmd": (
            f"python tools/elastic_drill.py --replay {args.out}"
        ),
    }
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=1)
    return result


def run_replay(path: str) -> dict:
    """Re-derives the preemption plan from the artifact's seed and
    compares injection multisets — the determinism the chaos plane
    promises (same seed => same schedule), checked end to end."""
    with open(path) as f:
        doc = json.load(f)
    victims, injections = plan_preemptions(
        int(doc["seed"]), doc["spec"], list(doc["candidates"]),
        int(doc["n_victims"]),
    )
    recorded = _inj_multiset(doc.get("injections") or [])
    recomputed = _inj_multiset(injections)
    return {
        "drill": "elastic-replay",
        "seed": doc["seed"],
        "recorded": len(recorded),
        "recomputed": len(recomputed),
        "victims_match": victims == doc.get("victims"),
        "ok": bool(recorded) and recorded == recomputed
        and victims == doc.get("victims"),
    }


def main() -> int:
    # Driver SIGTERM must run the finally blocks (runner.stop/lighthouse
    # shutdown) or the spawned trainers orphan-spin on quorum retries.
    def _term(_signum, _frame):
        raise SystemExit(143)

    signal.signal(signal.SIGTERM, _term)
    os.chdir(REPO)
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--quick", action="store_true",
                   help="suite_gate lane: the full 2->8->3 walk, short "
                   "step count, fixed seed")
    p.add_argument("--replay", type=str, default=None, metavar="BENCH",
                   help="re-derive the preemption plan from a recorded "
                   "BENCH_ELASTIC.json and assert the injection "
                   "multiset matches (no processes launched)")
    p.add_argument("--seed", type=int, default=QUICK_SEED)
    p.add_argument("--spec", type=str, default=QUICK_SPEC,
                   help="preempt-kind chaos rules for the eviction plan")
    p.add_argument("--steps", type=int, default=260)
    p.add_argument("--resize-every", type=int, default=12,
                   help="K: grow at step ~K, shrink at step ~2K")
    p.add_argument("--peak", type=int, default=8)
    p.add_argument("--final-world", type=int, default=3)
    p.add_argument("--batch-size", type=int, default=256,
               help="256 keeps the step compute-dominant on a "
               "shared-core box, so samples/s compares worlds "
               "fairly (overhead-dominant steps would charge "
               "resizing for scheduler contention)")
    p.add_argument("--goodput-floor", type=float, default=BUDGETS[0][2])
    p.add_argument("--deadline", type=float, default=900.0)
    p.add_argument("--out", type=str,
                   default=os.path.join(REPO, "BENCH_ELASTIC.json"))
    args = p.parse_args()
    report = run_replay(args.replay) if args.replay else run_drill(args)
    print(json.dumps(report), flush=True)
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
