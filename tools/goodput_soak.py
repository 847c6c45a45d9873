"""Seeded goodput soak: replica-second accounting under a mid-run kill.

Launches a real 2-replica DDP run paced to ~1 s/step, SIGKILLs replica
group 1 once around step N/3 (the paper's 1-kill-per-100-steps drill
shape), and audits the time-accounting plane end to end from the
replicas' own ``goodput_window`` journals via tools/goodput_report.py:

  G1 tiling       — every window's badput splits sum to its duration
                    and every incarnation's windows sum to its ledger
                    total (eps 1e-6): accounted time provably covers
                    wall clock.
  G2 incarnations — the kill shows up in the accounts: the killed
                    group journals >= 2 incarnations and the gap
                    between them lands in the ``down`` account.
  G3 attribution  — the kill's recovery episode is joined to the
                    goodput windows it overlapped, so the per-fault-kind
                    cost table has a populated ``process_loss`` row.

The headline is **goodput retention** — 1 - fault_badput /
(accounted - init_compile). The soak checks ``BUDGETS`` below against
the report it has just built: a broken or unmeasured budget is listed
under ``budget_problems`` and fails the run.

The outcome is ONE JSON line plus a ``BENCH_GOODPUT.json`` artifact
carrying the seed, spec, full goodput report, and journal dir (which
``tools/goodput_report.py --from-bench`` re-audits). A light seeded
control-plane chaos rule rides along so ``--replay BENCH_GOODPUT.json``
has a non-trivial determinism contract: the re-run must fire the
identical injection multiset.

``--quick`` is the suite_gate lane shape: 2 replicas, 100 paced steps,
one kill, fixed seed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

from torchft_tpu import chaos  # noqa: E402
from torchft_tpu.coordination import LighthouseServer  # noqa: E402
from torchft_tpu.orchestration import (  # noqa: E402
    ReplicaGroupRunner,
    render_topology,
)

import goodput_report  # noqa: E402
import obs_report  # noqa: E402
from drills import check_budgets  # noqa: E402

# The soak's budgets: (metric, direction, bound, why). Both are absolute:
# raw fault-badput seconds swing with where the kill lands, so a bound
# relative to an earlier run either flakes or cannot fail.
BUDGETS = (
    ("goodput.retention", "higher", 0.95,
     "the paper's claim: under 5% throughput lost at one failure per "
     "hundred steps"),
    ("goodput.fault_badput_s", "lower", 12.0,
     "replica-seconds one kill may cost: about 7 s here (down, heal, "
     "the survivor's quorum wait), with room for where in a step it lands"),
)


def budget_values(report: dict) -> dict:
    summ = report.get("summary") or {}
    return {
        "goodput.retention": summ.get("goodput_retention"),
        "goodput.fault_badput_s": summ.get("fault_badput_s"),
    }

# Light control-plane-only chaos: bounded commit-vote delays that land in
# the straggler_idle/exposed_comm accounts, NOT the fault-badput kinds —
# the retention headline must isolate the kill's cost. The rule exists so
# --replay has a non-empty injection multiset to assert on.
QUICK_SPEC = "rpc_delay@ctrl:match=should_commit:ms=80:every=10:count=3"
QUICK_SEED = 2718


def _specs(cmd, n_groups, lighthouse, chaos_env, result_dir, journal_dir):
    env = {
        "JAX_PLATFORMS": "cpu",
        "PYTHONUNBUFFERED": "1",
        "TORCHFT_QUORUM_TIMEOUT_SEC": "120",
        "TORCHFT_TIMEOUT_SEC": "10",
    }
    if chaos_env:
        env["TORCHFT_CHAOS"] = chaos_env
    os.makedirs(journal_dir, exist_ok=True)
    return render_topology(
        list(cmd) + ["--result-dir", result_dir],
        num_replica_groups=n_groups,
        lighthouse_addr=lighthouse.address(),
        env=env,
        journal_dir=journal_dir,
    )


def _wait_step_mark(runner, log_dir, group, incarnation, marks, deadline_s):
    deadline = time.time() + deadline_s
    path = os.path.join(log_dir, f"replica{group}_rank0.r{incarnation}.log")
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


def _injections(events):
    """Fired-injection multiset keys, for the replay contract."""
    out = []
    for ev in events:
        if ev.get("event") != "chaos_inject":
            continue
        a = ev.get("attrs", {})
        out.append([
            a.get("origin", "python"), a.get("kind"), a.get("plane"),
            a.get("site"), a.get("rule"), a.get("visit"),
        ])
    return out


def _inj_multiset(injections):
    """Order-free fingerprint: journal interleaving across replicas and
    incarnations may differ between same-seed runs, WHAT fired may not."""
    return sorted(tuple(i) for i in injections)


def run_soak(args) -> dict:
    spec = args.spec
    chaos_env = f"seed:{args.seed},spec:{spec}" if spec else ""
    if chaos_env:
        # Fail on a malformed spec HERE, not as wedged trainers later.
        chaos.parse_spec(chaos_env)

    workdir = tempfile.mkdtemp(prefix="goodput_soak_")
    result_dir = os.path.join(workdir, "results")
    log_dir = os.path.join(workdir, "logs")
    journal_dir = os.path.join(workdir, "journal")
    lighthouse = LighthouseServer(
        bind="127.0.0.1:0",
        min_replicas=2,
        join_timeout_ms=30000,
        quorum_tick_ms=50,
        heartbeat_timeout_ms=5000,
    )
    runner = ReplicaGroupRunner(
        _specs(
            [
                sys.executable, "train_ddp.py", "--model", "cnn",
                "--steps", str(args.steps), "--batch-size", "8",
                "--min-replicas", "2",
                # Paced steps: the steady-state replica-second pool must
                # dwarf the kill's fault badput or retention measures the
                # box's speed, not the recovery cost.
                "--step-min-s", str(args.step_min_s),
            ],
            args.replicas, lighthouse, chaos_env, result_dir, journal_dir,
        ),
        max_restarts=max(args.kills * 2, 1),
        log_dir=log_dir,
    )
    t0 = time.time()
    runner.start()
    kills_done = 0
    try:
        for k in range(args.kills):
            # Kill in the first half so plenty of paced steps remain for
            # the relaunch to heal, replay, and settle back to compute.
            mark = max(1, int(args.steps * (k + 1) / (2 * args.kills + 1)))
            assert _wait_step_mark(
                runner, log_dir, 1, kills_done, range(mark, mark + 4),
                args.deadline,
            ), f"group 1 never reached step {mark}"
            assert runner.kill_group(1), "kill failed"
            kills_done += 1
        wedge_free = runner.run_until_done(timeout=args.deadline)
    finally:
        runner.stop()
        lighthouse.shutdown()
    wall_s = time.time() - t0

    # -- harvest: journals -> audited accounts ----------------------------
    events = obs_report.load_events([journal_dir])
    report = goodput_report.analyze(events)
    problems = goodput_report.check(report)
    summ = report["summary"]
    injections = _injections(events)

    # -- G1: tiling -------------------------------------------------------
    g1 = summ["num_windows"] > 0 and not problems

    # -- G2: the kill shows up as incarnations + down seconds -------------
    g2 = summ["num_incarnations"] >= args.replicas + kills_done
    if kills_done > 0:
        g2 = g2 and summ["badput_s"]["down"] > 0

    # -- G3: per-fault-kind cost attributed -------------------------------
    pl = (summ["fault_cost"] or {}).get("process_loss") or {}
    g3 = kills_done == 0 or (
        pl.get("episodes", 0) >= kills_done
        and pl.get("total_cost_s", 0.0) > 0
    )

    result = {
        "soak": "goodput",
        "seed": args.seed,
        "spec": spec,
        "steps": args.steps,
        "step_min_s": args.step_min_s,
        "replicas": args.replicas,
        "kills": kills_done,
        "wedge_free": bool(wedge_free),
        "injections_fired": len(injections),
        "check_problems": problems,
        "summary": summ,
        "invariants": {
            "accounts_tile": bool(g1),
            "kill_accounted": bool(g2),
            "fault_cost_attributed": bool(g3),
        },
        "wall_s": round(wall_s, 1),
        "journal_dir": journal_dir,
    }
    result["budget_problems"] = check_budgets(budget_values(result), BUDGETS)
    result["ok"] = bool(g1 and g2 and g3 and wedge_free
                        and not result["budget_problems"])
    artifact = {
        **result,
        "replicas_acct": report["replicas"],
        "injections": injections,
        "report_cmd": (
            f"python tools/goodput_report.py --from-bench {args.out} --check"
        ),
        "replay_cmd": f"python tools/goodput_soak.py --replay {args.out}",
    }
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=1)
    return result


def run_replay(args) -> dict:
    with open(args.replay) as f:
        ref = json.load(f)
    args.seed = ref["seed"]
    args.spec = ref["spec"]
    args.steps = ref["steps"]
    args.step_min_s = ref.get("step_min_s", args.step_min_s)
    args.kills = ref.get("kills", 0)
    args.out = args.out or (args.replay + ".replay")
    report = run_soak(args)
    with open(args.out) as f:
        new = json.load(f)
    report["replay_of"] = args.replay
    report["multiset_identical"] = (
        _inj_multiset(ref.get("injections") or [])
        == _inj_multiset(new.get("injections") or [])
    )
    report["ok"] = report["ok"] and report["multiset_identical"]
    return report


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
                   help="suite_gate lane: 2 replicas, 100 paced steps, "
                   "1 kill, fixed seed")
    p.add_argument("--replay", type=str, default=None,
                   help="BENCH_GOODPUT.json to re-run; asserts the "
                   "injection multiset is identical")
    p.add_argument("--seed", type=int, default=QUICK_SEED)
    p.add_argument("--spec", type=str, default=QUICK_SPEC,
                   help="chaos rules ('' disables injection)")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--step-min-s", type=float, default=1.0, dest="step_min_s")
    p.add_argument("--replicas", type=int, default=2)
    p.add_argument("--kills", type=int, default=1,
                   help="SIGKILL relaunches of group 1")
    p.add_argument("--deadline", type=float, default=600.0)
    p.add_argument("--out", type=str, default=None)
    args = p.parse_args()
    if args.out is None and args.replay is None:
        args.out = os.path.join(REPO, "BENCH_GOODPUT.json")
    report = run_replay(args) if args.replay else run_soak(args)
    print(json.dumps(report), flush=True)
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
