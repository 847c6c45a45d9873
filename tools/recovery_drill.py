"""Seeded recovery drill: kill/heal cycles measured end to end.

Launches a real 2-replica (``--quick``) or N-replica DDP run, SIGKILLs
replica group 1 mid-run so it must relaunch and heal from a live peer,
and — with the heal-plane chaos rules armed (``abort_heal`` then
``ckpt_truncate``) — forces the first recovery attempts to fail so the
drill exercises retry, cause latching, and the eventual good transfer.

The replicas' own journals are then stitched into failure->recovery
episodes by ``telemetry.detect_episodes`` (via tools/recovery_report.py,
rotation-aware loading included) and the drill asserts:

  R1 episodes     — at least one closed episode was detected, and
                    ``recovery_report.check`` passes: every episode's
                    detect/quorum/transfer/rebuild/catchup phases tile
                    its TTR exactly.
  R2 attribution  — the root cause of some episode is the kill
                    (``process_loss``) or a heal-plane injection, and
                    every failed heal attempt latched a cause/phase.
  R3 bandwidth    — at least one receiver-side ``heal_xfer`` was
                    accounted (bytes + wire/serialize/lock split), so
                    heal GiB/s per transport is measurable.

The outcome is ONE JSON line plus a ``BENCH_RECOVERY.json`` artifact
carrying TTR p50/p95 (total and per phase), heal bandwidth per
transport, the full episode list, and the journal dir — which
``tools/recovery_report.py --from-bench`` renders. The drill checks
``BUDGETS`` below against that report itself: a broken or unmeasured
budget is listed under ``budget_problems`` and fails the run.

``--quick`` is the suite_gate lane shape: 2 replicas, one kill, fixed
seed, heal chaos armed.
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

import obs_report  # noqa: E402
import recovery_report  # noqa: E402
from drills import check_budgets  # noqa: E402

# The drill's budgets: (metric, direction, bound, why). Absolute, because
# a clean run's TTR is bimodal: it swings 5 s <-> 30 s with how many
# commit-gate vote timeouts (10 s each here) land inside the window, so
# a bound relative to an earlier run either flakes or cannot fail.
BUDGETS = (
    ("recovery.ttr_p50_s", "lower", 60.0,
     "TORCHFT_TTR_BUDGET_S's default: the bound obs_top flags live "
     "replicas against"),
    ("recovery.ttr_p95_s", "lower", 60.0,
     "the same ceiling on the tail, both injected heal failures included"),
    ("recovery.phase_p95_s.detect", "lower", 12.0,
     "in-run latches are immediate; a silent kill costs up to "
     "TORCHFT_TIMEOUT_SEC (10 s here)"),
    ("recovery.phase_p95_s.quorum", "lower", 30.0,
     "about one TORCHFT_TIMEOUT_SEC per failed heal attempt, and the "
     "re-quorum itself"),
    ("recovery.phase_p95_s.transfer", "lower", 5.0,
     "state bytes over heal bandwidth: milliseconds at drill scale"),
    ("recovery.phase_p95_s.rebuild", "lower", 5.0,
     "process-group reconfigure: under a second at drill scale"),
    ("recovery.phase_p95_s.catchup", "lower", 60.0,
     "vote-gather timeouts after failed heals, recompile, re-warm: the "
     "remainder of the TTR ceiling"),
    ("recovery.heal_gib_s.http", "higher", 0.02,
     "a tenth of what the drill's loopback heal shows: a collapsed "
     "transfer, not a slow box"),
)


def budget_values(report: dict) -> dict:
    summ = report.get("summary") or {}
    vals = {
        "recovery.ttr_p50_s": summ.get("ttr_p50_s"),
        "recovery.ttr_p95_s": summ.get("ttr_p95_s"),
    }
    for ph, row in (summ.get("phases") or {}).items():
        vals[f"recovery.phase_p95_s.{ph}"] = row.get("p95_s")
    for transport, row in (summ.get("heal_gib_s") or {}).items():
        vals[f"recovery.heal_gib_s.{transport}"] = row.get("p50")
    return vals


# First heal attempt dies in planning (abort_heal), the second gets a
# truncated checkpoint stream mid-transfer (ckpt_truncate), the third
# must succeed — three distinct failure signatures for the episode
# detector to latch from ONE kill.
QUICK_SPEC = "abort_heal@heal:count=1;ckpt_truncate@heal:count=1"
QUICK_SEED = 4242


def _specs(cmd, n_groups, lighthouse, chaos_env, result_dir, journal_dir):
    env = {
        "JAX_PLATFORMS": "cpu",
        "PYTHONUNBUFFERED": "1",
        "TORCHFT_QUORUM_TIMEOUT_SEC": "120",
        # A failed heal costs one commit-gate vote-gather timeout before
        # the next quorum retries it; the default 30 s would dominate
        # the drill's wall clock (and its measured TTR).
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


def run_drill(args) -> dict:
    spec = args.spec
    chaos_env = f"seed:{args.seed},spec:{spec}" if spec else ""
    if chaos_env:
        # Fail on a malformed spec HERE, not as wedged trainers later.
        chaos.parse_spec(chaos_env)

    workdir = tempfile.mkdtemp(prefix="recovery_drill_")
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
            # Kill in the first half of the run so enough steps remain
            # for the relaunch to heal AND commit (an episode only
            # closes on a committed gate).
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

    # -- harvest: journals -> episodes ------------------------------------
    events = obs_report.load_events([journal_dir])
    report = recovery_report.analyze(events)
    problems = recovery_report.check(report)
    episodes = report["episodes"]
    summ = report["summary"]
    closed = [e for e in episodes if not e["open"]]

    # -- R1: episodes detected, phases tile -------------------------------
    r1 = bool(closed) and not problems

    # -- R2: root cause + latched failed attempts -------------------------
    causes = {e["root_cause"]["kind"] for e in episodes}
    latched = [
        a
        for e in episodes
        for row in e["replicas"].values()
        for a in row["attempts"]
        if not a.get("ok")
    ]
    r2 = bool(causes & {"process_loss", "chaos"}) and all(
        a.get("cause") for a in latched
    )
    if args.kills > 0 and spec:
        # Both heal chaos kinds must actually have fired.
        r2 = r2 and len(latched) >= 2

    # -- R3: heal bandwidth accounted -------------------------------------
    r3 = bool(summ["heal_gib_s"]) and all(
        row["bytes"] > 0 for row in summ["heal_gib_s"].values()
    )

    result = {
        "drill": "recovery",
        "seed": args.seed,
        "spec": spec,
        "steps": args.steps,
        "replicas": args.replicas,
        "kills": kills_done,
        "wedge_free": bool(wedge_free),
        "episodes_detected": len(episodes),
        "episodes_closed": len(closed),
        "check_problems": problems,
        "summary": summ,
        "invariants": {
            "episodes_tile": bool(r1),
            "root_cause_attributed": bool(r2),
            "bandwidth_accounted": bool(r3),
        },
        "wall_s": round(wall_s, 1),
        "journal_dir": journal_dir,
    }
    result["budget_problems"] = check_budgets(budget_values(result), BUDGETS)
    result["ok"] = bool(r1 and r2 and r3 and wedge_free
                        and not result["budget_problems"])
    artifact = {
        **result,
        "episodes": episodes,
        "report_cmd": (
            f"python tools/recovery_report.py --from-bench {args.out}"
        ),
    }
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=1)
    return result


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
                   help="suite_gate lane: 2 replicas, 1 kill, fixed seed, "
                   "heal chaos armed")
    p.add_argument("--seed", type=int, default=QUICK_SEED)
    p.add_argument("--spec", type=str, default=QUICK_SPEC,
                   help="heal-plane chaos rules ('' disables injection)")
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--replicas", type=int, default=2)
    p.add_argument("--kills", type=int, default=1,
                   help="SIGKILL relaunches of group 1 (each must heal)")
    p.add_argument("--deadline", type=float, default=600.0)
    p.add_argument("--out", type=str,
                   default=os.path.join(REPO, "BENCH_RECOVERY.json"))
    args = p.parse_args()
    report = run_drill(args)
    print(json.dumps(report), flush=True)
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
