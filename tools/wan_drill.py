"""Two-region WAN drill: DiLoCo across a throttled, lossy, partition-prone
link.

Launches two DiLoCo replica groups ("regions") whose only connection is the
replica-axis data plane, marks that link ``wan`` via ``TORCHFT_LINKS`` (15 s
connect budget, striped sockets, int8 wire), and arms a seeded
``TORCHFT_CHAOS`` schedule that degrades the link three ways:

  throttle — a token bucket pacing every cross-region byte (sustained
             rate + burst), the WAN-bandwidth model
  stall    — fixed-cadence frame stalls, the WAN-jitter/loss model
  reset    — a mid-run burst of connection tears: the first tears are
             absorbed IN-COLLECTIVE by stripe failover (surviving sockets
             adopt the dead stripe's byte range), the rest exhaust the
             stripe set, abort the step, and force the latch -> quorum ->
             reconfigure heal — the full link-kill + recovery story

plus a control-plane ``rpc_delay`` on the commit vote so the drill spans
both planes. The invariants checked from the regions' own journals are
chaos_soak's, tightened with the failover contract:

  I1 agreement   — both regions finish at the same outer step with the
                   same global-fragment sha256, and each region's commit
                   sequence is strictly monotonic. (Unlike chaos_soak,
                   the per-region gate sequences are NOT required to be
                   identical: a torn sync can time out one region's
                   vote-gather while the other commits, and the loser
                   heals from the winner — final-state equality is the
                   contract, not lockstep votes.)
  I2 no wedge    — both regions exit cleanly within the deadline.
  I3 recovery    — every injection is followed by a committed sync within
                   ``--recovery-bound`` seconds.
  F  failover    — at least one ``stripe_failover`` journal event fired:
                   a leg died mid-collective and its range was re-assigned
                   without aborting the step.

The outcome is one JSON line plus a ``BENCH_WAN.json`` artifact carrying
per-link-class GiB/s (from the engine's always-on byte/busy counters),
failover/rejoin counts, per-injection recovery times, and the full
injection sequence. Replay with::

    python tools/wan_drill.py --replay BENCH_WAN.json

which re-runs the identical schedule and asserts the injection MULTISET
(origin, kind, plane, site, rule, visit — per region) is identical.
Unlike chaos_soak, the fingerprint is order-insensitive: the native data
plane fires from per-stripe sender threads, so the journal ORDER of
same-site injections is racy while the seeded set of firing visits is
not — sorting canonicalizes exactly the part the seed pins down.

``--quick`` is the suite_gate lane shape: fixed seed, built-in spec, small
step budget.
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

from torchft_tpu import chaos  # noqa: E402
from torchft_tpu.coordination import LighthouseServer  # noqa: E402
from torchft_tpu.orchestration import (  # noqa: E402
    ReplicaGroupRunner,
    render_topology,
)
from torchft_tpu.process_group import parse_links  # noqa: E402

# Every region sees every cross-region peer as wan: striped link (4
# sockets — the failover headroom), int8 wire (the wan preset), generous
# connect budget for the post-partition redial. Symmetric by construction
# (one spec in every environment), which the acceptor validates.
WAN_LINKS = "*=wan,streams=4"

# The quick schedule. The throttle activates once per site (then paces
# silently) and every other rule is visit-addressed and count-bounded, so
# the set of (site, rule, visit) that fires is a pure function of the
# seed: replayable even though WHICH stripe draws a torn visit and which
# op a visit lands in drift with scheduling.
#   throttle — 128 MiB/s sustained, 4 MiB burst on every wan byte (data)
#   stall    — 30 ms frame stalls on a fixed cadence (data)
#   rpc_delay— commit votes delayed 80 ms on a fixed cadence (ctrl)
#   reset x2 — the degraded-link double feature: two SPACED tears (one
#              leg each — survivors must adopt the range in-collective:
#              the stripe_failover contract), then a burst of 6
#              consecutive tears that exhausts the stripe set -> abort ->
#              latch -> quorum -> reconfigure heal (the link kill)
QUICK_SPEC = (
    "throttle@data:link=wan:rate=134217728:bucket=4194304;"
    "stall@data:link=wan:every=7:ms=30:count=4;"
    "rpc_delay@ctrl:match=should_commit:ms=80:every=3:count=3;"
    "reset@data:link=wan:after=10:every=7:count=2;"
    "reset@data:link=wan:after=26:count=6"
)

QUICK_SEED = 2077


def _specs(cmd, n_groups, lighthouse, env_extra, result_dir, journal_dir):
    env = {
        "JAX_PLATFORMS": "cpu",
        "PYTHONUNBUFFERED": "1",
        "TORCHFT_QUORUM_TIMEOUT_SEC": "120",
        # A torn sync costs one vote-gather timeout before the quorum
        # retries it; the default 30 s would dominate the drill's clock.
        "TORCHFT_TIMEOUT_SEC": "10",
        # The striped engine is where in-collective failover lives.
        "TORCHFT_PG": "native",
        **env_extra,
    }
    os.makedirs(journal_dir, exist_ok=True)
    return render_topology(
        list(cmd) + ["--result-dir", result_dir],
        num_replica_groups=n_groups,
        lighthouse_addr=lighthouse.address(),
        env=env,
        journal_dir=journal_dir,
    )


def _read_journal(path):
    out = []
    try:
        with open(path) as f:
            for line in f:
                try:
                    out.append(json.loads(line))
                except ValueError:
                    pass  # torn tail line of a killed incarnation
    except OSError:
        pass
    return out


def _injections(events):
    """The region's fired-injection sequence, in journal order."""
    out = []
    for ev in events:
        if ev.get("event") != "chaos_inject":
            continue
        a = ev.get("attrs", {})
        out.append(
            {
                "ts": ev.get("ts"),
                "step": ev.get("step"),
                "origin": a.get("origin", "python"),
                "kind": a.get("kind"),
                "plane": a.get("plane"),
                "site": a.get("site"),
                "rule": a.get("rule"),
                "visit": a.get("visit"),
            }
        )
    return out


def _commits(events):
    """[(ts, step)] of committed gates, journal order."""
    return [
        (ev.get("ts"), ev.get("step"))
        for ev in events
        if ev.get("event") == "commit_gate"
        and ev.get("attrs", {}).get("committed")
    ]


def _failovers(events):
    """stripe_failover journal events, split mid-collective vs rejoin."""
    evs = [
        dict(ev.get("attrs", {}), ts=ev.get("ts"))
        for ev in events
        if ev.get("event") == "stripe_failover"
    ]
    return (
        [e for e in evs if e.get("dir") != "rejoin"],
        [e for e in evs if e.get("dir") == "rejoin"],
    )


def _link_gib_s(events, links_spec):
    """Per-link-class effective GiB/s from the LAST native_counters event
    (the engine's cumulative byte/busy counters). Lane busy-ns accumulate
    across the n_streams parallel stripes, so wall time is busy/streams —
    the same normalization process_group.peer_gib_s applies."""
    last = None
    for ev in events:
        if ev.get("event") == "native_counters":
            last = ev.get("attrs", {})
    if not last:
        return {}
    default, overrides = parse_links(links_spec)
    n_streams = max(int(last.get("n_streams", 1)), 1)
    agg = {}
    for p in last.get("peers", []):
        cls = p.get("link") or overrides.get(
            int(p.get("peer", -1)), default
        ).cls
        busy = int(p.get("tx_busy_ns", 0)) + int(p.get("rx_busy_ns", 0))
        nbytes = int(p.get("tx_bytes", 0)) + int(p.get("rx_bytes", 0))
        if busy <= 0 or nbytes <= 0:
            continue
        b, n = agg.get(cls, (0, 0))
        agg[cls] = (b + nbytes, n + busy)
    return {
        cls: round(nbytes / float(1 << 30) / (busy / n_streams / 1e9), 3)
        for cls, (nbytes, busy) in agg.items()
    }


def _seq_key(injections):
    """The determinism fingerprint: what fired, where, on which visit —
    as a SORTED multiset. Journal order is excluded on purpose: native
    stripe legs race for visit numbers on a shared site, so same-seed
    runs interleave identically-numbered firings differently while the
    set of (site, rule, visit) that fire is pinned by the seed."""
    return sorted(
        (
            i["origin"] or "",
            i["kind"] or "",
            i["plane"] or "",
            i["site"] or "",
            i["rule"] if i["rule"] is not None else -1,
            i["visit"] if i["visit"] is not None else -1,
        )
        for i in injections
    )


def run_drill(args) -> dict:
    chaos_env = f"seed:{args.seed},spec:{args.spec}"
    # Fail on a malformed spec/link map HERE, not as 2 wedged regions.
    chaos.parse_spec(chaos_env)
    parse_links(args.links)

    workdir = tempfile.mkdtemp(prefix="wan_drill_")
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
                sys.executable, "train_diloco.py",
                # Outer-step addressed (not an inner-step budget): a sync
                # torn by the link kill is retried until it lands, so both
                # regions always REACH the target instead of running out
                # of inner steps mid-heal.
                "--outer-steps", str(args.outer_steps),
                "--sync-every", str(args.sync_every),
                "--n-fragments", "2",
                "--fragment-sync-delay", "1",
                "--batch-size", "2",
                "--seq-len", "32",
                "--min-replicas", "2",
            ],
            2,
            lighthouse,
            {"TORCHFT_CHAOS": chaos_env, "TORCHFT_LINKS": args.links},
            result_dir,
            journal_dir,
        ),
        max_restarts=1,
        log_dir=log_dir,
    )
    t0 = time.time()
    runner.start()
    try:
        wedge_free = runner.run_until_done(timeout=args.deadline)
    finally:
        runner.stop()
        lighthouse.shutdown()
    wall_s = time.time() - t0

    # -- harvest ----------------------------------------------------------
    results, journals = {}, {}
    for g in (0, 1):
        try:
            with open(os.path.join(result_dir, f"group{g}.json")) as f:
                results[g] = json.load(f)
        except (OSError, ValueError):
            results[g] = None
        journals[g] = _read_journal(
            os.path.join(journal_dir, f"journal_replica{g}_rank0.jsonl")
        )
    injections = {g: _injections(journals[g]) for g in (0, 1)}
    commits = {g: _commits(journals[g]) for g in (0, 1)}
    fo = {g: _failovers(journals[g]) for g in (0, 1)}
    link_gib = {g: _link_gib_s(journals[g], args.links) for g in (0, 1)}

    # -- I1: the regions agree --------------------------------------------
    shas = [r.get("global_sha") if r else None for r in results.values()]
    steps = [r.get("final_outer_step") if r else None for r in results.values()]
    committed_steps = {g: [s for (_, s) in commits[g]] for g in (0, 1)}
    mono = all(
        all(a < b for a, b in zip(committed_steps[g], committed_steps[g][1:]))
        for g in (0, 1)
    )
    i1 = (
        None not in shas
        and len(set(shas)) == 1
        and len(set(steps)) == 1
        and mono
    )

    # -- I2: no region wedged ---------------------------------------------
    i2 = bool(wedge_free) and None not in steps

    # -- I3: bounded recovery per injection -------------------------------
    recoveries = []
    i3 = True
    for g in (0, 1):
        last_commit = max((ts for (ts, _) in commits[g]), default=0.0)
        for inj in injections[g]:
            after = [ts for (ts, _) in commits[g] if ts >= inj["ts"]]
            rec = round(min(after) - inj["ts"], 3) if after else None
            recoveries.append(
                {
                    "region": g,
                    "kind": inj["kind"],
                    "plane": inj["plane"],
                    "site": inj["site"],
                    "recovery_s": rec,
                }
            )
            if rec is None:
                # Legal only for a fault injected after the region's final
                # commit (nothing left in the run to commit).
                if inj["ts"] <= last_commit:
                    i3 = False
            elif rec > args.recovery_bound:
                i3 = False

    # -- F: the link died mid-collective and the stripes carried it -------
    n_failover = sum(len(fo[g][0]) for g in (0, 1))
    n_rejoin = sum(len(fo[g][1]) for g in (0, 1))

    n_inj = sum(len(v) for v in injections.values())
    kinds = sorted(set(i["kind"] for v in injections.values() for i in v))
    planes = sorted(set(i["plane"] for v in injections.values() for i in v))
    report = {
        "drill": "wan",
        "seed": args.seed,
        "spec": args.spec,
        "links": args.links,
        "outer_steps": args.outer_steps,
        "sync_every": args.sync_every,
        "injections_fired": n_inj,
        "kinds_fired": kinds,
        "planes_fired": planes,
        "stripe_failovers": n_failover,
        "stripe_rejoins": n_rejoin,
        "link_gib_s": link_gib,
        "invariants": {
            "agreement": bool(i1),
            "no_wedge": bool(i2),
            "bounded_recovery": bool(i3),
            "failover_fired": n_failover > 0,
        },
        "final_outer_steps": steps,
        "max_recovery_s": max(
            (r["recovery_s"] for r in recoveries if r["recovery_s"]),
            default=0.0,
        ),
        "wall_s": round(wall_s, 1),
        "journal_dir": journal_dir,
    }
    report["ok"] = bool(
        i1
        and i2
        and i3
        and n_failover > 0
        and "throttle" in kinds
        and "reset" in kinds
        and len(planes) >= 2
    )
    artifact = {
        **report,
        "injections": {str(g): injections[g] for g in (0, 1)},
        "recoveries": recoveries,
        "replay_cmd": f"python tools/wan_drill.py --replay {args.out}",
    }
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=1)
    return report


def run_replay(args) -> dict:
    with open(args.replay) as f:
        ref = json.load(f)
    args.seed = ref["seed"]
    args.spec = ref["spec"]
    args.links = ref.get("links", WAN_LINKS)
    args.outer_steps = ref["outer_steps"]
    args.sync_every = ref.get("sync_every", 4)
    args.out = args.out or (args.replay + ".replay")
    report = run_drill(args)
    with open(args.out) as f:
        new = json.load(f)
    matches = {}
    for g in ("0", "1"):
        matches[g] = _seq_key(ref["injections"][g]) == _seq_key(
            new["injections"][g]
        )
    report["replay_of"] = args.replay
    report["sequence_identical"] = all(matches.values())
    report["ok"] = report["ok"] and report["sequence_identical"]
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
                   help="suite_gate lane: fixed seed, built-in spec")
    p.add_argument("--replay", type=str, default=None,
                   help="BENCH_WAN.json to re-run; asserts the injection "
                   "multiset is identical")
    p.add_argument("--seed", type=int, default=QUICK_SEED)
    p.add_argument("--spec", type=str, default=QUICK_SPEC)
    p.add_argument("--links", type=str, default=WAN_LINKS)
    p.add_argument("--outer-steps", type=int, default=5)
    p.add_argument("--sync-every", type=int, default=4,
                   help="inner steps per sync; must be divisible by the "
                   "fragment count (2)")
    p.add_argument("--recovery-bound", type=float, default=120.0)
    p.add_argument("--deadline", type=float, default=600.0)
    p.add_argument("--out", type=str, default=None)
    args = p.parse_args()
    if args.out is None and args.replay is None:
        args.out = os.path.join(REPO, "BENCH_WAN.json")
    report = run_replay(args) if args.replay else run_drill(args)
    print(json.dumps(report), flush=True)
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
