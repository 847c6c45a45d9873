#!/usr/bin/env python
"""Fleet-plane smoke: a 2-replica drill that proves the LIVE health plane.

Spawns a lighthouse + two numpy-only demo trainers with digests enabled,
injects a deterministic chaos ``stall`` on ONE replica's heartbeat path
(``stall@ctrl:match=heartbeat`` — the manager binary's heartbeat loop runs
under that chaos ctx), and polls ``/fleet.json`` WHILE the run is going,
asserting:

  * both replicas appear in the fleet table,
  * both eventually carry a step digest,
  * the stalled replica is flagged a straggler ONLINE — while its
    training processes are still running, not in a post-mortem report,
  * ``obs_top.py --once --check`` renders the live table cleanly,
  * the lighthouse anomalies journal as ``anomaly`` events through the
    exporter's cursor helper.

Run directly or via ``bash tools/suite_gate.sh fleet``.
"""

from __future__ import annotations

import os
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

import obs_export  # noqa: E402
import obs_report  # noqa: E402
import obs_top  # noqa: E402
from torchft_tpu.coordination import LighthouseServer  # noqa: E402
from torchft_tpu.orchestration import (  # noqa: E402
    ReplicaGroupRunner,
    render_topology,
)
from torchft_tpu.telemetry import EventLog  # noqa: E402

STEPS = 40
STEP_SLEEP = 0.25
VICTIM_GROUP = "1"
# Stall every heartbeat RPC of the victim's manager binary by 1.5 s: the
# declared cadence is 100 ms, so the jitter budget (max(8x cadence, 1 s))
# blows on every closed gap. Deterministic (seeded) and ctrl-plane only —
# the data plane and quorum RPCs keep running, which is exactly the
# asymmetric "slow but not dead" straggler lockstep DDP can't surface
# through step rates.
VICTIM_CHAOS = "seed:7,spec:stall@ctrl:match=heartbeat:ms=1500"


def main() -> int:
    workdir = tempfile.mkdtemp(prefix="obs_fleet_smoke_")
    journal_dir = os.path.join(workdir, "journal")
    log_dir = os.path.join(workdir, "logs")
    lighthouse = LighthouseServer(
        bind="127.0.0.1:0", min_replicas=2, join_timeout_ms=30000,
        quorum_tick_ms=50,
        # Way above the injected 1.5 s heartbeat stall: the point is a
        # flagged straggler, not a quorum eviction.
        heartbeat_timeout_ms=30000,
    )
    addr = lighthouse.address()
    specs = render_topology(
        [
            sys.executable, "-m", "torchft_tpu.orchestration.demo_trainer",
            "--steps", str(STEPS), "--dim", "8", "--min-replicas", "2",
            "--step-sleep", str(STEP_SLEEP),
        ],
        num_replica_groups=2,
        lighthouse_addr=addr,
        env={"JAX_PLATFORMS": "cpu", "PYTHONUNBUFFERED": "1"},
        journal_dir=journal_dir,
    )
    for spec in specs:
        if str(spec.replica_group) == VICTIM_GROUP:
            spec.env["TORCHFT_CHAOS"] = VICTIM_CHAOS

    runner = ReplicaGroupRunner(specs, max_restarts=0, log_dir=log_dir)
    t0 = time.time()
    runner.start()

    seen_both = False
    max_n_digest = 0
    straggler_live = None  # (replica_id, flags) seen while trainers ran
    obs_top_check = None   # rc of obs_top --once --check during the run
    finished_cleanly = False
    try:
        deadline = time.monotonic() + 240
        while time.monotonic() < deadline:
            running = runner.monitor_once()
            trainers_alive = bool(runner.live_pids())
            try:
                fleet = obs_top.fetch_fleet(addr, timeout=5.0)
            except Exception:  # noqa: BLE001 - lighthouse may still boot
                fleet = {}
            replicas = fleet.get("replicas") or {}
            groups = {str(rid).split(":", 1)[0] for rid in replicas}
            if {"0", "1"} <= groups:
                seen_both = True
            max_n_digest = max(
                max_n_digest,
                int((fleet.get("agg") or {}).get("n_digest", 0)),
            )
            if trainers_alive and straggler_live is None:
                for rid, row in replicas.items():
                    if str(rid).startswith(VICTIM_GROUP + ":") and (
                        row.get("straggler")
                    ):
                        straggler_live = (rid, sorted(row.get("flags") or []))
                        print(
                            f"straggler flagged ONLINE at "
                            f"+{time.time() - t0:.1f}s: {rid} "
                            f"flags={straggler_live[1]}",
                            flush=True,
                        )
                        break
            if straggler_live is not None and obs_top_check is None:
                proc = subprocess.run(
                    [sys.executable, os.path.join(REPO, "tools", "obs_top.py"),
                     "--lighthouse", addr, "--once", "--check"],
                    capture_output=True, text=True, timeout=30,
                )
                obs_top_check = proc.returncode
                sys.stdout.write(proc.stdout)
                sys.stderr.write(proc.stderr)
            done = not running
            if done:
                finished_cleanly = runner.run_until_done(timeout=1)
                break
            time.sleep(0.5)

        # Journal the anomalies the way a polling exporter would, then
        # prove the journal round-trips through obs_report's loader.
        final_fleet = obs_top.fetch_fleet(addr, timeout=5.0)
        exporter_log = EventLog(
            os.path.join(journal_dir, "exporter.jsonl"),
            replica_id="exporter",
        )
        cursor = obs_export.journal_anomalies(exporter_log, final_fleet, 0)
        exporter_log.close()
    finally:
        runner.stop()
        lighthouse.shutdown()

    assert finished_cleanly, (
        f"demo run did not finish cleanly (logs in {log_dir})"
    )
    assert seen_both, "never saw both replica groups in /fleet.json"
    assert max_n_digest >= 2, (
        f"expected digests from both replicas, peak n_digest={max_n_digest}"
    )
    assert straggler_live is not None, (
        "stalled replica was never flagged straggler while the run "
        f"was live (logs in {log_dir})"
    )
    assert "hb_jitter" in straggler_live[1], (
        f"expected hb_jitter among straggler flags, got {straggler_live[1]}"
    )
    assert obs_top_check == 0, (
        f"obs_top --once --check failed rc={obs_top_check}"
    )
    assert cursor > 0, "no anomalies journaled from the final fleet scrape"
    events = obs_report.load_events([journal_dir])
    anomaly_events = [e for e in events if e.get("event") == "anomaly"]
    assert anomaly_events, "exporter journal has no anomaly events"
    kinds = {e.get("attrs", {}).get("kind") for e in anomaly_events}
    assert "hb_jitter" in kinds, f"anomaly kinds journaled: {kinds}"

    print(
        f"\nfleet smoke OK: straggler={straggler_live[0]} "
        f"flags={straggler_live[1]} anomalies_journaled={cursor} "
        f"wall={time.time() - t0:.1f}s"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
