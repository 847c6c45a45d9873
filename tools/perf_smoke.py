#!/usr/bin/env python
"""Perf-attribution smoke: a journaled 2-replica DDP drill asserted
end-to-end through ``tools/perf_report.py``.

Spawns a lighthouse + two ``train_ddp.py`` CNN trainers (CPU, socket PG)
with the event journal AND ``TORCHFT_PERF`` on, then checks that:

* the merged journal analyzes into per-(step, replica) critical-path
  rows whose phases tile the step window exactly (``perf_report.check``);
* the run-level exposed allreduce is the dominant exposed interval and
  clears a conservative floor. (The CNN drill's per-step quorum round
  is the same order as its 0.4 MB allreduce, so its fraction sits far
  below that of a step that does nothing but wait for the allreduce;
  tests/test_perf_attr.py's ``test_fully_exposed_allreduce`` checks the
  algebra on such a step.);
* ``--emit``-equivalent re-journaling produces ``perf_step`` events;
* the ``perf_model`` event from the TORCHFT_PERF compile-time hook is
  present, so the MFU plumbing is exercised (CPU ⇒ mfu=None, honestly).

Run directly or via ``bash tools/suite_gate.sh perf``.
"""

from __future__ import annotations

import argparse
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

import obs_report  # noqa: E402
import perf_report  # noqa: E402
from torchft_tpu.coordination import LighthouseServer  # noqa: E402
from torchft_tpu.orchestration import (  # noqa: E402
    ReplicaGroupRunner,
    render_topology,
)

STEPS = 6


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--expect-exposed-allreduce", type=float, default=None,
                   help="assert the run-level exposed-allreduce fraction "
                   "is within --tol of this value")
    p.add_argument("--min-exposed-allreduce", type=float, default=0.15,
                   help="floor when no exact expectation is given "
                   "(measured 0.35 on the 1-core CI box; quorum rounds "
                   "and skew waits trade places run to run)")
    p.add_argument("--tol", type=float, default=0.10)
    args = p.parse_args(argv)

    workdir = tempfile.mkdtemp(prefix="perf_smoke_")
    journal_dir = os.path.join(workdir, "journal")
    log_dir = os.path.join(workdir, "logs")
    os.makedirs(journal_dir, exist_ok=True)
    lighthouse = LighthouseServer(
        bind="127.0.0.1:0", min_replicas=2, join_timeout_ms=60000,
        quorum_tick_ms=50, heartbeat_timeout_ms=5000,
    )
    specs = render_topology(
        [
            sys.executable, "train_ddp.py", "--model", "cnn",
            "--steps", str(STEPS), "--batch-size", "8",
            "--min-replicas", "2",
        ],
        num_replica_groups=2,
        lighthouse_addr=lighthouse.address(),
        env={
            "JAX_PLATFORMS": "cpu",
            "PYTHONUNBUFFERED": "1",
            "TORCHFT_QUORUM_TIMEOUT_SEC": "120",
            "TORCHFT_TIMEOUT_SEC": "10",
            "TORCHFT_PERF": "1",
        },
        journal_dir=journal_dir,
    )
    runner = ReplicaGroupRunner(specs, max_restarts=0, log_dir=log_dir)
    t0 = time.time()
    runner.start()
    try:
        ok = runner.run_until_done(timeout=300)
    finally:
        runner.stop()
        lighthouse.shutdown()
    assert ok, f"DDP drill did not finish cleanly (logs in {log_dir})"

    events = obs_report.load_events([journal_dir])
    assert events, f"no journal events written under {journal_dir}"
    report = perf_report.analyze(events)
    errs = perf_report.check(report)
    assert not errs, "perf_report check failed:\n  " + "\n  ".join(errs)
    s = report["summary"]
    assert s["num_rows"] >= 2, f"expected >=2 analyzed rows, got {s}"

    frac = s["exposed_allreduce_frac"]
    assert frac is not None, "no exposed-allreduce fraction computed"
    if args.expect_exposed_allreduce is not None:
        assert abs(frac - args.expect_exposed_allreduce) <= args.tol, (
            f"exposed-allreduce fraction {frac:.4f} not within {args.tol} "
            f"of {args.expect_exposed_allreduce:.4f}"
        )
    else:
        assert frac >= args.min_exposed_allreduce, (
            f"exposed-allreduce fraction {frac:.4f} below the "
            f"{args.min_exposed_allreduce} floor — the socket-PG drill "
            f"should be allreduce-dominated (journal in {journal_dir})"
        )

    emit_path = os.path.join(journal_dir, "perf_steps.jsonl")
    n = perf_report.emit_perf_steps(report, emit_path)
    assert n == s["num_rows"], f"emitted {n} perf_step events, " \
        f"expected {s['num_rows']}"

    assert report["perf_models"], (
        "no perf_model event in the journal — TORCHFT_PERF compile-time "
        "hook did not fire"
    )
    assert report["mfu"] is not None, "perf_model present but no MFU block"

    print(perf_report.render_text(report))
    print(
        f"\nperf smoke OK: exposed_allreduce_frac={frac:.4f} "
        f"overlap_frac={s['overlap_frac']} rows={s['num_rows']} "
        f"perf_step_events={n} wall={time.time() - t0:.1f}s"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
