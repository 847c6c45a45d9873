"""The benchmark's command: one run of one cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process never imports JAX: a chip belongs to one process, and every
replica group of the cell is a child (worker.py) with its own chip. It
builds the control plane when its binaries are missing or stale, starts a
lighthouse where the mix asks for one, starts the groups in parallel,
waits, joins what they wrote, and prints the contract's JSON object as
the last line of its standard output. Everything else (phase timings,
per-group details, logs) goes to standard error or into the run's
directory, ``.bench_runs/<cell>/`` in the checkout. On any failure it
prints no result and exits non-zero.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import time
from statistics import fmean
from typing import Any, Dict, List, Optional, Tuple

T0 = time.time()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import cells  # noqa: E402

CPP_DIR = os.path.join(ROOT, "torchft_tpu", "_cpp")
# Exit within the driver's 360 s for a run whose programs are cached; the
# first run of a cell in a checkout compiles and may take 1200 s.
DEADLINE_S = 1100.0
# A run that lost steps says why in at most this many lines of standard error.
LOST_STEP_LINES = 40


class RunFailure(Exception):
    pass


def say(msg: str) -> None:
    print(f"[run +{time.time() - T0:6.1f}s] {msg}", file=sys.stderr, flush=True)


def build_control_plane() -> None:
    """``make all`` is a no-op when the binaries are newer than their
    sources; a checkout's first run builds them (no ``clean``)."""
    if not os.path.isdir(CPP_DIR):
        raise RunFailure(f"{CPP_DIR} is missing: nothing to benchmark here")
    proc = subprocess.run(
        ["make", "-C", CPP_DIR, "-j8", "all"], capture_output=True, text=True
    )
    if proc.returncode != 0:
        raise RunFailure(f"make all failed:\n{proc.stderr[-3000:]}")


def start_lighthouse(min_replicas: int, log) -> Tuple["subprocess.Popen[str]", int]:
    """The lighthouse binary with the defaults of
    ``coordination.LighthouseServer`` (the parent stays off the package),
    and the port it listens on."""
    proc = subprocess.Popen(
        [
            os.path.join(CPP_DIR, "bin", "lighthouse"),
            "--bind-host", "127.0.0.1", "--port", "0",
            "--min-replicas", str(min_replicas),
            "--join-timeout-ms", "60000",
            "--quorum-tick-ms", "100",
            "--heartbeat-timeout-ms", "5000",
            "--parent-pid", str(os.getpid()),
        ],
        stdout=subprocess.PIPE, stderr=log, text=True,
    )
    assert proc.stdout is not None
    deadline = time.time() + 10.0
    while time.time() < deadline:
        line = proc.stdout.readline()
        if line.startswith("LISTENING "):
            return proc, int(line.split()[1])
        if not line and proc.poll() is not None:
            break
    proc.kill()
    proc.wait()
    raise RunFailure("the lighthouse did not start")


def group_env(mix: Dict[str, Any], group: int, run_dir: str,
              lighthouse: Optional[str]) -> Dict[str, str]:
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["REPLICA_GROUP_ID"] = str(group)
    # Paths are the only thing of the program's the benchmark sets.
    env["TORCHFT_JOURNAL_FILE"] = os.path.join(run_dir, f"journal_g{group}.jsonl")
    if lighthouse:
        env["TORCHFT_LIGHTHOUSE"] = lighthouse
    if int(mix["groups"]) > 1:
        # One chip of the host for each group (libtpu's own variables;
        # each process is a one-chip "slice" with its own port).
        env.update({
            "TPU_VISIBLE_CHIPS": str(group),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
            "CLOUD_TPU_TASK_ID": "0",
            "TPU_PROCESS_ADDRESSES": f"localhost:{8476 + group}",
            "TPU_PROCESS_PORT": str(8476 + group),
        })
    return env


def stop_all(procs: List["subprocess.Popen"]) -> None:
    for p in procs:
        if p.poll() is None:
            p.send_signal(signal.SIGTERM)
    for p in procs:
        try:
            p.wait(timeout=10)
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    # Tests run a table of their own on the CPU; BENCHMARK.json's cells
    # run on a TPU or not at all.
    ap.add_argument("--table", default="")
    args = ap.parse_args()

    cell = cells.load_cell(args.workload, args.table)
    mix = cell.mix
    platform = "tpu"
    if args.table:
        platform = cells.load_json(args.table).get("platform", "tpu")
    n_groups = int(mix["groups"])
    run_dir = os.path.join(ROOT, ".bench_runs", cell.name)
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)

    procs: List[subprocess.Popen] = []
    workers: List[subprocess.Popen] = []
    logs = contextlib.ExitStack()  # the children's output files
    try:
        build_control_plane()
        say("control plane ready")
        lighthouse = None
        if mix.get("min_replicas"):
            lh, port = start_lighthouse(
                int(mix["min_replicas"]),
                logs.enter_context(open(os.path.join(run_dir, "lighthouse.log"), "w")),
            )
            procs.append(lh)
            lighthouse = f"127.0.0.1:{port}"
        for g in range(n_groups):
            cmd = [
                sys.executable, "-m", "benchmark.worker",
                "--workload", cell.name, "--seed", str(args.seed),
                "--seconds", str(args.seconds), "--trace", str(args.trace),
                "--group", str(g), "--run-dir", run_dir, "--t0", repr(T0),
                "--platform", platform, "--table", args.table,
            ]
            log = logs.enter_context(
                open(os.path.join(run_dir, f"worker_g{g}.log"), "w")
            )
            w = subprocess.Popen(
                cmd, cwd=ROOT, env=group_env(mix, g, run_dir, lighthouse),
                stdout=log, stderr=subprocess.STDOUT,
            )
            workers.append(w)
            procs.append(w)
        # Any group that fails fails the run at once: the others would
        # wait for it at the quorum.
        pending = set(range(n_groups))
        while pending:
            if time.time() - T0 > DEADLINE_S:
                raise RunFailure("deadline passed")
            for g in sorted(pending):
                rc = workers[g].poll()
                if rc is None:
                    continue
                if rc != 0:
                    raise RunFailure(f"group {g} exited with {rc}")
                pending.discard(g)
            time.sleep(0.05)
    except RunFailure as e:
        stop_all(procs)
        for g in range(len(workers)):
            path = os.path.join(run_dir, f"worker_g{g}.log")
            if os.path.exists(path):
                with open(path, errors="replace") as f:
                    sys.stderr.write(f"--- worker_g{g}.log ---\n{f.read()[-6000:]}\n")
        print(f"benchmark run FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        stop_all(procs)
        logs.close()

    return report(cell, run_dir, args.trace, platform)


def report(cell: cells.Cell, run_dir: str, trace: int, platform: str) -> int:
    """What is left once every group has exited: the end of each worker's
    log, why a lost step was lost, and the joined line. Everything but
    the line goes to standard error, the compared numbers last."""
    n_groups = int(cell.mix["groups"])
    for g in range(n_groups):
        with open(os.path.join(run_dir, f"worker_g{g}.log"), errors="replace") as f:
            sys.stderr.write(f.read()[-4000:])
    results = [
        cells.load_json(os.path.join(run_dir, f"result_g{g}.json"))
        for g in range(n_groups)
    ]
    # A group whose window lost a step carries its journal's refused gates
    # and evictions (gate_readers.explain). Written here, after the tails
    # and not inside one that the cut above may lose: the driver keeps no
    # run directory, only the end of this stream.
    lost = [
        f"{line} group={r['group']}" for r in results for line in r.get("lost_steps", [])
    ]
    for line in lost[:LOST_STEP_LINES]:
        print(line, file=sys.stderr)
    if len(lost) > LOST_STEP_LINES:
        say(f"{len(lost) - LOST_STEP_LINES} more such lines are in {run_dir}")
    try:
        line = json.dumps(join(cell, results, trace, platform))
    except RunFailure as e:
        print(f"benchmark run FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(line, flush=True)
    return 0


def join(cell: cells.Cell, results: List[Dict[str, Any]], trace: int,
         platform: str) -> Dict[str, Any]:
    """The contract's object from the groups' result files.

    ``peak_hbm_gib`` is the SMALLEST over the cell's groups of each
    group's ``memory_peak_bytes`` (itself the largest process-lifetime
    ``peak_bytes_in_use`` over that group's devices). Why not the
    largest: untraced, a step loop that does not donate dispatches the
    next gradient program before the update has executed, so a group's
    peak is the step's footprint plus as much of the next gradient as
    exists by then, a race between host and device that lands on one of
    three levels (13.634, 13.853, once 14.315 GiB in the four-group cell)
    by no rule anyone found; the largest of four flips between them from
    run to run with no code in the path, 1.6% apart under a 1% bound. The
    smallest group read the lowest level in every run that was counted. A
    true rise in the step's footprint raises every group's reading, the
    smallest too, so the metric guards what it guarded. Why not the sum
    or the mean: five or more levels 0.4% apart, and another unit. In a
    cell of one group the smallest is the largest. ``device`` keeps the
    other end, ``memory_peak_bytes`` (the largest: what an allocator would
    fail at), and every group's reading in group order.
    """
    windows = [r["window"] for r in results]
    t_start = min(w["t_start"] for w in windows)
    t_end = max(w["t_end"] for w in windows)
    tokens = sum(w["tokens"] for w in windows)
    peaks = [r["memory_peak_bytes"] for r in results]  # in group order
    kinds = {r["device"]["kind"] for r in results}
    device: Dict[str, Any] = {
        "platform": results[0]["device"]["platform"],
        "kind": sorted(kinds)[0],
        "count": sum(r["device"]["count"] for r in results),
        "memory_peak_bytes": max(peaks),
        "memory_peak_bytes_by_group": peaks,
    }
    values = {
        "setup_s": t_start - T0,
        "tok_s_chip": tokens / (t_end - t_start) / cell.chips,
        "peak_hbm_gib": min(peaks) / 2**30,
    }

    # -- correct ------------------------------------------------------------
    reference = results[0]["checks"]["reference"]
    compiled = max(r["checks"]["programs_compiled_in_window"] for r in results)
    states = {(r["fingerprint"], r["steps_done"]) for r in results}
    # Every number `correct` compares, beside its limit: in the line under
    # a key of its own, and the last lines of standard error.
    compared = {
        "loss_rel_diff": {"value": reference["loss_rel_diff"],
                          "limit": reference["loss_rel_tol"]},
        "grad_rel_l2_worst": {"value": reference["grad_rel_l2_worst"],
                              "limit": reference["grad_rel_l2_tol"]},
        "programs_compiled_in_window": {"value": compiled, "limit": 0},
        "distinct_group_states": {"value": len(states), "limit": 1},
    }
    checks: Dict[str, bool] = {
        "platform": all(r["device"]["platform"] == platform for r in results),
        "one_device_kind": len(kinds) == 1,
        "chips": device["count"] == cell.chips,
        "nothing_compiled_in_window": compiled == 0,
        "reference": bool(reference["ok"]),
        "losses_finite": all(math.isfinite(x) for r in results for x in r["losses"]),
        # The system's guarantee: after a commit every replica group holds
        # the same model, bit for bit (every rank dequantizes the same
        # bytes of the reduced gradient). No later PR may weaken this.
        "groups_hold_equal_parameters": len(states) == 1,
    }
    for r in results:
        for k, v in r["checks"].items():
            if isinstance(v, bool):
                checks[k] = checks.get(k, True) and v
    say(f"checks: {checks}")
    say(f"reference: {reference}")

    out: Dict[str, Any] = {
        "correct": all(checks.values()),
        "attempted": sum(w["attempted"] for w in windows),
        "failed": sum(w["failed"] for w in windows),
    }
    if trace:
        units = {m["name"]: m["unit"] for m in cell.per_layer}
        names = [n for n in units if all(n in r["per_layer"] for r in results)]
        # A per-layer metric of a cell with several groups is the mean
        # over the groups.
        out["metrics"] = {
            n: {"value": fmean([r["per_layer"][n] for r in results]), "unit": units[n]}
            for n in names
        }
        traces = [r["trace"] for r in results if "trace" in r]
        if platform == "tpu" and len(traces) != len(results):
            raise RunFailure("a traced run on a TPU gave no device trace")
        if traces:
            device["busy_s"] = fmean([t["busy_s"] for t in traces])
            device["window_s"] = fmean([t["window_s"] for t in traces])
            out["breakdown"] = {
                "device_ops": traces[0]["device_ops"],
                "idle_gaps": traces[0]["idle_gaps"],
            }
    else:
        out["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end
        }
    out["device"] = device
    out["compared"] = compared
    say(f"end to end: {values}; memory_peak_bytes by group {peaks}; group 0 step "
        f"median {sorted(results[0]['step_s'])[len(results[0]['step_s']) // 2]:.4f}s")
    for name, c in compared.items():
        say(f"compared: {name} {c['value']} limit {c['limit']}")
    return out


if __name__ == "__main__":
    sys.exit(main())
