"""Proof that the fault-tolerant trainer starts and heals on the chip.

    python chip_smoke.py            # one chip (what the driver runs)
    python chip_smoke.py --chips 4  # one process on all four chips

Default: builds the C++ control plane from the tracked sources, starts a
lighthouse, runs ``train_hsdp.py --model small`` as two replica groups —
group 0 on the chip (4x2048 tokens a step, flash attention, int8 device
quantize), group 1 pinned to the CPU by environment (1x64 tokens, same
125M-parameter gradient payload on the wire) — SIGKILLs each once and
asserts, from the journals, metrics, logs and result files, that they
committed in lockstep, took the compiled branches, and healed both ways.

``--chips 4`` runs only the sharded path: one group on the fsdp=2 x tp=2
mesh ``auto_mesh(4)`` gives, after the same run in a process that sees one
chip; losses must agree and the state must really be spread four ways.

This process never initialises a JAX backend: a chip belongs to one
process, and everything on the device runs in a child. The last stdout
line is ``{"ok": true, "device": {...}}`` as the chip child reported it;
on any failure there is a message, a non-zero exit, and no such line.
"""

from __future__ import annotations

import argparse
import dataclasses
import glob
import json
import math
import os
import shutil
import statistics
import subprocess
import sys
import time
from typing import Any, Callable, Dict, List

REPO = os.path.dirname(os.path.abspath(__file__))
CPP_DIR = os.path.join(REPO, "torchft_tpu", "_cpp")
# Everything the run writes lands here (git-ignored, returned by the tool).
OUT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")
DEADLINE_S = 1100.0  # the driver allows 1200 s, compilation included


class SmokeFailure(AssertionError):
    pass


def check(cond: Any, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - _T0:6.1f}s] {msg}", flush=True)


_T0 = time.monotonic()


@dataclasses.dataclass(frozen=True)
class Size:
    """What a run trains. ``REAL`` is the only size ``main`` uses; tests
    pass ``DEBUG`` to the functions below to rehearse the control flow on
    the CPU (never selectable from the command line or the environment)."""

    model: str
    chip_batch: int
    chip_seq: int
    peer_batch: int
    peer_seq: int
    attn: str
    steps: int
    platform: str  # what the chip group must report
    poll_s: float


REAL = Size("small", 4, 2048, 1, 64, "flash", 24, "tpu", 0.5)
DEBUG = Size("debug", 8, 64, 2, 64, "default", 200, "cpu", 0.02)

# After the last heal the groups are bitwise equal; from there each applies
# the same dequantized gradient through AdamW on its own backend, whose
# fp32 division/rsqrt differ in the last bits. One AdamW step moves a
# parameter by ~lr = 3e-4, so 1e-5 absolute is far below one wrong step
# and far above accumulated rounding.
PARAM_ATOL = 1e-5
# Same seed and batches on one chip and on four: bf16 matmuls reduce in a
# different order across tp/fsdp shards.
LOSS_RTOL_4CHIP = 1e-2


# ---------------------------------------------------------------------------
# Pieces shared by both modes
# ---------------------------------------------------------------------------


def preflight(want_chips: int) -> None:
    """Asks a throwaway child what JAX finds (the parent stays off JAX).
    Fails before anything is built when there is no accelerator."""
    code = (
        "import jax, json; d = jax.devices(); print(json.dumps({"
        "'platform': d[0].platform, 'kind': d[0].device_kind, "
        "'count': len(d)}))"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=300,
    )
    check(proc.returncode == 0, f"preflight child failed:\n{proc.stderr[-2000:]}")
    dev = json.loads(proc.stdout.strip().splitlines()[-1])
    check(dev["platform"] == "tpu", f"JAX found no TPU: {dev}")
    check(
        dev["count"] >= want_chips,
        f"need {want_chips} chip(s), JAX sees {dev['count']}",
    )
    say(f"preflight: {dev}")


def build_control_plane() -> None:
    """The binaries must come from the files git tracks, not from a bin/
    that happened to be on disk (.gitignore hides it; the chip tool copies
    the disk). clean and all run as two makes: under -j they would race."""
    t0 = time.monotonic()
    for target in (["clean"], ["-j8", "all"]):
        proc = subprocess.run(
            ["make", "-C", CPP_DIR, *target], capture_output=True, text=True
        )
        check(
            proc.returncode == 0,
            f"make {' '.join(target)} failed:\n{proc.stderr[-3000:]}",
        )
    for name in ("lighthouse", "torchft_manager", "libtftcollectives.so"):
        check(
            os.path.exists(os.path.join(CPP_DIR, "bin", name)),
            f"build produced no {name}",
        )
    say(f"built control plane from source in {time.monotonic() - t0:.1f}s")


def read_jsonl(path: str) -> List[Dict[str, Any]]:
    out = []
    if os.path.exists(path):
        with open(path) as f:
            for line in f:
                try:
                    out.append(json.loads(line))
                except json.JSONDecodeError:
                    pass  # a SIGKILL can cut the last line
    return out


class Run:
    """One supervised job: lighthouse + runner + the files it writes."""

    def __init__(self, name: str, n_groups: int, min_replicas: int,
                 poll_s: float) -> None:
        from torchft_tpu.coordination import LighthouseServer

        self.dir = os.path.join(OUT_DIR, name)
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir)
        self.n_groups = n_groups
        self.poll_s = poll_s
        # join_timeout only matters above min_replicas; with
        # min_replicas == n_groups no group can ever train alone, however
        # slow the other's first compile is.
        self.lighthouse = LighthouseServer(
            bind="127.0.0.1:0",
            min_replicas=min_replicas,
            join_timeout_ms=60000,
            quorum_tick_ms=50,
            heartbeat_timeout_ms=5000,
        )
        self.runner = None
        self.t_deadline = _T0 + DEADLINE_S

    def start(self, cmds: List[List[str]], envs: List[Dict[str, str]]) -> None:
        from torchft_tpu.orchestration import (
            ReplicaGroupRunner,
            render_topology,
        )

        specs = render_topology(
            cmds[0],
            num_replica_groups=self.n_groups,
            lighthouse_addr=self.lighthouse.address(),
            journal_dir=self.dir,
        )
        for spec, cmd, env in zip(specs, cmds, envs):
            spec.cmd = cmd
            spec.env.update(env)
            spec.env["TORCHFT_METRICS_FILE"] = self.metrics_path(
                spec.replica_group
            )
            spec.env["TORCHFT_PERF"] = "1"
        self.runner = ReplicaGroupRunner(
            specs, max_restarts=1, poll_interval=0.2, log_dir=self.dir
        )
        self.runner.start()

    def stop(self) -> None:
        if self.runner is not None:
            self.runner.stop()
        self.lighthouse.shutdown()

    # -- files -------------------------------------------------------------

    def metrics_path(self, group: int) -> str:
        return os.path.join(self.dir, f"metrics_group{group}.jsonl")

    def commits(self, group: int) -> List[Dict[str, Any]]:
        """The trainer's own per-committed-step lines (those with a loss),
        in file order — incarnations append to one file."""
        return [
            r for r in read_jsonl(self.metrics_path(group)) if "loss" in r
        ]

    def journal(self, group: int) -> List[Dict[str, Any]]:
        return read_jsonl(
            os.path.join(self.dir, f"journal_replica{group}_rank0.jsonl")
        )

    def events(self, group: int, kind: str) -> List[Dict[str, Any]]:
        return [
            {**e.get("attrs", {}), "ts": e["ts"], "step": e.get("step"),
             "replica_id": e.get("replica_id"), "trace": e.get("trace")}
            for e in self.journal(group)
            if e.get("event") == kind
        ]

    def log(self, group: int, incarnation: int) -> str:
        path = os.path.join(
            self.dir, f"replica{group}_rank0.r{incarnation}.log"
        )
        return open(path, errors="replace").read() if os.path.exists(path) else ""

    def result(self, group: int) -> Dict[str, Any]:
        with open(os.path.join(self.dir, "results", f"group{group}.json")) as f:
            return json.load(f)

    # -- supervision -------------------------------------------------------

    def wait_for(self, what: str, pred: Callable[[], bool]) -> None:
        """Polls ``pred`` while supervising (a killed group is relaunched
        by ``monitor_once``). A group that dies beyond its one planned
        kill, or the deadline, fails the run."""
        while not pred():
            if time.monotonic() > self.t_deadline:
                raise SmokeFailure(f"deadline passed waiting for: {what}")
            self.runner.monitor_once()  # relaunches what it can
            live = self.runner.live_pids()
            for idx in range(self.n_groups):
                check(
                    idx in live or self.runner.clean_exit(idx),
                    f"group {idx} died beyond its planned kill while "
                    f"waiting for: {what}",
                )
            time.sleep(self.poll_s)

    def tail(self, group: int) -> str:
        logs = sorted(glob.glob(os.path.join(self.dir, f"replica{group}_*.log")))
        return "".join(
            f"--- {os.path.basename(p)} ---\n"
            + open(p, errors="replace").read()[-3000:]
            for p in logs[-2:]
        )

    def kill_and_wait_rejoin(self, victim: int) -> Dict[str, Any]:
        """SIGKILLs one group and waits until its new incarnation has
        healed from the survivor and committed three steps with it."""
        survivor = 1 - victim
        before = len(self.commits(victim))
        heals_before = len(self.events(victim, "heal_done"))
        sends_before = len(self.events(survivor, "heal_send_done"))
        last_step = self.commits(victim)[-1]["step"]
        t_kill = time.time()
        check(self.runner.kill_group(victim), f"could not kill group {victim}")
        say(f"SIGKILL group {victim} after its step {int(last_step)}")
        self.wait_for(
            f"group {victim} to heal and commit 3 steps after its kill",
            lambda: len(self.events(victim, "heal_done")) > heals_before
            and len(self.commits(victim)) >= before + 3,
        )
        check(
            self.runner.restarts[victim] == 1,
            f"group {victim} restarted {self.runner.restarts[victim]}x",
        )
        heal = self.events(victim, "heal_done")[-1]
        check(
            len(self.events(survivor, "heal_send_done")) > sends_before,
            f"group {survivor} never served a checkpoint to group {victim}",
        )
        first_back = self.commits(victim)[before]
        check(
            first_back["step"] >= last_step,
            f"group {victim} rejoined at step {first_back['step']}, "
            f"behind its own last commit {last_step}",
        )
        recv = [
            x for x in self.events(victim, "heal_xfer")
            if x.get("dir") == "recv" and x["ts"] >= t_kill
        ]
        out = {
            "victim": victim,
            "rejoined_at_step": int(first_back["step"]),
            "heal_s": heal["elapsed_s"],
            "heal_bytes": sum(int(x.get("nbytes", 0)) for x in recv),
            "down_s": first_back["ts"] - t_kill,
        }
        say(f"group {victim} healed from group {survivor}: {json.dumps(out)}")
        return out


def trainer_cmd(size: Size, batch: int, seq: int, result_dir: str,
                min_replicas: int, attn: str) -> List[str]:
    return [
        sys.executable, os.path.join(REPO, "train_hsdp.py"),
        "--model", size.model,
        "--batch", str(batch), "--seq", str(seq),
        "--steps", str(size.steps),
        "--attn", attn,
        "--quantize",
        "--min-replicas", str(min_replicas),
        "--result-dir", result_dir,
    ]


def perf_models(run: Run, group: int) -> List[Dict[str, Any]]:
    """One ``perf_model`` event per incarnation, in order."""
    return [e for e in run.events(group, "perf_model")
            if e.get("name") == "hsdp_grad_step"]


def check_losses(losses: List[float], who: str) -> None:
    check(losses and all(math.isfinite(x) for x in losses),
          f"{who}: non-finite loss in {losses}")
    head, tail = statistics.mean(losses[:3]), statistics.mean(losses[-3:])
    check(tail < head, f"{who}: loss did not fall ({head:.4f} -> {tail:.4f})")


# ---------------------------------------------------------------------------
# Default: two replica groups (chip + CPU peer), kill and heal both ways
# ---------------------------------------------------------------------------


def run_ft(size: Size) -> Dict[str, Any]:
    """The main path: lighthouse -> Manager -> quorum -> device step ->
    replica-axis allreduce -> commit vote -> live heal. Returns the device
    the chip group reported."""
    run = Run("ft", n_groups=2, min_replicas=2, poll_s=size.poll_s)
    results = os.path.join(run.dir, "results")
    on_chip = size.platform == "tpu"
    try:
        run.start(
            cmds=[
                trainer_cmd(size, size.chip_batch, size.chip_seq, results,
                            2, size.attn),
                trainer_cmd(size, size.peer_batch, size.peer_seq, results,
                            2, "default"),
            ],
            # Group 0 takes whatever JAX gives (the chip); the peer is
            # pinned before its backend can initialise, by environment.
            envs=[{}, {"JAX_PLATFORMS": "cpu"}],
        )
        run.wait_for(
            "both groups to commit 6 steps together",
            lambda: min(len(run.commits(0)), len(run.commits(1))) >= 6,
        )
        first = perf_models(run, 0)[0]
        say(
            "first quorum up: chip group "
            f"platform={first['platform']} kind={first['device_kind']} "
            f"compile_s={first['compile_s']:.1f} "
            f"cache_hit={first['cache_hit']} "
            f"tpu_custom_calls={first['tpu_custom_calls']}; peer "
            f"compile_s={perf_models(run, 1)[0]['compile_s']:.1f}"
        )
        check(first["platform"] == size.platform,
              f"chip group runs on {first['platform']!r}")
        warm = [r["step_s"] for r in run.commits(0)[2:]]
        heal_peer = run.kill_and_wait_rejoin(1)
        heal_chip = run.kill_and_wait_rejoin(0)
        run.wait_for(
            "both groups to finish",
            lambda: run.runner.clean_exit(0) and run.runner.clean_exit(1),
        )
    except BaseException:
        for g in range(2):
            print(run.tail(g), file=sys.stderr, flush=True)
        raise
    finally:
        run.stop()

    res = [run.result(g) for g in range(2)]
    commits = [run.commits(g) for g in range(2)]
    pm = perf_models(run, 0)
    worst = max(
        abs(a - b)
        for la, lb in zip(res[0]["param_sample"], res[1]["param_sample"])
        for a, b in zip(la, lb)
    )

    # -- observations (one run; not claims), before anything can fail -----
    splits: Dict[str, List[float]] = {}
    for e in run.events(0, "goodput_window"):
        if e["committed"] and e["residual"] == "compute":
            for kind, sec in e["splits"].items():
                splits.setdefault(kind, []).append(sec)
    def per_step_bytes(kind: str) -> int:
        """Median over step windows (the Manager's step-scoped trace id)
        of the bytes that event kind moved."""
        by_trace: Dict[str, int] = {}
        for e in run.events(0, kind):
            if e["trace"] and e.get("ok", True):
                by_trace[e["trace"]] = by_trace.get(e["trace"], 0) + e["nbytes"]
        return int(statistics.median(by_trace.values())) if by_trace else 0

    print(json.dumps({
        "observed": {
            "chip_compile_s": [round(m["compile_s"], 2) for m in pm],
            "chip_compile_cache_hit": [m["cache_hit"] for m in pm],
            "peer_compile_s": [round(m["compile_s"], 2)
                               for m in perf_models(run, 1)],
            "ft_step_ms_median_warm": round(
                statistics.median(warm) * 1e3, 1),
            # The Manager's ledger of a committed chip-group step window:
            # compute is what is left after the named waits.
            "chip_step_split_median_s": {
                k: round(statistics.median(v), 3) for k, v in splits.items()},
            "wire_bytes_per_step": per_step_bytes("pg_collective"),
            "grad_fp32_bytes_per_step": per_step_bytes("allreduce_issue"),
            "discarded_steps": [
                sum(not e["committed"] for e in run.events(g, "commit_gate"))
                for g in range(2)],
            "heal_peer_from_chip": heal_peer,
            "heal_chip_from_peer": heal_chip,
            "chip_peak_bytes_in_use":
                (res[0]["memory"][0] or {}).get("peak_bytes_in_use"),
            "chip_losses": [round(r["loss"], 4) for r in commits[0]],
            "param_max_abs_diff": worst,
        }
    }), flush=True)

    # -- lockstep ----------------------------------------------------------
    for g in range(2):
        check(res[g]["final_step"] == size.steps,
              f"group {g} ended at step {res[g]['final_step']}")
        alone = [r for r in commits[g] if r["num_participants"] != 2]
        check(not alone, f"group {g} committed without its peer: {alone[:3]}")
        steps = {int(r["step"]) for r in commits[g]}
        # A SIGKILL may land between a step's allreduce and the victim's
        # own commit line; the survivor then holds that one step alone.
        missing = set(range(size.steps)) - steps
        check(len(missing) <= 1,
              f"group {g} never committed steps {sorted(missing)}")
    check(
        {int(r["step"]) for r in commits[0] + commits[1]}
        == set(range(size.steps)),
        "some step was committed by neither group",
    )
    check_losses([r["loss"] for r in commits[0]], "chip group")
    say(f"lockstep: {size.steps} steps, participants == 2 throughout; "
        f"chip-group loss {commits[0][0]['loss']:.4f} -> "
        f"{commits[0][-1]['loss']:.4f}")

    # -- the compiled branches --------------------------------------------
    check(len(pm) == 2, f"expected 2 chip-group incarnations, saw {len(pm)}")
    for i, m in enumerate(pm):
        check(m["platform"] == size.platform,
              f"chip group incarnation {i} ran on {m['platform']!r}")
        log = run.log(0, i)
        check(f"devices: platform={size.platform}" in log,
              f"chip group incarnation {i} never reported its devices")
        check("asked=flash traced=dense" not in log,
              f"incarnation {i}: flash was asked and dense was traced")
        if on_chip:
            check("asked=flash traced=flash" in log,
                  f"incarnation {i} did not trace the flash kernel")
            check(m["tpu_custom_calls"] > 0,
                  f"incarnation {i}: no tpu_custom_call in the grad step")
    for g, want in ((0, "device" if on_chip else "host"), (1, "host")):
        paths = {e.get("quant_path") for e in run.events(g, "allreduce_issue")}
        check(paths == {want},
              f"group {g} allreduce took quantize path {paths}, not {want!r}")
    # Same wire format: both decoded every reduced payload to the same
    # update, or the parameters could not agree.
    check(worst <= PARAM_ATOL,
          f"parameters differ across groups by {worst:.3e} > {PARAM_ATOL}")
    say(f"parameters agree across groups: max |diff| {worst:.3e} "
        f"(tolerance {PARAM_ATOL}) over "
        f"{sum(len(x) for x in res[0]['param_sample'])} sampled values")

    # -- the restarted chip trainer ---------------------------------------
    check(pm[1]["cache_hit"],
          "the restarted chip trainer's compile missed the cache")
    if not pm[0]["cache_hit"]:
        check(pm[1]["compile_s"] < pm[0]["compile_s"],
              f"restart compiled in {pm[1]['compile_s']:.1f}s, first in "
              f"{pm[0]['compile_s']:.1f}s")
    return res[0]["device"]


# ---------------------------------------------------------------------------
# --chips 4: one process on the fsdp=2 x tp=2 mesh vs. one chip
# ---------------------------------------------------------------------------

# Standard libtpu recipe for a process that should see one chip of a host.
ONE_CHIP_ENV = {
    "TPU_VISIBLE_CHIPS": "0",
    "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
    "TPU_PROCESS_BOUNDS": "1,1,1",
}


def run_single(name: str, size: Size, env: Dict[str, str],
               want_devices: int) -> Dict[str, Any]:
    """One replica group with the Manager in the loop; returns its losses
    and result file."""
    run = Run(name, n_groups=1, min_replicas=1, poll_s=size.poll_s)
    results = os.path.join(run.dir, "results")
    try:
        run.start(
            cmds=[trainer_cmd(size, size.chip_batch, size.chip_seq, results,
                              1, size.attn)],
            envs=[env],
        )
        run.wait_for(f"{name} to finish", lambda: run.runner.clean_exit(0))
    except BaseException:
        print(run.tail(0), file=sys.stderr, flush=True)
        raise
    finally:
        run.stop()
    res = run.result(0)
    check(res["device"]["platform"] == size.platform
          and res["device"]["count"] == want_devices,
          f"{name} ran on {res['device']}, wanted {want_devices} x "
          f"{size.platform}")
    commits = run.commits(0)
    check([int(r["step"]) for r in commits] == list(range(size.steps)),
          f"{name} did not commit steps 0..{size.steps - 1} in order")
    pm = perf_models(run, 0)[0]
    say(f"{name}: {res['device']} compile_s={pm['compile_s']:.1f} "
        f"step_ms_median={statistics.median(r['step_s'] for r in commits[2:]) * 1e3:.1f} "
        f"loss {commits[0]['loss']:.4f} -> {commits[-1]['loss']:.4f}")
    return {"losses": [r["loss"] for r in commits], "result": res}


def run_four_chips(size: Size, one_chip_env: Dict[str, str],
                   n: int = 4) -> Dict[str, Any]:
    one = run_single("one_chip", size, one_chip_env, 1)
    four = run_single("four_chips", size, {}, n)
    check(all(math.isfinite(x) for x in one["losses"] + four["losses"]),
          "non-finite loss")
    for k, (a, b) in enumerate(zip(one["losses"], four["losses"])):
        check(abs(a - b) <= LOSS_RTOL_4CHIP * abs(a),
              f"step {k}: loss {b:.5f} on {n} chips vs {a:.5f} on one")
    say(f"losses agree to rtol {LOSS_RTOL_4CHIP}: one chip {one['losses']}, "
        f"{n} chips {four['losses']}")

    # Four-way placement: code that has only ever seen virtual CPU devices
    # may put everything on the first.
    res = four["result"]
    sharded = [p for p in res["placement"] if not p["replicated"]]
    size_of = lambda shape: math.prod(shape)  # noqa: E731
    for p in sharded:
        check(p["n_shards"] == n and size_of(p["shard"]) < size_of(p["shape"]),
              f"sharded leaf is not spread over {n} devices: {p}")
    total = sum(size_of(p["shape"]) for p in res["placement"])
    frac = sum(size_of(p["shape"]) for p in sharded) / total
    check(frac > 0.9, f"only {frac:.1%} of the state is sharded")
    observed = {"sharded_fraction_of_state": round(frac, 4)}
    if size.platform == "tpu":  # the CPU backend keeps no allocator stats
        in_use = [m["bytes_in_use"] for m in res["memory"]]
        check(len(in_use) == n and min(in_use) > 0
              and max(in_use) < 2 * min(in_use),
              f"bytes_in_use is uneven across devices: {in_use}")
        observed.update(
            bytes_in_use_per_device=in_use,
            peak_bytes_in_use_per_device=[
                m["peak_bytes_in_use"] for m in res["memory"]],
            one_chip_peak_bytes_in_use=one["result"]["memory"][0][
                "peak_bytes_in_use"],
        )
    print(json.dumps({"observed": observed}), flush=True)
    return res["device"]


# ---------------------------------------------------------------------------


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = parser.parse_args()

    preflight(args.chips)
    build_control_plane()
    if args.chips == 4:
        # Dense attention: the Pallas kernel is not partitioned over tp.
        size = dataclasses.replace(REAL, attn="default", steps=8)
        device = run_four_chips(size, ONE_CHIP_ENV)
    else:
        device = run_ft(REAL)

    jax = sys.modules.get("jax")
    check(jax is None or not jax._src.xla_bridge._backends,
          "the smoke's parent initialised a JAX backend")
    check(device["platform"] == "tpu" and device["count"] == args.chips,
          f"ran on {device}")
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        sys.exit(1)
