"""One replica group of one cell: build the model from the configuration
file and the job from the traffic mix, check it against the plain
reference, warm up, run the window, and write what was seen.

Started by run.py, one process per group, each on its own chip(s). The
worker owns the clock, the window, the stop rule, the spans and the
device trace; the trainer file named by the mix owns what one step is
(trainers/raw.py says what such a file provides); the architecture named
by the configuration owns the model, its reference and its operation
counts (cells.py says what such a directory provides). Nothing here
names a configuration, a mix, a cell, a per-layer metric, a model class
or an architecture.

JAX and the program are imported inside ``main`` so that the import and
the chip's acquisition can be timed as parts of the set-up.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import glob
import importlib
import json
import os
import statistics
import sys
import time
from typing import Any, Callable, Dict, Iterator, List, Optional

from benchmark import cells, gate_readers

SPAN_PREFIX = "bench::"
# The reference check's sample: one sequence of this many tokens (or the
# mix's sequence if shorter). Two by two flash tiles, so the kernel's
# causal block skipping is inside what is compared.
CHECK_SEQ = 1024


@dataclasses.dataclass
class StepOut:
    committed: bool
    loss: float  # read on the host: the step's device work is done
    tokens: int  # training tokens this step contributed if committed
    # What else the step's program counted (its ``metrics`` but the loss),
    # as host floats by the program's own names; {} where it counts nothing.
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)


class CompileLog:
    """Counts and times what JAX compiles or loads from its persistent
    cache, so that set-up can report it and the window can prove it had
    none. One ``backend_compile_duration`` event per program, hit or
    miss; tracing and lowering nest and are not added in."""

    def __init__(self) -> None:
        from jax import monitoring

        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, name: str, secs: float, **_: Any) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.programs += 1

    def _on_event(self, name: str, **_: Any) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1


class Ctx:
    """What a trainer is given."""

    def __init__(self, cell: cells.Cell, seed: int, group: int, blocked: bool) -> None:
        import jax
        import jax.numpy as jnp

        from torchft_tpu.parallel import auto_mesh
        from torchft_tpu.parallel.train import build_model

        self.cell, self.mix, self.config = cell, cell.mix, cell.config
        self.seed, self.group, self.blocked = seed, group, blocked
        self.n_groups = int(self.mix["groups"])
        self.batch_size, self.seq = int(self.mix["batch"]), int(self.mix["seq"])
        self.tokens_per_step = self.batch_size * self.seq
        self.cfg = cell.adapter.model_config(self.config, self.seq)
        self.mesh = auto_mesh(len(jax.devices()))
        self.model = build_model(self.cfg, self.mesh)
        self.phases: Dict[str, float] = {}
        self.step_spans: Dict[str, float] = {}  # the open step's spans
        vocab, b, s = self.cfg.vocab_size, self.batch_size, self.seq
        base = jax.random.fold_in(jax.random.PRNGKey(seed), group)

        @jax.jit
        def make_batch(base, step):
            # The traffic: seeded uniform tokens, each group its own
            # stream, next-token targets, nothing masked. The key is an
            # argument: closed over, the seed would be a constant of the
            # program and every new seed would miss the compile cache.
            toks = jax.random.randint(
                jax.random.fold_in(base, step), (b, s + 1), 0, vocab
            )
            return {
                "inputs": toks[:, :-1],
                "targets": toks[:, 1:],
                "mask": jnp.ones((b, s), jnp.int32),
            }

        self._make_batch, self._base_key = make_batch, base

    def batch(self, step: int) -> Dict[str, Any]:
        return self._make_batch(self._base_key, step)

    def block(self, x: Any) -> Any:
        """In the traced run every span ends on finished device work, so
        that self-times are real; in the timed run this does nothing."""
        if self.blocked:
            import jax

            jax.block_until_ready(x)
        return x

    @contextlib.contextmanager
    def phase(self, name: str) -> Iterator[None]:
        """A part of the set-up, in wall seconds (whatever JAX compiles or
        loads inside it included; ``CompileLog`` says how much that was).
        The first quorum's part is the Manager's construction and the
        first step's quorum wait together."""
        t0 = time.time()
        try:
            yield
        finally:
            self.phases[name] = self.phases.get(name, 0.0) + time.time() - t0

    @contextlib.contextmanager
    def span(self, name: str) -> Iterator[None]:
        from jax.profiler import TraceAnnotation

        t0 = time.time()
        with TraceAnnotation(SPAN_PREFIX + name):
            try:
                yield
            finally:
                self.step_spans[name] = (
                    self.step_spans.get(name, 0.0) + time.time() - t0
                )


class StopRule:
    """Ends the window on a whole step, the same step in every group.

    Group 0 decides, at the start of step i, that i is the last when the
    window's time would run out inside it (judged by the previous step's
    length), and publishes i in a file. It then runs step i itself, and
    since a step of a cell with several groups cannot finish without
    group 0, every group finds the file before it could start step i+1.
    """

    def __init__(self, run_dir: str, group: int, seconds: float) -> None:
        self.path = os.path.join(run_dir, "stop_after")
        self.group, self.seconds = group, seconds
        self.last: Optional[int] = None

    def reached(self, i: int, elapsed: float, step_estimate: float) -> bool:
        if self.last is None and self.group == 0:
            if elapsed + step_estimate >= self.seconds:
                self.last = i
                with open(self.path + ".tmp", "w") as f:
                    f.write(str(i))
                os.replace(self.path + ".tmp", self.path)
        elif self.last is None and os.path.exists(self.path):
            with open(self.path) as f:
                self.last = int(f.read())
        return self.last is not None and i > self.last


def reference_check(ctx: Ctx) -> Dict[str, Any]:
    """Loss and gradients of the system's own model and loss code against
    the architecture's reference.py, at the published widths on one
    seeded sample, before the optimizer state exists (so that it stays
    below the step's own memory peak). The tolerances are the
    reference's."""
    import jax
    import jax.numpy as jnp

    from torchft_tpu.parallel.train import (
        build_model,
        make_grad_step,
        state_shardings,
    )

    config, reference = ctx.config, ctx.cell.reference
    s = min(CHECK_SEQ, ctx.seq)
    model = build_model(ctx.cell.adapter.sample_config(ctx.cfg, s), ctx.mesh)
    shardings = state_shardings(model, ctx.mesh, (1, s))
    key = jax.random.fold_in(jax.random.PRNGKey(ctx.seed), 0x5EED)
    toks = jax.random.randint(key, (1, s + 1), 0, ctx.cfg.vocab_size)
    sample = {
        "inputs": toks[:, :-1],
        "targets": toks[:, 1:],
        "mask": jnp.ones((1, s), jnp.int32),
    }
    params = jax.jit(
        lambda rng, tokens: model.init(rng, tokens)["params"],
        out_shardings=shardings.params,
    )(jax.random.PRNGKey(ctx.seed), sample["inputs"])
    loss_sys, g_sys = make_grad_step(model, ctx.mesh, shardings)(params, sample)
    loss_ref, g_ref = jax.jit(
        lambda p, b: reference.loss_and_grads(p, b, config)
    )(params, sample)

    @jax.jit
    def compare(a, b):
        return jax.tree_util.tree_map(
            lambda x, y: jnp.linalg.norm((x - y).ravel())
            / jnp.linalg.norm(y.ravel()),
            a, b,
        )

    errs = jax.tree_util.tree_leaves_with_path(compare(g_sys, g_ref))
    worst_path, worst = max(errs, key=lambda kv: float(kv[1]))
    loss_sys, loss_ref = float(loss_sys), float(loss_ref)
    loss_rel = abs(loss_sys - loss_ref) / abs(loss_ref)
    return {
        "tokens": s,
        "loss_system": loss_sys,
        "loss_reference": loss_ref,
        "loss_rel_diff": loss_rel,
        "grad_rel_l2_worst": float(worst),
        "grad_rel_l2_worst_leaf": jax.tree_util.keystr(worst_path),
        "loss_rel_tol": reference.LOSS_REL_TOL,
        "grad_rel_l2_tol": reference.GRAD_REL_L2_TOL,
        "ok": bool(
            loss_rel <= reference.LOSS_REL_TOL
            and float(worst) <= reference.GRAD_REL_L2_TOL
        ),
    }


def fingerprint(tree: Any) -> str:
    """A position-sensitive checksum of every bit of ``tree``, taken on
    the device: equal strings across groups mean equal parameters."""
    import hashlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def sums(t):
        def one(x):
            bits = jax.lax.bitcast_convert_type(
                x.astype(jnp.float32), jnp.uint32
            ).ravel()
            weight = jnp.arange(bits.size, dtype=jnp.uint32) * jnp.uint32(
                2654435761
            ) + jnp.uint32(1)
            return jnp.stack([bits.sum(), (bits * weight).sum()])

        return jnp.stack([one(x) for x in jax.tree_util.tree_leaves(t)])

    return hashlib.sha256(np.asarray(sums(tree)).tobytes()).hexdigest()


def load_metric_readers(cell: cells.Cell, table_path: str) -> Dict[str, Callable]:
    """``read(run)`` of every per-layer metric this cell reports, each
    from its own file: beside the table first, then benchmark/metrics."""
    readers = {}
    for m in cell.per_layer:
        path = cells.find_file(table_path, "metrics", m["name"] + ".py")
        if not path:
            raise cells.CellError(f"per-layer metric {m['name']!r} has no reader file")
        readers[m["name"]] = cells.load_module(path).read
    return readers


def read_jsonl(path: str) -> List[Dict[str, Any]]:
    out = []
    if os.path.exists(path):
        with open(path) as f:
            for line in f:
                try:
                    out.append(json.loads(line))
                except json.JSONDecodeError:
                    pass  # a line still being written
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--table", default="")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, required=True)
    ap.add_argument("--group", type=int, required=True)
    ap.add_argument("--run-dir", required=True)
    ap.add_argument("--t0", type=float, required=True)
    ap.add_argument("--platform", required=True)
    args = ap.parse_args()
    t_main = time.time()
    cell = cells.load_cell(args.workload, args.table)
    mix = cell.mix
    tag = f"[{cell.name} g{args.group}]"

    def say(msg: str) -> None:
        print(f"{tag} +{time.time() - args.t0:6.1f}s {msg}", file=sys.stderr, flush=True)

    import jax

    # The program's trainers place the cache themselves
    # (_train_common.enable_compile_cache): the checkout's .jax_cache, or
    # JAX_COMPILATION_CACHE_DIR where the machine sets it. Every program
    # is kept, however small, so that only a checkout's first run compiles.
    from _train_common import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    import torchft_tpu  # noqa: F401 - the whole package, as a trainer pays it
    from benchmark import trace_reduce

    t_import = time.time()
    devices = jax.devices()
    t_acquire = time.time()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    say(f"devices: {device}")
    if device["platform"] != args.platform:
        say(f"wanted platform {args.platform!r}: no fallback under these metric names")
        return 3
    if device["count"] != int(mix["chips_per_group"]):
        say(f"wanted {mix['chips_per_group']} device(s) for this group")
        return 3

    compiles = CompileLog()
    ctx = Ctx(cell, args.seed, args.group, bool(args.trace))
    checks: Dict[str, Any] = {}
    if args.group == 0:
        with ctx.phase("check"):
            checks["reference"] = reference_check(ctx)
        say(f"reference check: {checks['reference']}")

    trainer_mod = importlib.import_module(f"benchmark.trainers.{mix['trainer']}")
    trainer = trainer_mod.Trainer(ctx)
    records: List[Dict[str, Any]] = []
    trace_dir = os.path.join(args.run_dir, f"trace_g{args.group}")
    tracing = False
    try:
        # -- warm-up: every program of the loop, outside the window -------
        warm: List[Dict[str, Any]] = []
        with ctx.phase("warm"):
            for _ in range(int(mix["warmup_steps"])):
                ctx.step_spans = {}
                t0 = time.time()
                out = trainer.step()
                trainer.sync()
                warm.append({"s": time.time() - t0, "loss": out.loss,
                             "committed": out.committed, "spans": ctx.step_spans})
        say(f"warm-up: {[round(w['s'], 2) for w in warm]}")
        # A group's first quorum wait is set-up of its own (the groups
        # start at different times); the trainer's span holds it.
        quorum_s = warm[0]["spans"].get("quorum", 0.0) if args.trace else 0.0
        setup_compile_s = compiles.seconds
        loads_before, misses_before = compiles.programs, compiles.cache_misses

        # -- the window ---------------------------------------------------
        stop = StopRule(args.run_dir, args.group, args.seconds)
        trace_first, trace_n = 1, int(mix["trace_steps"])
        t_begin = time.time()
        estimate = warm[-1]["s"]
        i = 0
        while not stop.reached(i, time.time() - t_begin, estimate):
            if args.trace and i == trace_first:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 2
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                tracing = True
            ctx.step_spans = {}
            t0 = time.time()
            with jax.profiler.StepTraceAnnotation(SPAN_PREFIX + "step", step_num=i):
                out = trainer.step()
                if tracing:
                    trainer.sync()
            t1 = time.time()
            records.append({"i": i, "t0": t0, "t1": t1, "loss": out.loss,
                            "committed": out.committed, "tokens": out.tokens,
                            "counters": out.counters,
                            "spans": ctx.step_spans, "traced": tracing})
            if tracing and i == trace_first + trace_n - 1:
                jax.profiler.stop_trace()
                tracing = False
            estimate = t1 - t0
            i += 1
        trainer.sync()
        t_end = time.time()
        if tracing:
            jax.profiler.stop_trace()
            tracing = False
        # Compiled in the window: persistent-cache misses. A program that
        # is traced again and fetched from the cache every step (one the
        # program under test never jits) is counted apart and reported.
        checks["programs_compiled_in_window"] = compiles.cache_misses - misses_before
        reloads = compiles.programs - loads_before
        checks.update(trainer.checks())
        param_fingerprint = fingerprint(trainer.fingerprint_tree())
        stats = [d.memory_stats() or {} for d in jax.local_devices()]
    finally:
        if tracing:
            jax.profiler.stop_trace()
        trainer.close()

    committed = [r for r in records if r["committed"]]
    window_s = t_end - records[0]["t0"]
    tokens = sum(r["tokens"] for r in committed)
    peak = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    result: Dict[str, Any] = {
        "group": args.group,
        "device": device,
        "seed": args.seed,
        "window": {"t_start": records[0]["t0"], "t_end": t_end,
                   "attempted": len(records),
                   "failed": len(records) - len(committed), "tokens": tokens},
        "memory_peak_bytes": peak,
        "memory_stats": stats,
        "checks": checks,
        "fingerprint": param_fingerprint,
        "steps_done": int(mix["warmup_steps"]) + len(records),
        "losses": [w["loss"] for w in warm] + [r["loss"] for r in records],
        "step_s": [r["t1"] - r["t0"] for r in records],
        "compile": {"setup_s": setup_compile_s, "programs": compiles.programs,
                    "reloaded_in_window": reloads,
                    "cache_hits": compiles.cache_hits,
                    "cache_misses": compiles.cache_misses},
    }
    say(f"window: {len(records)} steps in {window_s:.2f}s, "
        f"{tokens / window_s:.0f} tok/s, peak {peak / 2**30:.2f} GiB, "
        f"step median {statistics.median(result['step_s']):.3f}s")
    journal: List[Dict[str, Any]] = []
    if args.trace or result["window"]["failed"]:
        journal = read_jsonl(os.environ.get("TORCHFT_JOURNAL_FILE", ""))
    if result["window"]["failed"]:
        # A run that lost a step says why: run.py prints these.
        result["lost_steps"] = gate_readers.explain(journal)

    if args.trace:
        setup = {
            "launch": t_main - args.t0,
            "import": t_import - t_main,
            "acquire": t_acquire - t_import,
            "check": ctx.phases.get("check", 0.0),
            "init": ctx.phases.get("init", 0.0),
            "quorum": ctx.phases.get("manager", 0.0) + quorum_s,
            "warm": ctx.phases.get("warm", 0.0) - quorum_s,
            "compile": setup_compile_s,
        }
        files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                              "*", "*.xplane.pb")))
        trace = trace_reduce.reduce(files[-1], SPAN_PREFIX) if files else None
        window_events = [
            e for e in journal if records[0]["t0"] <= e.get("ts", 0.0) <= t_end
        ]
        run = {
            "cell": cell,
            "records": records,
            "window_s": window_s,
            "tok_s_chip": tokens / window_s / int(mix["chips_per_group"]),
            "journal": window_events,
            "trace": trace,
            "traced_steps": sum(r["traced"] for r in records),
            "programs_reloaded": reloads,
            "setup": setup,
            "device_kind": device["kind"],
            "memory_stats": stats,
            "peaks": cells.load_json(os.path.join(cells.HERE, "peaks.json")),
        }
        metrics = {}
        for name, read in load_metric_readers(cell, args.table).items():
            value = read(run)
            if value is not None:
                metrics[name] = float(value)
        result["per_layer"] = metrics
        result["setup_parts"] = setup
        if trace is not None:
            result["trace"] = {
                "busy_s": trace.busy_s,
                "window_s": trace.window_s,
                "device_ops": trace.top_ops(10),
                "idle_gaps": trace.top_gaps(10),
            }

    out_path = os.path.join(args.run_dir, f"result_g{args.group}.json")
    with open(out_path + ".tmp", "w") as f:
        json.dump(result, f)
    os.replace(out_path + ".tmp", out_path)
    return 0


if __name__ == "__main__":
    # Run under the module's real name: the trainer files import
    # ``benchmark.worker`` and must find these very classes.
    from benchmark.worker import main as _main

    sys.exit(_main())
