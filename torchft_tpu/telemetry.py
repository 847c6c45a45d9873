"""Tracing, timing, metrics export, and the collective flight recorder.

TPU-native translation of the reference's observability subsystem:

- ``trace_span(name)``: the reference wraps every hot-path method in
  ``torch.profiler.record_function("torchft::manager::*")`` (reference:
  manager.py:379,430,574,586,600,650,671,705,760,786,793 and
  local_sgd.py:277,293,375,390,411). Here the same span names feed
  ``jax.profiler.TraceAnnotation`` so they appear in XLA/perfetto traces,
  and wall-time is accumulated in a process-local registry that tests and
  metrics lines can read without a trace viewer.
- ``timeit(name)``: checkpoint-transfer wall-time logging (reference:
  http_transport.py:31-36, pg_transport.py:80-85 ``_timeit``).
- ``MetricsLogger``: per-step scalar export as JSONL (the reference emits
  TensorBoard scalars incl. num_participants/current_step,
  train_diloco.py:219-232; TensorBoard isn't a dependency here so the
  sink is a plain JSONL file any plotter can consume).
- ``trace_window(step)``: scheduled profiler windows for train scripts
  (reference: train_ddp.py:169-174 runs torch.profiler.profile with a
  schedule exporting Chrome traces). Gated by env vars so production runs
  pay nothing.
- ``FlightRecorder``: ring buffer of recent collective ops dumped to disk
  on PG abort when ``TORCHFT_TRIGGER_FR_ON_ABORT=true`` (reference: the
  NCCL flight-recorder dump via named pipe, process_group.py:89-108,
  812-813).

Everything degrades to near-zero overhead: spans are two monotonic reads,
a thread-local stack push and a dict update (plus two wall-clock reads
and a list append while a journal is configured); the recorder is a
deque append; metrics/trace windows are off unless their env vars are
set.
"""

from __future__ import annotations

import atexit
import bisect
import collections
import contextlib
import functools
import itertools
import json
import os
import threading
import time
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

from . import knobs

__all__ = [
    "trace_span",
    "traced",
    "Span",
    "DDP_ROOT_SPAN",
    "current_span",
    "in_span",
    "next_bucket",
    "name_next_bucket",
    "span_parent",
    "drain_spans",
    "span_stats",
    "span_percentiles",
    "reset_span_stats",
    "timeit",
    "timed",
    "MetricsLogger",
    "get_metrics_logger",
    "EVENT_KINDS",
    "EventLog",
    "get_event_log",
    "StepDigest",
    "DigestWindow",
    "reset_event_log",
    "set_default_replica_id",
    "trace_window",
    "reset_trace_window",
    "FlightRecorder",
    "flight_recorder",
]


# ----------------------------------------------------------------------
# Spans
# ----------------------------------------------------------------------

# Fixed log-spaced histogram boundaries shared by every span: 1µs doubling
# up to ~137s (28 finite buckets + one overflow). Precomputed once so the
# hot-path cost is a bisect over a tuple plus a list increment — no
# allocation per observation.
_HIST_BOUNDS: tuple = tuple(1e-6 * (2.0 ** i) for i in range(28))
_HIST_NBUCKETS = len(_HIST_BOUNDS) + 1


class _SpanStats:
    """Process-local span accounting: count + total/max wall seconds, plus a
    fixed-bucket latency histogram per span (log-spaced; p50/p95/p99 come
    from :func:`span_percentiles`)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._stats: Dict[str, Dict[str, float]] = {}
        self._hist: Dict[str, List[int]] = {}

    def add(self, name: str, dt: float) -> None:
        with self._lock:
            s = self._stats.get(name)
            if s is None:
                s = self._stats[name] = {
                    "count": 0, "total_s": 0.0, "max_s": 0.0
                }
                self._hist[name] = [0] * _HIST_NBUCKETS
            s["count"] += 1
            s["total_s"] += dt
            if dt > s["max_s"]:
                s["max_s"] = dt
            self._hist[name][bisect.bisect_left(_HIST_BOUNDS, dt)] += 1

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            return {k: dict(v) for k, v in self._stats.items()}

    def hist_snapshot(self) -> Dict[str, List[int]]:
        with self._lock:
            return {k: list(v) for k, v in self._hist.items()}

    def reset(self) -> None:
        with self._lock:
            self._stats.clear()
            self._hist.clear()


_SPAN_STATS = _SpanStats()


def span_stats() -> Dict[str, Dict[str, float]]:
    """Snapshot of per-span {count, total_s, max_s} accumulated so far."""
    return _SPAN_STATS.snapshot()


def _hist_percentile(buckets: List[int], q: float) -> float:
    """Upper-bound estimate of the q-quantile from bucket counts.

    Edge cases (regression-tested): all-zero buckets -> 0.0 (no samples is
    not "the first boundary"); a run of empty leading buckets must never
    satisfy the target (``cum >= target`` holds vacuously at target <= 0,
    which used to report bucket 0's bound for q ~ 0 even when every sample
    sat in a much higher bucket); a single occupied bucket returns that
    bucket's upper bound for every q."""
    total = sum(buckets)
    if total == 0:
        return 0.0
    target = q * total
    cum = 0
    for i, c in enumerate(buckets):
        if c == 0:
            continue  # an empty prefix can't contain any quantile
        cum += c
        if cum >= target:
            if i < len(_HIST_BOUNDS):
                return _HIST_BOUNDS[i]
            # Overflow bucket: no upper bound; report the last boundary.
            return _HIST_BOUNDS[-1]
    return _HIST_BOUNDS[-1]


def span_percentiles(
    name: Optional[str] = None,
) -> Dict[str, Dict[str, float]]:
    """Per-span latency percentiles {p50, p95, p99} (seconds), estimated
    from the fixed log-spaced histogram (each value is the upper boundary
    of the bucket containing that quantile — an over-estimate within one
    2x bucket). Pass ``name`` to restrict to one span."""
    hist = _SPAN_STATS.hist_snapshot()
    if name is not None:
        hist = {name: hist[name]} if name in hist else {}
    return {
        k: {
            "p50": _hist_percentile(v, 0.50),
            "p95": _hist_percentile(v, 0.95),
            "p99": _hist_percentile(v, 0.99),
        }
        for k, v in hist.items()
    }


def reset_span_stats() -> None:
    _SPAN_STATS.reset()


def observe_span(name: str, dt: float) -> None:
    """Record an externally-timed duration into the span histogram.

    For call sites that already hold a wall-clock delta (e.g. a process
    group timing its own collective) and want it in the same
    ``span_stats``/``span_percentiles`` tables as ``span()``-wrapped
    regions, without nesting a context manager."""
    _SPAN_STATS.add(name, dt)


class _ByteCounters:
    """Process-local byte accounting (e.g. data-plane wire traffic).

    The quantized collectives exist to cut wire bytes; these counters
    make the cut MEASURABLE on any backend (the reference proves its
    codec the same way — by byte math, torchft/quantization.py) instead
    of inferring it from wall times."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counts: Dict[str, int] = {}

    def add(self, name: str, n: int) -> None:
        with self._lock:
            self._counts[name] = self._counts.get(name, 0) + int(n)

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counts)

    def reset(self) -> None:
        with self._lock:
            self._counts.clear()


_BYTE_COUNTERS = _ByteCounters()


def add_bytes(name: str, n: int) -> None:
    """Accumulates ``n`` bytes under ``name`` (cheap; lock + dict add)."""
    _BYTE_COUNTERS.add(name, n)


def byte_stats() -> Dict[str, int]:
    """Snapshot of per-counter byte totals accumulated so far."""
    return _BYTE_COUNTERS.snapshot()


def reset_byte_stats() -> None:
    _BYTE_COUNTERS.reset()


# The step-scoped span tree. Every ``trace_span`` is a node: its parent is
# the span open on the same thread (a thread-local stack; a collective
# thread borrows its issuer's through ``span_parent``). While a journal
# is configured, closed spans are also kept in ``_SPAN_BUFFER`` until the
# Manager's commit gate drains them into ONE ``step_spans`` event, so an
# operator reads a whole step's tree from the journal and the benchmark
# reads stage times without the profiler. The buffer is bounded: past
# ``SPAN_BUFFER_CAP`` spans are counted in ``dropped``, never kept.
SPAN_BUFFER_CAP = 4096

# Root of the tree in a DDP step: everything the replica-axis allreduce
# does on the caller's thread is inside it, and each bucket's collective
# thread hangs its stage spans under it. Its time, less the wait for the
# gradients, is the ledger's exposed_comm (Manager.note_exposed_comm).
DDP_ROOT_SPAN = "torchft::ddp::allreduce_grads"

_UNRESOLVED = object()
_TRACE_ANNOTATION: Any = _UNRESOLVED
_SPAN_IDS = itertools.count(1)
_SPAN_TLS = threading.local()


def _trace_annotation() -> Any:
    """``jax.profiler.TraceAnnotation``, or None where jax's profiler
    cannot be imported; resolved on the first span, once."""
    global _TRACE_ANNOTATION
    if _TRACE_ANNOTATION is _UNRESOLVED:
        try:
            from jax.profiler import TraceAnnotation
        except Exception:  # noqa: BLE001 - spans work without a profiler
            TraceAnnotation = None
        _TRACE_ANNOTATION = TraceAnnotation
    return _TRACE_ANNOTATION


class Span:
    """One ``trace_span``, open or closed. ``attrs`` may be added to
    inside the block (they reach the journal record, not the profiler's
    annotation, which is written when the span opens); ``elapsed_s`` is
    set when the span closes."""

    __slots__ = (
        "name", "id", "parent", "top", "attrs", "t0", "elapsed_s", "_issued"
    )

    def __init__(
        self, name: str, parent: Optional["Span"], attrs: Dict[str, Any]
    ) -> None:
        self.name = name
        self.id = next(_SPAN_IDS)
        self.parent = None if parent is None else parent.id
        # The outermost ancestor: what a bucket's ordinal counts under.
        self.top: "Span" = self if parent is None else parent.top
        self.attrs = attrs
        self.t0: Optional[float] = None  # time.time(), when recorded
        self.elapsed_s: Optional[float] = None
        self._issued = 0


class _SpanBuffer:
    """Closed spans of the current step, ``[name, t0, t1, id, parent,
    thread, attrs]`` each, with ``time.time()`` times like every journal
    ``ts``."""

    def __init__(self, cap: int) -> None:
        self._lock = threading.Lock()
        self._cap = cap
        self._spans: List[list] = []
        self._dropped = 0

    def add(self, rec: list) -> None:
        with self._lock:
            if len(self._spans) < self._cap:
                self._spans.append(rec)
            else:
                self._dropped += 1

    def drain(self) -> Tuple[List[list], int]:
        with self._lock:
            spans, dropped = self._spans, self._dropped
            self._spans, self._dropped = [], 0
        return spans, dropped


_SPAN_BUFFER = _SpanBuffer(SPAN_BUFFER_CAP)


def drain_spans() -> Tuple[List[list], int]:
    """Takes the closed spans buffered since the last drain and the count
    of those dropped at the cap. A span still open stays out until the
    drain after it closes."""
    return _SPAN_BUFFER.drain()


def _span_stack() -> List[Span]:
    stack = getattr(_SPAN_TLS, "stack", None)
    if stack is None:
        stack = _SPAN_TLS.stack = []
    return stack


def current_span() -> Optional[Span]:
    """The innermost span open on this thread."""
    stack = _span_stack()
    return stack[-1] if stack else None


def in_span(name: str) -> bool:
    """Whether a span called ``name`` is open on this thread."""
    return any(s.name == name for s in _span_stack())


def next_bucket() -> Optional[int]:
    """The ordinal of the next collective issued under this thread's
    outermost open span (for DDP the ``allreduce_grads`` root, so the
    step's buckets count 0, 1, 2, ...); None with no span open."""
    span = current_span()
    if span is None:
        return None
    top = span.top
    top._issued += 1
    return top._issued - 1


def name_next_bucket(bucket: int) -> None:
    """Makes ``bucket`` the next answer of :func:`next_bucket` on this
    thread (and ``bucket + 1`` the one after): a caller that issues its
    buckets in another order than it numbers them (DDP: smallest first)
    keeps each one's own number on its collective's spans."""
    span = current_span()
    if span is not None:
        span.top._issued = bucket


@contextlib.contextmanager
def span_parent(parent: Optional[Span]) -> Iterator[None]:
    """Hangs the spans this thread opens inside the block under
    ``parent``, a span open on the thread that issued this one's work."""
    stack = _span_stack()
    saved = stack[:]
    stack[:] = [] if parent is None else [parent]
    try:
        yield
    finally:
        stack[:] = saved


@contextlib.contextmanager
def trace_span(name: str, **attrs: Any) -> Iterator[Span]:
    """Named hot-path span: shows up in jax profiler traces (``attrs`` as
    the annotation's arguments) AND in :func:`span_stats`, and is a node
    of the step's span tree (above). Span names mirror the reference's
    ``torchft::manager::*`` convention so traces are comparable. Attrs
    whose value is None are left out."""
    attrs = {k: v for k, v in attrs.items() if v is not None}
    stack = _span_stack()
    span = Span(name, stack[-1] if stack else None, attrs)
    recorded = bool(_journal_path_from_env())
    cls = _trace_annotation()
    ann = None
    if cls is not None:
        try:
            ann = cls(name, **attrs)
            ann.__enter__()
        except Exception:
            ann = None
    stack.append(span)
    if recorded:
        span.t0 = time.time()
    t0 = time.monotonic()
    try:
        yield span
    finally:
        span.elapsed_s = time.monotonic() - t0
        if recorded:
            _SPAN_BUFFER.add([
                name,
                round(span.t0, 6),
                round(time.time(), 6),
                span.id,
                span.parent,
                threading.get_ident(),
                span.attrs,
            ])
        if stack and stack[-1] is span:
            stack.pop()
        if ann is not None:
            try:
                ann.__exit__(None, None, None)
            except Exception:
                pass
        _SPAN_STATS.add(name, span.elapsed_s)


def traced(name: str) -> Callable:
    """Decorator form of :func:`trace_span` — wraps the whole function body
    in the named span."""

    def deco(fn: Callable) -> Callable:
        @functools.wraps(fn)
        def wrapper(*args: Any, **kwargs: Any) -> Any:
            with trace_span(name):
                return fn(*args, **kwargs)

        return wrapper

    return deco


def timed(name: str) -> Callable:
    """Decorator form of :func:`timeit` — logs the function's wall-time."""

    def deco(fn: Callable) -> Callable:
        @functools.wraps(fn)
        def wrapper(*args: Any, **kwargs: Any) -> Any:
            with timeit(name):
                return fn(*args, **kwargs)

        return wrapper

    return deco


@contextlib.contextmanager
def timeit(name: str, logger: Optional[Any] = None) -> Iterator[dict]:
    """Logs the wall-time of a block (checkpoint transfers, heals).
    ``logger`` needs an ``info(msg)`` method; defaults to module logging.
    Exceptions from the block propagate (and are still timed).

    Yields a dict whose ``elapsed_s`` is filled when the block exits, so
    a caller needing the duration shares THIS clock instead of running a
    second one alongside."""
    t0 = time.monotonic()
    holder: dict = {"elapsed_s": None}
    try:
        yield holder
    finally:
        # No return/break in this finally: it would swallow in-flight
        # exceptions (PEP 601) — a failed heal must stay failed.
        dt = time.monotonic() - t0
        holder["elapsed_s"] = dt
        _SPAN_STATS.add(name, dt)
        msg = f"{name} took {dt:.3f}s"
        logged = False
        if logger is not None:
            try:
                logger.info(msg)
                logged = True
            except Exception:
                pass
        if not logged:
            import logging

            logging.getLogger("torchft_tpu").info(msg)


# ----------------------------------------------------------------------
# Metrics (JSONL scalar sink)
# ----------------------------------------------------------------------

class MetricsLogger:
    """Appends one JSON line per ``log`` call: {"step": N, "ts": ..., **scalars}.

    The reference exports TensorBoard scalars (num_participants,
    current_step, loss; train_diloco.py:219-232). JSONL keeps the same
    information with zero dependencies; `jq`/pandas/TensorBoard ingest it
    trivially.
    """

    def __init__(self, path: str) -> None:
        self._path = path
        self._lock = threading.Lock()
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        # One append-mode handle for the logger's lifetime: reopening per
        # log() costs a syscall-heavy open/close on every train step.
        self._fh: Optional[Any] = open(path, "a")
        atexit.register(self.close)

    def log(self, step: int, **scalars: Any) -> None:
        rec: Dict[str, Any] = {"step": int(step), "ts": time.time()}
        for k, v in scalars.items():
            try:
                rec[k] = float(v)
            except (TypeError, ValueError):
                rec[k] = str(v)
        line = json.dumps(rec)
        with self._lock:
            if self._fh is None:  # closed: drop rather than raise mid-step
                return
            self._fh.write(line + "\n")
            self._fh.flush()

    def close(self) -> None:
        with self._lock:
            if self._fh is not None:
                try:
                    self._fh.close()
                finally:
                    self._fh = None


_METRICS_LOGGER: Optional[MetricsLogger] = None
_METRICS_LOCK = threading.Lock()


def get_metrics_logger() -> Optional[MetricsLogger]:
    """Process-wide metrics sink, enabled by ``TORCHFT_METRICS_FILE``.
    Returns None (and costs one env read) when unset."""
    global _METRICS_LOGGER
    path = knobs.get_str("TORCHFT_METRICS_FILE")
    if not path:
        return None
    with _METRICS_LOCK:
        if _METRICS_LOGGER is None or _METRICS_LOGGER._path != path:
            if _METRICS_LOGGER is not None:
                _METRICS_LOGGER.close()
            _METRICS_LOGGER = MetricsLogger(path)
        return _METRICS_LOGGER


# ----------------------------------------------------------------------
# Event journal (structured step-event JSONL)
# ----------------------------------------------------------------------

# Central schema registry of journal event kinds: every production
# ``EventLog.emit(...)`` / ``Manager._journal(...)`` call site must use a
# kind registered here, with a one-line meaning.  The contract linter
# (``tools/tft_lint.py``, rule ``event-kind-registry``) enforces this
# statically over ``torchft_tpu/`` and ``tools/`` — consumers
# (``obs_report.py``, ``obs_trace.py``, ``chaos_soak.py``) key off these
# exact strings, so an unregistered or misspelled kind silently drops
# events from every downstream timeline.  Tests are exempt (they emit
# throwaway kinds on purpose).  Runtime stays permissive: emit() does not
# validate, so ad-hoc kinds in notebooks/tests still work.
EVENT_KINDS: Dict[str, str] = {
    # -- quorum / commit (manager.py) ----------------------------------
    "quorum_start": "quorum attempt begins (async or sync path)",
    "quorum_ready": "quorum returned; carries replica set + max_step",
    "quorum_abort": "quorum failed or was aborted; collectives poisoned",
    "commit_gate": "should_commit verdict for the step window: committed, "
                   "local_vote, cause (manager.GATE_CAUSES), quorum_id, "
                   "participants, hb_rounds/hb_gap_max_ms/hb_rtt_max_ms/"
                   "hb_late since the last gate, rss_peak_bytes and the "
                   "same getrusage's cumulative cpu_user_s/cpu_sys_s/"
                   "minflt/nivcsw",
    "goodput": "per-commit goodput/step-rate sample",
    # -- healing / checkpoint (manager.py, checkpointing/*) ------------
    "heal_start": "this replica starts healing from a live peer",
    "heal_done": "heal finished; weights/step adopted",
    "heal_failed": "heal attempt failed; will retry or abort",
    "heal_send_start": "serving a checkpoint to a healing peer begins",
    "heal_send_done": "serving a checkpoint to a healing peer finished",
    "ckpt_send": "checkpoint transport sent state to a peer",
    "ckpt_recv": "checkpoint transport received state from a peer",
    # -- allreduce lifecycle (manager.py) ------------------------------
    "allreduce_issue": "outer-axis allreduce handed to the data plane",
    "allreduce_complete": "outer-axis allreduce completed (or errored)",
    "step_spans": "the step's tree of trace spans, one event per commit "
                  "gate: spans=[[name, t0, t1, id, parent, thread, attrs]], "
                  "dropped (spans lost at the buffer's cap)",
    # -- process group / native engine (process_group.py) --------------
    "pg_configure": "process group (re)configured for a new quorum",
    "pg_configure_failed": "process group configure attempt failed",
    "pg_collective": "process-group collective completed: op, nbytes, tag, "
                     "elapsed_s, queued_s, ok; over the Python sockets also "
                     "tx_bytes/rx_bytes, send_s/peer_wait_s/recv_s with "
                     "send_cpu_s/recv_cpu_s, and rx_fresh_bytes (large "
                     "payloads received into memory made for them)",
    "pg_abort": "process group aborted in-flight collectives",
    "pg_native_mesh": "native engine mesh established (peers, streams)",
    "native_collective": "native-engine flight-recorder record drained",
    "native_counters": "native-engine per-peer byte/busy counters snapshot",
    # -- local SGD / DiLoCo (local_sgd.py) -----------------------------
    "local_sgd_sync": "LocalSGD outer sync performed",
    "fragment_prepare_sync": "DiLoCo fragment staged for outer sync",
    "fragment_perform_sync": "DiLoCo fragment outer sync performed",
    # -- control-plane RPC (coordination.py) ---------------------------
    "rpc_retry": "idempotent control RPC retried after a failure",
    "server_start": "lighthouse/manager server process started",
    "server_stop": "lighthouse/manager server process stopped",
    # -- chaos plane (chaos.py, process_group.py) ----------------------
    "chaos_inject": "seeded fault injected (kind/plane/site/visit)",
    "stripe_failover": "striped link leg died; range re-assigned or rejoined",
    # -- fleet observability tools (tools/obs_export.py) ---------------
    "lighthouse_status": "periodic lighthouse status scrape snapshot",
    "anomaly": "exporter-detected anomaly (straggler, hb gap, error)",
    "anomaly_overflow": "lighthouse anomaly ring dropped records (rise edge)",
    # -- perf attribution (perf.py, tools/perf_report.py) --------------
    "perf_model": "compile-time FLOPs/bytes of a jitted train step",
    "perf_step": "per-(step,replica) critical-path/overlap attribution",
    # -- recovery forensics (checkpointing/*, tools/recovery_report.py) -
    "heal_xfer": "heal transfer accounting: bytes, wire/serialize/lock "
                 "windows, per-chunk splits, retry counts",
    "recovery_episode": "stitched failure->recovery episode with TTR "
                        "phase decomposition (detect/quorum/transfer/"
                        "rebuild/catchup)",
    # -- elastic membership (manager.py) -------------------------------
    "elastic_join": "replica group joined a live quorum mid-run (deliberate "
                    "scale-up; healed in via checkpoint transport)",
    "elastic_leave": "replica group left the quorum gracefully (drain/"
                     "preemption; step committed, peers unpoisoned)",
    # -- control-plane HA (manager.py) ----------------------------------
    "lh_failover": "manager advanced to the next lighthouse in the list "
                   "(active entry's heartbeat lease lapsed)",
    "lh_epoch": "a quorum carrying a new fencing epoch was accepted "
                "(standby takeover observed; stale primaries now fenced)",
    # -- multi-tenant / federation (tools/fleet_load.py) -----------------
    "job_churn": "seeded churn burst applied inside one job namespace "
                 "(kills/joins scoped to that island; siblings must stay "
                 "bit-exact)",
    "district_failover": "district lighthouse failed over; the root "
                         "accepted a higher epoch for the district and "
                         "fenced the stale primary's rollups",
    # -- failure-evidence plane (manager.py, coordination.py, tools/) ----
    "failure_signal": "failure evidence observed (source in "
                      "SIGNAL_SOURCES): subject replica, observation "
                      "site, monotonic signal seq",
    "signal_overflow": "lighthouse signal ring dropped records (rise "
                       "edge, like anomaly_overflow)",
    "lh_evicted": "the lighthouse's evidence plane had evicted THIS group "
                  "and has heard from it again: its gap_ms, budget_ms, "
                  "out_ms and what it erased, beside the sender's own gap "
                  "and round trip for the heartbeat that came back",
    # -- goodput ledger (manager.py, tools/goodput_report.py) -----------
    "goodput_window": "one accounted wall-clock window: per-kind second "
                      "splits (BADPUT_KINDS) that tile [t0, t1] exactly",
    "slo_burn": "lighthouse SLO burn-rate rise edge: a job's goodput "
                "fraction is eating its error budget faster than the "
                "configured burn threshold",
}

# Closed enum of failure-evidence signal sources.  Mirrored positionally
# by ``kSignalSourceNames`` in ``_cpp/lighthouse.cc`` (lint rule
# ``signal-sources``): every ``failure_signal`` journal event and every
# lighthouse signal-ring entry carries exactly one of these strings.
#   hb_lapse       lighthouse fleet scan saw a cadence-aware heartbeat gap
#   lease_expiry   manager's active-lighthouse lease lapsed (no acks)
#   digest_anomaly fleet digest flag rise-edge (commit stall, step lag, ...)
#   rpc_error      control RPC connect refused/reset on the retry path
#   native_abort   native engine abort / all-stripes-dead / heal failure
#   proc_death     runner observed the trainer process die
SIGNAL_SOURCES: tuple = (
    "hb_lapse",
    "lease_expiry",
    "digest_anomaly",
    "rpc_error",
    "native_abort",
    "proc_death",
)

# Closed classification of where a replica-second can go.  Mirrored positionally
# by ``kBadputKindNames`` in ``_cpp/lighthouse.cc`` (lint rule
# ``badput-kinds``): every second the :class:`TimeLedger` accounts lands
# in exactly one of these buckets, and the per-replica accounts must TILE
# wall-clock (``tools/goodput_report.py --check``, eps 1e-6).
#   init_compile   process start -> first commit gate (imports, tracing,
#                  XLA compile, first quorum formation)
#   compute        committed-gate window residual: the training work the
#                  job exists to do (the only GOODput bucket)
#   exposed_comm   allreduce wall time not overlapped with compute
#   quorum_wait    blocked on quorum formation / re-formation
#   heal           receiving state from a live peer (this replica heals)
#   discarded_step failed-gate window residual: work thrown away because
#                  the commit gate said no
#   replay_catchup committed-gate residual for windows re-running steps
#                  the fleet already passed (post-heal catchup)
#   straggler_idle blocked on the commit-gate vote gather (waiting for
#                  slower peers' votes)
#   drain          graceful leave / shutdown handshake
#   down           process not running (between incarnations; attributed
#                  journal-side by goodput_report from inter-incarnation
#                  gaps, never self-reported)
BADPUT_KINDS: tuple = (
    "init_compile",
    "compute",
    "exposed_comm",
    "quorum_wait",
    "heal",
    "discarded_step",
    "replay_catchup",
    "straggler_idle",
    "drain",
    "down",
)

# Badput kinds that only ever accrue because of a FAULT (vs the perf
# badput present in a fault-free run: exposed_comm, quorum_wait,
# straggler_idle).  The headline goodput-retention metric charges only
# these against the run.
FAULT_BADPUT_KINDS: tuple = (
    "heal",
    "discarded_step",
    "replay_catchup",
    "drain",
    "down",
)


class TimeLedger:
    """Per-replica wall-clock accountant over :data:`BADPUT_KINDS`.

    The frontier design makes tiling true *by construction*: every call
    to :meth:`account` closes the window ``[frontier, upto]``, clamps the
    caller's per-kind splits to fit it, assigns the unclaimed remainder
    to ``residual``, and advances the frontier to ``upto``.  The sum of
    all buckets therefore always equals ``frontier - origin`` up to
    float rounding — there is no code path that can leak or double-count
    a second.  ``Manager`` drives it once per commit gate plus once at
    drain; ``tools/goodput_report.py`` re-checks the invariant offline
    from the ``goodput_window`` journal events.

    ``now`` (monotonic seconds) is injectable for deterministic tests.
    """

    def __init__(self, now: Optional[float] = None) -> None:
        t = time.monotonic() if now is None else float(now)
        self._lock = threading.Lock()
        self._origin = t
        self._frontier = t
        self._acct: Dict[str, float] = {k: 0.0 for k in BADPUT_KINDS}

    def account(
        self,
        splits: Dict[str, float],
        residual: str,
        upto: Optional[float] = None,
    ) -> Dict[str, float]:
        """Close the window ``[frontier, upto]``: credit each ``splits``
        kind its seconds (scaled down proportionally if they over-claim
        the window), the remainder to ``residual``.  Returns the per-kind
        seconds actually credited (the ``goodput_window`` event body)."""
        if residual not in self._acct:
            raise ValueError(f"unknown badput kind {residual!r}")
        t = time.monotonic() if upto is None else float(upto)
        with self._lock:
            window = max(t - self._frontier, 0.0)
            claimed: Dict[str, float] = {}
            total = 0.0
            for kind, s in splits.items():
                if kind not in self._acct:
                    raise ValueError(f"unknown badput kind {kind!r}")
                s = max(float(s), 0.0)
                if s > 0.0:
                    claimed[kind] = s
                    total += s
            if total > window and total > 0.0:
                scale = window / total
                claimed = {k: v * scale for k, v in claimed.items()}
                total = window
            claimed[residual] = claimed.get(residual, 0.0) + (window - total)
            for kind, s in claimed.items():
                self._acct[kind] += s
            self._frontier = max(self._frontier, t)
            return claimed

    def totals(self) -> Dict[str, float]:
        """Per-kind cumulative seconds (copy)."""
        with self._lock:
            return dict(self._acct)

    def acct_vector(self) -> List[float]:
        """Cumulative seconds positionally ordered by
        :data:`BADPUT_KINDS` — the digest wire form (``acct`` key)."""
        with self._lock:
            return [self._acct[k] for k in BADPUT_KINDS]

    def total_s(self) -> float:
        with self._lock:
            return self._frontier - self._origin

    def tiling_error_s(self) -> float:
        """|sum(buckets) - accounted wall| — float noise only, by
        construction; exported so tests can pin the invariant."""
        with self._lock:
            return abs(
                sum(self._acct.values()) - (self._frontier - self._origin)
            )


class EventLog:
    """Structured step-event journal: one JSON line per event,
    ``{ts, replica_id, step, event, **attrs}``.

    Where :class:`MetricsLogger` records per-step scalars, the journal
    records the *sequence* of control-plane events (quorum start/ready,
    heal start/done, allreduce issue/complete, commit verdicts, PG
    configure/abort, checkpoint send/recv) with enough attributes that
    ``tools/obs_report.py`` can merge journals from every replica into a
    step-aligned timeline. Lock-cheap: one json.dumps + one os.write per
    event, and events only fire at control-plane frequency (a handful per
    step), never per-microbatch.

    The journal file is opened ``O_APPEND`` and each record is a *single*
    ``os.write`` of one complete line: POSIX atomic appends mean several
    replica processes can share one journal file (``TORCHFT_JOURNAL_FILE``
    pointing everyone at the same path) without interleaving partial
    lines. The in-process lock still serializes threads sharing this
    EventLog instance.

    ``TORCHFT_JOURNAL_MAX_MB`` caps journal size: once the (approximate)
    size crosses the cap the file is renamed to ``<path>.1`` (replacing
    any previous rotation) and a fresh file is opened at the same path.
    Size tracking is one fstat at open plus the byte count of each write,
    so the cap costs nothing per event. Rotation is single-writer-safe:
    the rename happens under this instance's lock, between complete
    lines; processes *sharing* one journal path should leave the cap
    unset (each process would rotate on its own counter). Unset = no cap,
    byte-for-byte the previous behavior.
    """

    def __init__(self, path: str, replica_id: Optional[str] = None) -> None:
        self._path = path
        self._lock = threading.Lock()
        if replica_id is None:
            replica_id = knobs.get_raw("TORCHFT_REPLICA_ID") or (
                _DEFAULT_REPLICA_ID
                or os.environ.get("REPLICA_GROUP_ID", f"pid{os.getpid()}")
            )
        self.replica_id = replica_id
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        self._fd: int = os.open(
            path, os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644
        )
        try:
            self._max_bytes = int(
                float(knobs.get_raw("TORCHFT_JOURNAL_MAX_MB") or "0")
                * (1 << 20)
            )
        except ValueError:
            self._max_bytes = 0
        self._approx_size = 0
        if self._max_bytes > 0:
            try:
                self._approx_size = os.fstat(self._fd).st_size
            except OSError:
                pass
        atexit.register(self.close)

    def emit(
        self,
        event: str,
        step: Optional[int] = None,
        replica_id: Optional[str] = None,
        trace: Optional[str] = None,
        **attrs: Any,
    ) -> None:
        rec: Dict[str, Any] = {
            "ts": time.time(),
            "replica_id": self.replica_id if replica_id is None else replica_id,
            "step": None if step is None else int(step),
            "event": event,
        }
        if trace:
            rec["trace"] = trace
        if attrs:
            rec["attrs"] = attrs
        try:
            line = json.dumps(rec, default=str)
        except Exception:
            return  # never let journaling break the train loop
        data = (line + "\n").encode("utf-8", errors="replace")
        with self._lock:
            if self._fd < 0:
                return
            try:
                os.write(self._fd, data)
            except Exception:
                return
            if self._max_bytes > 0:
                self._approx_size += len(data)
                if self._approx_size >= self._max_bytes:
                    self._rotate_locked()

    def _rotate_locked(self) -> None:
        """Rename-based rotation (caller holds ``self._lock``): the full
        journal becomes ``<path>.1`` (clobbering the previous rotation)
        and writing continues into a fresh file at ``<path>``. On any
        failure the journal keeps appending to whatever fd it has —
        rotation is best-effort, losing telemetry to an ENOSPC rename is
        worse than an oversized journal."""
        try:
            os.close(self._fd)
        except OSError:
            pass
        self._fd = -1
        try:
            os.rename(self._path, self._path + ".1")
        except OSError:
            pass  # already moved/removed: reopen below recreates the path
        try:
            self._fd = os.open(
                self._path, os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644
            )
            self._approx_size = os.fstat(self._fd).st_size
        except OSError:
            self._fd = -1

    def close(self) -> None:
        with self._lock:
            if self._fd >= 0:
                try:
                    os.close(self._fd)
                finally:
                    self._fd = -1


_EVENT_LOG: Optional[EventLog] = None
_EVENT_LOCK = threading.Lock()
_DEFAULT_REPLICA_ID: Optional[str] = None


def set_default_replica_id(replica_id: str) -> None:
    """Pins the ``replica_id`` stamped on journal events that don't pass
    one explicitly (process-group / transport call sites). The Manager
    calls this with its own id so every event from its process folds onto
    one timeline row in ``tools/obs_report.py`` — otherwise those events
    fall back to ``REPLICA_GROUP_ID``, which need not match the trainer's
    chosen manager id. ``TORCHFT_REPLICA_ID`` still wins."""
    global _DEFAULT_REPLICA_ID
    _DEFAULT_REPLICA_ID = replica_id
    with _EVENT_LOCK:
        if _EVENT_LOG is not None and not knobs.get_raw("TORCHFT_REPLICA_ID"):
            _EVENT_LOG.replica_id = replica_id


def _journal_path_from_env() -> str:
    """Journal destination: ``TORCHFT_JOURNAL_FILE`` wins; else
    ``TORCHFT_JOURNAL_DIR`` derives a per-process filename. Empty when
    neither is set (journal disabled)."""
    path = knobs.get_str("TORCHFT_JOURNAL_FILE")
    if path:
        return path
    d = knobs.get_str("TORCHFT_JOURNAL_DIR")
    if not d:
        return ""
    rid = os.environ.get("REPLICA_GROUP_ID", "x")
    rank = os.environ.get("RANK", "0")
    return os.path.join(d, f"journal_replica{rid}_rank{rank}_{os.getpid()}.jsonl")


def get_event_log() -> Optional[EventLog]:
    """Process-wide event journal, enabled by ``TORCHFT_JOURNAL_FILE`` or
    ``TORCHFT_JOURNAL_DIR``. Returns None (two env reads, no allocation)
    when neither is set — callers guard with ``if log is not None`` so the
    disabled hot path stays free."""
    global _EVENT_LOG
    path = _journal_path_from_env()
    if not path:
        return None
    with _EVENT_LOCK:
        if _EVENT_LOG is None or _EVENT_LOG._path != path:
            if _EVENT_LOG is not None:
                _EVENT_LOG.close()
            _EVENT_LOG = EventLog(path)
        return _EVENT_LOG


def reset_event_log() -> None:
    """Closes and forgets the cached journal and the pinned default
    replica id (tests / re-exec)."""
    global _EVENT_LOG, _DEFAULT_REPLICA_ID
    with _EVENT_LOCK:
        if _EVENT_LOG is not None:
            _EVENT_LOG.close()
        _EVENT_LOG = None
        _DEFAULT_REPLICA_ID = None


# ----------------------------------------------------------------------
# Live fleet digest (heartbeat-carried health summary)
# ----------------------------------------------------------------------

# Span names the digest's phase block is built from. quorum/heal/commit
# already exist; allreduce_wait and step_compute are observed by the
# Manager at the commit gate (manager.py) specifically so the digest can
# report what the trainer *experiences* independent of backend.
DIGEST_PHASE_SPANS: Dict[str, str] = {
    "q": "torchft::manager::_async_quorum",
    "h": "torchft::manager::recv_checkpoint",
    "c": "torchft::manager::step_compute",
    "a": "torchft::manager::allreduce_wait",
    "m": "torchft::manager::should_commit",
}


def _sig4(x: float) -> float:
    """Round to 4 significant digits — keeps the wire digest compact
    without losing anything a health dashboard can display."""
    try:
        return float(f"{float(x):.4g}")
    except (TypeError, ValueError, OverflowError):
        return 0.0


class DigestWindow:
    """Rolling window over commit-gate outcomes, feeding
    :class:`StepDigest` its step-rate and goodput.

    The Manager calls :meth:`note_gate` once per ``should_commit`` with
    the gate verdict and the gate-to-gate wall time (heal time already
    excluded, matching the cumulative goodput bookkeeping). Rate and
    goodput are then computed over the trailing ``window_s`` seconds, so
    the digest reports *current* health, not a lifetime average that a
    long-dead stall would take hours to move.

    ``now`` is injectable everywhere for deterministic tests.
    """

    def __init__(self, window_s: float = 60.0) -> None:
        self.window_s = float(window_s)
        self._lock = threading.Lock()
        # (t, step, committed, dt_s) per gate, oldest first.
        self._gates: collections.deque = collections.deque()
        self._last_step = 0

    def note_gate(
        self,
        step: int,
        committed: bool,
        dt_s: float,
        now: Optional[float] = None,
    ) -> None:
        t = time.monotonic() if now is None else now
        with self._lock:
            self._gates.append((t, int(step), bool(committed), float(dt_s)))
            if committed:
                self._last_step = max(self._last_step, int(step))
            self._prune_locked(t)

    def _prune_locked(self, now: float) -> None:
        cutoff = now - self.window_s
        while self._gates and self._gates[0][0] < cutoff:
            self._gates.popleft()

    def snapshot(self, now: Optional[float] = None) -> Dict[str, float]:
        """{"step", "rate", "gp"} over the trailing window. Rate is
        committed gates per second of window span; goodput is committed
        gate-seconds over total gate-seconds (1.0 when nothing failed,
        0.0 when nothing ran)."""
        t = time.monotonic() if now is None else now
        with self._lock:
            self._prune_locked(t)
            committed = [g for g in self._gates if g[2]]
            total_dt = sum(g[3] for g in self._gates)
            good_dt = sum(g[3] for g in committed)
            span = t - self._gates[0][0] if self._gates else 0.0
            if span <= 0.0:
                span = total_dt  # single gate: fall back to its own cost
            rate = len(committed) / span if span > 0.0 else 0.0
            return {
                "step": self._last_step,
                "rate": rate,
                "gp": (good_dt / total_dt) if total_dt > 0.0 else 0.0,
            }


class StepDigest:
    """Compact per-replica health digest carried on lighthouse heartbeats.

    Wire form (short keys; ``to_json()`` is guaranteed ≤ 512 bytes):

    .. code-block:: json

        {"v": 1, "step": 420, "rate": 1.25, "gp": 0.98,
         "ph": {"q": [0.003, 0.008], "h": [0, 0], "c": [0.101, 0.105],
                "a": [0.012, 0.02], "m": [0.001, 0.002]},
         "bw": {"1": 1.25, "2": 0.9},
         "err": 0, "chaos": 3, "cf": 0}

    ``ph`` maps phase → [p50_s, p95_s] for quorum|heal|compute|allreduce|
    commit (keys q/h/c/a/m, see :data:`DIGEST_PHASE_SPANS`); ``bw`` maps
    peer rank → effective GiB/s on the native data plane (absent on the
    socket backend); ``err`` is the error-latch state, ``chaos`` the
    injection count, ``cf`` the consecutive-commit-failure streak;
    ``acct`` is the cumulative :class:`TimeLedger` account — seconds per
    badput kind, positionally ordered by :data:`BADPUT_KINDS` (a plain
    array keeps it inside the byte budget; both ends share the enum). The
    budget exists because the digest rides the 100 ms-interval heartbeat:
    it must stay cheap to build, send, and parse every tick.
    """

    MAX_WIRE_BYTES = 512
    MAX_PEERS = 8

    def __init__(
        self,
        step: int,
        rate: float,
        goodput: float,
        phases: Optional[Dict[str, List[float]]] = None,
        peer_gib_s: Optional[Dict[str, float]] = None,
        errored: bool = False,
        chaos_injections: int = 0,
        commit_failures: int = 0,
        acct: Optional[List[float]] = None,
    ) -> None:
        self.step = int(step)
        self.rate = float(rate)
        self.goodput = float(goodput)
        self.phases = dict(phases or {})
        self.peer_gib_s = dict(peer_gib_s or {})
        self.errored = bool(errored)
        self.chaos_injections = int(chaos_injections)
        self.commit_failures = int(commit_failures)
        self.acct = None if acct is None else [float(v) for v in acct]

    @classmethod
    def collect(
        cls,
        window: DigestWindow,
        peer_gib_s: Optional[Dict[str, float]] = None,
        errored: bool = False,
        chaos_injections: int = 0,
        commit_failures: int = 0,
        now: Optional[float] = None,
        ledger: Optional["TimeLedger"] = None,
    ) -> "StepDigest":
        """Builds a digest from a :class:`DigestWindow` plus the process's
        own span histograms (:func:`span_percentiles`) — no extra timers,
        only reads of accounting that already exists."""
        snap = window.snapshot(now=now)
        pct = span_percentiles()
        phases: Dict[str, List[float]] = {}
        for key, span_name in DIGEST_PHASE_SPANS.items():
            p = pct.get(span_name)
            if p is not None:
                phases[key] = [p["p50"], p["p95"]]
        return cls(
            step=int(snap["step"]),
            rate=snap["rate"],
            goodput=snap["gp"],
            phases=phases,
            peer_gib_s=peer_gib_s,
            errored=errored,
            chaos_injections=chaos_injections,
            commit_failures=commit_failures,
            acct=None if ledger is None else ledger.acct_vector(),
        )

    def to_wire(self) -> Dict[str, Any]:
        """Short-key dict form; peers capped at :data:`MAX_PEERS` (highest
        bandwidth kept — the interesting peers are the fast lanes whose
        *absence* signals trouble) and floats rounded to 4 significant
        digits so the JSON stays inside the heartbeat budget."""
        wire: Dict[str, Any] = {
            "v": 1,
            "step": self.step,
            "rate": _sig4(self.rate),
            "gp": _sig4(self.goodput),
        }
        if self.phases:
            wire["ph"] = {
                k: [_sig4(v[0]), _sig4(v[1])]
                for k, v in sorted(self.phases.items())
                if isinstance(v, (list, tuple)) and len(v) >= 2
            }
        if self.peer_gib_s:
            top = sorted(
                self.peer_gib_s.items(),
                key=lambda kv: (-float(kv[1]), str(kv[0])),
            )[: self.MAX_PEERS]
            wire["bw"] = {
                str(k)[:8]: _sig4(v) for k, v in sorted(top)
            }
        wire["err"] = 1 if self.errored else 0
        if self.chaos_injections:
            wire["chaos"] = self.chaos_injections
        if self.commit_failures:
            wire["cf"] = self.commit_failures
        if self.acct is not None:
            wire["acct"] = [_sig4(v) for v in self.acct[: len(BADPUT_KINDS)]]
        return wire

    def to_json(self) -> str:
        """Compact JSON, hard-capped at :data:`MAX_WIRE_BYTES`: if the
        encoded form is somehow over budget the bandwidth map is dropped
        first, then the phase block, then the badput account — a
        truncated digest beats a heartbeat frame that old lighthouses
        might refuse to read."""
        wire = self.to_wire()
        for drop in (None, "bw", "ph", "acct"):
            if drop is not None:
                wire.pop(drop, None)
            s = json.dumps(wire, separators=(",", ":"))
            if len(s.encode("utf-8")) <= self.MAX_WIRE_BYTES:
                return s
        return json.dumps(
            {"v": 1, "step": self.step}, separators=(",", ":")
        )

    @classmethod
    def from_wire(cls, wire: Dict[str, Any]) -> "StepDigest":
        """Inverse of :meth:`to_wire` (tolerant: unknown keys ignored,
        missing keys default — the compat contract both directions)."""
        ph = wire.get("ph") or {}
        return cls(
            step=int(wire.get("step", 0) or 0),
            rate=float(wire.get("rate", 0.0) or 0.0),
            goodput=float(wire.get("gp", 0.0) or 0.0),
            phases={
                k: [float(v[0]), float(v[1])]
                for k, v in ph.items()
                if isinstance(v, (list, tuple)) and len(v) >= 2
            },
            peer_gib_s={
                str(k): float(v) for k, v in (wire.get("bw") or {}).items()
            },
            errored=bool(wire.get("err", 0)),
            chaos_injections=int(wire.get("chaos", 0) or 0),
            commit_failures=int(wire.get("cf", 0) or 0),
            acct=(
                [float(v) for v in wire["acct"]]
                if isinstance(wire.get("acct"), (list, tuple))
                else None
            ),
        )


# ----------------------------------------------------------------------
# Scheduled profiler windows for train scripts
# ----------------------------------------------------------------------

_TRACE_STATE = {"active": False, "done": False, "stop_at": -1}
_TRACE_LOCK = threading.Lock()


def _trace_stop() -> None:
    try:
        import jax

        jax.profiler.stop_trace()
    except Exception:
        pass
    _TRACE_STATE["active"] = False
    _TRACE_STATE["done"] = True


def trace_window(step: int) -> None:
    """Call once per train step. When ``TORCHFT_TRACE_DIR`` is set, starts a
    ``jax.profiler`` trace once the step counter reaches
    ``TORCHFT_TRACE_START`` (default 5; ``>=`` so a heal that jumps the
    counter past it still records) and stops it ``TORCHFT_TRACE_COUNT``
    (default 3) steps later, writing a perfetto/XPlane trace under the dir.
    An atexit hook closes a window still open when the run ends early.
    No-op otherwise (reference: train_ddp.py:169-174 scheduled windows)."""
    trace_dir = knobs.get_str("TORCHFT_TRACE_DIR")
    if not trace_dir:
        return
    start = knobs.get_int("TORCHFT_TRACE_START")
    count = knobs.get_int("TORCHFT_TRACE_COUNT")
    with _TRACE_LOCK:
        if (
            not _TRACE_STATE["active"]
            and not _TRACE_STATE["done"]
            and step >= start
        ):
            try:
                import atexit

                import jax

                jax.profiler.start_trace(trace_dir)
                _TRACE_STATE["active"] = True
                _TRACE_STATE["stop_at"] = step + count
                atexit.register(_trace_atexit)
            except Exception:
                _TRACE_STATE["done"] = True
        elif _TRACE_STATE["active"] and step >= _TRACE_STATE["stop_at"]:
            _trace_stop()


def _trace_atexit() -> None:
    with _TRACE_LOCK:
        if _TRACE_STATE["active"]:
            _trace_stop()


def reset_trace_window() -> None:
    """Re-arms the one-shot profiler window: stops a trace still running
    and clears the done flag so the next :func:`trace_window` call can
    schedule a fresh window (tests, multi-run processes)."""
    with _TRACE_LOCK:
        if _TRACE_STATE["active"]:
            _trace_stop()
        _TRACE_STATE["active"] = False
        _TRACE_STATE["done"] = False
        _TRACE_STATE["stop_at"] = -1


# ----------------------------------------------------------------------
# Flight recorder
# ----------------------------------------------------------------------

_DUMP_LOCK = threading.Lock()
_DUMP_COUNT = 0


class FlightRecorder:
    """Ring buffer of recent collective operations, dumped to a JSON file
    when the PG aborts and ``TORCHFT_TRIGGER_FR_ON_ABORT`` is truthy
    (reference: NCCL flight recorder, process_group.py:89-108,812-813).

    Each record: seq, op, tag, nbytes, rank, world, status
    (issued/ok/error), and wall timestamps. The dump answers "what was in
    flight when the ring wedged" without a debugger attached.
    """

    def __init__(self, capacity: int = 256) -> None:
        self._lock = threading.Lock()
        self._buf: collections.deque = collections.deque(maxlen=capacity)
        # seq -> record index alongside the deque so complete() is O(1)
        # instead of a reverse scan of the ring.
        self._by_seq: Dict[int, Dict[str, Any]] = {}
        self._seq = 0

    def record(
        self,
        op: str,
        tag: str = "",
        nbytes: int = 0,
        rank: int = -1,
        world: int = -1,
    ) -> int:
        """Records an issued op; returns its seq for later completion."""
        with self._lock:
            self._seq += 1
            seq = self._seq
            rec = {
                "seq": seq,
                "op": op,
                "tag": tag,
                "nbytes": int(nbytes),
                "rank": rank,
                "world": world,
                "status": "issued",
                "t_issued": time.time(),
            }
            if len(self._buf) == self._buf.maxlen:
                # Deque is full: the append below evicts the oldest record;
                # drop it from the index so the dict can't grow unbounded.
                self._by_seq.pop(self._buf[0]["seq"], None)
            self._buf.append(rec)
            self._by_seq[seq] = rec
            return seq

    def complete(self, seq: int, error: Optional[str] = None) -> None:
        with self._lock:
            rec = self._by_seq.get(seq)
            if rec is not None:
                rec["status"] = "error" if error else "ok"
                rec["t_done"] = time.time()
                if error:
                    rec["error"] = error[:500]

    def snapshot(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [dict(r) for r in self._buf]

    def dump(self, reason: str, path: Optional[str] = None) -> str:
        """Writes the buffer to ``path`` (default
        ``$TORCHFT_FR_DIR or /tmp/torchft_tpu_fr_<pid>.json``); returns the
        path written."""
        if path is None:
            d = knobs.get_str("TORCHFT_FR_DIR")
            # Timestamp (unique across process restarts with recycled
            # PIDs, e.g. PID 1 in a container) + per-process counter
            # (unique within a millisecond): a later dump can never
            # overwrite the evidence from the abort that mattered.
            with _DUMP_LOCK:
                global _DUMP_COUNT
                _DUMP_COUNT += 1
                n = _DUMP_COUNT
            path = os.path.join(
                d,
                f"torchft_tpu_fr_{os.getpid()}_"
                f"{int(time.time() * 1000)}_{n:03d}.json",
            )
        payload = {
            "reason": reason,
            "pid": os.getpid(),
            "ts": time.time(),
            "ops": self.snapshot(),
        }
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump(payload, f, indent=1)
        return path

    def maybe_dump_on_abort(self, reason: str) -> Optional[str]:
        """Dump iff TORCHFT_TRIGGER_FR_ON_ABORT is truthy (the reference's
        exact gate, process_group.py:91)."""
        if not knobs.get_bool("TORCHFT_TRIGGER_FR_ON_ABORT"):
            return None
        try:
            return self.dump(reason)
        except Exception:
            return None


flight_recorder = FlightRecorder()


# ----------------------------------------------------------------------
# Perf attribution: interval-overlap math over journal span windows
# ----------------------------------------------------------------------
# Consumed by tools/perf_report.py and tools/obs_report.py. Every journal
# event that closes a span carries its completion wall-clock ``ts`` plus
# ``attrs.elapsed_s``, so the span's window is [ts - elapsed_s, ts];
# ``allreduce_issue`` additionally timestamps the moment the collective
# went in flight. That is enough to compute exposed-vs-hidden comm as
# interval set algebra instead of phase-duration sums (which double-count
# whenever windows overlap — e.g. DDP bucket allreduces, or a quorum
# overlapping the forward pass).

Interval = Tuple[float, float]


def merge_intervals(intervals: List[Interval]) -> List[Interval]:
    """Sorted union of half-open intervals; empty/inverted inputs drop."""
    ivs = sorted((a, b) for a, b in intervals if b > a)
    out: List[Interval] = []
    for a, b in ivs:
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def union_s(intervals: List[Interval]) -> float:
    return sum(b - a for a, b in merge_intervals(intervals))


def intersect_intervals(
    xs: List[Interval], ys: List[Interval]
) -> List[Interval]:
    """union(xs) ∩ union(ys) as a merged interval list."""
    xs, ys = merge_intervals(xs), merge_intervals(ys)
    out: List[Interval] = []
    i = j = 0
    while i < len(xs) and j < len(ys):
        a = max(xs[i][0], ys[j][0])
        b = min(xs[i][1], ys[j][1])
        if b > a:
            out.append((a, b))
        if xs[i][1] <= ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract_intervals(
    xs: List[Interval], ys: List[Interval]
) -> List[Interval]:
    """union(xs) minus union(ys)."""
    xs, ys = merge_intervals(xs), merge_intervals(ys)
    out: List[Interval] = []
    j = 0
    for a, b in xs:
        cur = a
        while j < len(ys) and ys[j][1] <= cur:
            j += 1
        k = j
        while k < len(ys) and ys[k][0] < b:
            if ys[k][0] > cur:
                out.append((cur, ys[k][0]))
            cur = max(cur, ys[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


# The blocking phases of one managed step, in pipeline order. "compute"
# is everything inside the step window not covered by a blocking phase.
PERF_PHASES = ("quorum", "heal", "compute", "allreduce", "commit")
_PHASE_LETTER = {
    "quorum": "q", "heal": "h", "compute": "c", "allreduce": "a",
    "commit": "m",
}


def step_phase_windows(
    events: List[Dict[str, Any]],
) -> Dict[str, List[Interval]]:
    """Span windows for ONE (step, replica)'s journal events.

    Returns interval lists keyed ``quorum``/``heal``/``commit`` (blocking
    control-plane waits), ``comm_inflight`` (allreduce issue→complete),
    ``comm_exposed`` (the tail of each in-flight window the trainer spent
    blocked in ``wait()``; ``allreduce_complete.elapsed_s`` is exactly
    that wait), and ``step`` (the full step window). Events may arrive in
    any order; pairing is FIFO by timestamp."""
    win: Dict[str, List[Interval]] = {
        "quorum": [], "heal": [], "commit": [],
        "comm_inflight": [], "comm_exposed": [], "step": [],
    }
    evs = sorted(events, key=lambda e: float(e.get("ts", 0.0)))
    t_lo: Optional[float] = None
    t_hi: Optional[float] = None
    issues: List[float] = []
    for ev in evs:
        name = ev.get("event")
        attrs = ev.get("attrs") or {}
        ts = float(ev.get("ts", 0.0))
        el = float(attrs.get("elapsed_s") or 0.0)
        bound = False
        if name == "quorum_start":
            bound = True
        elif name == "quorum_ready":
            win["quorum"].append((ts - el, ts))
            ts = ts - el  # the wait began before the journal line landed
            bound = True
        elif name == "heal_done":
            win["heal"].append((ts - el, ts))
            bound = True
        elif name == "allreduce_issue":
            issues.append(ts)
            bound = True
        elif name == "allreduce_complete":
            t0 = issues.pop(0) if issues else ts - el
            win["comm_inflight"].append((min(t0, ts - el), ts))
            win["comm_exposed"].append((ts - el, ts))
            bound = True
        elif name == "commit_gate":
            win["commit"].append((ts - el, ts))
            bound = True
        # Only phase events bound the step window: a shutdown `goodput`
        # or a drained `native_counters` landing seconds later must not
        # stretch the final step's "compute" to the process exit.
        if bound:
            t_lo = ts if t_lo is None else min(t_lo, ts)
            t_hi_ev = float(ev.get("ts", 0.0))
            t_hi = t_hi_ev if t_hi is None else max(t_hi, t_hi_ev)
    if t_lo is not None and t_hi is not None and t_hi > t_lo:
        win["step"] = [(t_lo, t_hi)]
    return win


def comm_attribution(win: Dict[str, List[Interval]]) -> Dict[str, Any]:
    """Interval-overlap attribution for one (step, replica).

    ``exposed_s``: comm time the trainer was blocked on (union of wait
    windows). ``hidden_s``: in-flight comm covered by compute (in-flight
    minus exposed minus other blocking waits). ``overlap_frac``: hidden /
    in-flight — the fraction of comm the step actually hid.
    ``compute_s`` is the step-window complement of every blocking wait,
    so quorum+heal+allreduce+commit+compute tile the step exactly (the
    ``--check`` invariant in tools/perf_report.py)."""
    step = win.get("step") or []
    blocking = {
        "quorum": win["quorum"],
        "heal": win["heal"],
        "allreduce": win["comm_exposed"],
        "commit": win["commit"],
    }
    # Clip everything to the step window and de-overlap the blocking
    # phases in pipeline-priority order so they tile, never double-count.
    phases: Dict[str, List[Interval]] = {}
    covered: List[Interval] = []
    for name in ("quorum", "heal", "allreduce", "commit"):
        clipped = intersect_intervals(blocking[name], step)
        own = subtract_intervals(clipped, covered)
        phases[name] = own
        covered = merge_intervals(covered + own)
    compute = subtract_intervals(step, covered)
    inflight = intersect_intervals(win["comm_inflight"], step)
    exposed_s = union_s(phases["allreduce"])
    inflight_s = union_s(inflight)
    hidden_s = union_s(intersect_intervals(inflight, compute))
    total_s = union_s(step)
    out: Dict[str, Any] = {
        "total_s": total_s,
        "quorum_s": union_s(phases["quorum"]),
        "heal_s": union_s(phases["heal"]),
        "allreduce_s": exposed_s,
        "commit_s": union_s(phases["commit"]),
        "compute_s": union_s(compute),
        "comm_inflight_s": inflight_s,
        "comm_exposed_s": exposed_s,
        "comm_hidden_s": hidden_s,
        "overlap_frac": (hidden_s / inflight_s) if inflight_s > 0 else None,
        "exposed_frac": (exposed_s / total_s) if total_s > 0 else None,
    }
    return out


def perf_fingerprint(attr: Dict[str, Any]) -> str:
    """Deterministic step fingerprint: phases by share of the step wall,
    largest first, as ``<letter><pct>`` joined by ``>`` (e.g. ``a98>c2``
    = 98% exposed allreduce, 2% compute). Zero-share phases drop."""
    total = float(attr.get("total_s") or 0.0)
    if total <= 0:
        return "-"
    parts = []
    for phase in PERF_PHASES:
        pct = int(round(100.0 * float(attr.get(f"{phase}_s") or 0.0) / total))
        if pct > 0:
            parts.append((pct, _PHASE_LETTER[phase]))
    parts.sort(key=lambda p: (-p[0], p[1]))
    return ">".join(f"{letter}{pct}" for pct, letter in parts) or "-"


def dominant_exposed(attr: Dict[str, Any]) -> Tuple[str, float]:
    """(phase, seconds) of the largest *blocking* interval — the thing a
    speed PR should attack first. Compute is excluded: a compute-bound
    step has no exposed stall (callers report it separately)."""
    best = max(
        ("quorum", "heal", "allreduce", "commit"),
        key=lambda p: float(attr.get(f"{p}_s") or 0.0),
    )
    return best, float(attr.get(f"{best}_s") or 0.0)


def lane_exposed_attribution(
    events: List[Dict[str, Any]],
) -> Dict[Tuple[Any, Any, Any], Dict[str, float]]:
    """Per-(peer, stripe, dir) *sole-runner* time across the
    ``native_collective`` lane windows: for each record, the nanoseconds
    where only that lane was still in flight — the tail the collective's
    completion was actually waiting on. Interval subtraction per record,
    aggregated across records (engine-clock ns never mixes with wall ts).
    """
    agg: Dict[Tuple[Any, Any, Any], Dict[str, float]] = {}
    for ev in events:
        if ev.get("event") != "native_collective":
            continue
        lanes = (ev.get("attrs") or {}).get("lanes") or []
        wins: List[Tuple[Tuple[Any, Any, Any], Interval, int]] = []
        for ln in lanes:
            try:
                t0, t1 = int(ln.get("t0_ns") or 0), int(ln.get("t1_ns") or 0)
                if t1 <= t0:
                    continue
                key = (ln.get("peer"), ln.get("stripe"), ln.get("dir"))
                wins.append((key, (float(t0), float(t1)),
                             int(ln.get("bytes") or 0)))
            except (TypeError, ValueError, AttributeError):
                continue
        for i, (key, iv, nbytes) in enumerate(wins):
            others = [w[1] for j, w in enumerate(wins) if j != i]
            sole_ns = union_s(subtract_intervals([iv], others))
            a = agg.setdefault(
                key, {"sole_s": 0.0, "busy_s": 0.0, "bytes": 0.0,
                      "count": 0.0},
            )
            a["sole_s"] += sole_ns / 1e9
            a["busy_s"] += (iv[1] - iv[0]) / 1e9
            a["bytes"] += nbytes
            a["count"] += 1
    return agg


# ----------------------------------------------------------------------
# Recovery forensics: failure -> recovery episode detection.
#
# Where the perf plane above attributes ONE steady-state step, this
# section attributes an entire failure episode: the window from the
# moment something broke (error latch, abort, process loss) until the
# first step committed afterwards. Each episode's time-to-recover (TTR)
# decomposes into five phases that tile the episode window exactly, with
# the same interval-algebra rigor as ``comm_attribution``:
#
#   detect   - uncovered time before the first recovery wait: the error
#              had happened but no quorum/heal/reconfigure was running
#              yet (latch latency, backoff, process relaunch).
#   quorum   - blocking quorum waits (``quorum_ready.elapsed_s`` spans).
#   transfer - checkpoint transfer (``heal_done.elapsed_s`` spans; the
#              ``heal_xfer`` events break this down further into wire /
#              serialize / lock-wait and per-chunk windows).
#   rebuild  - process-group reconfiguration (``pg_configure`` spans).
#   catchup  - the uncovered remainder after recovery work started:
#              re-running the step, optimizer rebuild, the commit gate.
#
# Episodes are detected per replica from its own journal, then stitched
# across replicas by window overlap: a kill on replica 1 produces a
# relaunch episode on replica 1 AND abort/reconfigure fallout on replica
# 0 — those merge into one cross-replica episode with a root cause and
# cascade edges.
# ----------------------------------------------------------------------

RECOVERY_PHASES = ("detect", "quorum", "transfer", "rebuild", "catchup")

# Journal kinds that latch a failure (open/extend an episode).
_EPISODE_LATCHES = (
    "heal_failed", "quorum_abort", "pg_abort", "pg_configure_failed",
)


def _episode_replica(ev: Dict[str, Any]) -> str:
    """Replica-group key: ``"1:uuid" -> "1"`` (matches obs_report)."""
    rid = ev.get("replica_id")
    return str(rid).split(":", 1)[0] if rid is not None else "?"


def _new_episode(t_start: float, trigger: Dict[str, Any]) -> Dict[str, Any]:
    return {
        "t_start": t_start,
        "t_end": None,
        "trigger": {
            "event": trigger.get("event"),
            "ts": float(trigger.get("ts", t_start)),
            "replica": _episode_replica(trigger),
        },
        "win": {"quorum": [], "transfer": [], "rebuild": []},
        "signals": [],
        "attempts": [],
        "xfer": [],
        "impact": False,
        "relaunch": False,
        "failed_gates": 0,
        "trace": None,
        "quorum_id": None,
        "max_step": None,
        "open": False,
    }


def _local_episodes(revs: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """One replica's episodes from its ts-sorted journal events.

    An episode opens on a failure latch (``heal_failed``/``quorum_abort``/
    ``pg_abort``/``pg_configure_failed``), a failed allreduce, or a
    healing quorum from a relaunched process (the killed incarnation left
    no latch — its journal just stops). It closes at the first
    ``commit_gate(committed=True)``. A latch-free window that commits is
    discarded (not an episode); an episode that never commits before the
    journal ends stays ``open`` (in-progress at harvest time)."""
    eps: List[Dict[str, Any]] = []
    cur: Optional[Dict[str, Any]] = None
    last_qstart: Optional[Dict[str, Any]] = None
    for ev in revs:
        name = ev.get("event")
        attrs = ev.get("attrs") or {}
        ts = float(ev.get("ts", 0.0))
        el = float(attrs.get("elapsed_s") or 0.0)
        failed_ar = (
            name == "allreduce_complete" and attrs.get("ok") is False
        )
        if name in _EPISODE_LATCHES or failed_ar:
            if cur is None:
                cur = _new_episode(ts, ev)
            if name in _EPISODE_LATCHES:
                cur["impact"] = True
            cur["signals"].append({
                "event": name, "ts": ts, "replica": _episode_replica(ev),
                "cause": attrs.get("cause") or attrs.get("error"),
                "phase": attrs.get("phase"),
            })
            if name == "heal_failed":
                cur["attempts"].append({
                    "ok": False,
                    "ts": ts,
                    "cause": attrs.get("cause"),
                    "phase": attrs.get("phase"),
                    "error": attrs.get("error"),
                })
        elif name == "quorum_start":
            last_qstart = ev
        elif name == "quorum_ready":
            t0 = ts - el
            if attrs.get("heal") and cur is None:
                # Relaunched process healing back in: start the episode
                # at its first quorum attempt (or the wait start if the
                # quorum_start line predates this incarnation's journal).
                start = ev
                if last_qstart is not None and float(
                    last_qstart.get("ts", 0.0)
                ) <= t0:
                    start = last_qstart
                cur = _new_episode(
                    min(float(start.get("ts", ts)), t0), start
                )
                cur["relaunch"] = True
            if cur is not None:
                cur["win"]["quorum"].append((t0, ts))
                if attrs.get("heal"):
                    cur["impact"] = True
                cur["trace"] = ev.get("trace") or cur["trace"]
                if attrs.get("quorum_id") is not None:
                    cur["quorum_id"] = attrs.get("quorum_id")
                if attrs.get("max_step") is not None:
                    cur["max_step"] = attrs.get("max_step")
        elif name == "pg_configure":
            if cur is not None and el > 0:
                cur["win"]["rebuild"].append((ts - el, ts))
        elif name == "heal_start":
            if cur is None:
                cur = _new_episode(ts, ev)
            cur["impact"] = True
        elif name == "heal_done":
            if cur is None:
                cur = _new_episode(ts - el, ev)
            cur["impact"] = True
            cur["win"]["transfer"].append((ts - el, ts))
            cur["attempts"].append({
                "ok": True, "ts": ts, "peer": attrs.get("peer"),
                "elapsed_s": el,
            })
            if attrs.get("max_step") is not None:
                cur["max_step"] = attrs.get("max_step")
        elif name == "heal_xfer":
            if cur is not None:
                cur["xfer"].append({
                    "ts": ts,
                    "dir": attrs.get("dir"),
                    "transport": attrs.get("transport"),
                    "nbytes": int(attrs.get("nbytes") or 0),
                    "elapsed_s": el,
                    "wire_s": float(attrs.get("wire_s") or 0.0),
                    "ser_s": float(attrs.get("ser_s") or 0.0),
                    "lock_s": float(attrs.get("lock_s") or 0.0),
                    "retries": int(attrs.get("retries") or 0),
                })
        elif name == "commit_gate":
            if cur is None:
                continue
            if attrs.get("committed"):
                if cur["impact"]:
                    cur["t_end"] = ts
                    eps.append(cur)
                cur = None
            else:
                cur["impact"] = True
                cur["failed_gates"] += 1
    if cur is not None and cur["impact"]:
        cur["open"] = True
        last_ts = float(revs[-1].get("ts", cur["t_start"])) if revs else 0.0
        cur["t_end"] = max(last_ts, cur["t_start"])
        eps.append(cur)
    return eps


def episode_phase_windows(
    ep: Dict[str, Any],
) -> Dict[str, List[Interval]]:
    """Tile one local episode's window into the five RECOVERY_PHASES.

    Recorded waits are clipped to the episode window and de-overlapped
    in priority order quorum > transfer > rebuild (a heal that overlaps
    its quorum wait is counted once). The uncovered remainder splits at
    the first recovery wait: everything before it is ``detect`` (the
    failure had happened, no recovery machinery was running yet),
    everything after is ``catchup``. By construction the five phases
    tile [t_start, t_end] exactly — the ``recovery_report.py --check``
    invariant."""
    t0 = float(ep["t_start"])
    t1 = float(ep["t_end"] if ep["t_end"] is not None else ep["t_start"])
    window = [(t0, t1)] if t1 > t0 else []
    phases: Dict[str, List[Interval]] = {}
    covered: List[Interval] = []
    for name in ("quorum", "transfer", "rebuild"):
        clipped = intersect_intervals(ep["win"][name], window)
        own = subtract_intervals(clipped, covered)
        phases[name] = own
        covered = merge_intervals(covered + own)
    rest = subtract_intervals(window, covered)
    split = covered[0][0] if covered else t1
    phases["detect"] = intersect_intervals(rest, [(t0, split)])
    phases["catchup"] = subtract_intervals(rest, [(t0, split)])
    return phases


def _episode_row(ep: Dict[str, Any]) -> Dict[str, Any]:
    """One per-replica row of a cross-replica episode: phase seconds
    (tiling the row window), heal attempts, and transfer accounting."""
    wins = episode_phase_windows(ep)
    t0 = float(ep["t_start"])
    t1 = float(ep["t_end"] if ep["t_end"] is not None else ep["t_start"])
    xfer_recv = [x for x in ep["xfer"] if x.get("dir") == "recv"]
    xfer: Dict[str, Any] = {}
    if xfer_recv:
        nbytes = sum(x["nbytes"] for x in xfer_recv)
        elapsed = sum(x["elapsed_s"] for x in xfer_recv)
        xfer = {
            "nbytes": nbytes,
            "elapsed_s": elapsed,
            "wire_s": sum(x["wire_s"] for x in xfer_recv),
            "ser_s": sum(x["ser_s"] for x in xfer_recv),
            "lock_s": sum(x["lock_s"] for x in xfer_recv),
            "retries": sum(x["retries"] for x in xfer_recv),
            "transport": xfer_recv[-1].get("transport"),
            "gib_s": (
                (nbytes / float(1 << 30)) / elapsed if elapsed > 0 else None
            ),
        }
    return {
        "t_start": t0,
        "t_end": t1,
        "ttr_s": t1 - t0,
        "phases": {k: union_s(wins[k]) for k in RECOVERY_PHASES},
        "phase_windows": {k: wins[k] for k in RECOVERY_PHASES},
        "trigger": ep["trigger"],
        "signals": ep["signals"],
        "attempts": ep["attempts"],
        "failed_attempts": sum(
            1 for a in ep["attempts"] if not a.get("ok")
        ),
        "failed_gates": ep["failed_gates"],
        "relaunch": ep["relaunch"],
        "open": ep["open"],
        "trace": ep["trace"],
        "quorum_id": ep["quorum_id"],
        "max_step": ep["max_step"],
        "xfer": xfer,
    }


def detect_episodes(
    events: List[Dict[str, Any]], lookback_s: float = 10.0
) -> List[Dict[str, Any]]:
    """Stitch per-replica journals into cross-replica recovery episodes.

    Per-replica episodes whose windows overlap merge into one episode
    record with an ``id``, the union window, per-replica rows (each
    tiling its own window into RECOVERY_PHASES), a root cause, cascade
    edges from the root replica to every other replica that latched a
    failure inside the window, correlated ``chaos_inject`` records
    (fired within ``lookback_s`` before the window or inside it), and
    the donor's ``heal_send_*`` spans."""
    evs = sorted(events, key=lambda e: float(e.get("ts", 0.0)))
    by_replica: Dict[str, List[Dict[str, Any]]] = {}
    chaos: List[Dict[str, Any]] = []
    sends: List[Dict[str, Any]] = []
    for ev in evs:
        name = ev.get("event")
        if name == "chaos_inject":
            chaos.append(ev)
        elif name in ("heal_send_start", "heal_send_done", "heal_xfer"):
            if name != "heal_xfer" or (
                (ev.get("attrs") or {}).get("dir") == "send"
            ):
                sends.append(ev)
        by_replica.setdefault(_episode_replica(ev), []).append(ev)

    local: List[Tuple[str, Dict[str, Any]]] = []
    for rid, revs in by_replica.items():
        for ep in _local_episodes(revs):
            local.append((rid, ep))
    local.sort(key=lambda p: float(p[1]["t_start"]))

    # Merge per-replica episodes by window overlap (chained).
    groups: List[List[Tuple[str, Dict[str, Any]]]] = []
    g_end = None
    for rid, ep in local:
        t0 = float(ep["t_start"])
        t1 = float(ep["t_end"] if ep["t_end"] is not None else t0)
        if groups and g_end is not None and t0 <= g_end:
            groups[-1].append((rid, ep))
            g_end = max(g_end, t1)
        else:
            groups.append([(rid, ep)])
            g_end = t1
    out: List[Dict[str, Any]] = []
    for idx, group in enumerate(groups):
        rows = {rid: _episode_row(ep) for rid, ep in group}
        w0 = min(r["t_start"] for r in rows.values())
        w1 = max(r["t_end"] for r in rows.values())
        # Primary replica: the healer (a successful heal attempt), else
        # a relaunch, else the longest-suffering row.
        def _rank(rid: str) -> Tuple[int, int, float]:
            r = rows[rid]
            healed = any(a.get("ok") for a in r["attempts"])
            return (
                1 if healed else 0,
                1 if r["relaunch"] else 0,
                r["ttr_s"],
            )
        primary = max(rows, key=_rank)
        ep_chaos = [
            {
                "ts": float(c.get("ts", 0.0)),
                "replica": _episode_replica(c),
                "kind": (c.get("attrs") or {}).get("kind"),
                "plane": (c.get("attrs") or {}).get("plane"),
                "site": (c.get("attrs") or {}).get("site"),
            }
            for c in chaos
            if w0 - lookback_s <= float(c.get("ts", 0.0)) <= w1
        ]
        # Root cause precedence: a relaunch pins the loss on the relaunched
        # process itself (the kill left no latch to point at); else the
        # earliest correlated chaos injection; else the earliest latch.
        all_signals = sorted(
            (s for r in rows.values() for s in r["signals"]),
            key=lambda s: s["ts"],
        )
        if rows[primary]["relaunch"]:
            # The kill itself left no journal line; the earliest fleet-
            # wide evidence (a survivor's abort, or the relaunch) dates it.
            root: Dict[str, Any] = {
                "replica": primary, "kind": "process_loss", "ts": w0,
            }
        elif ep_chaos:
            c0 = ep_chaos[0]
            root = {
                "replica": c0["replica"], "kind": "chaos",
                "ts": c0["ts"], "chaos": c0,
            }
        elif all_signals:
            s0 = all_signals[0]
            root = {
                "replica": s0["replica"], "kind": "latch",
                "ts": s0["ts"], "signal": s0,
            }
        else:
            root = {
                "replica": primary, "kind": "unknown",
                "ts": rows[primary]["t_start"],
            }
        cascade = []
        seen_replicas = {root["replica"]}
        for s in all_signals:
            if s["replica"] in seen_replicas:
                continue
            seen_replicas.add(s["replica"])
            cascade.append({
                "from": root["replica"],
                "to": s["replica"],
                "signal": s["event"],
                "dt_s": s["ts"] - float(root["ts"]),
            })
        donors = []
        for ev in sends:
            ts = float(ev.get("ts", 0.0))
            if not (w0 <= ts <= w1):
                continue
            attrs = ev.get("attrs") or {}
            donors.append({
                "replica": _episode_replica(ev),
                "event": ev.get("event"),
                "ts": ts,
                "elapsed_s": float(attrs.get("elapsed_s") or 0.0),
                "nbytes": int(attrs.get("nbytes") or 0),
            })
        out.append({
            "id": f"e{idx}",
            "t_start": w0,
            "t_end": w1,
            "ttr_s": w1 - w0,
            "primary": primary,
            "replicas": rows,
            "root_cause": root,
            "cascade": cascade,
            "chaos": ep_chaos,
            "donors": donors,
            "open": any(r["open"] for r in rows.values()),
            "trace": rows[primary]["trace"],
            "max_step": rows[primary]["max_step"],
        })
    return out
