#!/usr/bin/env python
"""fleet_load: synthetic-fleet load harness for the lighthouse health plane.

Spawns a real C++ lighthouse, then drives it with N lightweight synthetic
replicas — no trainers, no JAX — each a nonblocking framed-JSON connection
sending heartbeats that carry a realistic :class:`~torchft_tpu.telemetry.
StepDigest` wire payload. A single-threaded ``selectors`` event loop
multiplexes all N connections (the box has one core; threads would only
benchmark the scheduler), while the lighthouse runs its usual
thread-per-connection model on the other side.

Per fleet size N the harness measures, and writes to ``BENCH_FLEET.json``:

* heartbeat+digest round-trip p50/p95 (the per-step hot path),
* quorum formation: all N replicas join one quorum (``min_replicas=N``)
  and each records first-send -> response latency,
* ``/fleet.json``, ``/metrics`` and ``/status.json`` HTTP serve latency
  *while the whole fleet keeps heartbeating*,
* lighthouse CPU per phase (utime+stime from ``/proc/<pid>/stat``).

At the largest N it also runs the before/after experiment the scaling
rework is judged by: ``/fleet.json`` serve p95 under full heartbeat load
with snapshot caching off (``fleet_snap_ms=0``, the old build-under-lock
behaviour) vs on (100 ms). The run fails unless caching cuts p95 by >= 2x
and the budgets of ``BUDGETS`` and ``TRIPWIRES`` below that bind in it
hold; the broken or unmeasured ones are listed under ``budget_problems``.

Usage::

    python tools/fleet_load.py                  # N = 64, 256, 1024
    python tools/fleet_load.py --quick          # N = 64 only (CI lane)
    python tools/fleet_load.py --sizes 64 512   # custom ladder
    python tools/fleet_load.py --out /tmp/b.json

``--quick`` is what ``tools/suite_gate.sh fleetload`` runs: one small
fleet, the same budget assertions, no before/after (caching wins are only
interesting at O(1000) rows).
"""

from __future__ import annotations

import argparse
import json
import os
import selectors
import socket
import struct
import sys
import time
from typing import Any, Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from torchft_tpu import _net  # noqa: E402
from torchft_tpu.coordination import LighthouseServer  # noqa: E402
from torchft_tpu.telemetry import StepDigest  # noqa: E402

from drills import check_budgets  # noqa: E402

# The drill's budgets: (metric, direction, bound, why). Generous
# multiples of what the reworked lighthouse does on this class of box
# (single shared core, N server threads): tripwires for O(N) regressions
# on the hot paths, not performance targets. A row binds in the run that
# measures it: ``.n<N>`` in a ladder that has N replicas, ``restart_*``
# under --restart-lighthouse, ``multijob_*.m<M>x<N>`` under --multijob
# at that shape.
BUDGETS = (
    ("fleet.fleet_json_p95_us.n256", "lower", 300_000,
     "served from the 100 ms snapshot while 256 replicas heartbeat"),
    ("fleet.fleet_json_p95_us.n1024", "lower", 500_000,
     "the same at 1,024: a cached serve, not a rebuild under the lock"),
    ("fleet.quorum_formation_ms.n1024", "lower", 2000,
     "half the 4003 ms the timer-scan quorum took at this N: the "
     "delta-driven gate fires the round inline at the last arrival"),
    ("fleet.restart_reregister_s.n256", "lower", 30,
     "a warm restart slower to re-absorb the fleet blows the "
     "control-plane TTR budget"),
    ("fleet.restart_repopulate_s.n256", "lower", 60,
     "/fleet.json's aggregate back to N within the TTR ceiling"),
    ("fleet.multijob_formation_p95_ms.m4x2", "lower", 2000,
     "per-job quorum formation across M jobs sharing two districts"),
    ("fleet.multijob_formation_p95_ms.m16x4", "lower", 2000,
     "the same at the full shape"),
    ("fleet.multijob_sibling_hb_p95_us.m4x2", "lower", 400_000,
     "a sibling job's heartbeat hot path DURING another job's churn storm"),
    ("fleet.multijob_sibling_hb_p95_us.m16x4", "lower", 400_000,
     "the same at the full shape"),
    ("fleet.multijob_isolation_violations.m4x2", "lower", 0,
     "sibling control-plane state stays bit-exact through the storm"),
    ("fleet.multijob_isolation_violations.m16x4", "lower", 0,
     "the same at the full shape"),
)
# What the ladder and the quick restart are held to besides, checked the
# same way: the heartbeat hot path at every N, and the small sizes.
TRIPWIRES = (
    ("fleet.hb_p95_us.n64", "lower", 100_000, "heartbeat + digest round trip"),
    ("fleet.hb_p95_us.n256", "lower", 200_000, "the same"),
    ("fleet.hb_p95_us.n1024", "lower", 400_000, "the same"),
    ("fleet.fleet_json_p95_us.n64", "lower", 200_000, "cached serve"),
    ("fleet.quorum_formation_ms.n64", "lower", 1500, "first register to broadcast"),
    ("fleet.quorum_formation_ms.n256", "lower", 2000, "the same"),
    ("fleet.restart_reregister_s.n64", "lower", 30, "as at n256"),
    ("fleet.restart_repopulate_s.n64", "lower", 60, "as at n256"),
)
MIN_SPEEDUP = 2.0  # cached vs uncached /fleet.json p95 at the largest N


def budget_values(report: Dict[str, Any]) -> Dict[str, Any]:
    """Every budgeted metric of the sections ``report`` holds (``fleets``,
    ``restart``, ``multijob``), None where the section lacks the value."""
    vals: Dict[str, Any] = {}
    for n, res in (report.get("fleets") or {}).items():
        vals[f"fleet.hb_p95_us.n{n}"] = (
            res.get("heartbeat") or {}).get("p95_us")
        vals[f"fleet.fleet_json_p95_us.n{n}"] = (
            (res.get("http") or {}).get("fleet_json") or {}).get("p95_us")
        vals[f"fleet.quorum_formation_ms.n{n}"] = (
            res.get("quorum") or {}).get("formation_ms")
    rst = report.get("restart")
    if rst:
        for key in ("reregister_s", "repopulate_s"):
            vals[f"fleet.restart_{key}.n{rst.get('n')}"] = rst.get(key)
    mj = report.get("multijob")
    if mj:
        tag = f"m{mj.get('m_jobs')}x{mj.get('n_per_job')}"
        viol = (mj.get("isolation") or {}).get("violations")
        vals[f"fleet.multijob_formation_p95_ms.{tag}"] = mj.get(
            "formation_p95_ms")
        vals[f"fleet.multijob_sibling_hb_p95_us.{tag}"] = (
            mj.get("sibling_heartbeat") or {}).get("p95_us")
        vals[f"fleet.multijob_isolation_violations.{tag}"] = (
            None if viol is None else len(viol))
    return vals


def budget_problems(report: Dict[str, Any], section: str) -> List[str]:
    """The budgets that bind in the run that measured ``section`` of the
    report (its metrics, at the sizes it ran), checked against it."""
    values = budget_values({section: report.get(section)})
    rows = [r for r in BUDGETS + TRIPWIRES if r[0] in values]
    return check_budgets(values, rows)


_CLK_TCK = os.sysconf("SC_CLK_TCK")


def _pct(vals: List[float], q: float) -> float:
    """Nearest-rank percentile; 0 on empty."""
    if not vals:
        return 0.0
    s = sorted(vals)
    return s[min(len(s) - 1, int(q * len(s)))]


def _proc_cpu_s(pid: int) -> float:
    """utime+stime of one process, in seconds."""
    with open(f"/proc/{pid}/stat") as f:
        parts = f.read().rsplit(") ", 1)[1].split()
    # Fields after the comm field: index 11 = utime, 12 = stime.
    return (int(parts[11]) + int(parts[12])) / _CLK_TCK


def _mk_digest(step: int, rid_n: int) -> Dict[str, Any]:
    """A realistic digest payload: full phase block + a few peer lanes."""
    return StepDigest(
        step=step,
        rate=1.0 + (rid_n % 7) * 0.01,
        goodput=0.97,
        phases={k: [0.001 * (i + 1), 0.002 * (i + 1)]
                for i, k in enumerate(("q", "h", "c", "a", "m"))},
        peer_gib_s={f"p{j}": 2.0 + j for j in range(4)},
        errored=False,
        chaos_injections=0,
        commit_failures=0,
    ).to_wire()


class Conn:
    """One synthetic replica: a nonblocking framed-JSON connection with a
    single request in flight at a time. The heartbeat frame is prebuilt
    once (fixed step near the fleet median, per-replica rate) so queueing
    one costs an append, not a JSON encode — the harness must not spend
    the shared core it is trying to load the lighthouse with."""

    __slots__ = ("sock", "rid", "rid_n", "job", "out", "inbuf", "need",
                 "t0", "rtts_us", "rounds", "step", "done", "hb_frame",
                 "pending", "next_at")

    def __init__(self, sock: socket.socket, rid_n: int, job: str = "",
                 hb_interval_ms: int = 1000) -> None:
        self.sock = sock
        self.rid_n = rid_n
        self.job = job
        self.rid = (f"{job}:synth-{rid_n:05d}" if job
                    else f"synth-{rid_n:05d}")
        self.out = bytearray()
        self.inbuf = bytearray()
        self.need: Optional[int] = None  # payload bytes still expected
        self.t0 = 0
        self.rtts_us: List[float] = []
        self.rounds = 0
        self.step = 100 + rid_n % 2  # within the step_lag tolerance
        self.done = False
        self.pending = False
        self.next_at = 0.0
        hb: Dict[str, Any] = {
            "type": "heartbeat", "replica_id": self.rid,
            "timeout_ms": 5000, "hb_interval_ms": hb_interval_ms,
            "digest": _mk_digest(self.step, rid_n),
        }
        if job:
            hb["job"] = job
        payload = json.dumps(hb, separators=(",", ":")).encode()
        self.hb_frame = struct.pack(">I", len(payload)) + payload

    def queue(self, obj: Dict[str, Any]) -> None:
        payload = json.dumps(obj, separators=(",", ":")).encode()
        self.out += struct.pack(">I", len(payload)) + payload
        self.t0 = time.perf_counter_ns()

    def queue_heartbeat(self) -> None:
        self.out += self.hb_frame
        self.t0 = time.perf_counter_ns()

    def on_readable(self) -> int:
        """Drains the socket; returns how many complete frames arrived."""
        frames = 0
        while True:
            try:
                chunk = self.sock.recv(65536)
            except BlockingIOError:
                break
            if not chunk:
                raise ConnectionError(f"{self.rid}: closed by lighthouse")
            self.inbuf += chunk
            while True:
                if self.need is None:
                    if len(self.inbuf) < 4:
                        break
                    self.need = struct.unpack(">I", self.inbuf[:4])[0]
                    del self.inbuf[:4]
                if len(self.inbuf) < self.need:
                    break
                del self.inbuf[:self.need]  # response content not needed
                self.need = None
                frames += 1
            if len(chunk) < 65536:
                break
        return frames

    def on_writable(self) -> None:
        while self.out:
            try:
                n = self.sock.send(self.out)
            except BlockingIOError:
                return
            del self.out[:n]


def connect_fleet(addr: str, n: int, batch: int = 64, job: str = "",
                  hb_interval_ms: int = 1000) -> List[Conn]:
    """N nonblocking connections, batched under the listener's backlog
    (128) so a 1024-strong fleet doesn't SYN-flood its own lighthouse.
    ``job`` tags every frame with that namespace (multi-tenant mode)."""
    host, port = _net.parse_addr(addr)
    conns: List[Conn] = []
    for lo in range(0, n, batch):
        pending: Dict[int, Conn] = {}
        sel = selectors.DefaultSelector()
        for i in range(lo, min(lo + batch, n)):
            s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            s.setblocking(False)
            s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            try:
                s.connect((host, port))
            except BlockingIOError:
                pass
            c = Conn(s, i, job=job, hb_interval_ms=hb_interval_ms)
            pending[s.fileno()] = c
            sel.register(s, selectors.EVENT_WRITE, c)
        deadline = time.monotonic() + 30
        while pending and time.monotonic() < deadline:
            for key, _ in sel.select(timeout=1.0):
                c = key.data
                err = c.sock.getsockopt(socket.SOL_SOCKET, socket.SO_ERROR)
                if err:
                    raise ConnectionError(
                        f"{c.rid}: connect failed: {os.strerror(err)}")
                sel.unregister(c.sock)
                pending.pop(c.sock.fileno(), None)
                conns.append(c)
        sel.close()
        if pending:
            raise TimeoutError(
                f"{len(pending)} connects unfinished in batch at {lo}")
    return conns


def _pump(sel: selectors.BaseSelector, conns: List[Conn],
          on_frame, deadline: float) -> None:
    """Shared event-loop core: flush writes, deliver frames to
    ``on_frame(conn)`` until every conn reports done or the deadline."""
    while time.monotonic() < deadline:
        if all(c.done for c in conns):
            return
        for key, mask in sel.select(timeout=0.5):
            c = key.data
            if mask & selectors.EVENT_WRITE:
                c.on_writable()
                if not c.out:
                    sel.modify(c.sock, selectors.EVENT_READ, c)
            if mask & selectors.EVENT_READ:
                for _ in range(c.on_readable()):
                    on_frame(c)
                if c.out:
                    sel.modify(
                        c.sock,
                        selectors.EVENT_READ | selectors.EVENT_WRITE, c)
    undone = sum(1 for c in conns if not c.done)
    raise TimeoutError(f"phase timed out with {undone} conns unfinished")


def heartbeat_phase(conns: List[Conn], rounds: int,
                    timeout_s: float = 300.0) -> Dict[str, Any]:
    """Every replica sends ``rounds`` digest-carrying heartbeats, one in
    flight per connection; per-request RTTs are pooled fleet-wide."""
    sel = selectors.DefaultSelector()
    for c in conns:
        c.rtts_us, c.rounds, c.done = [], 0, False
        c.queue_heartbeat()
        sel.register(c.sock, selectors.EVENT_READ | selectors.EVENT_WRITE, c)

    def on_frame(c: Conn) -> None:
        c.rtts_us.append((time.perf_counter_ns() - c.t0) / 1e3)
        c.rounds += 1
        if c.rounds >= rounds:
            c.done = True
        else:
            c.queue_heartbeat()

    _pump(sel, conns, on_frame, time.monotonic() + timeout_s)
    sel.close()
    rtts = [v for c in conns for v in c.rtts_us]
    return {"n": len(rtts), "p50_us": round(_pct(rtts, 0.50)),
            "p95_us": round(_pct(rtts, 0.95))}


def quorum_phase(conns: List[Conn], timeout_s: float = 300.0,
                 stagger_first_s: float = 0.0) -> Dict[str, Any]:
    """All N replicas request one quorum (the lighthouse was started with
    ``min_replicas=N``); latency is first-send -> own response.

    ``stagger_first_s`` flushes ``conns[0]``'s request that long before
    the rest of the fleet: the elastic-rejoin order, where the joiner
    registers before the incumbent members re-request. Without it a
    one-shot round can race the incumbents' prev-member fast path (the
    joiner would be picked up by the NEXT round — which a one-shot
    harness never issues)."""
    sel = selectors.DefaultSelector()

    def enqueue(c: Conn) -> None:
        c.rtts_us, c.done = [], False
        req: Dict[str, Any] = {
            "type": "quorum", "timeout_ms": int(timeout_s * 1000),
            "requester": {
                "replica_id": c.rid, "address": f"addr-{c.rid}",
                "store_address": "", "step": c.step, "world_size": 1,
                "shrink_only": False, "commit_failures": 0, "data": {},
            },
        }
        if c.job:
            req["job"] = c.job
        c.queue(req)
        sel.register(c.sock, selectors.EVENT_READ | selectors.EVENT_WRITE, c)

    def on_frame(c: Conn) -> None:
        c.rtts_us.append((time.perf_counter_ns() - c.t0) / 1e3)
        c.done = True

    t0 = time.monotonic()
    rest = conns
    if stagger_first_s > 0 and len(conns) > 1:
        enqueue(conns[0])
        stop = time.monotonic() + stagger_first_s
        while time.monotonic() < stop:
            for key, mask in sel.select(timeout=0.05):
                c = key.data
                if mask & selectors.EVENT_WRITE:
                    c.on_writable()
                    if not c.out:
                        sel.modify(c.sock, selectors.EVENT_READ, c)
                if mask & selectors.EVENT_READ:
                    for _ in range(c.on_readable()):
                        on_frame(c)
        rest = conns[1:]
    for c in rest:
        enqueue(c)
    _pump(sel, conns, on_frame, t0 + timeout_s + 30)
    sel.close()
    lat = [v for c in conns for v in c.rtts_us]
    return {"n": len(lat), "p50_us": round(_pct(lat, 0.50)),
            "p95_us": round(_pct(lat, 0.95)),
            "formation_ms": round((time.monotonic() - t0) * 1e3)}


def http_phase(conns: List[Conn], addr: str, probes: int,
               concurrency: int = 4,
               paths=("/fleet.json", "/metrics", "/status.json"),
               timeout_s: float = 600.0) -> Dict[str, Dict[str, Any]]:
    """Serve-latency probes WHILE the whole fleet keeps heartbeating.

    The churn is paced to ~1000 heartbeats/s total (each replica on an
    even stagger): enough write pressure that every probe races live
    table mutations, but below the point where the one shared core
    measures its own run queue instead of the serve path.

    Each endpoint is probed by ``concurrency`` pollers at once — the
    realistic consumer pattern (obs_top + obs_export + operators all
    polling the same lighthouse), and exactly the load the snapshot
    cache exists for: one rebuild per staleness window amortized across
    every reader, where the uncached path pays a full O(N) rebuild per
    request. Latency is request-flushed -> EOF (``Connection: close``)."""
    host, port = _net.parse_addr(addr)
    n = len(conns)
    hb_interval = max(0.05, n / 1000.0)
    sel = selectors.DefaultSelector()
    t_start = time.monotonic()
    for i, c in enumerate(conns):
        c.pending = False
        c.next_at = t_start + i * hb_interval / n
        sel.register(c.sock, selectors.EVENT_READ, c)

    def start_probe(path: str) -> Dict[str, Any]:
        s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        s.setblocking(False)
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        try:
            s.connect((host, port))
        except BlockingIOError:
            pass
        probe = {
            "sock": s, "path": path, "t0": 0, "nread": 0,
            "out": bytearray(f"GET {path} HTTP/1.1\r\nHost: {host}\r\n"
                             f"Connection: close\r\n\r\n".encode()),
        }
        sel.register(s, selectors.EVENT_READ | selectors.EVENT_WRITE, probe)
        return probe

    results: Dict[str, List[float]] = {}
    deadline = time.monotonic() + timeout_s
    for path in paths:
        lats: List[float] = []
        results[path] = lats
        todo = probes
        active: List[Dict[str, Any]] = []
        while (todo or active) and time.monotonic() < deadline:
            while todo and len(active) < concurrency:
                active.append(start_probe(path))
                todo -= 1
            now = time.monotonic()
            for c in conns:
                if not c.pending and now >= c.next_at:
                    c.queue_heartbeat()
                    c.pending = True
                    sel.modify(
                        c.sock,
                        selectors.EVENT_READ | selectors.EVENT_WRITE, c)
            for key, mask in sel.select(timeout=0.02):
                if isinstance(key.data, dict):
                    probe = key.data
                    s = probe["sock"]
                    if mask & selectors.EVENT_WRITE and probe["out"]:
                        try:
                            sent = s.send(probe["out"])
                            del probe["out"][:sent]
                        except BlockingIOError:
                            pass
                        if not probe["out"]:
                            probe["t0"] = time.perf_counter_ns()
                            sel.modify(s, selectors.EVENT_READ, probe)
                    if mask & selectors.EVENT_READ:
                        try:
                            chunk = s.recv(65536)
                        except BlockingIOError:
                            continue
                        if chunk:
                            probe["nread"] += len(chunk)
                            continue
                        # EOF: response complete.
                        if probe["nread"] == 0:
                            raise ConnectionError(
                                f"empty HTTP response for {probe['path']}")
                        lats.append(
                            (time.perf_counter_ns() - probe["t0"]) / 1e3)
                        sel.unregister(s)
                        s.close()
                        active.remove(probe)
                    continue
                c = key.data
                if mask & selectors.EVENT_WRITE:
                    c.on_writable()
                    if not c.out:
                        sel.modify(c.sock, selectors.EVENT_READ, c)
                if mask & selectors.EVENT_READ:
                    for _ in range(c.on_readable()):
                        c.pending = False
                        c.next_at = time.monotonic() + hb_interval
        if todo or active:
            raise TimeoutError(
                f"http phase: {todo} {path} probes unfinished")
    sel.close()
    return {
        p.strip("/").replace(".", "_"): {
            "n": len(v), "p50_us": round(_pct(v, 0.50)),
            "p95_us": round(_pct(v, 0.95)),
        }
        for p, v in results.items()
    }


def close_fleet(conns: List[Conn]) -> None:
    for c in conns:
        try:
            c.sock.close()
        except OSError:
            pass


def run_fleet(n: int, rounds: int, probes: int,
              fleet_snap_ms: int = 100,
              concurrency: int = 4) -> Dict[str, Any]:
    """One full ladder rung: spawn a lighthouse sized for N, run the
    heartbeat / quorum / http phases, sample lighthouse CPU per phase."""
    server = LighthouseServer(
        min_replicas=n, join_timeout_ms=120_000, quorum_tick_ms=50,
        heartbeat_timeout_ms=120_000, fleet_snap_ms=fleet_snap_ms,
    )
    pid = server._server._proc.pid
    out: Dict[str, Any] = {"n": n, "fleet_snap_ms": fleet_snap_ms}
    try:
        conns = connect_fleet(server.address(), n)
        try:
            cpu: Dict[str, Any] = {}
            for name, fn in (
                ("heartbeat", lambda: heartbeat_phase(conns, rounds)),
                ("quorum", lambda: quorum_phase(conns)),
                ("http", lambda: http_phase(
                    conns, server.address(), probes, concurrency)),
            ):
                c0, w0 = _proc_cpu_s(pid), time.monotonic()
                out[name] = fn()
                cpu[name] = {
                    "cpu_s": round(_proc_cpu_s(pid) - c0, 3),
                    "wall_s": round(time.monotonic() - w0, 3),
                }
            out["lighthouse_cpu"] = cpu
        finally:
            close_fleet(conns)
    finally:
        server.shutdown()
    return out


def restart_scenario(n: int, rounds: int) -> Dict[str, Any]:
    """Warm-restart storm at fleet size N: register N synthetic replicas
    against a state-dir'd lighthouse, kill it, restart it on the SAME
    port + state dir, then measure the re-register storm (all N conns
    reconnected and heartbeat-acked) and the time for the ``/fleet.json``
    aggregates to repopulate (``agg.n`` back to N) — the fleet tables are
    deliberately volatile (rebuilt from the heartbeat stream), so this is
    the observable cost of the durable-state design choice."""
    import tempfile

    from torchft_tpu.coordination import LighthouseClient

    state_dir = tempfile.mkdtemp(prefix="tft_lh_restart_")
    mk = lambda bind: LighthouseServer(  # noqa: E731
        bind=bind, min_replicas=n, join_timeout_ms=120_000,
        quorum_tick_ms=50, heartbeat_timeout_ms=120_000,
        fleet_snap_ms=100, state_dir=state_dir,
    )
    out: Dict[str, Any] = {"n": n}
    server = mk("0.0.0.0:0")
    try:
        addr = server.address()
        port = addr.rsplit(":", 1)[1]
        conns = connect_fleet(addr, n)
        out["register"] = heartbeat_phase(conns, rounds)
        close_fleet(conns)

        t0 = time.monotonic()
        server.shutdown()
        server = mk(f"0.0.0.0:{port}")
        out["restart_s"] = round(time.monotonic() - t0, 3)

        # Re-register storm: every replica reconnects at once (the real
        # fleet's managers all notice the dead conn within one heartbeat
        # interval) and must get a heartbeat ack from the warm process.
        t1 = time.monotonic()
        conns = connect_fleet(server.address(), n)
        try:
            out["reregister"] = heartbeat_phase(conns, 1)
            out["reregister_s"] = round(time.monotonic() - t1, 3)

            # Repopulation: /fleet.json aggregates are rebuilt from the
            # heartbeat stream; poll until the row count is back to N.
            cli = LighthouseClient(server.address())
            try:
                deadline = time.monotonic() + 120
                while time.monotonic() < deadline:
                    agg = (cli.fleet() or {}).get("agg") or {}
                    if int(agg.get("n", 0)) >= n:
                        break
                    time.sleep(0.05)
                else:
                    raise TimeoutError(
                        f"fleet agg never repopulated to n={n}")
                out["repopulate_s"] = round(time.monotonic() - t1, 3)
            finally:
                cli.close()
        finally:
            close_fleet(conns)
    finally:
        server.shutdown()
    return out


def roundtrip_phase(conns: List[Conn], mk_frame,
                    timeout_s: float = 60.0) -> None:
    """Send one arbitrary frame per connection, wait for every ack."""
    sel = selectors.DefaultSelector()
    for c in conns:
        c.done = False
        c.queue(mk_frame(c))
        sel.register(c.sock, selectors.EVENT_READ | selectors.EVENT_WRITE, c)

    def on_frame(c: Conn) -> None:
        c.done = True

    _pump(sel, conns, on_frame, time.monotonic() + timeout_s)
    sel.close()


def _job_state(status: Dict[str, Any], job: str) -> Dict[str, Any]:
    """The isolation-relevant slice of one job island's status: every
    field a sibling's churn storm must leave bit-exact."""
    j = (status.get("jobs") or {}).get(job) or {}
    fleet = j.get("fleet") or {}
    return {
        "quorum_id": j.get("quorum_id"),
        "quorum_generation": j.get("quorum_generation"),
        "joins_total": j.get("joins_total"),
        "leaves_total": j.get("leaves_total"),
        "anomaly_seq": fleet.get("anomaly_seq"),
    }


def multijob_scenario(m_jobs: int, n_per_job: int,
                      seed: int = 1234) -> Dict[str, Any]:
    """M jobs x N replicas across a district->root lighthouse topology.

    Proves the three namespace-plane contracts in one harness run:

    * **per-job quorum formation** — every job forms its own quorum on a
      shared district lighthouse; formation p50/p95 across jobs goes into
      the report (budgeted in BUDGETS),
    * **cross-job isolation** — a seeded churn storm (leave/rejoin bursts)
      inside one job must leave every sibling job's quorum id/generation,
      join/leave counters, and anomaly ring bit-exact, while the siblings'
      heartbeat hot path keeps meeting its latency budget,
    * **district failover fencing** — a warm standby takes over the storm
      job's district (PR-15 HA semantics: bumped fencing epoch); the root
      must record exactly that district's failover and keep its view of
      the sibling district's jobs untouched, and sibling-district quorums
      must stay un-wedged.

    Emits ``job_churn`` / ``district_failover`` journal events when a
    journal is configured (TORCHFT_JOURNAL_FILE / _DIR)."""
    import random
    import tempfile

    from torchft_tpu.coordination import LighthouseClient
    from torchft_tpu.telemetry import get_event_log

    rng = random.Random(seed)
    jobs = [f"job{i:02d}" for i in range(m_jobs)]
    # Jobs alternate across two districts; the storm job (and the HA drill)
    # live on d0, so d1 is the pure-sibling district.
    district_of = {job: ("d0" if i % 2 == 0 else "d1")
                   for i, job in enumerate(jobs)}
    storm_job = jobs[0]
    out: Dict[str, Any] = {
        "m_jobs": m_jobs, "n_per_job": n_per_job, "seed": seed,
        "districts": sorted(set(district_of.values())),
        "storm_job": storm_job,
    }
    failures: List[str] = []

    mk_opts = dict(min_replicas=n_per_job, join_timeout_ms=120_000,
                   quorum_tick_ms=50, heartbeat_timeout_ms=120_000,
                   fleet_snap_ms=100)
    root = LighthouseServer(min_replicas=1, join_timeout_ms=120_000,
                            quorum_tick_ms=50, heartbeat_timeout_ms=120_000)
    d0_state = tempfile.mkdtemp(prefix="tft_lh_d0_")
    d0 = LighthouseServer(state_dir=d0_state, district="d0",
                          root_addr=root.address(), **mk_opts)
    d1 = LighthouseServer(district="d1", root_addr=root.address(),
                          **mk_opts)
    d0_standby: Optional[LighthouseServer] = None
    addr_of = {"d0": d0.address(), "d1": d1.address()}
    job_conns: Dict[str, List[Conn]] = {}
    try:
        # The storm job gets one extra elastic replica so each churn burst
        # genuinely changes quorum membership (leave/rejoin alternation).
        for job in jobs:
            n = n_per_job + (1 if job == storm_job else 0)
            job_conns[job] = connect_fleet(
                addr_of[district_of[job]], n, job=job,
                hb_interval_ms=600_000)
        all_conns = [c for cs in job_conns.values() for c in cs]
        out["heartbeat"] = heartbeat_phase(all_conns, rounds=2)

        # Per-job quorum formation on shared, multi-tenant lighthouses.
        formation_ms: List[float] = []
        for job in jobs:
            q = quorum_phase(job_conns[job])
            formation_ms.append(q["formation_ms"])
        out["formation_ms_per_job"] = formation_ms
        out["formation_p50_ms"] = round(_pct(formation_ms, 0.50))
        out["formation_p95_ms"] = round(_pct(formation_ms, 0.95))

        # Baseline sibling state, then the seeded churn storm in one job.
        siblings = [j for j in jobs if j != storm_job]
        clients = {d: LighthouseClient(a) for d, a in addr_of.items()}
        before = {
            j: _job_state(clients[district_of[j]].status(), j)
            for j in siblings
        }
        storm = job_conns[storm_job]
        extra, base = storm[-1], storm[:-1]
        bursts = 4
        for burst in range(bursts):
            if burst % 2 == 0:
                roundtrip_phase([extra], lambda c: {
                    "type": "leave", "replica_id": c.rid, "job": c.job,
                    "timeout_ms": 5000,
                })
                members = base
                stagger = 0.0
            else:
                # The elastic replica rejoins: it registers first (the
                # real elastic-join order), then the incumbents re-request.
                members = [extra] + base
                stagger = 0.3
            for c in members:
                c.step += 1
            quorum_phase(members, stagger_first_s=stagger)
        # Unfenced chaos inside the island: a commit-failure streak flags a
        # commit_stall anomaly in the STORM job's ring only.
        victim = rng.choice(base)
        roundtrip_phase([victim], lambda c: {
            "type": "heartbeat", "replica_id": c.rid, "job": c.job,
            "timeout_ms": 5000, "hb_interval_ms": 600_000,
            "digest": dict(_mk_digest(c.step, c.rid_n), cf=5),
        })
        log = get_event_log()
        if log is not None:
            log.emit("job_churn", replica_id="fleet_load", job=storm_job,
                     bursts=bursts, district=district_of[storm_job])

        # Sibling hot path DURING the aftermath of the storm, then the
        # bit-exact isolation check.
        sib_conns = [c for j in siblings for c in job_conns[j]]
        sib_hb = heartbeat_phase(sib_conns, rounds=2)
        out["sibling_heartbeat"] = sib_hb
        after = {
            j: _job_state(clients[district_of[j]].status(), j)
            for j in siblings
        }
        violations = [
            {"job": j, "before": before[j], "after": after[j]}
            for j in siblings if before[j] != after[j]
        ]
        storm_state = _job_state(
            clients[district_of[storm_job]].status(), storm_job)
        out["storm"] = {
            "bursts": bursts,
            "quorum_generation": storm_state["quorum_generation"],
            "anomaly_seq": storm_state["anomaly_seq"],
        }
        out["isolation"] = {
            "siblings": len(siblings),
            "violations": violations,
        }
        if violations:
            failures.append(
                f"multijob: {len(violations)} sibling jobs perturbed by "
                f"{storm_job}'s churn storm")
        if (storm_state["quorum_generation"] or 0) < bursts:
            failures.append(
                f"multijob: storm job generation "
                f"{storm_state['quorum_generation']} did not advance "
                f"across {bursts} churn bursts")
        if not storm_state["anomaly_seq"]:
            failures.append(
                "multijob: storm job's commit-stall anomaly never fired")

        # District failover drill: a warm standby (same durable state dir)
        # takes over d0 with a bumped fencing epoch; the root must count
        # exactly one d0 failover and keep d1's rollup untouched.
        rcli = LighthouseClient(root.address())
        # Wait for the rollup cadence to converge (every d1 job visible at
        # the root), then freeze the "before" view for the bit-exact check.
        d1_expect = {j for j in jobs if district_of[j] == "d1"}
        d1_jobs_before: Dict[str, Any] = {}
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            root_before = rcli.status()
            d1_jobs_before = {
                j: (info or {}).get("n")
                for j, info in ((root_before.get("districts") or {})
                                .get("d1", {}).get("jobs") or {}).items()
            }
            if d1_expect <= set(d1_jobs_before):
                break
            time.sleep(0.25)
        else:
            failures.append(
                "multijob: root never converged on d1's job rollup")
        d0_standby = LighthouseServer(
            state_dir=d0_state, standby=True, district="d0",
            root_addr=root.address(), **mk_opts)
        close_fleet(storm)
        d0.shutdown()
        # The fleet's managers reconnect and re-request: the first quorum
        # RPC triggers the standby takeover (epoch fence bump).
        storm2 = connect_fleet(d0_standby.address(), n_per_job,
                               job=storm_job, hb_interval_ms=600_000)
        job_conns[storm_job] = storm2
        heartbeat_phase(storm2, rounds=1)
        quorum_phase(storm2)
        d0_after: Dict[str, Any] = {}
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            rs = rcli.status()
            d0_after = (rs.get("districts") or {}).get("d0") or {}
            if int(d0_after.get("failovers", 0)) >= 1:
                break
            time.sleep(0.25)
        else:
            failures.append(
                "multijob: root never observed the d0 standby takeover")
        rs = rcli.status()
        d1_after = (rs.get("districts") or {}).get("d1") or {}
        d1_jobs_after = {
            j: (info or {}).get("n")
            for j, info in (d1_after.get("jobs") or {}).items()
        }
        # Sibling-district quorums stay un-wedged through the takeover.
        sib_d1 = next(j for j in siblings if district_of[j] == "d1")
        for c in job_conns[sib_d1]:
            c.step += 1
        sib_q = quorum_phase(job_conns[sib_d1])
        out["failover"] = {
            "district": "d0",
            "epoch": d0_after.get("epoch"),
            "root_failovers": d0_after.get("failovers"),
            "stale_dropped": d0_after.get("stale_dropped"),
            "sibling_failovers": d1_after.get("failovers"),
            "sibling_jobs_before": d1_jobs_before,
            "sibling_jobs_after": d1_jobs_after,
            "sibling_formation_ms": sib_q["formation_ms"],
        }
        if int(d1_after.get("failovers", 0)) != 0:
            failures.append(
                "multijob: d1 recorded a failover during d0's takeover")
        if d1_jobs_before != d1_jobs_after:
            failures.append(
                "multijob: root's view of d1's jobs changed during d0's "
                f"takeover: {d1_jobs_before} -> {d1_jobs_after}")
        if log is not None:
            log.emit("district_failover", replica_id="fleet_load",
                     district="d0", epoch=d0_after.get("epoch"),
                     failovers=d0_after.get("failovers"))
        for cli in clients.values():
            cli.close()
        rcli.close()
    finally:
        for cs in job_conns.values():
            close_fleet(cs)
        for srv in (d0_standby, d0, d1, root):
            if srv is not None:
                try:
                    srv.shutdown()
                except Exception:  # noqa: BLE001
                    pass
    out["failures"] = failures
    out["pass"] = not failures
    return out


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--sizes", type=int, nargs="+", default=None,
                   help="fleet ladder (default 64 256 1024)")
    p.add_argument("--rounds", type=int, default=10,
                   help="heartbeats per replica per fleet (default 10)")
    p.add_argument("--probes", type=int, default=40,
                   help="HTTP probes per endpoint per fleet (default 40)")
    p.add_argument("--http-concurrency", type=int, default=4,
                   help="concurrent pollers per endpoint (default 4)")
    p.add_argument("--quick", action="store_true",
                   help="CI lane: N=64 only, no before/after experiment")
    p.add_argument("--restart-lighthouse", action="store_true",
                   help="run ONLY the warm-restart storm scenario at "
                        "N=256 (64 with --quick) and merge the result "
                        "into the existing report")
    p.add_argument("--multijob", action="store_true",
                   help="run ONLY the multi-job federation scenario "
                        "(M jobs x N replicas, district->root topology, "
                        "seeded churn storm + HA drill) and merge the "
                        "result into the existing report")
    p.add_argument("--jobs", type=int, default=None,
                   help="multijob: number of job namespaces "
                        "(default 16, 4 with --quick)")
    p.add_argument("--per-job", type=int, default=None,
                   help="multijob: replicas per job namespace "
                        "(default 4, 2 with --quick)")
    p.add_argument("--seed", type=int, default=1234,
                   help="multijob: churn-storm seed")
    p.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_FLEET.json"))
    args = p.parse_args(argv)
    sizes = args.sizes or ([64] if args.quick else [64, 256, 1024])

    if args.multijob:
        # Standalone scenario: merge into the existing BENCH_FLEET.json
        # (the ladder results stay).
        m = args.jobs if args.jobs is not None else (4 if args.quick else 16)
        npj = (args.per_job if args.per_job is not None
               else (2 if args.quick else 4))
        print(f"[fleet_load] multijob: {m} jobs x {npj} replicas, "
              f"district->root topology, seed={args.seed}", flush=True)
        mj = multijob_scenario(m, npj, seed=args.seed)
        mj["budget_problems"] = budget_problems({"multijob": mj}, "multijob")
        mj["pass"] = not mj["failures"] and not mj["budget_problems"]
        try:
            with open(args.out) as f:
                report = json.load(f)
        except (OSError, ValueError):
            report = {"schema": 1, "fleets": {}}
        report["multijob"] = mj
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"[fleet_load] multijob: formation p95="
              f"{mj['formation_p95_ms']}ms sibling hb p95="
              f"{mj['sibling_heartbeat']['p95_us']}us "
              f"violations={len(mj['isolation']['violations'])} "
              f"-> {args.out}", flush=True)
        for msg in mj["failures"] + mj["budget_problems"]:
            print(f"[fleet_load] MULTIJOB FAIL: {msg}", file=sys.stderr)
        return 0 if mj["pass"] else 1

    if args.restart_lighthouse:
        # Standalone scenario: merge into the existing BENCH_FLEET.json
        # (the ladder results stay).
        n = 64 if args.quick else 256
        print(f"[fleet_load] N={n}: lighthouse warm-restart storm",
              flush=True)
        rst = restart_scenario(n, rounds=2)
        try:
            with open(args.out) as f:
                report = json.load(f)
        except (OSError, ValueError):
            report = {"schema": 1, "fleets": {}}
        report["restart"] = rst
        failures = budget_problems(report, "restart")
        rst["budget_problems"] = failures
        rst["pass"] = not failures
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"[fleet_load] restart: down={rst['restart_s']}s "
              f"reregister={rst['reregister_s']}s "
              f"repopulate={rst['repopulate_s']}s -> {args.out}",
              flush=True)
        for msg in failures:
            print(f"[fleet_load] BUDGET FAIL: {msg}", file=sys.stderr)
        return 1 if failures else 0

    report: Dict[str, Any] = {
        "schema": 1, "quick": bool(args.quick),
        "rounds": args.rounds, "probes": args.probes,
        "http_concurrency": args.http_concurrency,
        "fleets": {},
    }
    failures: List[str] = []

    for n in sizes:
        print(f"[fleet_load] N={n}: spawning lighthouse + "
              f"{n} synthetic replicas", flush=True)
        res = run_fleet(n, args.rounds, args.probes,
                        concurrency=args.http_concurrency)
        report["fleets"][str(n)] = res
        print(f"[fleet_load] N={n}: hb p95={res['heartbeat']['p95_us']}us "
              f"quorum formation={res['quorum']['formation_ms']}ms "
              f"fleet.json p95={res['http']['fleet_json']['p95_us']}us",
              flush=True)

    if not args.quick:
        # Before/after at the largest N: the same probe mix with the
        # snapshot cache disabled, i.e. the pre-rework serve path that
        # rebuilt the full JSON for every request.
        n = max(sizes)
        print(f"[fleet_load] N={n}: before/after (fleet_snap_ms=0)",
              flush=True)
        before = run_fleet(n, args.rounds, args.probes, fleet_snap_ms=0,
                           concurrency=args.http_concurrency)
        after = report["fleets"][str(n)]
        b95 = before["http"]["fleet_json"]["p95_us"]
        a95 = after["http"]["fleet_json"]["p95_us"]
        speedup = b95 / a95 if a95 else float("inf")
        report["before_after"] = {
            "n": n,
            "fleet_json_p95_us_uncached": b95,
            "fleet_json_p95_us_cached": a95,
            "speedup": round(speedup, 2),
            "min_speedup": MIN_SPEEDUP,
        }
        print(f"[fleet_load] /fleet.json p95 at N={n}: uncached={b95}us "
              f"cached={a95}us speedup={speedup:.2f}x", flush=True)
        if speedup < MIN_SPEEDUP:
            failures.append(
                f"N={n}: cached /fleet.json speedup {speedup:.2f}x "
                f"< required {MIN_SPEEDUP}x")

    report["budget_problems"] = budget_problems(report, "fleets")
    failures += report["budget_problems"]
    report["pass"] = not failures
    report["failures"] = failures
    # The ladder rewrite keeps the standalone merge-in scenarios
    # (--restart-lighthouse / --multijob) from the previous report.
    try:
        with open(args.out) as f:
            prev = json.load(f)
        for key in ("restart", "multijob"):
            if key in prev and key not in report:
                report[key] = prev[key]
    except (OSError, ValueError):
        pass
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"[fleet_load] wrote {args.out}", flush=True)
    for msg in failures:
        print(f"[fleet_load] BUDGET FAIL: {msg}", file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
