"""The step-scoped span tree: ``telemetry.trace_span`` as a recorder
(parents, threads, attrs, the bounded buffer), the ``step_spans`` journal
event the commit gate flushes, the spans of both allreduce paths, and the
ledger's ``exposed_comm`` read from the tree's root."""

import glob
import json
import os
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

from tests.test_manager import make_manager, make_quorum_result  # noqa: E402
from torchft_tpu import telemetry  # noqa: E402
from torchft_tpu.ddp import DistributedDataParallel  # noqa: E402
from torchft_tpu.telemetry import DDP_ROOT_SPAN, trace_span  # noqa: E402

NAME, T0, T1, ID, PARENT, THREAD, ATTRS = range(7)


@pytest.fixture
def journal(tmp_path, monkeypatch):
    """A configured journal and an empty span buffer; yields a reader of
    the journal's events."""
    path = str(tmp_path / "journal.jsonl")
    monkeypatch.setenv("TORCHFT_JOURNAL_FILE", path)
    monkeypatch.delenv("TORCHFT_JOURNAL_DIR", raising=False)
    telemetry.reset_event_log()
    telemetry.drain_spans()

    def events(kind=None):
        if not os.path.exists(path):
            return []
        with open(path) as f:
            evs = [json.loads(line) for line in f]
        return [e for e in evs if kind is None or e["event"] == kind]

    yield events
    telemetry.reset_event_log()
    telemetry.drain_spans()


def _by_name(spans, name):
    return [s for s in spans if s[NAME] == name]


def _ancestors(spans, span):
    by_id = {s[ID]: s for s in spans}
    out = []
    while span[PARENT] is not None and span[PARENT] in by_id:
        span = by_id[span[PARENT]]
        out.append(span)
    return out


# ---------------------------------------------------------------------------
# The recorder
# ---------------------------------------------------------------------------


def test_no_journal_keeps_the_buffer_empty(monkeypatch):
    monkeypatch.delenv("TORCHFT_JOURNAL_FILE", raising=False)
    monkeypatch.delenv("TORCHFT_JOURNAL_DIR", raising=False)
    telemetry.drain_spans()
    telemetry.reset_span_stats()
    with trace_span("t::outer", bucket=1) as outer:
        with trace_span("t::inner"):
            pass
    assert telemetry.drain_spans() == ([], 0)
    # what trace_span did before it still does: histogram and duration
    assert telemetry.span_stats()["t::inner"]["count"] == 1
    assert outer.elapsed_s >= 0.0 and outer.t0 is None


def test_same_thread_children_lie_inside_their_parent(journal):
    with trace_span("t::root"):
        with trace_span("t::a", bucket=0, nbytes=8):
            time.sleep(0.002)
            with trace_span("t::a1"):
                time.sleep(0.002)
        with trace_span("t::b", skipped=None):
            time.sleep(0.002)
    spans, dropped = telemetry.drain_spans()
    assert dropped == 0 and len(spans) == 4
    by_id = {s[ID]: s for s in spans}
    (root,) = _by_name(spans, "t::root")
    assert root[PARENT] is None
    assert _by_name(spans, "t::a")[0][ATTRS] == {"bucket": 0, "nbytes": 8}
    assert _by_name(spans, "t::b")[0][ATTRS] == {}  # None-valued attrs go
    assert len({s[THREAD] for s in spans}) == 1
    for s in spans:
        if s[PARENT] is not None:
            p = by_id[s[PARENT]]
            assert p[T0] <= s[T0] <= s[T1] <= p[T1]
    # self time + children = duration, at every node
    for s in spans:
        kids = [k for k in spans if k[PARENT] == s[ID]]
        self_s = (s[T1] - s[T0]) - sum(k[T1] - k[T0] for k in kids)
        assert self_s >= 0.0
        assert self_s + sum(k[T1] - k[T0] for k in kids) == pytest.approx(
            s[T1] - s[T0]
        )
    assert [s[NAME] for s in _ancestors(spans, _by_name(spans, "t::a1")[0])] == [
        "t::a", "t::root"
    ]


def test_a_thread_borrows_its_issuers_span_as_parent(journal):
    seen = {}

    def work(parent):
        with telemetry.span_parent(parent):
            with trace_span("t::stage"):
                seen["top"] = telemetry.current_span().top.name
        # the thread's own stack is restored
        seen["after"] = telemetry.current_span()

    with trace_span("t::root"):
        assert telemetry.next_bucket() == 0
        with trace_span("t::issue") as issue:
            assert telemetry.next_bucket() == 1  # counted under the root
            t = threading.Thread(target=work, args=(telemetry.current_span(),))
            t.start()
            t.join(timeout=10)
            assert not t.is_alive()
    assert telemetry.next_bucket() is None  # no span open
    spans, _ = telemetry.drain_spans()
    (stage,) = _by_name(spans, "t::stage")
    assert stage[PARENT] == issue.id
    assert stage[THREAD] != _by_name(spans, "t::root")[0][THREAD]
    assert seen == {"top": "t::root", "after": None}


def test_over_the_cap_spans_are_counted_not_kept(journal):
    for _ in range(telemetry.SPAN_BUFFER_CAP + 25):
        with trace_span("t::many"):
            pass
    assert len(telemetry._SPAN_BUFFER._spans) == telemetry.SPAN_BUFFER_CAP
    spans, dropped = telemetry.drain_spans()
    assert len(spans) == telemetry.SPAN_BUFFER_CAP and dropped == 25
    assert telemetry.drain_spans() == ([], 0)


def test_annotation_is_resolved_once_and_keeps_the_spans_name(tmp_path):
    """``pull_ms``/``wire_ms``/``push_ms`` read the stage spans by name
    from the profiler's trace: attrs must travel as the annotation's
    arguments, not in its name."""
    import jax
    from jax.profiler import ProfileData, TraceAnnotation

    with trace_span("t::warm"):
        pass
    assert telemetry._TRACE_ANNOTATION is TraceAnnotation
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with trace_span("torchft::collectives::wire", bucket=3, nbytes=4096):
            time.sleep(0.001)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    found = [
        e for plane in ProfileData.from_file(path).planes
        for line in plane.lines for e in line.events
        if e.name.startswith("torchft::")
    ]
    assert [e.name for e in found] == ["torchft::collectives::wire"]
    assert dict(found[0].stats) == {"bucket": 3, "nbytes": 4096}


# ---------------------------------------------------------------------------
# The step_spans event at the commit gate
# ---------------------------------------------------------------------------


def test_step_spans_is_a_registered_event_kind_and_the_linter_passes():
    from torchft_tpu.lint import run_all

    assert "step_spans" in telemetry.EVENT_KINDS
    findings, ran = run_all(REPO, only={"event-kind-registry"})
    assert ran == ["event-kind-registry"]
    assert findings == [], "\n".join(f.format() for f in findings)


def test_one_step_spans_event_per_gate_committed_or_not(journal):
    m = make_manager()
    try:
        m.start_quorum()
        m.allreduce(np.ones(4, np.float32)).wait()
        assert m.should_commit()
        m.start_quorum()
        m.allreduce(np.ones(4, np.float32)).wait()
        m.report_error(RuntimeError("injected"))
        assert not m.should_commit()
    finally:
        m.shutdown()
    evs = journal("step_spans")
    gates = journal("commit_gate")
    assert len(evs) == len(gates) == 2
    assert [e["attrs"]["committed"] for e in evs] == [True, False]
    assert [e["step"] for e in evs] == [g["step"] for g in gates] == [0, 1]
    for e, gate in zip(evs, gates):
        assert e["trace"] == gate["trace"] and e["trace"].startswith("q1.")
        assert e["attrs"]["dropped"] == 0
        names = {s[NAME] for s in e["attrs"]["spans"]}
        # the gate's own span goes out with its own event
        assert {
            "torchft::manager::start_quorum",
            "torchft::manager::quorum_wait",
            "torchft::manager::host_copy",
            "torchft::manager::allreduce_wait",
            "torchft::manager::allreduce_scale",
            "torchft::manager::should_commit",
        } <= names
    # flushed: nothing of these steps is left behind
    assert telemetry.drain_spans() == ([], 0)


def test_a_span_open_at_the_gate_goes_out_with_the_next_gate(journal):
    m = make_manager()
    try:
        m.start_quorum()
        inflight = trace_span("t::outer_sync")
        inflight.__enter__()  # DiLoCo's outer sync spans a gate
        assert m.should_commit()
        inflight.__exit__(None, None, None)
        m.start_quorum()
        assert m.should_commit()
    finally:
        m.shutdown()
    first, second = journal("step_spans")
    assert not _by_name(first["attrs"]["spans"], "t::outer_sync")
    assert len(_by_name(second["attrs"]["spans"], "t::outer_sync")) == 1


# ---------------------------------------------------------------------------
# The host fp32 path's spans, its byte counts, exposed_comm
# ---------------------------------------------------------------------------


def _host_path_step(m, ddp):
    import jax.numpy as jnp

    m.start_quorum()
    grads = {
        "a": jnp.ones((300,), jnp.float32),  # 1200 B, on the device
        "b": jnp.ones((200,), jnp.float32),  # 800 B, on the device
        "c": np.ones((100,), np.float32),  # 400 B, already on the host
    }
    out = ddp.allreduce_grads(grads)
    assert m.should_commit()
    return out


def test_host_path_spans_and_hand_worked_bytes(journal):
    m = make_manager()
    # 1 kB buckets: a alone, then b, then c
    ddp = DistributedDataParallel(m, bucket_cap_mb=1000 / 2**20)
    try:
        out = _host_path_step(m, ddp)
    finally:
        m.shutdown()
    np.testing.assert_allclose(out["a"], 0.5)  # dummy pg, two participants
    (ev,) = journal("step_spans")
    spans = ev["attrs"]["spans"]
    (root,) = _by_name(spans, DDP_ROOT_SPAN)
    (pull,) = _by_name(spans, "torchft::ddp::pull")
    assert pull[ATTRS] == {"nbytes": 2000}  # the two device leaves
    assert len(_by_name(spans, "torchft::ddp::grads_wait")) == 1
    packs = _by_name(spans, "torchft::ddp::pack")
    # a wrapper's first call sizes its bucket buffers: every byte fresh.
    # Issued smallest first (ddp.issue_order), each under its layout index.
    assert [p[ATTRS] for p in packs] == [
        {"bucket": 2, "nbytes": 400, "fresh_bytes": 400, "reused_bytes": 0},
        {"bucket": 1, "nbytes": 800, "fresh_bytes": 800, "reused_bytes": 0},
        {"bucket": 0, "nbytes": 1200, "fresh_bytes": 1200, "reused_bytes": 0},
    ]
    copies = _by_name(spans, "torchft::manager::host_copy")
    # the concatenated bucket is writable: to_mutable copies nothing
    assert [c[ATTRS]["copied_bytes"] for c in copies] == [0, 0, 0]
    assert [c[ATTRS]["nbytes"] for c in copies] == [400, 800, 1200]
    scales = _by_name(spans, "torchft::manager::allreduce_scale")
    assert [s[ATTRS]["nbytes"] for s in scales] == [400, 800, 1200]
    assert [u[ATTRS]["bucket"] for u in _by_name(spans, "torchft::ddp::unpack")] == [
        2, 1, 0
    ]
    for name in ("torchft::manager::allreduce_wait", "torchft::manager::quorum_wait"):
        assert len(_by_name(spans, name)) == 3
    # every span of the allreduce, on one thread, has the root as ancestor
    inside = [s for s in spans if root[T0] <= s[T0] and s[T1] <= root[T1]
              and s is not root and s[THREAD] == root[THREAD]]
    assert len(inside) >= 20
    assert all(root in _ancestors(spans, s) for s in inside)

    # the benchmark's counter on the same event: pulled 2000 + packed 2400
    # + copied 0 + scaled 2400
    from benchmark.metrics import ar_host_bytes_step

    assert ar_host_bytes_step.read({"journal": [ev]}) == 6800


@pytest.mark.parametrize("case", ["avg-quorum-of-one", "sum-of-two", "avg-of-two"])
def test_a_scale_of_exactly_one_is_not_a_pass(journal, case):
    """Times 1.0 changes no bit, so ``_ManagedWork`` does not run it: the
    span is there with a count of 0. Any other scale is today's pass."""
    from torchft_tpu.process_group import ReduceOp

    world = 1 if case == "avg-quorum-of-one" else 2
    op = ReduceOp.SUM if case == "sum-of-two" else ReduceOp.AVG
    m = make_manager(quorum_result=make_quorum_result(
        replica_world_size=world, max_world_size=world))
    values = (np.arange(100, dtype=np.float32) - 48) / 64
    try:
        m.start_quorum()
        given = values.copy()
        (out,) = m.allreduce(given, reduce_op=op).wait()
        assert m.should_commit()
    finally:
        m.shutdown()
    scaled = case == "avg-of-two"
    assert out is given  # reduced in place, as ever
    assert out.tobytes() == (values * np.float32(0.5) if scaled else values).tobytes()
    (ev,) = journal("step_spans")
    (scale,) = _by_name(ev["attrs"]["spans"], "torchft::manager::allreduce_scale")
    assert scale[ATTRS] == {"nbytes": 400 if scaled else 0}


@pytest.mark.parametrize("case", ["nothing-writes", "scaled", "group-writes",
                                  "quantized", "not-participating"])
def test_a_read_only_input_with_scratch_is_copied_only_where_something_writes(
        journal, case):
    """``Manager.allreduce(..., scratch=)``: the input goes down as it is
    unless the call writes, and then it is copied into ``scratch``, not
    into new memory. ``host_copy`` records either."""
    from torchft_tpu.process_group import ProcessGroupDummy

    class _Writes(ProcessGroupDummy):
        def allreduce_writes(self, op=None):
            return True

    from torchft_tpu.manager import WorldSizeMode

    world = 2 if case == "scaled" else 1
    quorum = make_quorum_result(replica_world_size=world, max_world_size=world)
    kwargs = {}
    if case == "not-participating":  # a spare beyond the fixed size: zeros
        quorum = make_quorum_result(
            replica_rank=1, max_world_size=2, replica_world_size=2)
        kwargs = dict(min_replica_size=1,
                      world_size_mode=WorldSizeMode.FIXED_WITH_SPARES)
    m = make_manager(pg=_Writes() if case == "group-writes" else None,
                     quorum_result=quorum, use_async_quorum=False, **kwargs)
    values = (np.arange(1024, dtype=np.float32) % 97 - 48) / 64
    given = values.copy()
    given.flags.writeable = False
    scratch = np.full(1024, 777.0, np.float32)
    try:
        m.start_quorum()
        (out,) = m.allreduce(
            given, should_quantize=case == "quantized", scratch=scratch).wait()
    finally:
        m.shutdown()
    spans = telemetry.drain_spans()[0]
    (copy,) = _by_name(spans, "torchft::manager::host_copy")
    assert given.tobytes() == values.tobytes()
    if case == "nothing-writes":
        assert out is given and np.all(scratch == 777.0)
        assert copy[ATTRS] == {"nbytes": 4096, "copied_bytes": 0}
        return
    assert out is scratch
    assert copy[ATTRS] == {"nbytes": 4096, "copied_bytes": 4096}
    want = {"scaled": values * np.float32(0.5),
            "not-participating": np.zeros_like(values)}.get(case, values)
    assert out.tobytes() == want.tobytes()


def test_a_read_only_bucket_is_a_recorded_copy(journal):
    m = make_manager()
    try:
        m.start_quorum()
        ro = np.ones(64, np.float32)
        ro.flags.writeable = False
        m.allreduce(ro).wait()
        assert m.should_commit()
    finally:
        m.shutdown()
    (ev,) = journal("step_spans")
    (copy,) = _by_name(ev["attrs"]["spans"], "torchft::manager::host_copy")
    assert copy[ATTRS] == {"nbytes": 256, "copied_bytes": 256}


def test_exposed_comm_is_the_root_less_grads_wait_and_the_ledger_tiles(journal):
    import goodput_report

    m = make_manager()
    ddp = DistributedDataParallel(m)
    try:
        _host_path_step(m, ddp)  # the first window is init_compile
        _host_path_step(m, ddp)
        g = m.goodput()
    finally:
        m.shutdown()
    spans = journal("step_spans")[1]["attrs"]["spans"]
    window = journal("goodput_window")[1]["attrs"]
    (root,) = _by_name(spans, DDP_ROOT_SPAN)
    (waited,) = _by_name(spans, "torchft::ddp::grads_wait")
    want = (root[T1] - root[T0]) - (waited[T1] - waited[T0])
    # the ledger has the spans' monotonic durations, the journal their
    # wall-clock ends: equal to clock resolution
    assert window["splits"]["exposed_comm"] == pytest.approx(want, abs=2e-4)
    # ... which is more than the waits alone (the old rule): pull, packs
    # and the issue are in it
    waits = sum(s[T1] - s[T0] for s in
                _by_name(spans, "torchft::manager::allreduce_wait"))
    assert window["splits"]["exposed_comm"] > waits
    assert g["tiling_error_s"] < 1e-6
    assert goodput_report.check(goodput_report.analyze(journal())) == []


def test_without_the_ddp_wrapper_the_waits_price_exposed_comm(journal):
    """DiLoCo and LocalSGD drive ``Manager.allreduce`` themselves: their
    rule is unchanged, the sum of the ``work.wait()`` times."""
    m = make_manager()
    try:
        for _ in range(2):
            m.start_quorum()
            works = [m.allreduce(np.ones(256, np.float32)) for _ in range(3)]
            for w in works:
                w.wait()
            assert m.should_commit()
    finally:
        m.shutdown()
    spans = journal("step_spans")[1]["attrs"]["spans"]
    window = journal("goodput_window")[1]["attrs"]
    waits = sum(s[T1] - s[T0] for s in
                _by_name(spans, "torchft::manager::allreduce_wait"))
    assert window["splits"]["exposed_comm"] == pytest.approx(waits, abs=2e-4)


def test_pg_collective_reports_how_long_it_queued(journal):
    from torchft_tpu.process_group import ProcessGroupSocket, ReduceOp
    from torchft_tpu.store import TCPStoreServer

    store = TCPStoreServer()
    pg = ProcessGroupSocket(timeout=10.0)
    try:
        pg.configure(f"{store.address()}/q", 0, 1)
        works = [pg.allreduce(np.ones(1 << 16, np.float32), ReduceOp.SUM)
                 for _ in range(3)]
        for w in works:
            w.wait(timeout=10)
    finally:
        pg.shutdown()
        store.shutdown()
    evs = journal("pg_collective")
    assert len(evs) == 3
    for e in evs:
        assert e["attrs"]["queued_s"] >= 0.0 and e["attrs"]["elapsed_s"] >= 0.0


# ---------------------------------------------------------------------------
# The quantized path's tree, two ranks in one process
# ---------------------------------------------------------------------------

STAGES = ("dispatch", "quantize_pull", "wire_turn_wait", "wire", "dequant_push")


@pytest.mark.timeout(120)
def test_quantized_tree_two_ranks_three_buckets(journal):
    """What DDP's device path does (one ``Manager.allreduce`` of jax
    arrays per bucket under the root, then the waits), on two ranks whose
    process groups meet through a real store."""
    import jax.numpy as jnp

    from torchft_tpu.process_group import ProcessGroupSocket
    from torchft_tpu.store import TCPStoreServer

    store = TCPStoreServer()
    managers = [
        make_manager(
            pg=ProcessGroupSocket(timeout=30.0),
            use_async_quorum=False,
            quorum_result=make_quorum_result(
                store_address=store.address(), replica_rank=r,
                replica_world_size=2, max_step=5,
            ),
        )
        for r in range(2)
    ]
    roots = {}

    def run(rank):
        m = managers[rank]
        m.start_quorum()
        with trace_span(DDP_ROOT_SPAN) as root:
            roots[rank] = root.id
            works = [
                m.allreduce(
                    [jnp.full((2048,), float(rank + b), jnp.float32)],
                    should_quantize=True,
                )
                for b in range(3)
            ]
            outs = [w.wait() for w in works]
        assert m.should_commit()
        return [float(o[0][0]) for o in outs]

    try:
        with ThreadPoolExecutor(max_workers=2) as pool:
            results = [f.result(timeout=90)
                       for f in [pool.submit(run, r) for r in range(2)]]
    finally:
        for m in managers:
            m.shutdown()
        store.shutdown()
    assert results[0] == results[1]
    np.testing.assert_allclose(results[0], [0.5, 1.5, 2.5], rtol=0.02)

    # Two Managers share this process's buffer, so a gate may flush the
    # other's closed spans: ids join them whichever event they are in.
    evs = journal("step_spans")
    assert len(evs) == 2
    assert {e["trace"] for e in evs} == {"q1.s5"}  # the step's trace id
    assert all(e["attrs"]["dropped"] == 0 for e in evs)
    spans = [s for e in evs for s in e["attrs"]["spans"]]
    for rank in range(2):
        tree = [s for s in spans
                if roots[rank] in {a[ID] for a in _ancestors(spans, s)}]
        for b in range(3):
            for stage in STAGES:
                found = [s for s in _by_name(tree, f"torchft::collectives::{stage}")
                         if s[ATTRS].get("bucket") == b]
                assert len(found) == 1, (rank, b, stage)
                if stage != "wire_turn_wait":
                    assert found[0][ATTRS]["nbytes"] == 8192
        wires = sorted(
            (s[T0], s[T1]) for s in _by_name(tree, "torchft::collectives::wire")
        )
        # one process group's wire is serialised, in issue order
        assert all(a[1] <= b[0] for a, b in zip(wires, wires[1:]))
        for wire in _by_name(tree, "torchft::collectives::wire"):
            kids = [s[NAME].rsplit("::", 1)[1] for s in tree if s[PARENT] == wire[ID]]
            # host arithmetic between and after the socket operations:
            # accumulate and requantize (one pass a block where the native
            # codec runs, so one span), then join the gathered chunks
            assert sorted(kids) == [
                "wire_allgather", "wire_alltoall",
                "wire_reduce", "wire_reduce",
            ]
        # stage spans run on the buckets' own threads, not the caller's
        caller = next(s for s in spans if s[ID] == roots[rank])[THREAD]
        assert all(s[THREAD] != caller
                   for s in _by_name(tree, "torchft::collectives::wire"))
        assert all(s[THREAD] == caller
                   for s in _by_name(tree, "torchft::collectives::dispatch"))


def test_wire_reduce_spans_count_fresh_and_reused_bytes(journal):
    """From the second collective of a size on, the wire stage allocates
    nothing: every ``wire_reduce`` span says ``fresh_bytes`` 0 and how
    many scratch bytes it wrote instead."""
    from torchft_tpu.collectives import allreduce_quantized
    from torchft_tpu.process_group import ProcessGroupSocket
    from torchft_tpu.store import TCPStoreServer

    store = TCPStoreServer()
    groups = [ProcessGroupSocket(timeout=30.0) for _ in range(2)]
    n = 512 * 2 * 6
    rounds = []

    def run(rank):
        groups[rank].configure(f"{store.address()}/fresh", rank, 2)
        for _ in range(3):
            arr = np.full(n, float(rank + 1), np.float32)
            allreduce_quantized(groups[rank], [arr]).wait(timeout=30)
            if rank == 0:
                rounds.append(telemetry.drain_spans()[0])
        return arr

    try:
        with ThreadPoolExecutor(max_workers=2) as pool:
            results = [f.result(timeout=60)
                       for f in [pool.submit(run, r) for r in range(2)]]
    finally:
        for g in groups:
            g.shutdown()
        store.shutdown()
    np.testing.assert_allclose(results[0], 3.0, rtol=0.02)
    spans = [s for r in rounds for s in r] + telemetry.drain_spans()[0]
    wires = sorted(_by_name(spans, "torchft::collectives::wire"), key=lambda s: s[T0])
    assert len(wires) == 2 * 3  # ranks x collectives
    stages = [
        [s[ATTRS] for s in sorted(spans, key=lambda s: s[T0])
         if s[PARENT] == w[ID] and s[NAME].endswith("::wire_reduce")]
        for w in wires
    ]
    assert all(len(attrs) == 2 for attrs in stages)
    # the two ranks' first collectives grow the scratch ...
    for attrs in stages[:2]:
        assert all(a["fresh_bytes"] > 0 for a in attrs)
    # ... and nothing after them allocates. A chunk of 6 blocks: the
    # requantized 3,072 B + 24 B of scales (where the numpy passes run,
    # the fp32 sum and a piece per task as well), the joined payload
    # 6,144 B + 48 B
    for attrs in stages[2:]:
        assert [a["fresh_bytes"] for a in attrs] == [0, 0]
        assert all(a["reused_bytes"] > 0 for a in attrs)
        assert attrs[1]["reused_bytes"] == 6144 + 48
        # the first span says which codec reduced its blocks, the join none
        assert attrs[0]["native_blocks"] + attrs[0]["numpy_blocks"] == 6
        assert "native_blocks" not in attrs[1]
