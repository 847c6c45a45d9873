"""The host branch of ``DistributedDataParallel`` when every leaf handed in
is a ``jax.Array``: the buckets form a line (every device-to-host copy
started at once, one ``pull`` a bucket, a reduced bucket pushed back
while the next arrives) and device arrays come back, landed, with the
inputs' shardings. A bucket comes down as one array (its one leaf's
copy, or its leaves joined on the device) and that landing is the bucket:
``pack`` copies nothing, the manager copies into the kept buffer only
where something writes, a scale of exactly 1 is no pass. A tree with a
numpy leaf in it keeps the four passes and the kept views.

Beside ``test_ddp_pack_reuse.py`` (the kept buffers) and
``test_bucket_schedule.py`` (the order), whose helpers it uses. On the
CPU backend ``jax.device_put`` of a numpy view may alias it, which is
what the no-alias test would catch.
"""

import gc
import os
import sys
import weakref
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tests.test_ddp_pack_reuse import (  # noqa: E402
    KB,
    _all_fresh,
    _all_reused,
    _FailingPG,
    _managers,
    _values,
)
from torchft_tpu import telemetry  # noqa: E402
from torchft_tpu.collectives import bucketize  # noqa: E402
from torchft_tpu.ddp import DistributedDataParallel, issue_order  # noqa: E402
from torchft_tpu.store import TCPStoreServer  # noqa: E402

NAME, T0, T1, ID, PARENT, THREAD, ATTRS = range(7)
ROOT = "torchft::ddp::allreduce_grads"
PULL = "torchft::ddp::pull"
PACK = "torchft::ddp::pack"
UNPACK = "torchft::ddp::unpack"
PUSH = "torchft::ddp::push"
PUSH_WAIT = "torchft::ddp::push_wait"
ISSUE = "torchft::manager::allreduce"
HOST_COPY = "torchft::manager::host_copy"
SCALE = "torchft::manager::allreduce_scale"

# float32 leaves of 300, 200 and 60 + 7 values at 1 kB a bucket: three
# buckets, issued 67, 200, 300 values (layout indices 2, 1, 0).
SIZES = {"a": 300, "b": 200, "c": 60, "d": 7}
ORDER = [2, 1, 0]
BUCKET_BYTES = {0: 1200, 1: 800, 2: 268}


@pytest.fixture(autouse=True)
def journal(tmp_path, monkeypatch):
    """Spans are kept only while a journal is configured."""
    monkeypatch.setenv("TORCHFT_JOURNAL_FILE", str(tmp_path / "journal.jsonl"))
    monkeypatch.delenv("TORCHFT_JOURNAL_DIR", raising=False)
    telemetry.reset_event_log()
    telemetry.drain_spans()
    yield
    telemetry.reset_event_log()
    telemetry.drain_spans()


def _host_tree(rank, step, dtype=np.float32):
    return {k: _values(n, rank, step, salt, dtype)
            for salt, (k, n) in enumerate(SIZES.items())}


def _device_tree(rank, step, dtype=np.float32):
    import jax.numpy as jnp

    tree = {k: jnp.asarray(v) for k, v in _host_tree(rank, step, dtype).items()}
    tree["a"] = tree["a"].reshape(3, 100)
    return tree


def _numpy(tree):
    import jax

    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


def _recorded():
    """The spans closed since the last call (or the last commit gate,
    which flushes them into the journal)."""
    return [list(s) for s in telemetry.drain_spans()[0]]


def _step(m, ddp, grads, **kwargs):
    """One step: (averaged tree, committed, the allreduce's spans)."""
    m.start_quorum()
    out = ddp.allreduce_grads(grads, **kwargs)
    recorded = _recorded()
    return out, m.should_commit(), recorded


def _packs(recorded):
    return [s[ATTRS] for s in recorded if s[NAME] == PACK]


def _all_landed(packs):
    """Every bucket is the copy PJRT landed in that call: ``pack`` copied
    nothing into a kept buffer, and the memory is that step's."""
    return bool(packs) and all(
        p["nbytes"] == 0 and p["reused_bytes"] == 0
        and p["fresh_bytes"] == BUCKET_BYTES[p["bucket"]] for p in packs)


def _copied(recorded):
    """What ``Manager.allreduce`` copied into the kept buffers, a bucket."""
    return [s[ATTRS]["copied_bytes"] for s in _named(recorded, HOST_COPY)]


class _WritingPG(_FailingPG):
    """A group that reduces into what it is given, as a world of two or
    the subprocess group does: the manager must hand it writable memory."""

    def allreduce_writes(self, op=None):
        return True


PGS = {"reads-only": _FailingPG, "writes": _WritingPG}


def _named(recorded, name):
    return sorted((s for s in recorded if s[NAME] == name), key=lambda s: s[T0])


# ---------------------------------------------------------------------------
# What comes back: device arrays, the inputs' shardings, the right values
# ---------------------------------------------------------------------------


def test_device_leaves_come_back_as_device_arrays_with_their_shardings():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devices = jax.devices()
    assert len(devices) >= 4  # tests/conftest.py asks the CPU backend for eight
    mesh = Mesh(np.array(devices[:4]).reshape(2, 2), ("x", "y"))
    grads = {
        "rows": jax.device_put(  # sharded over both axes
            _values(64 * 8, 0, 0, 0).reshape(64, 8), NamedSharding(mesh, P("x", "y"))),
        "replicated": jax.device_put(
            _values(48, 0, 0, 1), NamedSharding(mesh, P())),
        "elsewhere": jax.device_put(_values(40, 0, 0, 2), devices[3]),
        "half": jnp.asarray(_values(90, 0, 0, 3, np.float16).reshape(9, 10)),
        "bf16": jnp.asarray(_values(33, 0, 0, 4), jnp.bfloat16),
    }
    (m,) = _managers(1)
    ddp = DistributedDataParallel(m, bucket_cap_mb=KB)
    try:
        out, committed, _ = _step(m, ddp, grads)
    finally:
        m.shutdown()
    assert committed and sorted(out) == sorted(grads)
    for k, g in grads.items():
        o = out[k]
        assert isinstance(o, jax.Array) and o is not g
        assert o.sharding == g.sharding and o.dtype == g.dtype and o.shape == g.shape
        assert o.is_fully_addressable and o.committed
        # a quorum of one, AVG: the device's own gradient, bit for bit
        assert np.asarray(o).tobytes() == np.asarray(g).tobytes()


@pytest.mark.timeout(120)
@pytest.mark.parametrize("kwargs", [dict(), dict(should_quantize=True)],
                         ids=["fp32", "host-int8"])
@pytest.mark.parametrize("world", [1, 2])
def test_three_steps_equal_the_numpy_path_bit_for_bit(world, kwargs):
    """Each rank runs two wrappers on one manager, step by step: device
    leaves in (the line) and the same values as numpy leaves (the kept
    views). Same buckets, same order, same arithmetic: the same bits."""
    import jax

    store = TCPStoreServer() if world > 1 else None
    managers = _managers(world, store)

    def run(rank):
        m = managers[rank]
        line = DistributedDataParallel(m, bucket_cap_mb=KB)
        views = DistributedDataParallel(m, bucket_cap_mb=KB)
        got = []
        for step in range(3):
            m.start_quorum()
            dev = line.allreduce_grads(_device_tree(rank, step), **kwargs)
            ref = views.allreduce_grads(_host_tree(rank, step), **kwargs)
            assert m.should_commit()
            assert all(isinstance(x, jax.Array) for x in dev.values())
            assert all(isinstance(x, np.ndarray) for x in ref.values())
            got.append(([np.array(x) for x in _numpy(dev)],
                        [np.array(x) for x in _numpy(ref)]))
        return got

    try:
        with ThreadPoolExecutor(max_workers=world) as pool:
            results = [f.result(timeout=90)
                       for f in [pool.submit(run, r) for r in range(world)]]
    finally:
        for m in managers:
            m.shutdown()
        if store is not None:
            store.shutdown()
    for step in range(3):
        for rank, got in enumerate(results):
            dev, ref = got[step]
            for d, r in zip(dev, ref):
                assert d.dtype == r.dtype and d.size == r.size
                assert d.tobytes() == r.tobytes()
            if world == 1 and not kwargs:  # the device's own gradient
                for d, g in zip(dev, _numpy(_device_tree(rank, step))):
                    assert d.tobytes() == g.tobytes()
        if not kwargs:  # exact arithmetic: the average of the ranks' leaves
            want = [sum(_numpy(_device_tree(r, step))[i] for r in range(world)) / world
                    for i in range(len(SIZES))]
            for d, w in zip(results[0][step][0], want):
                assert d.tobytes() == w.astype(np.float32).tobytes()


@pytest.mark.parametrize("pg", sorted(PGS))
def test_returned_leaves_alias_no_kept_buffer_and_outlive_the_next_call(pg):
    import jax

    (m,) = _managers(1, pg=PGS[pg]())
    ddp = DistributedDataParallel(m, bucket_cap_mb=KB)
    try:
        first, _, recorded = _step(m, ddp, _device_tree(0, 0))
        assert _copied(recorded) == (
            [BUCKET_BYTES[b] for b in ORDER] if pg == "writes" else [0, 0, 0])
        kept_then = [np.array(x) for x in _numpy(first)]
        flats = list(ddp._pack_buffers._flats)
        assert len(flats) == 3
        for leaf in jax.tree_util.tree_leaves(first):
            assert not any(np.shares_memory(np.asarray(leaf), f) for f in flats)
        # the next call has the same buffers to write, where it writes ...
        second, committed, _ = _step(m, ddp, _device_tree(0, 1))
        assert committed and all(
            a is b for a, b in zip(flats, ddp._pack_buffers._flats))
        # ... and so does a step that does not commit
        m.start_quorum()
        ddp.allreduce_grads(_device_tree(0, 2))
        m.report_error(RuntimeError("injected after the allreduce"))
        assert not m.should_commit()
    finally:
        m.shutdown()
    for leaf, then in zip(_numpy(first), kept_then):
        assert leaf.tobytes() == then.tobytes()
    for leaf, want in zip(_numpy(second), _numpy(_device_tree(0, 1))):
        assert leaf.tobytes() == want.tobytes()


def test_the_wrapper_keeps_no_device_array_past_its_return():
    """A reference kept to the next call would be a gradient of HBM."""
    import jax

    (m,) = _managers(1)
    ddp = DistributedDataParallel(m, bucket_cap_mb=KB)
    try:
        grads = _device_tree(0, 0)
        out, _, _ = _step(m, ddp, grads)
        refs = [weakref.ref(x) for tree in (grads, out)
                for x in jax.tree_util.tree_leaves(tree)]
        del grads, out
        gc.collect()
        assert [r() for r in refs] == [None] * len(refs)
    finally:
        m.shutdown()


# ---------------------------------------------------------------------------
# The spans of the line
# ---------------------------------------------------------------------------


def test_the_spans_of_a_step_of_three_buckets():
    (m,) = _managers(1)
    ddp = DistributedDataParallel(m, bucket_cap_mb=KB)
    grads = _device_tree(0, 0)
    leaves = _numpy(grads)
    assert issue_order(leaves, bucketize(leaves, ddp._bucket_cap)) == ORDER
    try:
        _, committed, recorded = _step(m, ddp, grads)
    finally:
        m.shutdown()
    assert committed
    (root,) = _named(recorded, ROOT)
    pulls, packs, pushes = (_named(recorded, n) for n in (PULL, PACK, PUSH))
    # one pull a bucket, in issue order, their bytes the gradient's
    assert [p[ATTRS] for p in pulls] == [
        {"bucket": b, "nbytes": BUCKET_BYTES[b]} for b in ORDER]
    assert sum(p[ATTRS]["nbytes"] for p in pulls) == sum(x.nbytes for x in leaves)
    # the landing is the bucket: pack ran a bucket and copied nothing
    # (parent: nbytes == the bucket's bytes, copied into the kept buffer)
    assert [p[ATTRS] for p in packs] == [
        {"bucket": b, "nbytes": 0, "fresh_bytes": BUCKET_BYTES[b],
         "reused_bytes": 0} for b in ORDER]
    assert [u[ATTRS]["bucket"] for u in _named(recorded, UNPACK)] == ORDER
    assert [p[ATTRS] for p in pushes] == [
        {"bucket": b, "nbytes": BUCKET_BYTES[b]} for b in ORDER]
    # Manager.allreduce is called smallest first: each call's own copy span
    # carries its payload's bytes, and at a quorum of one copied none
    issues = [s for s in _named(recorded, ISSUE) if s[PARENT] == root[ID]]
    assert len(issues) == 3
    copies = {s[PARENT]: s[ATTRS] for s in _named(recorded, HOST_COPY)}
    assert [copies[s[ID]] for s in issues] == [
        {"nbytes": BUCKET_BYTES[b], "copied_bytes": 0} for b in ORDER]
    # times exactly 1 is no pass: a span a bucket, each with a count of 0
    assert [s[ATTRS] for s in _named(recorded, SCALE)] == [{"nbytes": 0}] * 3
    # each bucket's own stages in order, and bucket k goes back before
    # bucket k+1 is pulled: the dummy group's collectives are done at issue
    pull_of, pack_of, push_of = (
        {s[ATTRS]["bucket"]: s for s in group} for group in (pulls, packs, pushes))
    for b in ORDER:
        assert pull_of[b][T1] <= pack_of[b][T0] <= pack_of[b][T1] <= push_of[b][T0]
    for earlier, later in zip(ORDER, ORDER[1:]):
        assert push_of[earlier][T1] <= pull_of[later][T0]
    early = [p for p in pushes if p[T0] < pulls[-1][T1]]
    assert len(early) == 2  # every bucket but the last
    # one wait for all of them to land, last, inside the root
    (landed,) = _named(recorded, PUSH_WAIT)
    assert pushes[-1][T1] <= landed[T0] and landed[T1] <= root[T1]
    assert all(s[PARENT] == root[ID] for s in pulls + packs + pushes + [landed])
    assert len(_named(recorded, "torchft::ddp::grads_wait")) == 1

    # the benchmark's readers on the same spans
    from benchmark.metrics import ar_host_bytes_step, ar_pull_ms

    run = {"journal": [{"event": "step_spans", "attrs": {"spans": recorded}}]}
    # pulled, and nothing else (parent: 3 * 2268, pulled + packed + scaled)
    assert ar_host_bytes_step.read(run) == 2268
    assert ar_pull_ms.read(run) == pytest.approx(
        sum(p[T1] - p[T0] for p in pulls) * 1e3)


def test_a_bucket_whose_collective_is_not_done_is_not_waited_for_early():
    """The line never blocks on a collective between two pulls: a bucket
    still in flight goes back in the drain after the last issue."""
    from torchft_tpu.process_group import ProcessGroupDummy
    from torchft_tpu.work import DummyWork

    class _SlowWork(DummyWork):
        def done(self):
            return False

    class _SlowPG(ProcessGroupDummy):
        def allreduce(self, tensors, op=None):
            return _SlowWork(list(tensors))

    (m,) = _managers(1, pg=_SlowPG())
    ddp = DistributedDataParallel(m, bucket_cap_mb=KB)
    grads = _device_tree(0, 0)
    try:
        out, committed, recorded = _step(m, ddp, grads)
    finally:
        m.shutdown()
    assert committed
    pulls, pushes = _named(recorded, PULL), _named(recorded, PUSH)
    assert [p[ATTRS]["bucket"] for p in pushes] == ORDER  # drained in issue order
    assert pulls[-1][T1] <= pushes[0][T0]
    for got, want in zip(_numpy(out), _numpy(grads)):
        assert got.tobytes() == want.tobytes()


# ---------------------------------------------------------------------------
# A byte is touched once each way: what is copied, scaled and allocated
# ---------------------------------------------------------------------------


def test_at_a_quorum_of_one_the_kept_buffers_are_never_written():
    """The wrapper allocates no gradient of host memory of its own in
    steady state: ``np.empty`` touches no page, and nothing writes one."""
    (m,) = _managers(1)
    ddp = DistributedDataParallel(m, bucket_cap_mb=KB)
    try:
        _step(m, ddp, _device_tree(0, 0))
        flats = list(ddp._pack_buffers._flats)
        for f in flats:
            f[...] = 777.0
        for step in (1, 2, 3):
            out, committed, recorded = _step(m, ddp, _device_tree(0, step))
            assert committed and _all_landed(_packs(recorded))
            assert _copied(recorded) == [0, 0, 0]
            assert all(a is b for a, b in zip(flats, ddp._pack_buffers._flats))
            for got, want in zip(_numpy(out), _numpy(_device_tree(0, step))):
                assert got.tobytes() == want.tobytes()
        assert all(np.all(f == 777.0) for f in flats)
    finally:
        m.shutdown()


@pytest.mark.timeout(120)
@pytest.mark.parametrize("kwargs", [dict(), dict(should_quantize=True)],
                         ids=["fp32", "host-int8"])
def test_a_world_of_two_copies_into_the_kept_buffers_and_scales(kwargs, tmp_path):
    """Where something writes (the ring, the scale of 0.5, the quantized
    collective) the bucket goes into the kept buffer first, once, inside
    ``Manager.allreduce``, and both passes are counted."""
    store = TCPStoreServer()
    managers = _managers(2, store)

    def run(rank):
        m = managers[rank]
        ddp = DistributedDataParallel(m, bucket_cap_mb=KB)
        flats = None
        for step in range(3):
            m.start_quorum()
            ddp.allreduce_grads(_device_tree(rank, step), **kwargs)
            assert m.should_commit()
            if flats is None:
                flats = list(ddp._pack_buffers._flats)
            assert all(a is b for a, b in zip(flats, ddp._pack_buffers._flats))

    try:
        with ThreadPoolExecutor(max_workers=2) as pool:
            for f in [pool.submit(run, r) for r in range(2)]:
                f.result(timeout=90)
    finally:
        for m in managers:
            m.shutdown()
        store.shutdown()
    # Two Managers share the process's span buffer: read the journal.
    import json

    with open(tmp_path / "journal.jsonl") as f:
        spans = [s for e in map(json.loads, f) if e["event"] == "step_spans"
                 for s in e["attrs"]["spans"]]
    calls = 2 * 3 * 3  # ranks, steps, buckets
    packs = [s[ATTRS] for s in spans if s[NAME] == PACK]
    assert len(packs) == calls and _all_landed(packs)
    copies = [s[ATTRS] for s in spans if s[NAME] == HOST_COPY]
    assert len(copies) == calls
    assert all(c["copied_bytes"] == c["nbytes"] for c in copies)
    assert sum(c["copied_bytes"] for c in copies) == 2 * 3 * 2268
    scales = [s[ATTRS]["nbytes"] for s in spans if s[NAME] == SCALE]
    assert sorted(scales) == sorted(
        BUCKET_BYTES[b] for b in ORDER for _ in range(2 * 3))


def test_a_bucket_of_several_leaves_is_joined_by_one_program_a_layout(monkeypatch):
    """Three calls, one compile; and the device's temporary is gone when
    the call returns (it would be a bucket of HBM under ``apply_step``)."""
    import jax

    from torchft_tpu import ddp as ddp_module

    made = []
    real = ddp_module._join_leaves

    def joining(leaves):
        out = real(leaves)
        made.append((weakref.ref(out), [x.shape for x in leaves], out.shape))
        return out

    monkeypatch.setattr(ddp_module, "_join_leaves", joining)
    (m,) = _managers(1)
    ddp = DistributedDataParallel(m, bucket_cap_mb=KB)
    try:
        _step(m, ddp, _device_tree(0, 0))
        compiled = real._cache_size()
        for step in (1, 2, 3):
            out, committed, _ = _step(m, ddp, _device_tree(0, step))
            assert committed
            for got, want in zip(_numpy(out), _numpy(_device_tree(0, step))):
                assert got.tobytes() == want.tobytes()
        assert real._cache_size() == compiled
    finally:
        m.shutdown()
    # only the bucket of two leaves (c, d); a leaf alone needs no program
    assert [(shapes, flat) for _, shapes, flat in made] == [
        ([(60,), (7,)], (67,))] * 4
    del out
    gc.collect()
    assert [ref() for ref, _, _ in made] == [None] * 4
    assert isinstance(real, type(jax.jit(lambda x: x)))


def test_a_leaf_alone_comes_down_through_a_handle_only_the_call_holds(monkeypatch):
    """jax caches a host copy on the array it was asked of: asked of the
    caller's leaf it would live, a gradient of host memory, until the
    caller drops its tree. The copy is asked of a second handle on the
    same device buffer, and that handle is gone at return."""
    import jax

    handles = []
    real = jax.make_array_from_single_device_arrays

    def handle(shape, sharding, arrays):
        out = real(shape, sharding, arrays)
        (leaf,) = arrays
        assert out is not leaf and out.shape == leaf.shape
        assert out.unsafe_buffer_pointer() == leaf.unsafe_buffer_pointer()
        handles.append((weakref.ref(out), leaf.shape))
        return out

    monkeypatch.setattr(jax, "make_array_from_single_device_arrays", handle)
    (m,) = _managers(1)
    ddp = DistributedDataParallel(m, bucket_cap_mb=KB)
    try:
        grads = _device_tree(0, 0)
        out, committed, _ = _step(m, ddp, grads)
    finally:
        m.shutdown()
    assert committed
    # the two buckets of one leaf, in issue order: b (200), then a (3 x 100)
    assert [shape for _, shape in handles] == [(200,), (3, 100)]
    gc.collect()
    assert [ref() for ref, _ in handles] == [None, None]
    for got, want in zip(_numpy(out), _numpy(grads)):
        assert got.tobytes() == want.tobytes()


def test_leaves_on_several_devices_are_packed_on_the_host():
    """One program cannot join what lies on two devices without moving it:
    such a bucket keeps the host's pack, into the kept buffer."""
    import jax

    devices = jax.devices()
    grads = {"c": jax.device_put(_values(60, 0, 0, 2), devices[0]),
             "d": jax.device_put(_values(7, 0, 0, 3), devices[1])}
    (m,) = _managers(1)
    ddp = DistributedDataParallel(m, bucket_cap_mb=KB)
    try:
        for want_fresh in (268, 0):
            out, committed, recorded = _step(m, ddp, grads)
            assert committed
            assert _packs(recorded) == [{
                "bucket": 0, "nbytes": 268, "fresh_bytes": want_fresh,
                "reused_bytes": 268 - want_fresh}]
            assert _copied(recorded) == [0]
            for k in grads:
                assert out[k].sharding == grads[k].sharding
                assert np.asarray(out[k]).tobytes() == np.asarray(grads[k]).tobytes()
    finally:
        m.shutdown()


# ---------------------------------------------------------------------------
# Failure is what it was
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("failure", ["raises", "late", "latched"],
                         ids=["pg-raises-at-issue", "work-fails-at-wait",
                              "latched-manager-error"])
@pytest.mark.parametrize("writes", sorted(PGS))
def test_a_failed_step_retires_the_buffers_and_pushes_nothing_from_them(
        writes, failure):
    import jax

    pg = PGS[writes]()
    (m,) = _managers(1, pg=pg)
    ddp = DistributedDataParallel(m, bucket_cap_mb=KB)
    try:
        _step(m, ddp, _device_tree(0, 0))
        # (parent: _all_reused, every pack copied into its kept buffer)
        assert _all_landed(_packs(_step(m, ddp, _device_tree(0, 1))[2]))
        kept = list(ddp._pack_buffers._flats)

        m.start_quorum()
        if failure == "latched":
            m.report_error(RuntimeError("heal failed"))
        else:
            pg.mode = failure
        grads = _device_tree(0, 2)
        failed = ddp.allreduce_grads(grads)  # does not raise
        recorded = _recorded()
        assert m.errored() is not None
        assert not m.should_commit()
        pg.mode = None
        # nothing was read from a bucket an aborted collective may still
        # write: no push, and the caller has its own leaves back
        assert not _named(recorded, PUSH)
        assert len(_named(recorded, PUSH_WAIT)) == 1
        assert all(failed[k] is grads[k] for k in grads)
        assert ddp._pack_buffers._flats == []

        # what the failed collective holds: the kept buffers where the
        # group writes, else the landed copies, which nobody can write
        if failure != "latched":
            assert pg.held and all(
                any(h is k for k in kept) if writes == "writes"
                else not h.flags.writeable for h in pg.held)

        # start_quorum clears the latch; the next step sizes a new set
        out, committed, recorded = _step(m, ddp, _device_tree(0, 3))
        assert committed and _all_landed(_packs(recorded))
        assert not any(a is b for a in kept for b in ddp._pack_buffers._flats)
        for old in kept + [h for h in pg.held if h.flags.writeable]:
            old[...] = 777.0  # the aborted thread writes at last
        for got, want in zip(_numpy(out), _numpy(_device_tree(0, 3))):
            assert got.tobytes() == want.tobytes()
        assert all(isinstance(x, jax.Array) for x in out.values())
        flats = list(ddp._pack_buffers._flats)
        assert _all_landed(_packs(_step(m, ddp, _device_tree(0, 4))[2]))
        assert all(a is b for a, b in zip(flats, ddp._pack_buffers._flats))
    finally:
        m.shutdown()


def test_a_bucket_that_fails_midway_leaves_the_earlier_ones_pushed():
    """The first two buckets reduce, the third fails at its wait: the
    step does not commit, the third bucket's leaves are the inputs, and
    nothing raises."""
    import jax

    pg = _FailingPG()
    (m,) = _managers(1, pg=pg)
    ddp = DistributedDataParallel(m, bucket_cap_mb=KB)
    real = pg.allreduce
    calls = []

    def allreduce(tensors, op=None):
        calls.append(1)
        pg.mode = "late" if len(calls) == 3 else None
        return real(tensors, op)

    pg.allreduce = allreduce
    grads = _device_tree(0, 0)
    try:
        out, committed, recorded = _step(m, ddp, grads)
    finally:
        m.shutdown()
    assert not committed
    assert [p[ATTRS]["bucket"] for p in _named(recorded, PUSH)] == ORDER[:2]
    assert out["a"] is grads["a"]  # the last bucket in issue order: layout index 0
    assert all(isinstance(x, jax.Array) for x in out.values())
    assert ddp._pack_buffers._flats == []


def test_a_call_that_raises_retires_the_buffers():
    (m,) = _managers(1)
    ddp = DistributedDataParallel(m, bucket_cap_mb=KB)
    try:
        _step(m, ddp, _device_tree(0, 0))
        kept = list(ddp._pack_buffers._flats)
        m.start_quorum()
        real = m.allreduce
        m.allreduce = lambda *a, **k: (_ for _ in ()).throw(KeyboardInterrupt())
        with pytest.raises(KeyboardInterrupt):
            ddp.allreduce_grads(_device_tree(0, 1))
        m.allreduce = real
        assert ddp._pack_buffers._flats == []
        recorded = _recorded()
        assert len(_named(recorded, PULL)) == 1 and not _named(recorded, PUSH)
        out, committed, recorded = _step(m, ddp, _device_tree(0, 2))
        assert committed and _all_landed(_packs(recorded))
        assert len(ddp._pack_buffers._flats) == 3
        assert not any(a is b for a in kept for b in ddp._pack_buffers._flats)
        for got, want in zip(_numpy(out), _numpy(_device_tree(0, 2))):
            assert got.tobytes() == want.tobytes()
    finally:
        m.shutdown()


# ---------------------------------------------------------------------------
# A numpy leaf in the tree: the kept views, as before
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("numpy_leaves", [("a", "b", "c", "d"), ("d",)],
                         ids=["all-numpy", "one-numpy-leaf-among-device-leaves"])
def test_numpy_leaves_in_get_the_kept_views_out(numpy_leaves):
    (m,) = _managers(1)
    ddp = DistributedDataParallel(m, bucket_cap_mb=KB)

    def tree(step):
        dev, host = _device_tree(0, step), _host_tree(0, step)
        return {k: host[k] if k in numpy_leaves else dev[k] for k in dev}

    try:
        first, _, recorded = _step(m, ddp, tree(0))
        second, committed, _ = _step(m, ddp, tree(1))
    finally:
        m.shutdown()
    assert committed
    flats = ddp._pack_buffers._flats
    for k in SIZES:
        assert isinstance(first[k], np.ndarray) and isinstance(second[k], np.ndarray)
        assert any(np.shares_memory(second[k], f) for f in flats)
        # valid until the next call, which has written them again
        assert np.shares_memory(first[k], second[k])
        assert first[k].tobytes() == np.asarray(tree(1)[k]).tobytes()
    # the four passes of before, span for span: every bucket packed into
    # its kept buffer, fresh on the wrapper's first call, nothing for the
    # manager to copy
    assert _all_fresh(_packs(recorded))
    assert [p["nbytes"] for p in _packs(recorded)] == [BUCKET_BYTES[b] for b in ORDER]
    assert _copied(recorded) == [0, 0, 0]
    assert [s[ATTRS] for s in _named(recorded, SCALE)] == [{"nbytes": 0}] * 3
    # pulled whole, in one span that has no bucket; nothing is pushed
    (pull,) = _named(recorded, PULL)
    device_bytes = sum(4 * n for k, n in SIZES.items() if k not in numpy_leaves)
    assert pull[ATTRS] == {"nbytes": device_bytes}
    assert not _named(recorded, PUSH) and not _named(recorded, PUSH_WAIT)
    assert [u[ATTRS]["bucket"] for u in _named(recorded, UNPACK)] == ORDER
