"""Process-group tests: N ranks as threads sharing a store (reference:
process_group_test.py MultiPgBaseTest:863-1020), full collective surface,
crash-and-reconfigure resiliency, and the wrapper zoo."""

import struct
import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from torchft_tpu.process_group import (
    ErrorSwallowingProcessGroupWrapper,
    FakeProcessGroupWrapper,
    ManagedProcessGroup,
    ProcessGroupDummy,
    ProcessGroupSocket,
    ReduceOp,
)
from torchft_tpu.store import TCPStoreServer
from torchft_tpu.work import DummyWork


def _run_parallel(fns):
    """Runs one callable per rank in threads; returns results, re-raising
    the first failure."""
    with ThreadPoolExecutor(max_workers=len(fns)) as pool:
        futures = [pool.submit(fn) for fn in fns]
        return [f.result(timeout=60) for f in futures]


@pytest.fixture
def store():
    server = TCPStoreServer()
    yield server
    server.shutdown()


def _make_group(store, world_size, prefix="pg0", timeout=10.0):
    groups = [ProcessGroupSocket(timeout=timeout) for _ in range(world_size)]

    def configure(rank):
        groups[rank].configure(f"{store.address()}/{prefix}", rank, world_size)

    _run_parallel([lambda r=r: configure(r) for r in range(world_size)])
    return groups


@pytest.mark.parametrize("world_size", [2, 3, 4])
def test_allreduce_sum(store, world_size):
    groups = _make_group(store, world_size, prefix=f"ar{world_size}")
    expected = sum(range(world_size))

    def run(rank):
        arr = np.full((5, 3), float(rank), dtype=np.float32)
        out = groups[rank].allreduce(arr, ReduceOp.SUM).wait(timeout=30)
        return out[0]

    results = _run_parallel([lambda r=r: run(r) for r in range(world_size)])
    for r in results:
        np.testing.assert_allclose(r, expected)
    for g in groups:
        g.shutdown()


def test_allreduce_avg_and_inplace(store):
    groups = _make_group(store, 2, prefix="avg")

    def run(rank):
        arr = np.full(7, float(rank * 2), dtype=np.float32)  # 0 and 2 -> avg 1
        groups[rank].allreduce(arr, ReduceOp.AVG).wait(timeout=30)
        return arr  # reduced in place

    a, b = _run_parallel([lambda: run(0), lambda: run(1)])
    np.testing.assert_allclose(a, 1.0)
    np.testing.assert_allclose(b, 1.0)
    for g in groups:
        g.shutdown()


def test_allreduce_max_min(store):
    groups = _make_group(store, 3, prefix="maxmin")

    def run(rank, op):
        arr = np.array([float(rank)], dtype=np.float64)
        return groups[rank].allreduce(arr, op).wait(timeout=30)[0][0]

    maxes = _run_parallel([lambda r=r: run(r, ReduceOp.MAX) for r in range(3)])
    assert all(m == 2.0 for m in maxes)
    mins = _run_parallel([lambda r=r: run(r, ReduceOp.MIN) for r in range(3)])
    assert all(m == 0.0 for m in mins)
    for g in groups:
        g.shutdown()


def test_allgather_broadcast_reduce_scatter_alltoall_barrier(store):
    ws = 3
    groups = _make_group(store, ws, prefix="suite")

    def run(rank):
        pg = groups[rank]
        # allgather
        gathered = pg.allgather(np.array([rank, rank + 10])).wait(timeout=30)
        assert [g[0][0] for g in gathered] == list(range(ws))
        # broadcast from root 1
        arr = np.array([float(rank)], dtype=np.float64)
        pg.broadcast(arr, root=1).wait(timeout=30)
        assert arr[0] == 1.0
        # reduce_scatter: rank j receives sum over ranks of inputs[j]
        inputs = [np.full(4, float(rank + j), dtype=np.float32) for j in range(ws)]
        shard = pg.reduce_scatter(inputs, ReduceOp.SUM).wait(timeout=30)
        np.testing.assert_allclose(shard, sum(r + rank for r in range(ws)))
        # alltoall: output[j] = rank j's inputs[me]
        inputs = [np.array([rank * 10 + j]) for j in range(ws)]
        out = pg.alltoall(inputs).wait(timeout=30)
        assert [o[0] for o in out] == [j * 10 + rank for j in range(ws)]
        # ... and a list of arrays a rank travels in the one collective
        inputs = [
            [np.array([rank * 10 + j]), np.full(3, rank - j, np.float32)]
            for j in range(ws)
        ]
        out = pg.alltoall(inputs).wait(timeout=30)
        assert [o[0][0] for o in out] == [j * 10 + rank for j in range(ws)]
        for j, o in enumerate(out):
            assert o[1].dtype == np.float32
            np.testing.assert_array_equal(o[1], np.full(3, j - rank))
        # barrier
        pg.barrier().wait(timeout=30)
        return True

    assert all(_run_parallel([lambda r=r: run(r) for r in range(ws)]))
    for g in groups:
        g.shutdown()


def _alltoall_groups(backend, store, ws):
    if backend == "dummy":
        return [ProcessGroupDummy(rank=0, world=1)]
    if backend == "socket":
        return _make_group(store, ws, prefix="a2a-lists")
    if backend == "native":
        from torchft_tpu.process_group import ProcessGroupNative as cls
    else:
        from torchft_tpu.baby import ProcessGroupBabySocket as cls
    groups = [cls(timeout=20.0) for _ in range(ws)]
    _run_parallel(
        [
            lambda r=r: groups[r].configure(
                f"{store.address()}/a2a-{backend}", r, ws
            )
            for r in range(ws)
        ]
    )
    return groups


@pytest.mark.parametrize("backend", ["socket", "native", "dummy", "baby"])
def test_alltoall_takes_a_list_of_arrays_a_rank(store, backend):
    """What the quantized wire sends a turn: every rank's payload chunk
    and its scales in ONE alltoall. Arrays of a list keep their dtypes
    and shapes, one large enough for the baby group's shared memory; a
    bare array a rank still comes back bare."""
    groups = _alltoall_groups(backend, store, 2)
    ws = len(groups)

    def run(rank):
        pg = groups[rank]
        inputs = [
            [
                np.full(70_000, rank * 10 + j, dtype=np.int8),
                np.full((2, 3), rank - j, dtype=np.float32),
            ]
            for j in range(ws)
        ]
        nested = pg.alltoall(inputs).wait(timeout=60)
        bare = pg.alltoall([np.array([rank * 10 + j]) for j in range(ws)])
        return nested, bare.wait(timeout=60)

    try:
        results = _run_parallel([lambda r=r: run(r) for r in range(ws)])
        for rank, (nested, bare) in enumerate(results):
            assert len(nested) == len(bare) == ws
            for src in range(ws):
                q, s = nested[src]
                assert q.dtype == np.int8 and q.shape == (70_000,)
                assert s.dtype == np.float32 and s.shape == (2, 3)
                assert (q == src * 10 + rank).all() and (s == src - rank).all()
                assert bare[src].shape == (1,) and bare[src][0] == src * 10 + rank
        if ws > 1:
            # as many arrays for every rank, or the collective refuses
            uneven = [[np.zeros(1)], [np.zeros(1), np.zeros(1)]]
            with pytest.raises(Exception, match="as many arrays"):
                groups[0].alltoall(uneven).wait(timeout=30)
    finally:
        for g in groups:
            g.shutdown()


@pytest.mark.parametrize("dtype", [np.float32, np.float64, np.int32, np.float16])
def test_allreduce_dtype_sweep(store, dtype):
    """The wire carries any numpy dtype faithfully (reference: collectives
    view/split sweeps, _test_utils.py:26-111)."""
    groups = _make_group(store, 2, prefix=f"dt{np.dtype(dtype).name}")

    def run(rank):
        arr = np.full(37, rank + 1, dtype=dtype)  # odd size: uneven chunks
        groups[rank].allreduce(arr, ReduceOp.SUM).wait(timeout=30)
        return arr

    a, b = _run_parallel([lambda: run(0), lambda: run(1)])
    np.testing.assert_array_equal(a, np.full(37, 3, dtype=dtype))
    np.testing.assert_array_equal(b, a)
    assert a.dtype == np.dtype(dtype)
    for g in groups:
        g.shutdown()


def test_allreduce_noncontiguous_input(store):
    """A transposed (non-contiguous) array reduces correctly in place —
    the ring's reshape-copied path must write back through."""
    groups = _make_group(store, 2, prefix="noncontig")

    def run(rank):
        base = np.full((6, 4), float(rank + 1), dtype=np.float32)
        view = base.T  # non-contiguous
        assert not view.flags.c_contiguous
        groups[rank].allreduce(view, ReduceOp.SUM).wait(timeout=30)
        return view

    a, b = _run_parallel([lambda: run(0), lambda: run(1)])
    np.testing.assert_allclose(a, 3.0)
    np.testing.assert_allclose(b, 3.0)
    for g in groups:
        g.shutdown()


def test_send_recv(store):
    groups = _make_group(store, 2, prefix="p2p")

    def sender():
        groups[0].send([np.arange(6, dtype=np.float32)], dst=1, tag="x").wait(30)

    def receiver():
        (arr,) = groups[1].recv(src=0, tag="x").wait(30)
        return arr

    _, arr = _run_parallel([sender, receiver])
    np.testing.assert_allclose(arr, np.arange(6))
    for g in groups:
        g.shutdown()


def test_peer_conn_recv_fails_fast_after_peer_death():
    """A recv issued AFTER the peer connection died must fail immediately,
    not wait out the full per-tag timeout: the reader thread's death
    broadcast only reaches queues that already exist, and the send side
    already failed fast on self.dead — the asymmetry cost an abrupt-kill
    survivor two consecutive 30s timeout rounds (the sigkill_control
    drill) while its peer detected the death in under a second.
    A message delivered before the death must still be consumable."""
    import socket as socket_mod
    import time

    from torchft_tpu import _net
    from torchft_tpu.process_group import _PeerConn

    a, b = socket_mod.socketpair()
    conn = _PeerConn(a, peer=1)
    try:
        # Deliver one message, then kill the peer side.
        arr = np.arange(8, dtype=np.float32)
        _net.send_json(b, {"tag": "pre", "dtype": "float32", "shape": [8]})
        _net.send_frame(b, arr.tobytes())
        deadline = time.monotonic() + 5
        while conn.dead is None and "pre" not in conn._queues:
            if time.monotonic() > deadline:
                raise AssertionError("message never arrived")
            time.sleep(0.01)
        b.close()
        # Wait for the reader to observe the death.
        while conn.dead is None:
            if time.monotonic() > deadline:
                raise AssertionError("reader never observed peer death")
            time.sleep(0.01)

        # Buffered pre-death message is still consumable.
        np.testing.assert_array_equal(conn.recv("pre", timeout=5.0), arr)

        # A recv for a tag that never arrived must fail FAST (RuntimeError,
        # not a 30s TimeoutError).
        t0 = time.monotonic()
        with pytest.raises(RuntimeError, match="died"):
            conn.recv("never-sent", timeout=30.0)
        assert time.monotonic() - t0 < 1.0

        # A recv already PENDING when the death lands is covered by the
        # death broadcast (pre-existing behavior, pinned here): simulate
        # with a second pair.
        a2, b2 = socket_mod.socketpair()
        conn2 = _PeerConn(a2, peer=2)
        try:
            errs = []

            def waiter():
                t = time.monotonic()
                try:
                    conn2.recv("pending", timeout=30.0)
                except RuntimeError:
                    errs.append(time.monotonic() - t)

            th = threading.Thread(target=waiter)
            th.start()
            time.sleep(0.2)  # let the recv register its queue
            b2.close()
            th.join(timeout=5)
            assert not th.is_alive()
            assert errs and errs[0] < 2.0
        finally:
            conn2.close()
    finally:
        conn.close()
        try:
            b.close()
        except OSError:
            pass


def test_peer_conn_abort_tombstone_cleared_by_fresh_data():
    """An abort tombstone for a tag must not outlive the collective it
    belonged to: on a long-lived PG, p2p tags are REUSED (the parameter
    server's fixed session tags), so fresh data arriving under a
    tombstoned tag means a new generation started — the recv must deliver
    it, not keep raising the stale _CollectiveAborted forever."""
    import socket as socket_mod
    import time

    from torchft_tpu import _net
    from torchft_tpu.process_group import _CollectiveAborted, _PeerConn

    a, b = socket_mod.socketpair()
    conn = _PeerConn(a, peer=1)
    try:
        # Peer aborts collective "t1" (covers "t1" and "t1.*").
        _net.send_json(b, {"tag": "t1", "abort": True, "error": "leg died"})
        _net.send_frame(b, b"")
        deadline = time.monotonic() + 5
        while "t1" not in conn._aborted:
            if time.monotonic() > deadline:
                raise AssertionError("abort never registered")
            time.sleep(0.01)

        # The tombstone fails recvs under the prefix (sticky behavior).
        with pytest.raises(_CollectiveAborted):
            conn.recv("t1.0", timeout=5.0)

        # The peer starts a NEW collective reusing the tag: fresh data
        # must clear the tombstone and be delivered. (The clear happens
        # when the reader processes the frame — wait for it, since a recv
        # racing ahead of the wire legitimately still sees the tombstone.)
        arr = np.arange(6, dtype=np.float32)
        _net.send_json(b, {"tag": "t1.0", "dtype": "float32", "shape": [6]})
        _net.send_frame(b, arr.tobytes())
        while "t1" in conn._aborted:
            if time.monotonic() > deadline:
                raise AssertionError("fresh data never cleared the tombstone")
            time.sleep(0.01)
        np.testing.assert_array_equal(conn.recv("t1.0", timeout=5.0), arr)

        # Later recvs under the same prefix behave normally again.
        _net.send_json(b, {"tag": "t1.1", "dtype": "float32", "shape": [6]})
        _net.send_frame(b, arr.tobytes())
        np.testing.assert_array_equal(conn.recv("t1.1", timeout=5.0), arr)
    finally:
        conn.close()
        try:
            b.close()
        except OSError:
            pass


def test_collective_abort_propagates_to_live_peers(store):
    """A rank that abandons a collective (its own leg failed) must unblock
    the OTHER ranks' pending waits on that collective immediately — one
    wedged tag wait otherwise holds the whole group's next quorum hostage
    for the full socket timeout. Rank 2's alltoall dies instantly on a
    local ValueError; ranks 0/1 are mid-allreduce on the same collective
    sequence number and must fail fast via the abort broadcast (including
    transitively: rank 1 first blocks on healthy rank 0, whose own abort
    re-broadcast is what unblocks it)."""
    import time

    groups = _make_group(store, 3, timeout=30.0)
    t0 = time.monotonic()

    def survivor(r):
        work = groups[r].allreduce(np.ones(64, dtype=np.float32))
        with pytest.raises(Exception, match="aborted|died"):
            work.wait(timeout=60)

    def failer():
        # Wrong input count: fails locally before any wire traffic.
        work = groups[2].alltoall([np.ones(4, dtype=np.float32)])
        with pytest.raises(ValueError):
            work.wait(timeout=60)

    _run_parallel([lambda: survivor(0), lambda: survivor(1), failer])
    elapsed = time.monotonic() - t0
    # Without abort propagation the survivors wait out the 30s tag timeout.
    assert elapsed < 10, f"abort took {elapsed:.1f}s to propagate"
    for g in groups:
        g.shutdown()


def test_crash_and_reconfigure(store):
    """The resiliency scenario (reference: process_group_test.py:961-1020):
    kill the last rank mid-life, survivors' collectives raise, then a
    reconfigure against a fresh prefix with a smaller world succeeds."""
    ws = 3
    groups = _make_group(store, ws, prefix="crash1")

    groups[2].shutdown()  # crash the last rank

    def failing(rank):
        arr = np.ones(1024, dtype=np.float32)
        # The survivor's collective surfaces either the peer-abort
        # RuntimeError, its own tag timeout, or — when the send lands after
        # the crashed rank's socket closed — the raw BrokenPipeError /
        # ConnectionResetError (both OSError).
        with pytest.raises((RuntimeError, OSError)):
            groups[rank].allreduce(arr).wait(timeout=5)
        return True

    assert all(_run_parallel([lambda: failing(0), lambda: failing(1)]))

    # Reconfigure the survivors into a 2-world group under a new prefix.
    def reconfigure(rank):
        groups[rank].configure(f"{store.address()}/crash2", rank, 2)
        arr = np.full(3, float(rank), dtype=np.float32)
        groups[rank].allreduce(arr).wait(timeout=30)
        return arr

    a, b = _run_parallel([lambda: reconfigure(0), lambda: reconfigure(1)])
    np.testing.assert_allclose(a, 1.0)  # 0 + 1
    np.testing.assert_allclose(b, 1.0)
    assert groups[0].errored() is None  # configure cleared the latched error
    for g in groups[:2]:
        g.shutdown()


def test_abort_latches_error(store):
    groups = _make_group(store, 2, prefix="abort")
    groups[0].abort()
    assert groups[0].errored() is not None
    work = groups[0].allreduce(np.ones(2))
    with pytest.raises(RuntimeError):
        work.wait(timeout=5)
    for g in groups:
        g.shutdown()


def test_world_size_one_noop():
    pg = ProcessGroupSocket()
    pg.configure("unused:0/solo", 0, 1)
    arr = np.full(4, 7.0)
    out = pg.allreduce(arr, ReduceOp.SUM).wait(timeout=5)
    np.testing.assert_allclose(out[0], 7.0)
    pg.shutdown()


@pytest.mark.parametrize("group", ["socket-alone", "socket-of-two", "dummy",
                                   "swallowing", "fake", "base", "baby"])
def test_a_group_says_whether_its_allreduce_writes_its_inputs(store, group):
    """``allreduce_writes``: what ``Manager.allreduce`` asks before it
    copies a read-only input. True unless the group knows better, and a
    read-only array goes through a group that says False untouched."""
    from torchft_tpu.baby import ProcessGroupBabySocket
    from torchft_tpu.process_group import ProcessGroup

    ro = np.arange(6, dtype=np.float32)
    ro.flags.writeable = False
    if group == "socket-of-two":
        groups = _make_group(store, 2, prefix="writes")
        assert all(g.allreduce_writes(ReduceOp.SUM) for g in groups)
        for g in groups:
            g.shutdown()
        return
    if group in ("base", "baby"):  # the default; the child copies back
        pg = ProcessGroup() if group == "base" else ProcessGroupBabySocket()
        assert pg.allreduce_writes(ReduceOp.SUM) and pg.allreduce_writes()
        return
    if group == "socket-alone":
        pg = ProcessGroupSocket()
        pg.configure("unused:0/solo-writes", 0, 1)
        assert pg.allreduce_writes(ReduceOp.AVG)  # it divides in place
    else:
        pg = {"dummy": lambda p: p,
              "swallowing": ErrorSwallowingProcessGroupWrapper,
              "fake": FakeProcessGroupWrapper}[group](ProcessGroupDummy())
    assert not pg.allreduce_writes(ReduceOp.SUM)
    (out,) = pg.allreduce([ro], ReduceOp.SUM).wait(timeout=5)
    assert out is ro and not out.flags.writeable
    if group == "socket-alone":
        pg.shutdown()


def test_dummy_pg():
    pg = ProcessGroupDummy()
    arr = np.ones(3)
    out = pg.allreduce(arr).wait()
    np.testing.assert_allclose(out[0], 1.0)
    pg.configure("x:1/y", 0, 1)
    assert pg.configure_count == 1
    assert isinstance(pg.barrier(), DummyWork)


def test_error_swallowing_wrapper(store):
    inner = ProcessGroupDummy()
    wrapper = ErrorSwallowingProcessGroupWrapper(inner)
    fake_err = RuntimeError("injected")
    wrapper.report_error(fake_err)
    assert wrapper.error() is fake_err
    # Post-error allreduce is a no-op that returns the inputs.
    arr = np.ones(2)
    out = wrapper.allreduce(arr).wait()
    np.testing.assert_allclose(out[0], 1.0)
    # configure resets the error.
    wrapper.configure("x:1/y", 0, 1)
    assert wrapper.error() is None


def test_fake_wrapper_injects_error():
    wrapper = FakeProcessGroupWrapper(ProcessGroupDummy())
    wrapper.report_future_error(ValueError("boom"))
    with pytest.raises(ValueError, match="boom"):
        wrapper.allreduce(np.ones(1)).wait(timeout=5)
    # Next op is clean.
    wrapper.allreduce(np.ones(1)).wait(timeout=5)


def test_managed_pg_delegates_to_manager():
    class FakeManager:
        def __init__(self):
            self.calls = 0

        def allreduce(self, tensors):
            self.calls += 1
            return DummyWork(tensors)

        def num_participants(self):
            return 5

        def participating_rank(self):
            return 2

        def errored(self):
            return None

    m = FakeManager()
    pg = ManagedProcessGroup(m)
    pg.allreduce(np.ones(1)).wait()
    assert m.calls == 1
    assert pg.size() == 5
    assert pg.rank() == 2


def test_futures_engine():
    import concurrent.futures

    from torchft_tpu import futures

    fut = concurrent.futures.Future()
    wrapped = futures.future_timeout(fut, 0.2)
    with pytest.raises(TimeoutError):
        wrapped.result(timeout=5)

    fut2 = concurrent.futures.Future()
    wrapped2 = futures.future_timeout(fut2, 5.0)
    fut2.set_result(42)
    assert wrapped2.result(timeout=5) == 42

    fired = threading.Event()
    with futures.context_timeout(fired.set, 0.2):
        fired.wait(1.0)
    assert fired.is_set()

    not_fired = threading.Event()
    with futures.context_timeout(not_fired.set, 5.0):
        pass
    assert not not_fired.is_set()


def test_allreduce_quantized_accuracy(store):
    """Quantized allreduce matches exact allreduce within int8 tolerance
    (reference: collectives_test.py / quantization_test.py)."""
    from torchft_tpu.collectives import allreduce_quantized

    ws = 2
    groups = _make_group(store, ws, prefix="quant")
    rng = np.random.default_rng(0)
    data = [rng.standard_normal(2047).astype(np.float32) for _ in range(ws)]
    expected = sum(d.copy() for d in data)

    def run(rank):
        arr = data[rank].copy()
        allreduce_quantized(groups[rank], [arr]).wait(timeout=30)
        return arr

    results = _run_parallel([lambda r=r: run(r) for r in range(ws)])
    for r in results:
        # one quantize->dequantize round trip per value: ~1% of block max
        np.testing.assert_allclose(r, expected, atol=np.abs(expected).max() * 0.05)
    # must be meaningfully accurate, not garbage
    err = np.abs(results[0] - expected).mean() / (np.abs(expected).mean() + 1e-9)
    assert err < 0.02, f"mean relative error too high: {err}"
    for g in groups:
        g.shutdown()


def test_allreduce_quantized_buckets_reach_the_wire_in_issue_order(store):
    """Several quantized collectives in flight on one PG (DDP's gradient
    buckets) must pair across replicas by ISSUE order even when their
    quantize stages finish in another order — each runs on its own thread
    and the PG numbers ops as they are called. Rank 0 stalls its first
    bucket's quantize stage; unordered, its second bucket takes the first
    wire slot and the ranks exchange mismatched payloads."""
    import time

    from torchft_tpu.collectives import allreduce_quantized

    ws = 2
    groups = _make_group(store, ws, prefix="quant-order")
    # Different sizes per bucket: a swap cannot pass by accident.
    sizes = [4096, 1536, 7168]
    data = [
        [np.full(n, float(rank + 1 + b), np.float32) for b, n in enumerate(sizes)]
        for rank in range(ws)
    ]

    def run(rank):
        arrs = [a.copy() for a in data[rank]]
        stall = (lambda *_: time.sleep(0.5)) if rank == 0 else None
        works = [
            allreduce_quantized(
                groups[rank], [a], on_local_quantized=stall if b == 0 else None
            )
            for b, a in enumerate(arrs)
        ]
        for w in works:
            w.wait(timeout=30)
        return arrs

    results = _run_parallel([lambda r=r: run(r) for r in range(ws)])
    for arrs in results:
        for b, arr in enumerate(arrs):
            want = data[0][b] + data[1][b]
            np.testing.assert_allclose(arr, want, rtol=0.02)
    for g in groups:
        g.shutdown()


def _count_wire_bytes(groups):
    """Wraps every peer connection's send to count actual wire payload
    bytes; returns the counter dict."""
    sent = {"bytes": 0}
    for g in groups:
        inner = getattr(g, "_pg", g)  # unwrap wrappers
        for conn in inner._peers.values():
            orig = conn.send

            def wrapped(tag, arr, _orig=orig):
                sent["bytes"] += arr.nbytes
                return _orig(tag, arr)

            conn.send = wrapped
    return sent


def test_allreduce_quantized_jax_device_path(store):
    """Device-quantized allreduce: Pallas quantize -> int8 over the wire ->
    Pallas dequantize. Asserts numerics vs the exact fp32 sum AND >=3.5x
    wire byte reduction vs the fp32 ring allreduce (reference:
    collectives.py:297-415)."""
    import jax.numpy as jnp

    from torchft_tpu.collectives import allreduce_quantized_jax

    ws = 2
    n = 65536
    groups = _make_group(store, ws, prefix="qjax")
    rng = np.random.default_rng(1)
    data = [rng.standard_normal(n).astype(np.float32) for _ in range(ws)]
    expected = sum(d.copy() for d in data)

    sent = _count_wire_bytes(groups)

    def run(rank):
        arr = jnp.asarray(data[rank])
        outs = allreduce_quantized_jax(groups[rank], [arr]).wait(timeout=60)
        return np.asarray(outs[0])

    results = _run_parallel([lambda r=r: run(r) for r in range(ws)])
    quant_bytes = sent["bytes"]
    for r in results:
        np.testing.assert_allclose(
            r, expected, atol=np.abs(expected).max() * 0.05
        )
    err = np.abs(results[0] - expected).mean() / (np.abs(expected).mean() + 1e-9)
    assert err < 0.02, f"mean relative error too high: {err}"

    # Same payload through the plain fp32 ring allreduce.
    sent["bytes"] = 0
    def run_fp32(rank):
        arr = data[rank].copy()
        groups[rank].allreduce([arr]).wait(timeout=60)
        return arr

    _run_parallel([lambda r=r: run_fp32(r) for r in range(ws)])
    fp32_bytes = sent["bytes"]
    reduction = fp32_bytes / max(quant_bytes, 1)
    assert reduction >= 3.5, (
        f"wire byte reduction {reduction:.2f}x < 3.5x "
        f"(fp32={fp32_bytes}, quant={quant_bytes})"
    )
    for g in groups:
        g.shutdown()


def test_allreduce_quantized_jax_survives_donated_input(store):
    """The single-array fast path must snapshot the input: a donating
    jitted train step run during the overlapped window deletes the
    caller's buffer, and the deferred quantize+pull on the collective
    thread would then raise 'Array has been deleted' — latched as a
    spurious FT error (advisor finding r2, collectives.py)."""
    import jax.numpy as jnp

    from torchft_tpu.collectives import allreduce_quantized_jax

    ws = 2
    n = 4096
    groups = _make_group(store, ws, prefix="qjaxdon")
    rng = np.random.default_rng(7)
    data = [rng.standard_normal(n).astype(np.float32) for _ in range(ws)]
    expected = sum(d.copy() for d in data)

    def run(rank):
        # Already 1-D float32: ravel/astype short-circuit, the exact
        # aliasing case.
        arr = jnp.asarray(data[rank])
        work = allreduce_quantized_jax(groups[rank], [arr])
        arr.delete()  # what donate_argnums does to the buffer
        outs = work.wait(timeout=60)
        return np.asarray(outs[0])

    results = _run_parallel([lambda r=r: run(r) for r in range(ws)])
    for r in results:
        np.testing.assert_allclose(
            r, expected, atol=np.abs(expected).max() * 0.05
        )
    for g in groups:
        g.shutdown()


def test_allreduce_quantized_jax_scale_and_multi_array(store):
    """scale (divide-by-N) fuses into the device dequantize; multiple arrays
    of different shapes round-trip through one flat buffer."""
    import jax.numpy as jnp

    from torchft_tpu.collectives import allreduce_quantized_jax

    ws = 2
    groups = _make_group(store, ws, prefix="qjax2")
    rng = np.random.default_rng(2)
    shapes = [(128, 33), (700,), (5, 5, 5)]
    data = {
        r: [rng.standard_normal(s).astype(np.float32) for s in shapes]
        for r in range(ws)
    }
    expected = [
        (data[0][i] + data[1][i]) / ws for i in range(len(shapes))
    ]

    def run(rank):
        arrs = [jnp.asarray(a) for a in data[rank]]
        outs = allreduce_quantized_jax(
            groups[rank], arrs, scale=1.0 / ws
        ).wait(timeout=60)
        return [np.asarray(o) for o in outs]

    results = _run_parallel([lambda r=r: run(r) for r in range(ws)])
    for outs in results:
        assert [o.shape for o in outs] == shapes
        for o, e in zip(outs, expected):
            np.testing.assert_allclose(o, e, atol=np.abs(e).max() * 0.05)
    for g in groups:
        g.shutdown()


def test_allreduce_quantized_mixed_entry_points_interop(store):
    """One replica calls the numpy entry point, the other the jax entry
    point — the wire protocol is shared, so mixed-type replicas must
    produce the same (correct) result."""
    import jax.numpy as jnp

    from torchft_tpu.collectives import (
        allreduce_quantized,
        allreduce_quantized_jax,
    )

    ws = 2
    n = 4096
    groups = _make_group(store, ws, prefix="qmix")
    rng = np.random.default_rng(3)
    data = [rng.standard_normal(n).astype(np.float32) for _ in range(ws)]
    expected = data[0] + data[1]

    def run(rank):
        if rank == 0:
            arr = data[0].copy()
            allreduce_quantized(groups[0], [arr]).wait(timeout=60)
            return arr
        outs = allreduce_quantized_jax(
            groups[1], [jnp.asarray(data[1])]
        ).wait(timeout=60)
        return np.asarray(outs[0])

    results = _run_parallel([lambda r=r: run(r) for r in range(ws)])
    for r in results:
        np.testing.assert_allclose(r, expected, atol=np.abs(expected).max() * 0.05)
    for g in groups:
        g.shutdown()


def test_reduce_scatter_quantized(store):
    """Each rank ends with its own block-aligned reduced fp32 shard; shards
    tile the full buffer (reference: collectives.py:159-294)."""
    from torchft_tpu.collectives import reduce_scatter_quantized

    ws = 3
    n = 3000
    groups = _make_group(store, ws, prefix="rsq")
    rng = np.random.default_rng(5)
    data = [rng.standard_normal(n).astype(np.float32) for _ in range(ws)]
    expected = sum(d.copy() for d in data)

    def run(rank):
        return reduce_scatter_quantized(
            groups[rank], [data[rank].copy()]
        ).wait(timeout=60)

    results = _run_parallel([lambda r=r: run(r) for r in range(ws)])
    covered = np.zeros(n, bool)
    for shard, (start, end) in results:
        assert shard.shape == (end - start,)
        np.testing.assert_allclose(
            shard, expected[start:end],
            atol=np.abs(expected).max() * 0.05,
        )
        covered[start:end] = True
    assert covered.all(), "shards do not tile the buffer"

    # Tiny payload (fewer blocks than ranks): allgather fallback.
    def run_tiny(rank):
        return reduce_scatter_quantized(
            groups[rank], [data[rank][:700].copy()]
        ).wait(timeout=60)

    results = _run_parallel([lambda r=r: run_tiny(r) for r in range(ws)])
    exp = expected[:700]
    covered = np.zeros(700, bool)
    for shard, (start, end) in results:
        end = min(end, 700)
        np.testing.assert_allclose(
            shard[: end - start], exp[start:end], atol=np.abs(exp).max() * 0.05
        )
        covered[start:end] = True
    assert covered.all()
    for g in groups:
        g.shutdown()


def test_allreduce_quantized_int4_wire(store):
    """bits=4: nibble-packed wire payload, both numpy and jax entry
    points, result within int4 tolerance of the exact sum (and identical
    bytes -> identical result on every rank)."""
    import jax.numpy as jnp

    from torchft_tpu.collectives import (
        allreduce_quantized,
        allreduce_quantized_jax,
    )

    ws = 2
    n = 4 * 512 + 130  # several blocks + odd tail
    groups = _make_group(store, ws, prefix="q4")
    rng = np.random.default_rng(11)
    data = [rng.standard_normal(n).astype(np.float32) for _ in range(ws)]
    expected = data[0] + data[1]

    def run(rank):
        if rank == 0:
            arr = data[0].copy()
            allreduce_quantized(groups[0], [arr], bits=4).wait(timeout=60)
            return arr
        outs = allreduce_quantized_jax(
            groups[1], [jnp.asarray(data[1])], bits=4
        ).wait(timeout=60)
        return np.asarray(outs[0])

    results = _run_parallel([lambda r=r: run(r) for r in range(ws)])
    # int4 tolerance: block absmax / 7 per input + one requantize round
    tol = 3 * max(np.abs(d).max() for d in data) / 7.0
    for r in results:
        assert np.abs(r - expected).max() <= tol
    np.testing.assert_allclose(results[0], results[1], rtol=1e-6, atol=1e-6)
    # int4 on dense gaussian data is coarse by construction: block step =
    # absmax/7 (~0.43 here), so mean |err| ~ 2.5 half-steps across two
    # quantized inputs + the requantized sum => ~0.2 relative. That is
    # the regime error feedback exists for (see test_local_sgd EF test);
    # this gate just pins "decodes correctly", not "is precise".
    err = np.abs(results[0] - expected).mean() / (np.abs(expected).mean() + 1e-9)
    assert err < 0.3, f"mean relative error too high for int4: {err}"
    for g in groups:
        g.shutdown()


def test_reduce_scatter_quantized_int4(store):
    """bits=4 reduce_scatter: each rank gets its block-aligned shard of
    the fp32 sum, decoded from the nibble-packed wire."""
    from torchft_tpu.collectives import reduce_scatter_quantized

    ws = 2
    n = 4 * 512  # 4 blocks: 2 per rank
    groups = _make_group(store, ws, prefix="rs4")
    rng = np.random.default_rng(13)
    data = [rng.standard_normal(n).astype(np.float32) for _ in range(ws)]
    expected = data[0] + data[1]

    def run(rank):
        shard, (start, end) = reduce_scatter_quantized(
            groups[rank], [data[rank].copy()], bits=4
        ).wait(timeout=60)
        return shard, start, end

    results = _run_parallel([lambda r=r: run(r) for r in range(ws)])
    covered = []
    tol = 2 * max(np.abs(d).max() for d in data) / 7.0
    for shard, start, end in results:
        assert np.abs(shard[: end - start] - expected[start:end]).max() <= tol
        covered.append((start, end))
    assert covered == [(0, 1024), (1024, 2048)]
    for g in groups:
        g.shutdown()


def test_wire_byte_accounting_quantized_vs_fp32(store):
    """telemetry's pg_wire_tx counter makes the codec's byte cut
    measurable: an int4 allreduce of N fp32 values must move well under
    a quarter of the plain allreduce's wire bytes (nibble payload +
    fp32 block scales + the pipeline's allgather legs), and the plain
    allreduce provides the fp32 reference on the same wire."""
    from torchft_tpu import telemetry
    from torchft_tpu.collectives import allreduce_quantized
    from torchft_tpu.process_group import ReduceOp

    ws = 2
    n = 1 << 16  # 64k values, 256 KB fp32
    groups = _make_group(store, ws, prefix="bytes")
    data = np.ones(n, np.float32)

    telemetry.reset_byte_stats()
    _run_parallel(
        [
            (lambda r=r: groups[r].allreduce([data.copy()], ReduceOp.SUM)
             .wait(timeout=30))
            for r in range(ws)
        ]
    )
    fp32_tx = telemetry.byte_stats().get("pg_wire_tx", 0)
    assert fp32_tx >= n * 4, fp32_tx  # at least one full payload crossed

    telemetry.reset_byte_stats()
    _run_parallel(
        [
            (lambda r=r: allreduce_quantized(
                groups[r], [data.copy()], bits=4
            ).wait(timeout=30))
            for r in range(ws)
        ]
    )
    q4_tx = telemetry.byte_stats().get("pg_wire_tx", 0)
    assert 0 < q4_tx < fp32_tx * 0.25, (q4_tx, fp32_tx)

    for g in groups:
        g.shutdown()


def test_allreduce_quantized_int4_three_ranks_odd_size(store):
    """int4 + odd world size + non-block-multiple length: the nibble-
    packed payload must chunk across 3 ranks on BLOCK boundaries (bytes
    per block = BLOCK/2) without mis-splitting a packed byte, and every
    rank must decode the identical fp32 average."""
    from torchft_tpu.collectives import allreduce_quantized
    from torchft_tpu.process_group import ReduceOp

    ws = 3
    n = 2047  # not a block multiple; packed payload has a ragged tail
    groups = _make_group(store, ws, prefix="q4x3")
    rng = np.random.default_rng(21)
    data = [rng.standard_normal(n).astype(np.float32) for _ in range(ws)]
    expected = sum(d.copy() for d in data) / ws

    def run(rank):
        arr = data[rank].copy()
        allreduce_quantized(
            groups[rank], [arr], op=ReduceOp.AVG, bits=4
        ).wait(timeout=60)
        return arr

    results = _run_parallel([lambda r=r: run(r) for r in range(ws)])
    # All ranks decode the same bytes -> bitwise-identical results.
    np.testing.assert_array_equal(results[0], results[1])
    np.testing.assert_array_equal(results[0], results[2])
    # int4 tolerance: one quantize->dequantize round trip per value.
    tol = 2 * max(np.abs(d).max() for d in data) / 7.0
    np.testing.assert_allclose(results[0], expected, atol=tol)
    for g in groups:
        g.shutdown()


# ---------------------------------------------------------------------------
# The quantized wire stage in reused host buffers (collectives._WireScratch)
# ---------------------------------------------------------------------------

_B = 512  # collectives.BLOCK


def _oracle_quantize(flat, bits):
    """Blockwise quantize as it was written before the reused buffers:
    every intermediate a new array."""
    qmax = {8: 127.0, 4: 7.0}[bits]
    blocks = (flat.size + _B - 1) // _B
    padded = np.zeros(blocks * _B, dtype=np.float32)
    padded[: flat.size] = flat
    mat = padded.reshape(blocks, _B)
    s = np.abs(mat).max(axis=1)
    s /= qmax
    np.copyto(s, 1.0, where=(s == 0))
    buf = mat / s[:, None]
    np.rint(buf, out=buf)
    np.clip(buf, -qmax, qmax, out=buf)
    q = np.empty(blocks * _B, dtype=np.int8)
    q[:] = buf.reshape(-1)
    if bits == 4:
        u = q.astype(np.uint8) & 0xF
        q = (u[0::2] | (u[1::2] << 4)).view(np.int8)
    return q, s


def _oracle_dequantize(q, s, n, bits):
    if bits == 4:
        u = q.view(np.uint8)
        wide = np.empty(u.size * 2, dtype=np.uint8)
        wide[0::2] = u & 0xF
        wide[1::2] = u >> 4
        q = (wide ^ 8).astype(np.int8) - 8
    mat = q.astype(np.float32).reshape(s.size, _B)
    mat *= s[:, None]
    return mat.reshape(-1)[:n]


def _oracle_wire(data, bits):
    """(q_final, s_final, result) of the alltoall -> fp32 reduce ->
    requantize -> allgather protocol over ``data`` (one flat fp32 array a
    rank), every rank's view being the same."""
    ws = len(data)
    n = data[0].size
    bpb = _B // (8 // bits)
    quantized = [_oracle_quantize(d, bits) for d in data]
    blocks = quantized[0][1].size
    counts = [len(c) for c in np.array_split(np.arange(blocks), ws)]
    q_parts, s_parts = [], []
    off = 0
    for c in counts:
        acc = np.zeros(c * _B, np.float32)
        for q, s in quantized:  # rank order
            acc += _oracle_dequantize(
                q[off * bpb : (off + c) * bpb], s[off : off + c], c * _B, bits
            )
        rq, rs = _oracle_quantize(acc, bits)
        q_parts.append(rq)
        s_parts.append(rs)
        off += c
    q_final = np.concatenate(q_parts)
    s_final = np.concatenate(s_parts)
    return q_final, s_final, _oracle_dequantize(q_final, s_final, n, bits)


def _small_pieces(monkeypatch):
    """Pieces of 3 blocks and tasks of 8, so that a chunk of a few dozen
    blocks runs several tasks of several pieces with a ragged last one."""
    import torchft_tpu.collectives as C

    monkeypatch.setattr(C, "_PIECE_BLOCKS", 3)
    monkeypatch.setattr(C, "_BLOCKS_PER_TASK", 8)
    monkeypatch.setattr(C, "_NATIVE_BLOCKS_PER_TASK", 5)


def _wire_data(ws, n, seed):
    rng = np.random.default_rng(seed)
    data = [
        (rng.standard_normal(n) * rng.choice([1e-3, 1.0, 40.0], n)).astype(np.float32)
        for _ in range(ws)
    ]
    for d in data:
        d[_B : 2 * _B] = 0.0  # an all-zero block: scale 1.0
    return data


def _allreduce_quantized_all(groups, data, bits=8):
    from torchft_tpu.collectives import allreduce_quantized

    def run(rank):
        arr = data[rank].copy()
        allreduce_quantized(groups[rank], [arr], bits=bits).wait(timeout=60)
        return arr

    return _run_parallel([lambda r=r: run(r) for r in range(len(groups))])


@pytest.mark.parametrize("tail", [0, 777], ids=["whole", "ragged"])
@pytest.mark.parametrize("ws", [2, 3, 4])
@pytest.mark.parametrize("bits", [8, 4])
def test_quantized_wire_is_bit_equal_to_the_plain_oracle(
    store, monkeypatch, bits, ws, tail
):
    """Reduced payload, scales and final result equal, bit for bit, what
    the allocate-everything expressions give: same fp32 ``float(q) *
    scale`` per peer, summed in rank order, same blockwise requantize."""
    import torchft_tpu.collectives as C

    _small_pieces(monkeypatch)
    n = _B * ws * 37 + tail
    data = _wire_data(ws, n, seed=100 * bits + 10 * ws + (tail > 0))
    want_q, want_s, want = _oracle_wire(data, bits)
    groups = _make_group(store, ws, prefix=f"oracle{bits}{ws}{tail}")

    def run(rank):
        q, s = C.quantize_blockwise(data[rank], bits)
        return C._quantized_wire_pipeline(groups[rank], q, s, n, bits)

    for q_final, s_final in _run_parallel([lambda r=r: run(r) for r in range(ws)]):
        assert q_final.dtype == np.int8 and s_final.dtype == np.float32
        np.testing.assert_array_equal(q_final, want_q)
        np.testing.assert_array_equal(s_final.view(np.uint32), want_s.view(np.uint32))
    for got in _allreduce_quantized_all(groups, data, bits):
        np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))
    for g in groups:
        g.shutdown()


def test_quantized_wire_reuse_leaves_no_stale_values(store, monkeypatch):
    """A large bucket, a smaller one, the large one again on the same
    process groups: the buffers that held the larger payload have stale
    values behind the smaller one, and each result still equals a fresh
    group's."""
    _small_pieces(monkeypatch)
    ws = 3
    sizes = [_B * ws * 41 + 100, _B * ws * 5 + 3, _B * ws * 41 + 100]
    payloads = [_wire_data(ws, n, seed=7 + i) for i, n in enumerate(sizes)]
    kept = _make_group(store, ws, prefix="reuse-kept")
    for i, data in enumerate(payloads):
        fresh = _make_group(store, ws, prefix=f"reuse-fresh{i}")
        want = _allreduce_quantized_all(fresh, data)
        got = _allreduce_quantized_all(kept, data)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g.view(np.uint32), w.view(np.uint32))
        np.testing.assert_array_equal(got[0], _oracle_wire(data, 8)[2])
        for g in fresh:
            g.shutdown()
    for g in kept:
        g.shutdown()


def _second_wire_before_first_push(monkeypatch, module, name):
    """Makes the first collective of every process group hold its
    ``dequant_push`` — the call of ``module.name`` — until the group's
    second collective has left the wire: the first one's joined payload
    is then read after a later bucket has been through every buffer the
    wire turn owns."""
    import torchft_tpu.collectives as C

    cv = threading.Condition()
    wires = {}  # id(pg) -> collectives that have left the wire
    here = threading.local()
    real_wire = C._quantized_wire_pipeline
    real_push = getattr(module, name)

    def wire(pg, *args, **kwargs):
        out = real_wire(pg, *args, **kwargs)
        with cv:
            here.pg, here.ordinal = id(pg), wires.get(id(pg), 0)
            wires[id(pg)] = here.ordinal + 1
            cv.notify_all()
        return out

    def push(*args, **kwargs):
        if here.ordinal == 0:
            with cv:
                assert cv.wait_for(lambda: wires[here.pg] >= 2, timeout=60)
        return real_push(*args, **kwargs)

    monkeypatch.setattr(C, "_quantized_wire_pipeline", wire)
    monkeypatch.setattr(module, name, push)


@pytest.mark.parametrize("path", ["numpy", "device"])
def test_quantized_wire_result_outlives_the_next_wire_turn(store, monkeypatch, path):
    """Two buckets of one size in flight on one process group: the first
    one's result, decoded only after the second has finished its wire
    turn, is its own."""
    import jax.numpy as jnp

    import torchft_tpu.collectives as C
    from torchft_tpu.ops import quantization as Q

    ws = 2
    n = _B * ws * 4
    payloads = [_wire_data(ws, n, seed=31), _wire_data(ws, n, seed=32)]
    want = [_oracle_wire(data, 8)[2] for data in payloads]
    groups = _make_group(store, ws, prefix=f"own-{path}")
    if path == "device":
        monkeypatch.setenv("TORCHFT_FORCE_DEVICE_QUANT", "1")
        _second_wire_before_first_push(
            monkeypatch, Q, "dequantize_leaves_from_transfer"
        )
    else:
        _second_wire_before_first_push(monkeypatch, C, "dequantize_blockwise")

    def run(rank):
        if path == "device":
            works = [
                C.allreduce_quantized_jax(groups[rank], [jnp.asarray(p[rank])])
                for p in payloads
            ]
            return [np.asarray(w.wait(timeout=60)[0]) for w in works]
        arrs = [p[rank].copy() for p in payloads]
        works = [C.allreduce_quantized(groups[rank], [a]) for a in arrs]
        for w in works:
            w.wait(timeout=60)
        return arrs

    for outs in _run_parallel([lambda r=r: run(r) for r in range(ws)]):
        for out, w in zip(outs, want):
            if path == "device":  # the device decodes the same bytes
                np.testing.assert_allclose(out, w, rtol=1e-6, atol=1e-7)
            else:
                np.testing.assert_array_equal(out.view(np.uint32), w.view(np.uint32))
    assert not np.allclose(want[0], want[1])
    for g in groups:
        g.shutdown()


def test_reduce_scatter_quantized_shard_survives_a_later_collective(store):
    """The shard handed to the caller is a copy: the fp32 sum it was cut
    from belongs to the next wire turn."""
    from torchft_tpu.collectives import reduce_scatter_quantized

    ws = 2
    n = _B * ws * 6
    first, later = _wire_data(ws, n, seed=41), _wire_data(ws, n, seed=42)
    groups = _make_group(store, ws, prefix="rs-own")

    def run(rank):
        shard, span = reduce_scatter_quantized(
            groups[rank], [first[rank].copy()]
        ).wait(timeout=60)
        before = shard.copy()
        reduce_scatter_quantized(groups[rank], [later[rank].copy()]).wait(timeout=60)
        return shard, before, span

    for shard, before, (start, end) in _run_parallel(
        [lambda r=r: run(r) for r in range(ws)]
    ):
        np.testing.assert_array_equal(shard, before)
        np.testing.assert_allclose(
            shard, (first[0] + first[1])[start:end],
            atol=np.abs(first[0] + first[1]).max() * 0.05,
        )
    for g in groups:
        g.shutdown()


def test_quantized_wire_scratch_is_dropped_on_reconfigure(store):
    """A quorum that shrinks from three to two changes every chunk size:
    reconfigure lets the old buffers go, the next collective makes its
    own, and its result is right."""
    ws = 3
    groups = _make_group(store, ws, prefix="drop3")
    data = _wire_data(ws, _B * 6 * 5 + 9, seed=51)
    got = _allreduce_quantized_all(groups, data)
    np.testing.assert_array_equal(got[0], _oracle_wire(data, 8)[2])
    assert all("_quant_wire_scratch" in g.__dict__ for g in groups)
    old = groups[0].__dict__["_quant_wire_scratch"]

    groups[2].shutdown()
    assert "_quant_wire_scratch" not in groups[2].__dict__
    _run_parallel([
        lambda r=r: groups[r].configure(f"{store.address()}/drop2", r, 2)
        for r in range(2)
    ])
    assert all("_quant_wire_scratch" not in g.__dict__ for g in groups)
    got = _allreduce_quantized_all(groups[:2], data[:2])
    for g in got:
        np.testing.assert_array_equal(g, _oracle_wire(data[:2], 8)[2])
    assert groups[0].__dict__["_quant_wire_scratch"] is not old
    for g in groups[:2]:
        g.shutdown()


def test_a_quantized_collective_on_an_aborted_group_hangs_no_scratch_on_it(store):
    """What a torn step's collective does when it gets the wire turn
    after the abort: it fails, and the dead group pins no buffers."""
    from torchft_tpu.collectives import allreduce_quantized

    ws = 2
    groups = _make_group(store, ws, prefix="dead2")
    data = _wire_data(ws, _B * 6 * 5 + 9, seed=52)
    _allreduce_quantized_all(groups, data)
    for g in groups:
        g.abort()
        assert "_quant_wire_scratch" not in g.__dict__
        with pytest.raises(RuntimeError):
            allreduce_quantized(g, [data[0].copy()]).wait(timeout=30)
        assert "_quant_wire_scratch" not in g.__dict__
    for g in groups:
        g.shutdown()


def test_wire_scratch_lends_a_result_buffer_to_one_reader_at_a_time():
    """A joined payload goes back to the free list when its last view is
    gone (what a host-to-device copy in flight holds is a view), and not
    before; turn buffers grow once and are then reused."""
    from torchft_tpu.collectives import _WireScratch

    scratch = _WireScratch()
    q, s = scratch.result(4096, 8)
    assert (q.dtype, q.size, s.dtype, s.size) == (np.int8, 4096, np.float32, 8)
    assert scratch.counts() == {"fresh_bytes": 4096 + 32, "reused_bytes": 0}
    in_flight = q[100:200]
    del q, s
    q2, s2 = scratch.result(4096, 8)
    assert not np.shares_memory(in_flight, q2)
    assert scratch.counts()["fresh_bytes"] == 4096 + 32
    del in_flight
    q3, s3 = scratch.result(2048, 4)  # the first one is free again, and fits
    assert scratch.counts() == {"fresh_bytes": 0, "reused_bytes": 2048 + 16}
    assert not np.shares_memory(q2, q3) and not np.shares_memory(q3, s3)

    # several free buffers of different sizes: the smallest that fits is
    # lent; one that fits nothing asked for is replaced, not kept
    big, _ = scratch.result(100_000, 8)
    del q2, s2, q3, s3, big, _
    scratch.counts()
    assert sorted(b.size for b in scratch._free) == [4128, 4128, 100_064]
    small, _s = scratch.result(4096, 8)
    large, _l = scratch.result(50_000, 8)
    assert scratch.counts() == {"fresh_bytes": 0, "reused_bytes": 4128 + 50_080}
    huge, _h = scratch.result(200_000, 8)
    assert scratch.counts()["fresh_bytes"] == 200_032
    assert scratch._free == []  # the last 4,128-byte one made way
    del small, _s, large, _l, huge, _h

    acc = scratch.turn("acc", np.float32, 1000)
    acc[:] = 1.0
    assert scratch.counts() == {"fresh_bytes": 4000, "reused_bytes": 0}
    again = scratch.turn("acc", np.float32, (10, 50))
    assert again.shape == (10, 50) and np.shares_memory(acc, again)
    assert scratch.counts() == {"fresh_bytes": 0, "reused_bytes": 2000}


@pytest.mark.timeout(120)
@pytest.mark.parametrize("path", ["numpy", "device"])
def test_quantized_wire_many_buckets_in_flight_keep_their_own_results(
    store, monkeypatch, path
):
    """Twelve buckets of three sizes in flight at once on each of two
    ranks, threads switching every 10 us: the wire turn's buffers pass
    from bucket to bucket and the joined payloads go out and come back
    (on the device path whenever JAX lets go of them) while later buckets
    are on the wire, and every result is its own."""
    import sys

    import jax.numpy as jnp

    import torchft_tpu.collectives as C

    _small_pieces(monkeypatch)
    if path == "device":
        monkeypatch.setenv("TORCHFT_FORCE_DEVICE_QUANT", "1")
    ws = 2
    sizes = [_B * ws * n for n in (3, 11, 29)] * 4
    payloads = [_wire_data(ws, n, seed=60 + i) for i, n in enumerate(sizes)]
    want = [_oracle_wire(data, 8)[2] for data in payloads]
    groups = _make_group(store, ws, prefix=f"stress-{path}")

    def run(rank):
        if path == "device":
            works = [
                C.allreduce_quantized_jax(groups[rank], [jnp.asarray(p[rank])])
                for p in payloads
            ]
            return [np.asarray(w.wait(timeout=60)[0]) for w in works]
        arrs = [p[rank].copy() for p in payloads]
        works = [C.allreduce_quantized(groups[rank], [a]) for a in arrs]
        for w in works:
            w.wait(timeout=60)
        return arrs

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        results = _run_parallel([lambda r=r: run(r) for r in range(ws)])
    finally:
        sys.setswitchinterval(interval)
    for arrs in results:
        for got, w in zip(arrs, want):
            if path == "device":  # the device decodes the same bytes
                np.testing.assert_allclose(got, w, rtol=1e-6, atol=1e-7)
            else:
                np.testing.assert_array_equal(got.view(np.uint32), w.view(np.uint32))
    for g in groups:
        g.shutdown()


# ---------------------------------------------------------------------------
# Kept receive buffers (process_group._RecvBuffers)
# ---------------------------------------------------------------------------

_LARGE = 1 << 16  # _net.LARGE_FRAME: from here on a payload lands in a kept buffer


@pytest.fixture
def pg_journal(tmp_path, monkeypatch):
    """A configured journal; yields a reader of its ``pg_collective``
    attributes, one dict a collective, in file order."""
    import json

    from torchft_tpu import telemetry

    path = str(tmp_path / "journal.jsonl")
    monkeypatch.setenv("TORCHFT_JOURNAL_FILE", path)
    monkeypatch.delenv("TORCHFT_JOURNAL_DIR", raising=False)
    telemetry.reset_event_log()

    def collectives():
        with open(path) as f:
            evs = [json.loads(line) for line in f]
        return [e["attrs"] for e in evs if e["event"] == "pg_collective"]

    yield collectives
    telemetry.reset_event_log()


def _mesh_groups(backend, store, ws, prefix):
    """``ws`` ranks of the socket group, or of the native group, whose
    alltoall, reduce_scatter, send and recv ride the same Python mesh."""
    if backend == "socket":
        return _make_group(store, ws, prefix=prefix, timeout=30.0)
    from torchft_tpu import _native
    from torchft_tpu.process_group import ProcessGroupNative

    if not _native.is_available():
        pytest.skip("native engine unavailable")
    groups = [ProcessGroupNative(timeout=30.0) for _ in range(ws)]
    _run_parallel([
        lambda r=r: groups[r].configure(f"{store.address()}/{prefix}", r, ws)
        for r in range(ws)
    ])
    return groups


def _free_sizes(pg):
    return sorted(b.size for c in pg._peers.values() for b in c.buffers._free)


MESHES = pytest.mark.parametrize("backend", ["socket", "native"])


@MESHES
def test_wire_turns_land_in_kept_buffers_from_the_second_on(store, pg_journal, backend):
    """Four ranks, six wire turns of one size: the first turn's messages
    make their buffers (two a size and connection: a peer may be one
    message ahead), every later turn reads ``rx_fresh_bytes`` 0 on every
    collective that moved a payload over the Python mesh, and the result
    is the plain oracle's every time."""
    import torchft_tpu.collectives as C

    ws, turns = 4, 6
    chunk = _LARGE  # bytes of int8 payload a rank and chunk: just large
    n = ws * chunk
    groups = _mesh_groups(backend, store, ws, f"kept-{backend}")
    data = [_wire_data(ws, n, seed=60 + t) for t in range(turns)]

    def run(rank):
        outs = []
        for t in range(turns):
            q, s = C.quantize_blockwise(data[t][rank])
            q_f, s_f = C._quantized_wire_pipeline(groups[rank], q, s, n)
            outs.append((q_f.copy(), s_f.copy()))
        return outs

    try:
        results = _run_parallel([lambda r=r: run(r) for r in range(ws)])
        for t in range(turns):
            want_q, want_s, _ = _oracle_wire(data[t], 8)
            for outs in results:
                np.testing.assert_array_equal(outs[t][0], want_q)
                np.testing.assert_array_equal(outs[t][1], want_s)
        fresh = [e["rx_fresh_bytes"] for e in pg_journal()
                 if e["op"] in ("alltoall", "allgather") and "rx_fresh_bytes" in e]
        # per rank and turn one alltoall on the mesh, and on the socket
        # group one allgather beside it (the native group's rides its engine)
        per_turn = ws * (2 if backend == "socket" else 1)
        assert len(fresh) == per_turn * turns
        made = sorted(fresh, reverse=True)[: per_turn]
        # the first messages of the one size: its own buffer and a spare,
        # from each of three peers, on each rank
        assert sum(made) == ws * (ws - 1) * 2 * chunk
        assert sum(fresh) == sum(made)  # nothing after them
        # every rank's list holds exactly what those turns made, all free
        for g in groups:
            assert _free_sizes(g) == [chunk] * (2 * (ws - 1))
    finally:
        for g in groups:
            g.shutdown()


@MESHES
def test_a_received_array_that_is_alive_is_never_handed_out_again(store, backend):
    """Hold what one turn received, and a view of what another did,
    across ten more turns of the same sizes: their bytes stay, because a
    buffer somebody still sees is not on the free list; the turns
    meanwhile make new buffers instead, and once the holders let go the
    held buffers come back."""
    ws, n = 2, _LARGE // 4 + 512  # fp32: just over the threshold
    groups = _mesh_groups(backend, store, ws, f"held-{backend}")

    def turn(rank, t):
        mine = [np.full(n, 100 * t + 10 * rank + j, np.float32) for j in range(ws)]
        return groups[rank].alltoall(mine).wait(timeout=30)[1 - rank]

    def run(rank):
        whole = turn(rank, 0)
        view = turn(rank, 1)[7:99]
        kept = whole.copy(), view.copy()
        seen = []
        for t in range(2, 12):
            got = turn(rank, t)
            assert not np.shares_memory(got, whole)
            assert not np.shares_memory(got, view)
            seen.append(float(got[0]))
        np.testing.assert_array_equal(whole, kept[0])
        np.testing.assert_array_equal(view, kept[1])
        assert whole[0] == 10 * (1 - rank) + rank
        return seen

    try:
        results = _run_parallel([lambda r=r: run(r) for r in range(ws)])
        for rank, seen in enumerate(results):
            assert seen == [100.0 * t + 10 * (1 - rank) + rank for t in range(2, 12)]
        # the two held buffers and the two or three the later turns went
        # round in (a turn's result lives until the next one's replaces
        # it, and the peer may be a message ahead), all back now that the
        # holders are gone
        for g in groups:
            sizes = _free_sizes(g)
            assert set(sizes) == {4 * n} and 4 <= len(sizes) <= 5
    finally:
        for g in groups:
            g.shutdown()


@MESHES
def test_a_message_larger_than_the_turns_goes_with_the_wire_scratch(store, backend):
    """A heal-sized message over the group leaves its buffers on the
    connection's list once the receiver lets the array go, and
    ``_drop_wire_scratch`` (reconfigure, abort, shutdown) lets them go:
    nothing of it is retained, and the next message makes its own."""
    ws, big = 2, 1 << 20
    groups = _mesh_groups(backend, store, ws, f"heal-{backend}")

    def run(rank):
        pg = groups[rank]
        if rank == 0:
            pg.send([np.arange(big, dtype=np.uint8)], dst=1, tag="heal").wait(timeout=30)
            return None
        (got,) = pg.recv(src=0, tag="heal").wait(timeout=30)
        assert got[-1] == (big - 1) % 256
        del got
        return _free_sizes(pg)

    try:
        results = _run_parallel([lambda r=r: run(r) for r in range(ws)])
        assert results[1] == [big, big]
        conn = groups[1]._peers[0]
        groups[1]._drop_wire_scratch()
        assert _free_sizes(groups[1]) == [] and conn.buffers._seen == set()
        groups[1].abort()
        assert conn.buffers._free == []
    finally:
        for g in groups:
            g.shutdown()


def _torn_frame_conn(n):
    """A hand-made connection that has received one whole message of
    ``n`` bytes (so its list holds that size's two buffers, painted 0xAB)
    and is now half way through a second one: (conn, the peer's socket,
    the painted buffers)."""
    import socket as socket_mod
    import time

    from torchft_tpu import _net
    from torchft_tpu.process_group import _PeerConn

    a, b = socket_mod.socketpair()
    conn = _PeerConn(a, peer=1)
    header = {"tag": "t", "dtype": "uint8", "shape": [n]}
    _net.send_json(b, header)
    _net.send_frame(b, bytes(n))
    got = conn.recv("t", timeout=5.0)
    del got
    painted = list(conn.buffers._free)
    assert [p.size for p in painted] == [n, n]
    for p in painted:
        p[:] = 0xAB
    _net.send_json(b, header)
    b.sendall(struct.pack(">I", n) + bytes(n // 2))  # ... and no more
    deadline = time.monotonic() + 5
    while len(conn.buffers._free) == 2 and time.monotonic() < deadline:
        time.sleep(0.005)
    return conn, b, painted


@pytest.mark.parametrize("how", ["peer_death", "abort"])
def test_a_reader_that_stops_mid_frame_leaves_no_buffer_it_can_write_on_the_list(how):
    """The reader thread is half way into a kept buffer when the peer
    dies, or when this side aborts: that buffer is on no list anybody
    lends from (what still refers to it is the dead reader's own frame),
    and the one that stayed on the list was never written."""
    n = 4 * _LARGE
    conn, b, painted = _torn_frame_conn(n)
    try:
        assert len(conn.buffers._free) == 1  # the other is being filled
        (idle,) = conn.buffers._free
        if how == "peer_death":
            b.close()
        else:
            conn.close()
            assert conn.buffers._free == []  # close lets the list go
        conn._reader.join(timeout=5)
        assert not conn._reader.is_alive() and conn.dead is not None
        filling = next(p for p in painted if p is not idle)
        assert (idle == 0xAB).all()  # never lent, never written
        assert (filling[: n // 2] == 0).all()  # the half that arrived
        assert all(f is not filling for f in conn.buffers._free)
        with pytest.raises(RuntimeError, match="died"):
            conn.recv("t", timeout=5.0)
        assert all(f is not filling for f in conn.buffers._free)
    finally:
        conn.close()
        b.close()


@pytest.mark.timeout(120)
def test_kept_buffers_under_readers_that_let_go_on_other_threads():
    """More threads than cores and a short switch interval: one thread
    receives 400 messages of two sizes, six others check each array some
    messages later and drop it there, so buffers come back to the
    connection's list (a finalizer, on whichever thread held the last
    view) while its reader lends from it. A buffer handed out while an
    array still saw it would show as another message's bytes."""
    import queue
    import socket as socket_mod
    import sys

    from torchft_tpu.process_group import _PeerConn

    a, b = socket_mod.socketpair()
    left, right = _PeerConn(a, peer=1), _PeerConn(b, peer=0)
    sizes = (_LARGE, 3 * _LARGE)
    total, checkers = 400, 6
    held: "queue.Queue" = queue.Queue(maxsize=12)
    bad, seen = [], []

    def check():
        while True:
            item = held.get()
            if item is None:
                return
            i, arr = item
            if not (arr == i % 251).all() or arr.size != sizes[i % 2]:
                bad.append(i)
            seen.append(i)

    def send():
        for i in range(total):
            left.send(f"t{i}", np.full(sizes[i % 2], i % 251, np.uint8))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    threads = [threading.Thread(target=check) for _ in range(checkers)]
    threads.append(threading.Thread(target=send))
    try:
        for t in threads:
            t.start()
        for i in range(total):
            held.put((i, right.recv(f"t{i}", timeout=30.0)))
        for _ in range(checkers):
            held.put(None)
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
        left.close()
        right.close()
    assert bad == [] and sorted(seen) == list(range(total))


def test_receive_buffers_are_found_by_index_and_size_never_by_value(monkeypatch):
    """``_RecvBuffers.lend`` never compares arrays (``list.remove`` on a
    list of arrays did, elementwise, and cost PR 24 its chip time): with
    every comparison of two buffers forbidden it still lends, exact size
    first and the newest of a size; a size's first message makes its
    spare, a small frame gets nothing; and the list keeps ``KEEP_BYTES``
    at most, the oldest going first."""
    from torchft_tpu.process_group import _RecvBuffers

    class Buf(np.ndarray):
        def __eq__(self, other):
            raise AssertionError("buffers compared by value")

        __ne__ = __eq__

    real_empty = np.empty
    monkeypatch.setattr(
        "torchft_tpu.process_group.np.empty",
        lambda *a, **k: real_empty(*a, **k).view(Buf),
    )
    bufs = _RecvBuffers()
    assert bufs.lend(_LARGE - 1) == (None, 0)
    a, fresh = bufs.lend(_LARGE)
    assert (a.size, a.dtype, fresh) == (_LARGE, np.uint8, 2 * _LARGE)
    b, fresh = bufs.lend(_LARGE)  # the spare
    assert fresh == 0 and not np.shares_memory(a, b)
    c, fresh = bufs.lend(_LARGE)  # both are out: one more, no second spare
    assert fresh == _LARGE and bufs._free == []
    big, fresh = bufs.lend(3 * _LARGE)
    assert fresh == 6 * _LARGE and [f.size for f in bufs._free] == [3 * _LARGE]
    a[:] = 1
    b[:] = 2
    del a
    del b  # came back last: lent first
    again, fresh = bufs.lend(_LARGE)
    assert fresh == 0 and again[0] == 2
    del again, c, big
    assert sorted(f.size for f in bufs._free) == [_LARGE] * 3 + [3 * _LARGE] * 2

    monkeypatch.setattr(_RecvBuffers, "KEEP_BYTES", 5 * _LARGE)
    x, _ = bufs.lend(_LARGE)  # 3 x 64K + 2 x 192K on the list: over the cap
    # the oldest go until the rest fits: the big spare (made first of
    # those still there), then the small ones in the order they came back
    assert sum(f.size for f in bufs._free) <= 5 * _LARGE
    assert 3 * _LARGE in [f.size for f in bufs._free]
    bufs.drop()
    assert bufs._free == [] and bufs._seen == set()
    del x  # returns to the list that was, not to this one
    assert bufs._free == []


# ---------------------------------------------------------------------------
# The wire turn's codec: the library's one pass a block against numpy's passes
# ---------------------------------------------------------------------------


def _needs_codec():
    from torchft_tpu import _native

    if not _native.is_available():
        pytest.skip("native library unavailable")
    return _native


def _both_codecs(peers, blocks):
    """(payload, scales, fp32 sum) of ``peers``' chunk of ``blocks``
    blocks by the numpy passes and by the library."""
    import torchft_tpu.collectives as C

    native = _needs_codec()
    n = blocks * _B
    acc, rq, rs = np.empty(n, np.float32), np.empty(n, np.int8), np.empty(blocks, np.float32)
    tmp = np.empty(C._task_tmp_shape(blocks), np.float32)
    C._dequantize_sum(acc, peers, 8, tmp)
    C._quantize_into(acc, 8, rq, rs, tmp)
    assert C._native_codec(peers, blocks, 8) is native
    acc2, rq2, rs2 = np.full(n, 7.0, np.float32), np.full(n, 7, np.int8), np.full(blocks, 7.0, np.float32)
    C._parallel_over_blocks(
        blocks, native.q8_reducer(peers, acc2, rq2, rs2), C._NATIVE_BLOCKS_PER_TASK
    )
    # ... and the form the allreduce runs, which writes no sum
    rq3, rs3 = np.full(n, 7, np.int8), np.full(blocks, 7.0, np.float32)
    C._parallel_over_blocks(
        blocks, native.q8_reducer(peers, None, rq3, rs3), C._NATIVE_BLOCKS_PER_TASK
    )
    np.testing.assert_array_equal(rq2, rq3)
    np.testing.assert_array_equal(rs2.view(np.uint32), rs3.view(np.uint32))
    return (rq, rs, acc), (rq2, rs2, acc2)


def _assert_same_bits(numpy_out, native_out):
    (rq, rs, acc), (rq2, rs2, acc2) = numpy_out, native_out
    np.testing.assert_array_equal(rq, rq2)
    np.testing.assert_array_equal(rs.view(np.uint32), rs2.view(np.uint32))
    # the sums bit for bit, the sign of a zero aside (it requantizes to
    # the same byte): -0.0 == 0.0 here, and everything else by its bits
    np.testing.assert_array_equal(acc, acc2)
    nonzero = acc != 0
    np.testing.assert_array_equal(
        acc.view(np.uint32)[nonzero], acc2.view(np.uint32)[nonzero]
    )


@pytest.mark.parametrize("tasks", ["one_task", "several_tasks"])
@pytest.mark.parametrize("n_peers", [1, 2, 3, 4])
def test_native_codec_equals_numpy_bit_for_bit_on_random_chunks(
    monkeypatch, n_peers, tasks
):
    """Heavy-tailed random payloads (every block's largest lands on
    +-127), an all-zero block, a zero-padded last block and peers whose
    scales differ by orders of magnitude: the same bytes, scale bits and
    sums, in one task and in several ragged ones."""
    import torchft_tpu.collectives as C

    if tasks == "several_tasks":
        _small_pieces(monkeypatch)
    blocks = 23
    rng = np.random.default_rng(100 + n_peers)
    peers = []
    for p in range(n_peers):
        x = (rng.standard_normal(blocks * _B) * rng.choice([1e-4, 1.0, 300.0], blocks * _B)
             * 10.0 ** (3 * p)).astype(np.float32)
        x[_B : 2 * _B] = 0.0  # all zero
        x[-77:] = 0.0  # a short last block, padded
        peers.append(C.quantize_blockwise(x))
    assert all(np.abs(q).max() == 127 for q, _ in peers)
    _assert_same_bits(*_both_codecs(peers, blocks))


def test_native_codec_equals_numpy_on_ties_zero_sums_and_underflow():
    """Quotients that tie at .5 round to even (0.5 -> 0, 1.5 -> 2, 2.5 ->
    2, and their negatives); sums that cancel to +0.0 and a sum of
    -0.0s; a block whose largest is 127 scales exactly; a block of
    denormals whose scale underflows to 0 takes scale 1; and a received
    scale of 0, which no quantizer sends, still agrees."""
    blocks = 6
    q0 = np.zeros(blocks * _B, np.int8)
    q1 = np.zeros(blocks * _B, np.int8)
    s0 = np.ones(blocks, np.float32)
    s1 = np.ones(blocks, np.float32)
    # block 0: peer 0 pins the scale at c = 8 (127 * 8 / 127), peer 1
    # adds odd halves of it: quotients k + 0.5
    q0[0], s0[0] = 127, 8.0
    odd = np.array([1, 3, 5, 7, -1, -3, -5, -7, 251 - 256, 253], np.int64)
    q1[1 : 1 + odd.size] = odd.astype(np.int8)
    s1[0] = 4.0
    # block 1: cancels to +0.0 everywhere
    q0[_B : 2 * _B], q1[_B : 2 * _B] = 5, -5
    # block 2: zeros times a negative scale: a sum of -0.0s
    s0[2] = s1[2] = -1.0
    # block 3: clips and exact +-127
    q0[3 * _B : 4 * _B] = np.resize(np.array([127, -127, 64, -64, 1], np.int8), _B)
    q1[3 * _B : 4 * _B] = np.resize(np.array([127, -127, 63, -1, 0], np.int8), _B)
    s0[3], s1[3] = 0.75, 0.5
    # block 4: denormal sums, absmax / 127 underflows to 0
    q0[4 * _B : 5 * _B] = np.resize(np.array([1, -1, 0], np.int8), _B)
    s0[4] = np.float32(1e-45)
    s1[4] = np.float32(1e-45)
    # block 5: a zero scale from the wire
    q0[5 * _B :], q1[5 * _B :] = 9, 100
    s0[5], s1[5] = 0.0, 0.25
    peers = [(q0, s0), (q1, s1)]
    numpy_out, native_out = _both_codecs(peers, blocks)
    _assert_same_bits(numpy_out, native_out)
    rq, rs, acc = native_out
    assert rs[0] == 8.0 and rq[0] == 127
    np.testing.assert_array_equal(rq[1:9], [0, 2, 2, 4, 0, -2, -2, -4])
    assert (acc[_B : 2 * _B] == 0).all() and (rq[_B : 3 * _B] == 0).all()
    assert np.signbit(acc[2 * _B : 3 * _B]).all() and rs[1] == rs[2] == 1.0
    assert rq[3 * _B] == 127 and rq[3 * _B + 1] == -127
    assert rs[4] == 1.0 and (rq[4 * _B : 5 * _B] == 0).all()


def _wire_spans(drained):
    return [s[6] for s in drained if s[0].endswith("::wire_reduce") and "numpy_blocks" in s[6]]


@pytest.mark.parametrize("case", ["int8", "int4", "tiny", "no_library"])
def test_which_codec_reduces_is_read_from_the_payload_and_the_process(
    store, pg_journal, monkeypatch, case
):
    """8-bit whole blocks in a process with the library: every block by
    the native pass. 4-bit payloads, a payload of fewer blocks than
    ranks, and a process without the library: the numpy passes
    (``native_blocks`` 0), to the oracle's bytes."""
    from torchft_tpu import _native, telemetry

    native = _needs_codec()
    ws = 3
    bits = 4 if case == "int4" else 8
    n = 2 * _B - 5 if case == "tiny" else _B * ws * 9 + 13
    if case == "no_library":
        monkeypatch.setattr(_native, "is_available", lambda: False)
        monkeypatch.setattr(native, "q8_reducer", lambda *a: pytest.fail("called"))
    groups = _make_group(store, ws, prefix=f"which-{case}")
    data = _wire_data(ws, n, seed=70)
    telemetry.drain_spans()
    try:
        got = _allreduce_quantized_all(groups, data, bits=bits)
    finally:
        for g in groups:
            g.shutdown()
    if case == "tiny":
        want = sum(_oracle_dequantize(*_oracle_quantize(d, bits), n, bits) for d in data)
        # the fallback sums every rank's dequantized payload in fp32
        for g in got:
            np.testing.assert_allclose(g, want, rtol=1e-6)
    else:
        for g in got:
            np.testing.assert_array_equal(g, _oracle_wire(data, bits)[2])
    spans = _wire_spans(telemetry.drain_spans()[0])
    assert len(spans) == ws
    blocks = -(-n // _B)
    if case == "int8":
        assert sum(a["native_blocks"] for a in spans) == blocks
        assert all(a["numpy_blocks"] == 0 for a in spans)
    else:
        assert all(a["native_blocks"] == 0 for a in spans)
        assert sum(a["numpy_blocks"] for a in spans) == (
            blocks * ws if case == "tiny" else blocks
        )


def test_a_group_that_mixes_the_two_codecs_agrees_on_every_rank(store, monkeypatch):
    """One rank without the library among three with it: replicas that
    differ in what they can run still hold the same payload and scales,
    bit for bit, the oracle's."""
    import torchft_tpu.collectives as C

    _needs_codec()
    _small_pieces(monkeypatch)
    ws = 4
    n = _B * ws * 11 + 301
    groups = _make_group(store, ws, prefix="mixed-codec")
    data = _wire_data(ws, n, seed=71)
    numpy_rank = {}
    real = C._native_codec
    took = []

    def codec(peers, blocks, bits):
        chosen = None if threading.get_ident() in numpy_rank else real(peers, blocks, bits)
        took.append(chosen is not None)
        return chosen

    monkeypatch.setattr(C, "_native_codec", codec)

    def run(rank):
        if rank == 0:
            numpy_rank[threading.get_ident()] = True
        q, s = C.quantize_blockwise(data[rank])
        q_f, s_f = C._quantized_wire_pipeline(groups[rank], q, s, n)
        return q_f.copy(), s_f.copy()

    try:
        results = _run_parallel([lambda r=r: run(r) for r in range(ws)])
    finally:
        for g in groups:
            g.shutdown()
    assert sorted(took) == [False, True, True, True]
    want_q, want_s, _ = _oracle_wire(data, 8)
    for q_f, s_f in results:
        np.testing.assert_array_equal(q_f, want_q)
        np.testing.assert_array_equal(s_f.view(np.uint32), want_s.view(np.uint32))


def test_error_feedbacks_hook_sees_this_ranks_own_payload_as_before(store):
    """``on_local_quantized(flat, q, s)``: the rank's own quantized
    payload, what ``quantize_blockwise`` gives, and nothing the wire turn
    does afterwards (its own chunk now travels round the alltoall as a
    view of ``q``, not a copy) writes into it."""
    from torchft_tpu.collectives import allreduce_quantized, quantize_blockwise

    ws = 2
    n = _B * ws * 5 + 40
    groups = _make_group(store, ws, prefix="ef-hook")
    data = _wire_data(ws, n, seed=72)
    seen = {}

    def run(rank):
        def hook(flat, q, s):
            seen[rank] = (flat.copy(), q, s, q.copy(), s.copy())

        arr = data[rank].copy()
        allreduce_quantized(groups[rank], [arr], on_local_quantized=hook).wait(timeout=60)
        return arr

    try:
        got = _run_parallel([lambda r=r: run(r) for r in range(ws)])
    finally:
        for g in groups:
            g.shutdown()
    for rank in range(ws):
        flat, q, s, q_then, s_then = seen[rank]
        np.testing.assert_array_equal(flat, data[rank])
        want_q, want_s = quantize_blockwise(data[rank])
        np.testing.assert_array_equal(q_then, want_q)
        np.testing.assert_array_equal(s_then, want_s)
        np.testing.assert_array_equal(q, q_then)  # still, after the turn
        np.testing.assert_array_equal(s, s_then)
        np.testing.assert_array_equal(got[rank], _oracle_wire(data, 8)[2])


def test_reduce_scatter_quantized_is_its_numpy_form_bit_for_bit(store, monkeypatch):
    """The collective that hands the fp32 sum out keeps a form that
    writes it: the shard by the library equals the shard by numpy."""
    from torchft_tpu import _native
    from torchft_tpu.collectives import reduce_scatter_quantized

    _needs_codec()
    _small_pieces(monkeypatch)
    ws = 3
    n = _B * ws * 7 + 99
    data = _wire_data(ws, n, seed=73)

    def shards(prefix):
        groups = _make_group(store, ws, prefix=prefix)
        try:
            return _run_parallel([
                lambda r=r: reduce_scatter_quantized(
                    groups[r], [data[r].copy()]
                ).wait(timeout=60)
                for r in range(ws)
            ])
        finally:
            for g in groups:
                g.shutdown()

    native = shards("rs-native")
    monkeypatch.setattr(_native, "is_available", lambda: False)
    plain = shards("rs-numpy")
    covered = 0
    for (a, a_range), (b, b_range) in zip(native, plain):
        assert a_range == b_range
        np.testing.assert_array_equal(a, b)
        nz = a != 0
        np.testing.assert_array_equal(a.view(np.uint32)[nz], b.view(np.uint32)[nz])
        covered += a.size
    assert covered == n
