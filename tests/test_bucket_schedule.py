"""The schedule of a step's gradient buckets: ``ddp.issue_order`` (smallest
first, the same in both branches of ``DistributedDataParallel``) and the
turns the device path's device-to-host pulls take in that order
(``collectives._spawn_collective(..., pulls=True)``).

Everything runs on the CPU: the device branch of the wrapper is reached by
telling ``ddp`` alone that the backend is a TPU, and the device quantizer
by ``TORCHFT_FORCE_DEVICE_QUANT`` (the Pallas interpreter), so payloads
are a few blocks.
"""

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

import torchft_tpu.collectives as C  # noqa: E402
import torchft_tpu.ddp as ddp_module  # noqa: E402
from tests.test_manager import make_manager, make_quorum_result  # noqa: E402
from tests.test_process_group import (  # noqa: E402
    _B,
    _make_group,
    _oracle_wire,
    _run_parallel,
    _wire_data,
)
from torchft_tpu import telemetry  # noqa: E402
from torchft_tpu.ddp import DistributedDataParallel, issue_order  # noqa: E402
from torchft_tpu.ops import quantization as Q  # noqa: E402
from torchft_tpu.process_group import (  # noqa: E402
    ProcessGroupDummy,
    ProcessGroupSocket,
)
from torchft_tpu.store import TCPStoreServer  # noqa: E402

NAME, T0, T1, ID, PARENT, THREAD, ATTRS = range(7)
PULL = "torchft::collectives::quantize_pull"
PULL_WAIT = "torchft::collectives::pull_turn_wait"
WIRE = "torchft::collectives::wire"
CAP_4K = 4096 / 2**20  # bucket_cap_mb: a leaf of 1024 float32 fills one


@pytest.fixture
def store():
    server = TCPStoreServer()
    yield server
    server.shutdown()


@pytest.fixture
def spans(tmp_path, monkeypatch):
    """Spans are kept only while a journal is configured. The fixture is
    a reader of every span closed so far: those a commit gate has flushed
    into the journal, then those still in the buffer."""
    path = tmp_path / "journal.jsonl"
    monkeypatch.setenv("TORCHFT_JOURNAL_FILE", str(path))
    monkeypatch.delenv("TORCHFT_JOURNAL_DIR", raising=False)
    telemetry.reset_event_log()
    telemetry.drain_spans()

    def read():
        flushed = []
        if path.exists():
            for line in path.read_text().splitlines():
                event = json.loads(line)
                if event["event"] == "step_spans":
                    flushed += event["attrs"]["spans"]
        return flushed + [list(s) for s in telemetry.drain_spans()[0]]

    yield read
    telemetry.reset_event_log()
    telemetry.drain_spans()


@pytest.fixture
def as_tpu(monkeypatch):
    """``ddp`` sees a TPU backend (its device branch), nothing else does."""
    import jax

    class _Jax:
        default_backend = staticmethod(lambda: "tpu")

        def __getattr__(self, name):
            return getattr(jax, name)

    monkeypatch.setattr(ddp_module, "jax", _Jax())
    monkeypatch.setenv("TORCHFT_FORCE_DEVICE_QUANT", "1")


def _collective_threads():
    return [t for t in threading.enumerate() if t.name == "quant-collective"]


def _no_collective_thread_is_left(timeout=20.0):
    deadline = time.monotonic() + timeout
    while _collective_threads() and time.monotonic() < deadline:
        time.sleep(0.01)
    return not _collective_threads()


# ---------------------------------------------------------------------------
# The order function
# ---------------------------------------------------------------------------

LAYOUTS = {
    # leaf sizes, buckets (index groups), the order wanted
    "largest-first-and-last": ([900, 2, 70, 70, 1, 240, 900], None, [4, 1, 2, 3, 5, 0, 6]),
    "already-ascending": ([1, 2, 3], None, [0, 1, 2]),
    "all-ties-keep-layout-order": ([5, 5, 5, 5], None, [0, 1, 2, 3]),
    "several-leaves-a-bucket": ([10, 10, 3, 4, 30], [[0, 1], [2, 3], [4]], [1, 0, 2]),
    "a-tie-between-buckets-of-leaves": ([4, 4, 2, 6, 7], [[0, 1], [2, 3], [4]], [2, 0, 1]),
    "one-bucket": ([17], None, [0]),
    "no-bucket": ([], [], []),
}


@pytest.mark.parametrize("name", sorted(LAYOUTS))
def test_issue_order_is_ascending_stable_pure_and_blind_to_the_array_type(name):
    import jax.numpy as jnp

    sizes, buckets, want = LAYOUTS[name]
    if buckets is None:
        buckets = [[i] for i in range(len(sizes))]
    host = [np.zeros(n, np.float32) for n in sizes]
    device = [jnp.zeros((n,), jnp.float32) for n in sizes]
    before = [list(b) for b in buckets]
    got = issue_order(host, buckets)
    assert got == want
    assert sorted(got) == list(range(len(buckets)))  # a permutation
    counts = [sum(sizes[i] for i in buckets[b]) for b in got]
    assert counts == sorted(counts)  # ascending
    for i in range(len(got) - 1):  # ties in layout order
        assert counts[i] < counts[i + 1] or got[i] < got[i + 1]
    # pure: asked again, of other arrays of the same layout, of jax leaves
    assert issue_order(host, buckets) == got and buckets == before
    assert issue_order(device, buckets) == got
    assert issue_order([np.ones(n, np.float32) for n in sizes], buckets) == got


def test_issue_order_counts_elements_not_bytes():
    """A bucket's wire payload is one int8 a value whatever the dtype."""
    arrays = [np.zeros(100, np.float32), np.zeros(150, np.float16)]
    assert arrays[0].nbytes > arrays[1].nbytes
    assert issue_order(arrays, [[0], [1]]) == [0, 1]


def test_issue_order_of_the_real_bucketize_is_the_same_from_jax_leaves():
    """Beside ``test_device_and_host_bucket_layouts_identical``: the layout
    and the order are the same from device leaves and their host copies."""
    import jax.numpy as jnp

    leaves = [
        jnp.ones((300_000,), jnp.float32),
        jnp.ones((64,), jnp.int32),
        jnp.ones((200_000,), jnp.float32),
        jnp.ones((128, 128), jnp.float32),
        jnp.ones((32,), jnp.int32),
    ]
    host = [np.asarray(x) for x in leaves]
    cap = 1 * 1024 * 1024
    buckets = C.bucketize(leaves, cap)
    assert buckets == C.bucketize(host, cap) and len(buckets) >= 3
    order = issue_order(leaves, buckets)
    assert order == issue_order(host, buckets)
    assert order != list(range(len(buckets)))  # it does reorder this one


# ---------------------------------------------------------------------------
# Both branches of the wrapper issue in that order
# ---------------------------------------------------------------------------

LEAF_SIZES = [3072, 1024, 2048, 1024, 5120]  # a leaf a bucket at CAP_4K... and
ORDER = [1, 3, 2, 0, 4]  # ... smallest first, the tie in layout order


def _tree(rank, step, kind):
    import jax.numpy as jnp

    rng = np.random.default_rng(1000 * step + rank)
    leaves = [rng.standard_normal(n).astype(np.float32) for n in LEAF_SIZES]
    if kind == "device":
        leaves = [jnp.asarray(x) for x in leaves]
    return {f"l{i}": x for i, x in enumerate(leaves)}


def _spy_on_allreduce(m):
    """Element counts of the payloads ``m.allreduce`` is called with."""
    calls = []
    real = m.allreduce

    def allreduce(tensors, *args, **kwargs):
        items = tensors if isinstance(tensors, (list, tuple)) else [tensors]
        calls.append(sum(int(np.prod(t.shape)) for t in items))
        return real(tensors, *args, **kwargs)

    m.allreduce = allreduce
    return calls


@pytest.mark.parametrize("branch,kwargs", [
    ("device", dict(should_quantize=True)),
    ("host", dict()),
    ("host", dict(should_quantize=True)),
    ("host-ef", dict(should_quantize=True)),
], ids=["device-int8", "host-fp32", "host-int8", "host-int8-error-feedback"])
def test_both_branches_call_manager_allreduce_smallest_first(as_tpu, spans, branch, kwargs):
    pg = ProcessGroupDummy()
    m = make_manager(
        pg=pg, use_async_quorum=False,
        quorum_result=make_quorum_result(replica_world_size=1, max_world_size=1),
    )
    ddp = DistributedDataParallel(
        m, bucket_cap_mb=CAP_4K, error_feedback=branch == "host-ef")
    calls = _spy_on_allreduce(m)
    try:
        m.start_quorum()
        # numpy leaves, or error feedback, take the host branch on a TPU too
        grads = _tree(0, 0, "device" if branch != "host" else "host")
        out = ddp.allreduce_grads(grads, **kwargs)
        assert m.should_commit()
    finally:
        m.shutdown()
    assert calls == [LEAF_SIZES[b] for b in ORDER]
    for k, v in grads.items():  # a quorum of one: every leaf back in its place
        np.testing.assert_array_equal(np.asarray(out[k]), np.asarray(v))
    recorded = spans()
    if branch == "device":
        assert not [s for s in recorded if s[NAME] == "torchft::ddp::pack"]
    else:  # a bucket keeps its layout index on its spans
        for name in ("torchft::ddp::pack", "torchft::ddp::unpack"):
            assert [s[ATTRS]["bucket"] for s in recorded if s[NAME] == name] == ORDER


# ---------------------------------------------------------------------------
# A device-path replica and a host-path replica in one quorum
# ---------------------------------------------------------------------------


def _mixed_quorum_step(store, prefix):
    """One quantized step of two replicas, rank 0 through the device branch
    (jax leaves) and rank 1 through the host branch (numpy leaves); per
    rank (the leaves as numpy, the spans' bucket numbers in issue order)."""
    managers = [
        make_manager(
            pg=ProcessGroupSocket(timeout=30.0), use_async_quorum=False,
            quorum_result=make_quorum_result(
                store_address=f"{store.address()}/{prefix}", replica_rank=r,
                replica_world_size=2, max_world_size=2),
        )
        for r in range(2)
    ]
    issued = [_spy_on_allreduce(m) for m in managers]

    def run(rank):
        m = managers[rank]
        ddp = DistributedDataParallel(m, bucket_cap_mb=CAP_4K)
        m.start_quorum()
        out = ddp.allreduce_grads(
            _tree(rank, 7, "device" if rank == 0 else "host"), should_quantize=True)
        assert m.should_commit()
        return [np.array(out[f"l{i}"]) for i in range(len(LEAF_SIZES))]

    try:
        with ThreadPoolExecutor(max_workers=2) as pool:
            outs = [f.result(timeout=100) for f in [pool.submit(run, r) for r in range(2)]]
    finally:
        for m in managers:
            m.shutdown()
    return outs, issued


@pytest.mark.timeout(240)
def test_a_device_path_and_a_host_path_replica_exchange_the_right_buckets(
    as_tpu, store, spans, monkeypatch
):
    outs, issued = _mixed_quorum_step(store, "new-order")
    assert issued[0] == issued[1] == [LEAF_SIZES[b] for b in ORDER]
    kinds = {s[ATTRS].get("bucket") for s in spans() if s[NAME] == PULL_WAIT}
    assert kinds == set(range(len(LEAF_SIZES)))  # rank 0 did pull device chunks

    # The parent's order, layout order, on the same inputs: bucket for
    # bucket the same bits (the order enters no bucket's arithmetic).
    monkeypatch.setattr(
        ddp_module, "issue_order", lambda arrays, buckets: list(range(len(buckets))))
    parents, issued = _mixed_quorum_step(store, "layout-order")
    assert issued[0] == issued[1] == LEAF_SIZES
    for rank in range(2):
        for got, want in zip(outs[rank], parents[rank]):
            assert got.tobytes() == want.tobytes()

    # and it is the average the wire protocol gives for that bucket's own
    # two payloads (two leaves are the same size: a swap would show here)
    for i in range(len(LEAF_SIZES)):
        data = [np.asarray(_tree(r, 7, "host")[f"l{i}"]) for r in range(2)]
        want = _oracle_wire(data, 8)[2] / 2
        np.testing.assert_allclose(outs[1][i], want, rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(outs[0][i], want, rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# The pull turn
# ---------------------------------------------------------------------------


def _slow_pulls(monkeypatch, seconds=0.03, on_pull=None):
    """Every device pull takes ``seconds`` (unserialised, all of a step's
    would overlap); ``on_pull(n_elems)`` runs inside it first."""
    real = Q.pull_transfer_chunks

    def pull(chunks, n, bits=8):
        if on_pull is not None:
            on_pull(n)
        time.sleep(seconds)
        return real(chunks, n, bits)

    monkeypatch.setattr(Q, "pull_transfer_chunks", pull)


def _issue(pg, payloads):
    import jax.numpy as jnp

    return [C.allreduce_quantized_jax(pg, [jnp.asarray(p)]) for p in payloads]


@pytest.mark.timeout(180)
def test_pulls_of_one_step_take_turns_in_ticket_order_on_each_rank(
    store, spans, monkeypatch
):
    monkeypatch.setenv("TORCHFT_FORCE_DEVICE_QUANT", "1")
    _slow_pulls(monkeypatch)
    ws, n_buckets = 2, 5
    sizes = [_B * ws * k for k in (1, 2, 3, 4, 5)]
    payloads = [_wire_data(ws, n, seed=80 + i) for i, n in enumerate(sizes)]
    want = [_oracle_wire(data, 8)[2] for data in payloads]
    groups = _make_group(store, ws, prefix="pull-turns")
    roots = {}

    def run(rank):
        with telemetry.trace_span(telemetry.DDP_ROOT_SPAN) as root:
            roots[rank] = root.id
            works = _issue(groups[rank], [p[rank] for p in payloads])
            return [np.asarray(w.wait(timeout=60)[0]) for w in works]

    for outs in _run_parallel([lambda r=r: run(r) for r in range(ws)]):
        for got, w in zip(outs, want):
            np.testing.assert_allclose(got, w, rtol=1e-6, atol=1e-7)
    recorded = spans()
    for rank in range(ws):
        mine = [s for s in recorded if s[PARENT] == roots[rank]]
        pulls = sorted((s for s in mine if s[NAME] == PULL), key=lambda s: s[T0])
        assert [s[ATTRS]["bucket"] for s in pulls] == list(range(n_buckets))
        for a, b in zip(pulls, pulls[1:]):
            assert a[T1] <= b[T0]  # never two on the path at once
        waits = {s[ATTRS]["bucket"]: s for s in mine if s[NAME] == PULL_WAIT}
        assert sorted(waits) == list(range(n_buckets))
        for p in pulls:  # the wait is not inside the pull's own span
            assert waits[p[ATTRS]["bucket"]][T1] <= p[T0]
        # bucket k+1 pulls while bucket k is on the wire: the last pull
        # ends before the last wire does, and the wire kept issue order
        wires = sorted((s for s in mine if s[NAME] == WIRE), key=lambda s: s[T0])
        assert [s[ATTRS]["bucket"] for s in wires] == list(range(n_buckets))
        assert pulls[-1][T1] <= wires[-1][T1]
    assert _no_collective_thread_is_left()
    for g in groups:
        g.shutdown()


@pytest.mark.timeout(120)
def test_host_quantized_collectives_take_no_pull_turn(store, spans):
    """jax arrays off-TPU (the host quantizer) and numpy arrays: nothing
    to pull from a device, no turn, no ``pull_turn_wait`` span."""
    ws = 2
    n = _B * ws * 2
    data = _wire_data(ws, n, seed=90)
    groups = _make_group(store, ws, prefix="no-turn")

    def run(rank):
        (jax_out,) = _issue(groups[rank], [data[rank]])[0].wait(timeout=60)
        arr = data[rank].copy()
        C.allreduce_quantized(groups[rank], [arr]).wait(timeout=60)
        C.reduce_scatter_quantized(groups[rank], [data[rank].copy()]).wait(timeout=60)
        return np.asarray(jax_out), arr

    for jax_out, arr in _run_parallel([lambda r=r: run(r) for r in range(ws)]):
        np.testing.assert_array_equal(jax_out, arr)
    recorded = spans()
    assert len([s for s in recorded if s[NAME] == PULL]) == 2 * ws
    assert not [s for s in recorded if s[NAME] == PULL_WAIT]
    for g in groups:
        assert "_quant_pull_order" not in g.__dict__
        g.shutdown()


class _HeldThread(threading.Thread):
    """A collective's thread that starts only when the test says so."""

    gate = threading.Event()

    def run(self):
        if self.name == "quant-collective":
            assert _HeldThread.gate.wait(30)
        super().run()


@pytest.mark.parametrize("how", ["raises-before-its-pull", "cancelled", "raises-in-its-pull",
                                 "raises-between-pull-and-wire"])
def test_a_collective_that_never_takes_its_turn_passes_it_on(monkeypatch, how):
    """Three collectives with pull turns on one group; the first dies in
    the way named. The others pull and go to the wire in order, nobody is
    left waiting."""
    monkeypatch.setattr(threading, "Thread", _HeldThread)
    _HeldThread.gate.clear()
    pg = ProcessGroupDummy()
    log = []

    def dies(wire, pull):
        if how == "raises-before-its-pull":
            raise RuntimeError("reshape")
        with pull():
            if how == "raises-in-its-pull":
                raise RuntimeError("device gone")
        if how == "raises-between-pull-and-wire":
            raise RuntimeError("quantize")
        raise AssertionError("a cancelled collective never runs")

    def lives(k):
        def fn(wire, pull):
            with pull():
                log.append(("pull", k))
            with wire():
                log.append(("wire", k))
            return k

        return fn

    first = C._spawn_collective(pg, dies, 0, pulls=True)
    if how == "cancelled":
        assert first.cancel()
    others = [C._spawn_collective(pg, lives(k), k, pulls=True) for k in (1, 2)]
    _HeldThread.gate.set()
    assert [f.result(timeout=20) for f in others] == [1, 2]
    if how == "cancelled":
        assert first.cancelled()
    else:
        with pytest.raises(RuntimeError):
            first.result(timeout=20)
    assert [e for e in log if e[0] == "pull"] == [("pull", 1), ("pull", 2)]
    assert [e for e in log if e[0] == "wire"] == [("wire", 1), ("wire", 2)]
    assert _no_collective_thread_is_left()
    # and the next one issued finds both orders where they should be
    assert C._spawn_collective(pg, lives(3), 3, pulls=True).result(timeout=20) == 3
    assert _no_collective_thread_is_left()


@pytest.mark.timeout(180)
def test_an_abort_mid_step_passes_the_turns_on_and_the_next_step_completes(
    store, monkeypatch
):
    monkeypatch.setenv("TORCHFT_FORCE_DEVICE_QUANT", "1")
    ws = 2
    sizes = [_B * ws * k for k in (1, 2, 3)]
    torn = [_wire_data(ws, n, seed=100 + i) for i, n in enumerate(sizes)]
    nxt = [_wire_data(ws, n, seed=110 + i) for i, n in enumerate(sizes)]
    want = [_oracle_wire(data, 8)[2] for data in nxt]
    groups = _make_group(store, ws, prefix="torn")
    in_pull, release = threading.Event(), threading.Event()
    real = Q.pull_transfer_chunks

    def pull(chunks, n, bits=8):
        # rank 0's first pull of the torn step holds its turn until told
        if n == sizes[0] and not in_pull.is_set():
            in_pull.set()
            assert release.wait(60)
        return real(chunks, n, bits)

    monkeypatch.setattr(Q, "pull_transfer_chunks", pull)
    works = [_issue(groups[0], [p[0] for p in torn])]
    assert in_pull.wait(30)
    works.append(_issue(groups[1], [p[1] for p in torn]))
    # rank 1 has pulled and waits on the wire for rank 0
    deadline = time.monotonic() + 30
    while "_quant_wire_scratch" not in groups[1].__dict__:
        assert time.monotonic() < deadline
        time.sleep(0.01)
    time.sleep(0.2)
    assert "_quant_pull_order" in groups[0].__dict__
    for g in groups:
        g.abort()
    # the turns went with the wire scratch: what is issued next starts
    # over, and a collective of the torn step that reaches the wire after
    # the abort hangs no new scratch on the dead group
    for g in groups:
        assert "_quant_pull_order" not in g.__dict__
        assert "_quant_wire_scratch" not in g.__dict__
    release.set()
    failed = 0
    for rank_works in works:
        for w in rank_works:
            try:
                w.wait(timeout=30)
            except Exception:  # noqa: BLE001 - the torn step's collectives
                failed += 1
    assert failed >= 1
    assert _no_collective_thread_is_left()
    for g in groups:
        assert "_quant_wire_scratch" not in g.__dict__

    def configure(rank):
        groups[rank].configure(f"{store.address()}/torn-again", rank, ws)

    _run_parallel([lambda r=r: configure(r) for r in range(ws)])

    def run(rank):
        return [np.asarray(w.wait(timeout=60)[0])
                for w in _issue(groups[rank], [p[rank] for p in nxt])]

    for outs in _run_parallel([lambda r=r: run(r) for r in range(ws)]):
        for got, w in zip(outs, want):
            np.testing.assert_allclose(got, w, rtol=1e-6, atol=1e-7)
    assert _no_collective_thread_is_left()
    for g in groups:
        g.shutdown()


@pytest.mark.timeout(120)
def test_two_ranks_in_one_process_do_not_share_a_pull_turn(store, monkeypatch):
    """Rank 0 holds its pull turn until rank 1 has pulled: with one turn
    for the process, rank 1 would wait behind it and this would time out."""
    monkeypatch.setenv("TORCHFT_FORCE_DEVICE_QUANT", "1")
    ws = 2
    n = _B * ws * 2
    data = _wire_data(ws, n, seed=120)
    want = _oracle_wire(data, 8)[2]
    groups = _make_group(store, ws, prefix="own-turn")
    pulling = {}
    other_pulled = threading.Event()
    real = Q.pull_transfer_chunks

    def pull(chunks, n_elems, bits=8):
        rank = pulling[threading.get_ident()]
        if rank == 0:
            assert other_pulled.wait(30), "rank 1's pull waited for rank 0's turn"
        out = real(chunks, n_elems, bits)
        if rank == 1:
            other_pulled.set()
        return out

    monkeypatch.setattr(Q, "pull_transfer_chunks", pull)
    real_turn = C._turn

    def turn(order, ticket, wait_span, bucket):
        held = real_turn(order, ticket, wait_span, bucket)
        if wait_span.endswith("pull_turn_wait"):
            rank = next(r for r, g in enumerate(groups)
                        if g.__dict__.get("_quant_pull_order") is order)

            def tagged():
                pulling[threading.get_ident()] = rank
                return held()

            return tagged
        return held

    monkeypatch.setattr(C, "_turn", turn)

    def run(rank):
        if rank == 1:
            time.sleep(0.1)  # rank 0 is inside its pull first
        (out,) = _issue(groups[rank], [data[rank]])[0].wait(timeout=60)
        return np.asarray(out)

    for out in _run_parallel([lambda r=r: run(r) for r in range(ws)]):
        np.testing.assert_allclose(out, want, rtol=1e-6, atol=1e-7)
    assert groups[0].__dict__["_quant_pull_order"] is not groups[1].__dict__[
        "_quant_pull_order"]
    for g in groups:
        g.shutdown()


# ---------------------------------------------------------------------------
# Error feedback stays keyed by layout index
# ---------------------------------------------------------------------------


@pytest.mark.timeout(120)
def test_error_feedback_residual_of_layout_bucket_i_is_read_back_for_bucket_i(store):
    """Buckets of unequal size issued out of layout order: ``compensate``
    drops a residual whose size does not match, so a residual filed under
    another bucket's key would silently stop compensating."""
    managers = [
        make_manager(
            pg=ProcessGroupSocket(timeout=30.0), use_async_quorum=False,
            quorum_result=make_quorum_result(
                store_address=store.address(), replica_rank=r,
                replica_world_size=2, max_world_size=2),
        )
        for r in range(2)
    ]

    def run(rank):
        m = managers[rank]
        ddp = DistributedDataParallel(
            m, bucket_cap_mb=CAP_4K, error_feedback=True, quantize_bits=4)
        store_ = ddp._residuals
        compensate = store_.compensate
        seen = []

        def recording(key, flat):
            out = compensate(key, flat)
            seen.append((key, flat.size, out is not flat))
            if out is not flat:
                np.testing.assert_array_equal(out, flat + held[key])
            return out

        store_.compensate = recording
        per_step = []
        for step in range(2):
            held = {k: np.array(v) for k, v in store_._residuals.items()}
            m.start_quorum()
            ddp.allreduce_grads(_tree(rank, step, "host"), should_quantize=True)
            assert m.should_commit()
            per_step.append(list(seen))
            seen.clear()
        return per_step, {k: v.size for k, v in store_._residuals.items()}

    try:
        with ThreadPoolExecutor(max_workers=2) as pool:
            results = [f.result(timeout=90)
                       for f in [pool.submit(run, r) for r in range(2)]]
    finally:
        for m in managers:
            m.shutdown()
    for (first, second), residual_sizes in results:
        # asked in issue order, under the layout index, at that bucket's size
        assert first == [(b, LEAF_SIZES[b], False) for b in ORDER]
        assert second == [(b, LEAF_SIZES[b], True) for b in ORDER]
        assert residual_sizes == dict(enumerate(LEAF_SIZES))
