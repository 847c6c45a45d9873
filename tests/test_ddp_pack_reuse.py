"""The DDP wrapper's kept bucket buffers (host path, a numpy leaf in the
tree): ``pack`` copies each bucket's leaves into flat buffers the wrapper
keeps between calls, the averaged leaves come back as views of them, and
a failed step retires the set. (Every leaf a ``jax.Array``: the landed
copy is the bucket and the kept buffer is written only where something
writes, ``test_ddp_bucket_line.py``.)

Gradients are small multiples of 1/64, so every sum, average and
quantization below is exact arithmetic on any host and the recorded
digests of (e) hold wherever numpy does IEEE float32.
"""

import hashlib
import json
import os
import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tests.test_manager import make_manager, make_quorum_result  # noqa: E402
from torchft_tpu import telemetry  # noqa: E402
from torchft_tpu.collectives import bucketize  # noqa: E402
from torchft_tpu.ddp import DistributedDataParallel, issue_order  # noqa: E402
from torchft_tpu.process_group import (  # noqa: E402
    ProcessGroupDummy,
    ProcessGroupSocket,
)
from torchft_tpu.store import TCPStoreServer  # noqa: E402
from torchft_tpu.work import DummyWork, Work  # noqa: E402

NAME, ATTRS = 0, 6
PACK = "torchft::ddp::pack"
KB = 1024 / 2**20  # bucket_cap_mb of one kilobyte


@pytest.fixture(autouse=True)
def recorded_spans(tmp_path, monkeypatch):
    """Spans are kept only while a journal is configured."""
    monkeypatch.setenv("TORCHFT_JOURNAL_FILE", str(tmp_path / "journal.jsonl"))
    monkeypatch.delenv("TORCHFT_JOURNAL_DIR", raising=False)
    telemetry.reset_event_log()
    telemetry.drain_spans()
    yield str(tmp_path / "journal.jsonl")
    telemetry.reset_event_log()
    telemetry.drain_spans()


def _packs():
    """Attrs of the ``ddp::pack`` spans closed since the last call."""
    return [s[ATTRS] for s in telemetry.drain_spans()[0] if s[NAME] == PACK]


def _all_fresh(packs):
    """Every bucket was packed into memory made for it in that call."""
    return bool(packs) and all(
        p["fresh_bytes"] == p["nbytes"] and p["reused_bytes"] == 0 for p in packs)


def _all_reused(packs):
    """Every bucket was packed into a buffer the wrapper already had."""
    return bool(packs) and all(
        p["fresh_bytes"] == 0 and p["reused_bytes"] == p["nbytes"] for p in packs)


def _values(n, rank, step, salt, dtype=np.float32):
    """Exact in float16 and up: multiples of 1/64 within +-0.75."""
    i = np.arange(n, dtype=np.int64)
    return (((i * 7 + step * 13 + rank * 5 + salt * 3) % 97 - 48) / 64.0).astype(dtype)


def _fp32_tree(rank, step):
    import jax.numpy as jnp

    return {
        "a": jnp.asarray(_values(300, rank, step, 0).reshape(3, 100)),  # device
        "b": _values(200, rank, step, 1),  # already on the host
        "c": [jnp.asarray(_values(60, rank, step, 2)), _values(7, rank, step, 3)],
    }


def _mixed_tree(rank, step):
    tree = _fp32_tree(rank, step)
    tree["h"] = _values(90, rank, step, 4, np.float16).reshape(9, 10)
    tree["i"] = _values(50, rank, step, 5, np.float16)
    return tree


TREES = {"fp32": _fp32_tree, "mixed": _mixed_tree}


def _leaves(tree):
    import jax

    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


def _reference(make_tree, world, step):
    """What the parent computed, from new memory: every rank's leaves
    concatenated per dtype, summed over the ranks, scaled in place."""
    per_rank = [_leaves(make_tree(r, step)) for r in range(world)]
    out = []
    for i, leaf in enumerate(per_rank[0]):
        flat = np.concatenate([leaf.reshape(-1)])
        for other in per_rank[1:]:
            flat = flat + other[i].reshape(-1)
        flat *= 1.0 / world
        out.append(flat.reshape(leaf.shape))
    return out


def _managers(world, store=None, pg=None):
    if world == 1:
        return [make_manager(
            pg=pg if pg is not None else ProcessGroupDummy(),
            use_async_quorum=False,
            quorum_result=make_quorum_result(replica_world_size=1, max_world_size=1),
        )]
    return [
        make_manager(
            pg=ProcessGroupSocket(timeout=30.0),
            use_async_quorum=False,
            quorum_result=make_quorum_result(
                store_address=store.address(), replica_rank=r,
                replica_world_size=world, max_world_size=world,
            ),
        )
        for r in range(world)
    ]


def _step(m, ddp, grads, **kwargs):
    """One step: (averaged tree, committed, its packs' attrs)."""
    m.start_quorum()
    out = ddp.allreduce_grads(grads, **kwargs)
    packs = _packs()  # before the gate flushes them into the journal
    return out, m.should_commit(), packs


# ---------------------------------------------------------------------------
# (a) the values are the parent's, leaf for leaf, bit for bit
# ---------------------------------------------------------------------------


@pytest.mark.timeout(120)
@pytest.mark.parametrize("tree", sorted(TREES))
@pytest.mark.parametrize("world", [1, 2])
def test_three_steps_equal_a_reference_that_packs_into_new_memory(world, tree):
    make_tree = TREES[tree]
    store = TCPStoreServer() if world > 1 else None
    managers = _managers(world, store)

    def run(rank):
        m = managers[rank]
        ddp = DistributedDataParallel(m, bucket_cap_mb=KB)
        got = []
        for step in range(3):
            out, committed, _ = _step(m, ddp, make_tree(rank, step))
            assert committed
            # valid until the next call: compared (copied) before it
            got.append([np.array(x) for x in _leaves(out)])
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
        want = _reference(make_tree, world, step)
        for got in results:
            assert len(got[step]) == len(want)
            for g, w in zip(got[step], want):
                assert g.dtype == w.dtype and g.shape == w.shape
                assert g.tobytes() == w.tobytes()
    if tree == "mixed":
        assert {x.dtype for x in results[0][0]} == {
            np.dtype(np.float32), np.dtype(np.float16)}


# ---------------------------------------------------------------------------
# (b) from the second call on nothing is allocated
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cap_mb,n_buckets", [(32.0, 2), (KB, 4), (100 / 2**20, 6)],
                         ids=["one-bucket-a-dtype", "1kB-buckets", "a-leaf-a-bucket"])
def test_from_the_second_call_on_every_pack_reuses_its_buffer(cap_mb, n_buckets):
    (m,) = _managers(1)
    ddp = DistributedDataParallel(m, bucket_cap_mb=cap_mb)
    try:
        first, _, packs = _step(m, ddp, _mixed_tree(0, 0))
        assert len(packs) == n_buckets
        # every layout bucket once, fewest elements first
        leaves = _leaves(_mixed_tree(0, 0))
        assert [p["bucket"] for p in packs] == issue_order(
            leaves, bucketize(leaves, ddp._bucket_cap))
        assert sorted(p["bucket"] for p in packs) == list(range(n_buckets))
        assert _all_fresh(packs)
        assert sum(p["nbytes"] for p in packs) == 4 * 567 + 2 * 140
        first = _leaves(first)
        for step in (1, 2, 3):
            out, committed, packs = _step(m, ddp, _mixed_tree(0, step))
            assert committed
            assert len(packs) == n_buckets and _all_reused(packs)
            for new, old, want in zip(_leaves(out), first,
                                      _reference(_mixed_tree, 1, step)):
                assert np.shares_memory(new, old)
                assert new.tobytes() == want.tobytes()
                # ... which is why a caller copies what it keeps longer
                assert old.tobytes() == want.tobytes()
    finally:
        m.shutdown()


# ---------------------------------------------------------------------------
# (c) another layout sizes another set
# ---------------------------------------------------------------------------


def _other_tree(rank, step):
    return {"w": _values(300, rank, step, 6), "v": _values(321, rank, step, 7)}


@pytest.mark.parametrize("cap_bytes,make_tree", [
    (None, _other_tree), (100, _mixed_tree),  # 100 B: a leaf a bucket
], ids=["another-tree", "another-bucket-cap"])
def test_a_changed_layout_sizes_new_buffers_and_says_so(cap_bytes, make_tree):
    (m,) = _managers(1)
    ddp = DistributedDataParallel(m, bucket_cap_mb=KB)
    try:
        _step(m, ddp, _mixed_tree(0, 0))
        before, _, _ = _step(m, ddp, _mixed_tree(0, 1))
        before = _leaves(before)
        kept = [np.array(x) for x in before]
        if cap_bytes is not None:
            ddp._bucket_cap = cap_bytes
        out, committed, packs = _step(m, ddp, make_tree(0, 2))
        assert committed and _all_fresh(packs)
        for new, want in zip(_leaves(out), _reference(make_tree, 1, 2)):
            assert new.tobytes() == want.tobytes()
            assert not any(np.shares_memory(new, old) for old in before)
        # the set that went was not written again
        assert all(a.tobytes() == b.tobytes() for a, b in zip(before, kept))
        # the new layout is the kept one now; the old one would be fresh
        assert _all_reused(_step(m, ddp, make_tree(0, 3))[2])
    finally:
        m.shutdown()


# ---------------------------------------------------------------------------
# (d) a failed step retires the set
# ---------------------------------------------------------------------------


class _LateWork(Work):
    """A collective that fails at the wait, as an aborted one does."""

    def wait(self, timeout=None):
        raise RuntimeError("connection reset by peer")

    def done(self):
        return True

    def exception(self):
        return RuntimeError("connection reset by peer")

    def add_done_callback(self, fn):
        fn(self)


class _FailingPG(ProcessGroupDummy):
    """Fails the allreduces of one step and, like the thread of an
    aborted collective, keeps the arrays it was given."""

    def __init__(self):
        super().__init__()
        self.mode = None
        self.held = []

    def allreduce(self, tensors, op=None):
        if self.mode is None:
            return DummyWork(list(tensors))
        self.held.extend(tensors)
        if self.mode == "raises":
            raise RuntimeError("peer gone")
        return _LateWork()


@pytest.mark.parametrize("failure", ["raises", "late", "latched"],
                         ids=["pg-raises-at-issue", "work-fails-at-wait",
                              "latched-manager-error"])
def test_a_failed_step_retires_the_buffers(failure):
    pg = _FailingPG()
    (m,) = _managers(1, pg=pg)
    ddp = DistributedDataParallel(m, bucket_cap_mb=KB)
    try:
        _step(m, ddp, _fp32_tree(0, 0))
        assert _all_reused(_step(m, ddp, _fp32_tree(0, 1))[2])

        m.start_quorum()
        if failure == "latched":
            m.report_error(RuntimeError("heal failed"))
        else:
            pg.mode = failure
        failed = ddp.allreduce_grads(_fp32_tree(0, 2))
        assert m.errored() is not None
        assert not m.should_commit()
        pg.mode = None
        retired = _leaves(failed) + pg.held  # views of, and the buckets themselves

        # start_quorum clears the latch
        out, committed, packs = _step(m, ddp, _fp32_tree(0, 3))
        assert committed and _all_fresh(packs)
        out = _leaves(out)
        for new in out:
            assert not any(np.shares_memory(new, old) for old in retired)
        # the aborted collective's thread writes at last: it reaches nobody
        for old in retired:
            old[...] = 777.0
        for new, want in zip(out, _reference(_fp32_tree, 1, 3)):
            assert new.tobytes() == want.tobytes()
        # and the set after the failure is kept like any other
        nxt, _, packs = _step(m, ddp, _fp32_tree(0, 4))
        assert _all_reused(packs)
        assert all(np.shares_memory(a, b) for a, b in zip(_leaves(nxt), out))
    finally:
        m.shutdown()


def test_a_call_that_raises_retires_the_buffers():
    (m,) = _managers(1)
    ddp = DistributedDataParallel(m, bucket_cap_mb=KB)
    try:
        first = _leaves(_step(m, ddp, _fp32_tree(0, 0))[0])
        m.start_quorum()
        real = m.allreduce
        m.allreduce = lambda *a, **k: (_ for _ in ()).throw(KeyboardInterrupt())
        with pytest.raises(KeyboardInterrupt):
            ddp.allreduce_grads(_fp32_tree(0, 1))
        m.allreduce = real
        _packs()  # the call that raised had packed its first bucket
        out, _, packs = _step(m, ddp, _fp32_tree(0, 2))
        assert _all_fresh(packs)
        assert not any(np.shares_memory(a, b) for a in _leaves(out) for b in first)
    finally:
        m.shutdown()


# ---------------------------------------------------------------------------
# (e) error feedback keeps its arithmetic
# ---------------------------------------------------------------------------

# Per rank, sha256 over the three steps of: what each bucket's hook saw
# (the compensated flat bucket, its quantized payload, its scales), the
# residuals held after the step, and the averaged leaves. Recorded at the
# parent commit (2590b1a, which packs into new memory) by running this
# file as a script there.
EF_AT_PARENT = {
    8: ["5f90f7cd4c2087b2b86ce47a80088306c4dd06870279d5216a5c345d012039e9",
        "ef4595ac3ff3142430b31e13d550f53972cc566b0f0f9fe7f78d35b5ed003427"],
    4: ["18314bb000acc09639075ce30e20d353307cef99246988cd229107f435d5c4dd",
        "a7317df88ee68eb9d6df6ce83bc5d0215554fb867e146ab6386b9e22ac4d79c0"],
}


def _ef_rank(m, rank, bits, device=False):
    """Three steps of one rank: (digest, [(pack attrs, hooks run)] a step)."""
    ddp = DistributedDataParallel(
        m, bucket_cap_mb=KB, error_feedback=True, quantize_bits=bits)
    seen = []
    make_hook = ddp._residuals.make_hook

    def recording_hook(key):
        hook = make_hook(key)

        def on_local_quantized(wire_flat, q, s):
            seen.append((key, np.array(wire_flat), np.array(q), np.array(s)))
            hook(wire_flat, q, s)

        return on_local_quantized

    ddp._residuals.make_hook = recording_hook
    digest = hashlib.sha256()
    per_step = []
    for step in range(3):
        # off the 1/64 grid, so that 8 and 4 bits both drop something
        grads = {k: v * np.float32(1 / 3) for k, v in _other_tree(rank, step).items()}
        if device:  # the line of buckets: the same values as jax.Arrays
            import jax.numpy as jnp

            grads = {k: jnp.asarray(v) for k, v in grads.items()}
        m.start_quorum()
        out = ddp.allreduce_grads(grads, should_quantize=True)
        for key, flat, q, s in sorted(seen, key=lambda t: t[0]):
            for part in (flat, q, s):
                digest.update(part.tobytes())
        residuals = ddp._residuals._residuals
        assert sorted(residuals) == sorted(k for k, *_ in seen)
        assert any(np.any(r != 0) for r in residuals.values())
        for key in sorted(residuals):
            digest.update(residuals[key].tobytes())
        for leaf in _leaves(out):
            digest.update(leaf.tobytes())
        per_step.append(len(seen))
        seen.clear()
        assert m.should_commit()
    return digest.hexdigest(), per_step


def _ef_digests(bits, device=False):
    """Two ranks on the host-quantized wire with error feedback on."""
    store = TCPStoreServer()
    managers = _managers(2, store)
    try:
        with ThreadPoolExecutor(max_workers=2) as pool:
            return [f.result(timeout=90) for f in [
                pool.submit(_ef_rank, managers[r], r, bits, device)
                for r in range(2)]]
    finally:
        for m in managers:
            m.shutdown()
        store.shutdown()


@pytest.mark.timeout(120)
@pytest.mark.parametrize("leaves", ["numpy", "device"])
@pytest.mark.parametrize("bits", [8, 4])
def test_error_feedback_payload_and_residuals_are_the_parents(
        bits, leaves, recorded_spans):
    results = _ef_digests(bits, device=leaves == "device")
    assert [digest for digest, _ in results] == EF_AT_PARENT[bits]
    assert all(hooks == [2, 2, 2] for _, hooks in results)  # a bucket each
    # Two Managers share the process's span buffer, so a gate flushes the
    # other's closed spans too: read both ranks' packs from the journal.
    # The bucket itself is packed into kept memory from the second step
    # on; the compensated copy (flat + residual) is what is still fresh.
    with open(recorded_spans) as f:
        events = [json.loads(line) for line in f]
    packs = [s[ATTRS] for e in events if e["event"] == "step_spans"
             for s in e["attrs"]["spans"] if s[NAME] == PACK]
    assert len(packs) == 2 * 3 * 2
    if leaves == "device":
        # The landed copy is the bucket (fresh, nothing copied); from the
        # second step on the compensated copy is the one pass and is
        # fresh too. The kept buffers are never asked for.
        first = [p for p in packs if p["nbytes"] == 0]
        assert len(first) == 2 * 2 and all(p["fresh_bytes"] > 0 for p in first)
        assert all(p["fresh_bytes"] == 2 * p["nbytes"] for p in packs
                   if p not in first)
        assert all(p["reused_bytes"] == 0 for p in packs)
        return
    first = [p for p in packs if p["reused_bytes"] == 0]
    assert len(first) == 2 * 2 and _all_fresh(first)
    assert all(p["fresh_bytes"] == p["nbytes"] == p["reused_bytes"]
               for p in packs if p not in first)


if __name__ == "__main__":  # python tests/test_ddp_pack_reuse.py: the digests of (e)
    print(json.dumps({bits: [d for d, _ in _ef_digests(bits)] for bits in (8, 4)}, indent=1))
