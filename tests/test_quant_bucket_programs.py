"""The int8 replica allreduce's device path, one of each thing a bucket.

One compiled program takes a bucket's leaves down to the wire layout and
one brings the reduced payload back up as leaves; a bucket's
device-to-host copies are asked for in its pull turn, so one bucket at a
time in issue order; nothing is compiled after a layout's first call. Everything runs
on the CPU through the Pallas interpreter: payloads of a few blocks.

``PARENT`` holds digests of what the code before these programs
(035766e: ``quantize_for_transfer`` of the joined leaves, a kernel call a
16M-element chunk; ``dequantize_from_transfer`` times the scale) gave for
the same seeded inputs. ``python tests/test_quant_bucket_programs.py``
prints this tree's.
"""

import hashlib
import os
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import torchft_tpu.collectives as C  # noqa: E402
from tests.test_process_group import _make_group, _run_parallel  # noqa: E402
from torchft_tpu.collectives import (  # noqa: E402
    dequantize_blockwise,
    quantize_blockwise,
)
from torchft_tpu.ops import quantization as Q  # noqa: E402
from torchft_tpu.store import TCPStoreServer  # noqa: E402

# name -> [(shape, dtype), ...], a bucket's leaves in layout order
CASES = {
    # one float32 leaf of whole kernel tiles (32 rows of 512)
    "aligned_leaf": [((64, 256), "float32")],
    "unaligned_leaf": [((5, 1000), "float32")],
    # what DDP's norm bucket looks like, with a bf16 leaf thrown in
    "multi_leaf": [((4096,), "float32"), ((3, 700), "bfloat16"), ((4096,), "float32")],
    # run with _TRANSFER_CHUNK at four blocks: the bounded path, three
    # whole pieces and a tail
    "past_one_chunk": [((3 * 4 * 512 + 777,), "float32")],
}
SMALL_CHUNK = 4 * Q.BLOCK

PARENT = {
    ("aligned_leaf", 8): ("1a39e0f10b91ff7d", "bf8c88d10f851512", "bafffe83a555195f"),
    ("aligned_leaf", 4): ("f0c845f958b2a395", "368db936d0ca9f58", "08830abb76332ef9"),
    ("unaligned_leaf", 8): ("c2d882abfe9883c0", "b6ac15eb8563981f", "905687ee3806c594"),
    ("unaligned_leaf", 4): ("fbf3cfb4e83ff723", "4e76f966a441c7b7", "13bb93d5c539580c"),
    ("multi_leaf", 8): ("5b4ab440e38ba781", "7555a3d06a65745c", "b362160db7d4a227"),
    ("multi_leaf", 4): ("2877fb8dcb97ff5f", "53af65b8d713be97", "b532f6a5570fc742"),
    ("past_one_chunk", 8): ("5fd6ebd76d0f5197", "79a07bc6e3de0ea2", "b0f73af2d00698e8"),
    ("past_one_chunk", 4): ("3c5dde7ba4e80b2e", "881e34e9a3997928", "73c1ad81a509b03c"),
}
# The scales' last ulp is the CPU compiler's. At XLA's default level the
# division by 127 or 7 is folded into a reciprocal multiply (``PARENT``'s
# scales and what the host dequantizer makes of them, from 035766e); at
# level 0, the suite's (tests/conftest.py), it is a division: this tree's
# digests there, whose payloads, and whose scales at the default level, are
# ``PARENT``'s (``XLA_FLAGS=--xla_backend_optimization_level=0 python
# tests/test_quant_bucket_programs.py`` prints them).
LEVEL_0 = {
    ("aligned_leaf", 8): ("7ea083f23cac1dba", "f6d1af74d4d385bb"),
    ("aligned_leaf", 4): ("f7029af672b130aa", "825ad057643f602d"),
    ("unaligned_leaf", 8): ("7e0c61141360d2f3", "876f091df6bd3654"),
    ("unaligned_leaf", 4): ("3dd7ea531580d8a3", "24cc2f19a3369845"),
    ("multi_leaf", 8): ("16af072220167d0e", "2dbbabf7ce2784c0"),
    ("multi_leaf", 4): ("a3ab32a7d3176670", "c068d47f6e3f6de2"),
    ("past_one_chunk", 8): ("b49b9b5dfb2c538c", "cba6e4ece3775bed"),
    ("past_one_chunk", 4): ("e8af83c3b0922604", "4f705040064808d1"),
}
if "xla_backend_optimization_level=0" in os.environ.get("XLA_FLAGS", ""):
    PARENT = {case: (PARENT[case][0], *LEVEL_0[case]) for case in PARENT}


def make_leaves(spec, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), len(spec))
    return [
        (jax.random.normal(k, shape, jnp.float32) * (i + 1)).astype(dtype)
        for i, (k, (shape, dtype)) in enumerate(zip(keys, spec))
    ]


def host_flat(leaves):
    """The bucket's flat payload as the host path packs it."""
    return np.concatenate(
        [np.asarray(a.astype(jnp.float32)).reshape(-1) for a in leaves]
    )


def _digest(a):
    return hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()[:16]


def _chunk_for(name, monkeypatch):
    if name == "past_one_chunk":
        monkeypatch.setattr(Q, "_TRANSFER_CHUNK", SMALL_CHUNK)


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("name", list(CASES))
def test_down_program_is_the_host_quantizer_and_the_parent_bit_for_bit(
    name, bits, monkeypatch
):
    _chunk_for(name, monkeypatch)
    leaves = make_leaves(CASES[name])
    flat = host_flat(leaves)
    chunks, n = Q.quantize_for_transfer_async(leaves, bits)
    assert n == flat.size
    assert len(chunks) == (4 if name == "past_one_chunk" else 1)
    q, s, n = Q.pull_transfer_chunks(chunks, n, bits)
    q_host, s_host = quantize_blockwise(flat, bits)
    assert q.dtype == np.int8 and q.shape == q_host.shape
    # A bf16 leaf's values sit on the 4-bit rounding boundaries often
    # enough that the scale's last ulp (below) shows in the payload: there
    # a few values may differ from the host's by one level.
    levels = [
        C.unpack_nibbles(p, p.size * 2) if bits == 4 else p for p in (q, q_host)
    ]
    off = np.abs(levels[0].astype(np.int16) - levels[1].astype(np.int16))
    if (name, bits) == ("multi_leaf", 4):
        assert off.max() <= 1 and (off != 0).mean() < 1e-3
    else:
        assert off.max() == 0
    # XLA folds the division by 127 or 7 into a reciprocal multiply: a
    # scale can sit one ulp off the host's division; the payload matches
    # bit for bit, and both match the parent's (the digests).
    np.testing.assert_allclose(s, s_host, rtol=1e-6)
    assert (_digest(q), _digest(s)) == PARENT[(name, bits)][:2]
    # and the synchronous form is the same composition
    q2, s2, _ = Q.quantize_for_transfer(leaves, bits)
    np.testing.assert_array_equal(q2, q)
    np.testing.assert_array_equal(s2, s)


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("name", list(CASES))
def test_up_program_gives_the_leaves_of_the_host_dequantizer(
    name, bits, monkeypatch
):
    _chunk_for(name, monkeypatch)
    spec = CASES[name]
    leaves = make_leaves(spec)
    flat = host_flat(leaves)
    q, s, _ = Q.quantize_for_transfer(leaves, bits)
    shapes = [shape for shape, _ in spec]
    dtypes = [jnp.dtype(d) for _, d in spec]
    for scale in (1.0, 0.25, 1.0 / 3.0):
        got = Q.dequantize_leaves_from_transfer(q, s, shapes, dtypes, scale, bits)
        want = dequantize_blockwise(q, s, flat.size, bits)
        if scale != 1.0:
            want = want * np.float32(scale)
        if scale == 0.25:
            assert _digest(want) == PARENT[(name, bits)][2]
        offset = 0
        assert len(got) == len(spec)
        for leaf, (shape, dtype) in zip(got, spec):
            size = int(np.prod(shape))
            by_hand = jnp.asarray(want[offset : offset + size]).reshape(shape)
            offset += size
            assert leaf.shape == tuple(shape) and leaf.dtype == jnp.dtype(dtype)
            np.testing.assert_array_equal(
                np.asarray(leaf.astype(jnp.float32)),
                np.asarray(by_hand.astype(dtype).astype(jnp.float32)),
            )
    # the flat form is the one-leaf case of the same programs
    back = Q.dequantize_from_transfer(q, s, flat.size, bits)
    np.testing.assert_array_equal(
        np.asarray(back), dequantize_blockwise(q, s, flat.size, bits)
    )


# ---------------------------------------------------------------------------
# The copies
# ---------------------------------------------------------------------------


class _Recorded:
    """Stands in for one device array of a chunk: writes down when its
    copy is asked for and when the host waits for it."""

    def __init__(self, name, log, array):
        self.name, self.log, self.array = name, log, array

    def copy_to_host_async(self):
        self.log.append(("ask", self.name))

    def __array__(self, dtype=None, copy=None):
        self.log.append(("wait", self.name))
        return np.asarray(self.array)


def _recorded(chunks, name, log):
    return [
        (_Recorded(f"{name}{i}.q", log, q), _Recorded(f"{name}{i}.s", log, s), m)
        for i, (q, s, m) in enumerate(chunks)
    ]


@pytest.mark.parametrize("name", ["aligned_leaf", "past_one_chunk"])
def test_a_chunks_two_copies_are_asked_together_and_one_chunk_at_a_time(
    name, monkeypatch
):
    _chunk_for(name, monkeypatch)
    leaves = make_leaves(CASES[name])
    chunks, n = Q.quantize_for_transfer_async(leaves, 8)
    assert len(chunks) == (4 if name == "past_one_chunk" else 1)
    log = []
    q, s, _ = Q.pull_transfer_chunks(_recorded(chunks, "c", log), n, 8)
    want = []
    for i in range(len(chunks)):
        want += [("ask", f"c{i}.q"), ("ask", f"c{i}.s")]
        want += [("wait", f"c{i}.q"), ("wait", f"c{i}.s")]
    assert log == want
    q_host, s_host = quantize_blockwise(host_flat(leaves), 8)
    np.testing.assert_array_equal(q, q_host)
    np.testing.assert_allclose(s, s_host, rtol=1e-6)  # a last ulp, as above


# ---------------------------------------------------------------------------
# Through the collective: two ranks in one process, the device path forced
# ---------------------------------------------------------------------------


@pytest.fixture()
def pair(monkeypatch):
    monkeypatch.setenv("TORCHFT_FORCE_DEVICE_QUANT", "1")
    server = TCPStoreServer()
    groups = _make_group(server, 2, prefix="qb")
    yield groups
    for g in groups:
        g.shutdown()
    server.shutdown()


def _allreduce_both(groups, per_rank_leaves, **kwargs):
    def run(rank):
        work = C.allreduce_quantized_jax(
            groups[rank], per_rank_leaves[rank], **kwargs
        )
        return work.wait(timeout=60)

    return _run_parallel([lambda r=r: run(r) for r in range(len(groups))])


class _CompileCount:
    """The CPU twin of the benchmark's ``reloads_step``: one
    ``backend_compile_duration`` event a program JAX compiles or fetches
    from its cache (``benchmark/worker.py`` ``CompileLog``)."""

    def __init__(self):
        from jax import monitoring

        self.on = False
        self.programs = 0
        monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, secs, **_):
        if self.on and name == "/jax/core/compile/backend_compile_duration":
            self.programs += 1


@pytest.mark.timeout(180)
def test_three_calls_on_one_layout_compile_nothing_after_the_first(pair):
    spec = CASES["multi_leaf"]
    count = _CompileCount()
    results = []
    for call in range(3):
        per_rank = [make_leaves(spec, seed=10 * call + r) for r in range(2)]
        count.on = call > 0
        results.append(
            _allreduce_both(
                pair, per_rank, op=C.ReduceOp.AVG, scale=1.0 / (call + 1)
            )
        )
        jax.block_until_ready(results[-1])
    count.on = False
    assert count.programs == 0
    # and what came back is the wire's arithmetic on the joined leaves
    per_rank = [make_leaves(spec, seed=20 + r) for r in range(2)]
    flats = [host_flat(leaves) for leaves in per_rank]
    summed = sum(
        dequantize_blockwise(*quantize_blockwise(f, 8), f.size, 8) for f in flats
    )
    for outs in results[2]:
        got = np.concatenate(
            [np.asarray(o.astype(jnp.float32)).reshape(-1) for o in outs]
        )
        np.testing.assert_allclose(got, summed / 3 / 2, rtol=2e-2, atol=2e-2)
        assert [o.dtype for o in outs] == [jnp.dtype(d) for _, d in spec]


@pytest.mark.timeout(180)
def test_no_copy_is_asked_before_its_buckets_pull_turn_and_turns_go_in_issue_order(
    pair, monkeypatch
):
    logs = {0: [], 1: []}
    issued = {0: 0, 1: 0}
    real = Q.quantize_for_transfer_async
    rank_of = threading.local()

    def recording(x, bits=8):
        chunks, n = real(x, bits)
        rank = rank_of.rank
        name = "abc"[issued[rank]]
        issued[rank] += 1
        return _recorded(chunks, name, logs[rank]), n

    monkeypatch.setattr(Q, "quantize_for_transfer_async", recording)
    specs = [CASES["multi_leaf"], CASES["aligned_leaf"], CASES["unaligned_leaf"]]

    def run(rank):
        rank_of.rank = rank
        works = [
            C.allreduce_quantized_jax(pair[rank], make_leaves(spec, seed=rank))
            for spec in specs
        ]
        return [w.wait(timeout=60) for w in works]

    _run_parallel([lambda r=r: run(r) for r in range(2)])
    for rank in range(2):
        want = []
        for name in "abc":
            want += [("ask", f"{name}0.q"), ("ask", f"{name}0.s")]
            want += [("wait", f"{name}0.q"), ("wait", f"{name}0.s")]
        assert logs[rank] == want


if __name__ == "__main__":
    for case, case_spec in CASES.items():
        for width in (8, 4):
            Q._TRANSFER_CHUNK = SMALL_CHUNK if case == "past_one_chunk" else 1 << 27
            arrays = make_leaves(case_spec)
            payload, scales, count = Q.quantize_for_transfer(arrays, width)
            back = np.asarray(
                Q.dequantize_from_transfer(payload, scales, count, width)
            ) * np.float32(0.25)
            print(
                f'    ("{case}", {width}): ("{_digest(payload)}", '
                f'"{_digest(scales)}", "{_digest(back)}"),'
            )
