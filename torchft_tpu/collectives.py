"""Quantized collectives for the replica (DCN) axis.

Capability parity with the reference's ``torchft/collectives.py:159-415``:
``allreduce_quantized`` cuts outer-axis gradient traffic ~4x by sending
block-quantized int8 with per-block float scales instead of float32, using
the same alltoall -> local-reduce-in-full-precision -> allgather pipeline
(sums are computed in float32, so quantization error does not accumulate
across ranks; only one quantize->dequantize round trip per value).

The reference quantizes with Triton fp8 kernels on CUDA; here the host path
is vectorized numpy int8 (DCN transfers are host-driven), and
``torchft_tpu/ops/quantization.py`` provides Pallas TPU kernels for
quantizing on-device before the device->host pull.
"""

from __future__ import annotations

import contextlib
import os
import threading
import weakref
from typing import Callable, List, Sequence, Tuple

import numpy as np

from torchft_tpu import knobs
from torchft_tpu.process_group import ProcessGroup, ReduceOp
from torchft_tpu.telemetry import (
    current_span,
    next_bucket,
    span_parent,
    trace_span,
)
from torchft_tpu.work import DummyWork, FutureWork, Work

BLOCK = 512  # values per quantization scale


class _WireOrder:
    """Admits one process group's quantized collectives to the wire in
    ISSUE order. Each collective runs on its own thread and quantizes at
    its own pace, but the PG pairs ops across replicas by a sequence number
    taken when the op is called: with several buckets in flight, two that
    reach the wire in different orders on two replicas would exchange each
    other's payloads (seen on the chip: 16 gradient buckets of a 125M
    model, device-quantized on one replica and host-quantized on the
    other, failed every step with reshape errors).

    Who relies on it: wire order = issue order, and the callers' issue
    order is the same on every replica of a quorum. For
    ``DistributedDataParallel`` that is ``ddp.issue_order`` of the bucket
    layout, ascending size, on the device and the host branch alike; a
    quorum that mixes replicas from before and after that order would
    pair different buckets and is not supported.

    A second instance on the same group (``_quant_pull_order``) gives the
    device-to-host pulls of the device path their turns, in the same
    order: bucket k+1 pulls while bucket k is on the wire, and the
    smallest bucket's payload is the first the host waits for (ten
    blocking pulls at once moved 0.33 GB/s together and held the wire
    back 1.4 s a step on the chip; so did ten copies asked for at once
    and waited for in turn: PERF.md section 6, PR 40). It is
    taken only by a collective that has device chunks to pull, hangs on
    the group for the same reason the wire's does, and is let go with the
    wire scratch (``ProcessGroup._drop_wire_scratch``): the collectives of
    an aborted step pass their pull turns on among themselves, and the
    next step's pulls do not queue behind them (its wire turns do, as
    they always have: this instance stays)."""

    def __init__(self) -> None:
        self._cv = threading.Condition()
        self._issued = 0
        self._serving = 0

    def take(self) -> int:
        with self._cv:
            ticket = self._issued
            self._issued += 1
            return ticket

    def wait(self, ticket: int) -> None:
        """Blocks until it is ``ticket``'s turn; returns at once when that
        ticket has already been served."""
        with self._cv:
            self._cv.wait_for(lambda: self._serving >= ticket)

    def done(self, ticket: int) -> None:
        with self._cv:
            self._serving = max(self._serving, ticket + 1)
            self._cv.notify_all()


_wire_order_lock = threading.Lock()


class _WireScratch:
    """Host buffers of one process group's quantized wire stage, kept
    across steps: a payload-sized numpy array is mapped, page-faulted in
    and unmapped again every time it is made, which cost the stage more
    than its arithmetic. Sizes come from the payloads seen and only grow.
    The scratch hangs on the group beside its ``_WireOrder`` (several
    ranks can live in one process, so nothing here is module-global) and
    is let go when the group is torn down or reconfigured
    (``ProcessGroup._drop_wire_scratch``).

    Who may write what, and when:

    * ``turn(name, ...)`` buffers — the fp32 sum, the requantized chunk,
      the per-task pieces — belong to whoever holds the group's wire
      turn. One collective at a time does (``_spawn_collective``), and
      nothing of them may leave the turn: a caller that hands one out
      copies it first (``reduce_scatter_quantized``).
    * ``result(...)`` buffers — the joined payload and scales — are read
      after the turn has passed on, by ``dequant_push`` and by
      host-to-device copies that may still be in flight when the
      collective's thread returns. So each is lent out through an array
      of its own, and comes back to the free list only when that array
      and every view of it are gone: JAX holds the host array until its
      transfer is done (off-TPU it may alias it for as long as the device
      array lives), numpy readers until they return. A buffer some reader
      still sees is never rewritten; a new one is made instead.
    """

    def __init__(self) -> None:
        self._turn: dict = {}
        self._free: List[np.ndarray] = []  # result buffers nobody reads
        self._fresh = 0
        self._reused = 0

    def turn(self, name: str, dtype, shape) -> np.ndarray:
        """The turn's buffer called ``name`` at exactly ``shape``; its
        contents are whatever the last turn left there."""
        count = int(np.prod(shape))
        buf = self._turn.get(name)
        if buf is None or buf.size < count:
            buf = self._turn[name] = np.empty(count, dtype=dtype)
            self._fresh += buf.nbytes
        else:
            self._reused += count * buf.itemsize
        return buf[:count].reshape(shape)

    def result(self, q_bytes: int, n_scales: int) -> Tuple[np.ndarray, np.ndarray]:
        """Lends (int8 payload, fp32 scales) of exactly these sizes, out
        of one buffer; see the class docstring for when it comes back."""
        s_at = -(-q_bytes // 64) * 64
        nbytes = s_at + 4 * n_scales
        # By index, never by value: ``list.remove`` would compare arrays.
        # A buffer that comes back meanwhile is appended, so an index
        # taken here stays good.
        free = self._free
        by_size = sorted(range(len(free)), key=lambda i: free[i].size)
        fit = next((i for i in by_size if free[i].size >= nbytes), None)
        if fit is not None:
            owner = free.pop(fit)
            self._reused += nbytes
        else:
            if by_size:  # outgrown: the new one takes its place
                free.pop(by_size[0])
            owner = np.empty(nbytes, dtype=np.uint8)
            self._fresh += nbytes
        # Views of ``loan`` name ``loan`` as their base, not ``owner``:
        # numpy follows a chain of views down to the first array whose
        # base is not an array, and a memoryview is not one.
        loan = np.frombuffer(owner.data, dtype=np.uint8)
        weakref.finalize(loan, self._free.append, owner)
        return (
            loan[:q_bytes].view(np.int8),
            loan[s_at:nbytes].view(np.float32),
        )

    def counts(self) -> dict:
        """Bytes allocated and bytes reused since the last call: the
        ``fresh_bytes``/``reused_bytes`` of one ``wire_reduce`` span."""
        out = {"fresh_bytes": self._fresh, "reused_bytes": self._reused}
        self._fresh = self._reused = 0
        return out


def _wire_scratch(pg: ProcessGroup) -> _WireScratch:
    """``pg``'s scratch, made on first use, but never on a group that has
    latched an error: a collective of a torn step that gets its wire turn
    after the abort fails here (its socket operations would fail next)
    instead of hanging a new scratch on the dead group. ``abort`` latches
    the error before it drops the scratch, so the look after the
    ``setdefault`` catches an abort that ran beside it."""

    def check() -> None:
        err = pg.errored()
        if err is not None:
            pg.__dict__.pop("_quant_wire_scratch", None)
            raise RuntimeError(f"process group errored: {err}") from err

    check()
    with _wire_order_lock:
        scratch = pg.__dict__.setdefault("_quant_wire_scratch", _WireScratch())
    check()
    return scratch


def _turn(order: _WireOrder, ticket: int, wait_span: str, bucket: "int | None"):
    """``with held():`` waits for ``ticket``'s turn in ``order`` under a
    span called ``wait_span`` and passes the turn on at the end."""

    @contextlib.contextmanager
    def held():
        with trace_span(wait_span, bucket=bucket):
            order.wait(ticket)
        try:
            yield
        finally:
            order.done(ticket)

    return held


def _spawn_collective(
    pg: ProcessGroup, fn, bucket: "int | None" = None, pulls: bool = False
) -> "concurrent.futures.Future":
    """One daemon thread per in-flight quantized collective. A bounded pool
    would deadlock when several ranks live in one process (tests, parameter
    server): every rank's pipeline must make progress concurrently for any
    alltoall to complete.

    ``fn(wire)`` must run its PG ops inside ``with wire():`` — its turn in
    the issue order of ``pg`` (the ticket is taken here, on the caller's
    thread). The thread has no span stack of its own: it hangs its spans
    under the span open on the caller's thread now (for DDP a descendant
    of the ``allreduce_grads`` root), and the time it is blocked for its
    turn is the ``wire_turn_wait`` span of ``bucket``.

    With ``pulls`` (a collective that has device chunks to bring to the
    host) it is ``fn(wire, pull)``, and ``with pull():`` is its turn on the
    device-to-host path, in the same issue order (``pull_turn_wait``)."""
    import concurrent.futures

    with _wire_order_lock:
        order = pg.__dict__.setdefault("_quant_wire_order", _WireOrder())
        pull_order = (
            pg.__dict__.setdefault("_quant_pull_order", _WireOrder())
            if pulls
            else None
        )
    # Tickets now, on the caller's thread. ``turns`` is in the order the
    # collective takes them, which is the order the ``finally`` below
    # passes on those it never took.
    wire_turn = (order, order.take())
    turns = [wire_turn]
    args = [_turn(*wire_turn, "torchft::collectives::wire_turn_wait", bucket)]
    if pull_order is not None:
        pull_turn = (pull_order, pull_order.take())
        turns.insert(0, pull_turn)
        args.append(
            _turn(*pull_turn, "torchft::collectives::pull_turn_wait", bucket)
        )
    parent = current_span()
    fut: concurrent.futures.Future = concurrent.futures.Future()

    def run() -> None:
        try:
            with span_parent(parent):
                if fut.set_running_or_notify_cancel():
                    fut.set_result(fn(*args))
        except BaseException as e:  # noqa: BLE001 - delivered via the future
            fut.set_exception(e)
        finally:
            # Died before a turn of its own (or cancelled): still pass it
            # on, the pull's before the wire's.
            for o, t in turns:
                o.wait(t)
                o.done(t)

    threading.Thread(target=run, daemon=True, name="quant-collective").start()
    return fut


# Host-side (de)quantize runs task-parallel on threads (numpy ufuncs
# release the GIL); each task walks its block range in pieces, every pass
# written in place into buffers that already exist. What the earlier form
# (``zeros``, ``astype``, ``*=``, ``+=`` over payload-sized temporaries)
# paid for on the chip's host was fresh pages, not arithmetic: the wire
# stage of mistral-ft4 (480M gradient elements a step, four ranks at once,
# transparent hugepages off) took 6.9 s a step in that form and 0.52 s in
# this one (PR 24's chip runs; PERF.md section 5). The two sizes are from a
# sweep of the stage alone on that host, four processes at once: pieces of
# 1M values took 0.34 s a step against 0.49 at 256K (more numpy calls, all
# needing the GIL) and 0.51 at 8M (out of cache); tasks of 2M values, 0.50.
_BLOCKS_PER_TASK = 8 * 1024 * 1024 // BLOCK  # 8M values per parallel task
_PIECE_BLOCKS = 2048  # 1M values a pass
# The native codec's tasks (``_native.q8_reducer``) hold no GIL and make no
# numpy calls, so they can be as small as a numpy piece: on a 13-core host
# with four ranks at once, mistral-ft4's chunks took 0.21 s a step in tasks
# of 8M values (a chunk of 1-15M values is one or two of them), 0.13 s at
# 2M and 0.12-0.13 s at 1M (PR 57's chip runs, the wire stage alone).
_NATIVE_BLOCKS_PER_TASK = _PIECE_BLOCKS
_host_pool = None
_host_pool_lock = threading.Lock()


def _pool():
    global _host_pool
    with _host_pool_lock:
        if _host_pool is None:
            import concurrent.futures

            _host_pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=min(8, os.cpu_count() or 4),
                thread_name_prefix="quant-host",
            )
        return _host_pool


def _parallel_over_blocks(
    n_blocks: int, fn, per_task: "int | None" = None
) -> None:
    """Runs fn(block_start, block_end) over block ranges in parallel, of
    ``per_task`` blocks each (``_BLOCKS_PER_TASK`` unless given)."""
    per_task = per_task or _BLOCKS_PER_TASK
    if n_blocks <= per_task:
        fn(0, n_blocks)
        return
    tasks = []
    for start in range(0, n_blocks, per_task):
        tasks.append(_pool().submit(fn, start, min(start + per_task, n_blocks)))
    for t in tasks:
        t.result()


def _join(parts: "Sequence[np.ndarray]", out: np.ndarray) -> None:
    """``out[:]`` = the 1-D ``parts`` end to end. A payload's worth is a
    copy a part, side by side (a plain copy holds no GIL)."""
    if out.size <= _BLOCKS_PER_TASK * BLOCK:
        np.concatenate(parts, out=out)
        return
    tasks, off = [], 0
    for part in parts:
        tasks.append(_pool().submit(np.copyto, out[off : off + part.size], part))
        off += part.size
    for t in tasks:
        t.result()


def _task_tmp_shape(n_blocks: int) -> Tuple[int, int, int]:
    """One fp32 piece per task of :func:`_parallel_over_blocks`."""
    n_tasks = max(-(-n_blocks // _BLOCKS_PER_TASK), 1)
    return n_tasks, min(_PIECE_BLOCKS, n_blocks), BLOCK


def _task_tmp(tmp: "np.ndarray | None", b0: int, b1: int) -> np.ndarray:
    """The piece-sized fp32 temporary of the task over blocks [b0, b1):
    its own slot of ``tmp`` (shaped by :func:`_task_tmp_shape`), or a new
    one where the caller keeps no scratch."""
    if tmp is None:
        return np.empty((min(_PIECE_BLOCKS, b1 - b0), BLOCK), np.float32)
    return tmp[b0 // _BLOCKS_PER_TASK]


def _qmax(bits: int) -> float:
    """Symmetric integer range: 127 for int8, 7 for int4."""
    if bits == 8:
        return 127.0
    if bits == 4:
        return 7.0
    raise ValueError(f"unsupported quantization width: {bits} bits")


def pack_nibbles(q: np.ndarray) -> np.ndarray:
    """Packs int8 values in [-7, 7] two-per-byte (two's-complement 4-bit
    nibbles; even index -> low nibble). Wire format of the ``bits=4``
    codec — halves outer-axis bytes vs int8 (the reference's fp8 is
    8-bit; 4-bit matches the Streaming-DiLoCo-style compressed outer
    sync)."""
    u = q.astype(np.uint8) & 0xF
    return (u[0::2] | (u[1::2] << 4)).view(np.int8)


def unpack_nibbles(p: np.ndarray, n_vals: int) -> np.ndarray:
    """Inverse of :func:`pack_nibbles`; returns int8 values of length
    ``n_vals`` with sign extension."""
    u = p.view(np.uint8)
    out = np.empty(u.size * 2, dtype=np.uint8)
    out[0::2] = u & 0xF
    out[1::2] = u >> 4
    # Two's-complement sign extension of the 4-bit field.
    out = ((out ^ 8).astype(np.int8) - 8)
    return out[:n_vals]


def _quantize_into(
    flat: np.ndarray,
    bits: int,
    q_out: np.ndarray,
    s_out: np.ndarray,
    tmp: "np.ndarray | None",
) -> None:
    """Block-quantizes the contiguous fp32 ``flat`` into ``q_out`` (the
    payload bytes of ``s_out.size`` whole blocks, nibble-packed for 4
    bits; a short last block is padded with zeros) and ``s_out``. Every
    pass writes in place, into a piece of ``tmp`` per task."""
    n = flat.size
    qmax = _qmax(bits)
    bpb = BLOCK // (8 // bits)  # payload bytes per block

    def work(b0: int, b1: int) -> None:
        t = _task_tmp(tmp, b0, b1)
        for p0 in range(b0, b1, _PIECE_BLOCKS):
            p1 = min(p0 + _PIECE_BLOCKS, b1)
            src = flat[p0 * BLOCK : min(p1 * BLOCK, n)]
            if src.size != (p1 - p0) * BLOCK:  # tail: pad to whole blocks
                padded = np.zeros((p1 - p0) * BLOCK, dtype=np.float32)
                padded[: src.size] = src
                src = padded
            mat = src.reshape(p1 - p0, BLOCK)
            buf = t[: p1 - p0]
            s = s_out[p0:p1]
            np.abs(mat, out=buf)
            np.max(buf, axis=1, out=s)
            s /= qmax
            np.copyto(s, 1.0, where=(s == 0))
            np.divide(mat, s[:, None], out=buf)
            np.rint(buf, out=buf)
            np.clip(buf, -qmax, qmax, out=buf)
            if bits == 4:
                q_out[p0 * bpb : p1 * bpb] = pack_nibbles(
                    buf.astype(np.int8).reshape(-1)
                )
            else:
                q_out[p0 * bpb : p1 * bpb] = buf.reshape(-1)

    _parallel_over_blocks(s_out.size, work)


def quantize_blockwise(
    flat: np.ndarray, bits: int = 8
) -> Tuple[np.ndarray, np.ndarray]:
    """Block-quantizes a 1-D float array with one float32 scale per BLOCK
    values (the rowwise-fp8 analog of quantization.py:44-162). Returns
    (int8 payload, float32 scales); with ``bits=4`` the payload is
    nibble-packed (BLOCK/2 bytes per block)."""
    blocks = (flat.size + BLOCK - 1) // BLOCK
    q = np.empty(blocks * (BLOCK // (8 // bits)), dtype=np.int8)
    scales = np.empty(blocks, dtype=np.float32)
    flat = np.ascontiguousarray(flat, dtype=np.float32)
    _quantize_into(flat, bits, q, scales, None)
    return q, scales


def _dequantize_sum(
    out: np.ndarray,
    peers: "Sequence[Tuple[np.ndarray, np.ndarray]]",
    bits: int,
    tmp: "np.ndarray | None",
) -> None:
    """``out[:]`` = the sum over ``peers`` — (payload, scales) pairs of
    ``out.size // BLOCK`` whole blocks each — of ``float32(q) * scale``,
    added in the order given. The first peer is decoded straight into
    ``out``, each later one into a piece of ``tmp`` per task and added:
    no zero fill, no payload-sized temporary. One peer is a plain
    dequantize and needs no ``tmp``."""
    bpb = BLOCK // (8 // bits)
    out2 = out.reshape(-1, BLOCK)

    def work(b0: int, b1: int) -> None:
        t = _task_tmp(tmp, b0, b1) if len(peers) > 1 else None
        for p0 in range(b0, b1, _PIECE_BLOCKS):
            p1 = min(p0 + _PIECE_BLOCKS, b1)
            acc = out2[p0:p1]
            for i, (q, scales) in enumerate(peers):
                piece = q[p0 * bpb : p1 * bpb]
                if bits == 4:
                    piece = unpack_nibbles(piece, (p1 - p0) * BLOCK)
                into = acc if i == 0 else t[: p1 - p0]
                np.multiply(
                    piece.reshape(-1, BLOCK),
                    scales[p0:p1, None],
                    out=into,
                    dtype=np.float32,
                    casting="unsafe",
                )
                if i:
                    acc += into

    _parallel_over_blocks(out2.shape[0], work)


def dequantize_blockwise(
    q: np.ndarray, scales: np.ndarray, n: int, bits: int = 8
) -> np.ndarray:
    out = np.empty(scales.size * BLOCK, dtype=np.float32)
    _dequantize_sum(out, [(q, scales)], bits, None)
    return out[:n]


def _flatten(arrays: Sequence[np.ndarray]) -> Tuple[np.ndarray, List[int]]:
    sizes = [a.size for a in arrays]
    flat = np.concatenate([a.reshape(-1).astype(np.float32) for a in arrays])
    return flat, sizes


def _unflatten_into(
    arrays: Sequence[np.ndarray], flat: np.ndarray, sizes: List[int]
) -> None:
    offset = 0
    for a, n in zip(arrays, sizes):
        a[...] = flat[offset : offset + n].reshape(a.shape).astype(
            a.dtype, copy=False
        )
        offset += n


def _bucket_tags(arrays: Sequence) -> dict:
    """What every span of one bucket's collective carries: the bucket's
    ordinal under the caller's outermost open span (taken now, on the
    caller's thread) and its unquantized payload bytes."""
    return {
        "bucket": next_bucket(),
        "nbytes": sum(a.nbytes for a in arrays),
    }


def device_quantize() -> bool:
    """Whether jax-array payloads quantize ON DEVICE (Pallas kernels) or
    through the host numpy quantizer — the one rule, also journaled per
    allreduce so a run can prove which branch it took."""
    import jax

    return jax.default_backend() == "tpu" or knobs.get_bool(
        "TORCHFT_FORCE_DEVICE_QUANT"
    )


def allreduce_quantized_jax(
    pg: ProcessGroup,
    arrays: Sequence["jax.Array"],  # noqa: F821 - imported lazily
    op: ReduceOp = ReduceOp.SUM,
    scale: float = 1.0,
    bits: int = 8,
) -> Work:
    """Quantized allreduce for jax device arrays: quantize ON DEVICE with the
    Pallas kernels, pull int8 + per-block scales to host (~4x fewer bytes
    than fp32 across PCIe and then DCN), run the alltoall -> fp32 local
    reduce -> allgather wire pipeline on the quantized payload, and
    dequantize ON DEVICE (reference: collectives.py:297-415, with the
    device-side quantize the Triton kernels provide there).

    A bucket costs one of each thing, each way: one cached compiled
    program down (``ops.quantization.quantize_for_transfer_async``: the
    leaves joined, quantized and laid out for the wire), one
    device-to-host copy, asked for in the bucket's pull turn (so one at a
    time, in issue order), one alltoall, one allgather, one
    host-to-device transfer and one cached compiled program up
    (``dequantize_leaves_from_transfer``: its outputs are the leaves). No
    eager device operation runs per call but the snapshot below; a
    payload too large for one program takes the bounded path of those
    two functions, chosen from its size.

    Returns Work whose result is a list of NEW jax arrays (original
    shapes/dtypes), scaled by ``scale`` on device. The inputs are not
    mutated (jax arrays are immutable).
    """
    import jax.numpy as jnp

    from torchft_tpu.ops import quantization as Q

    if op not in (ReduceOp.SUM, ReduceOp.AVG):
        raise ValueError(f"allreduce_quantized supports SUM/AVG, got {op}")
    arrays = list(arrays)
    shapes = tuple(a.shape for a in arrays)
    dtypes = tuple(a.dtype for a in arrays)

    ws = pg.size()
    total_scale = scale / ws if op == ReduceOp.AVG else scale

    # On TPU the Pallas kernels quantize/dequantize ON DEVICE (int8 over
    # PCIe, ~4x fewer bytes).  Off-TPU those same kernels would run
    # through the Pallas INTERPRETER — a test shim, seconds per MB — so
    # the compiled-CPU deployment path is the vectorized host quantizer
    # (same wire format bit-for-bit; the bench peer already uses it for
    # exactly this reason).  TORCHFT_FORCE_DEVICE_QUANT forces the
    # device path anyway (Pallas interpreter off-TPU; a no-op on TPU,
    # where the device path is already taken): the cross-path
    # wire-equality test drives it.
    host_quant = not device_quantize()

    tags = _bucket_tags(arrays)
    # The caller-thread part; nothing in it waits for the device.
    with trace_span("torchft::collectives::dispatch", **tags):
        if ws <= 1:
            if scale == 1.0:
                return DummyWork(arrays)
            return DummyWork(
                Q.cut_leaves(
                    Q.flatten_leaves(arrays),
                    np.float32(scale),
                    shapes=shapes,
                    dtypes=dtypes,
                )
            )
        a0 = arrays[0]
        if len(arrays) == 1 and a0.ndim == 1 and a0.dtype == jnp.float32:
            # Nothing below copies such an input before this call
            # returns (its ravel and cast are the array itself), yet
            # parts of the pipeline touch it afterwards (host path: the
            # deferred host pull; device path: the quantize program,
            # enqueued but not yet executed) while the caller's next
            # train step may DONATE this buffer (make_train_step donates
            # its state), deleting it mid-use.  Materialize an
            # independent device snapshot before returning to the
            # caller.  (Below the ws<=1 return: the single-replica path
            # never defers.)
            arrays = [jnp.copy(a0)]

        # Device path: dispatch the quantize program NOW, on the caller's
        # thread. Async dispatch returns immediately, but enqueues the
        # kernel right behind the compute that produced the leaves —
        # BEFORE the caller's next training window. The deferred host
        # pull then overlaps that window; dispatched lazily from the
        # collective thread instead, the kernel would queue behind the
        # whole next window and the "pull" would spend its time waiting
        # on unrelated compute.
        flat = q_chunks = None
        n_elems = 0
        if host_quant:
            flat = Q.flatten_leaves(arrays)
        else:
            q_chunks, n_elems = Q.quantize_for_transfer_async(arrays, bits)
        # The enqueued program holds its own reference to the leaves (or
        # the snapshot); don't let the run() closure pin the fp32 inputs
        # across the multi-second wire pipeline too.
        del arrays, a0

    def run(wire, pull=contextlib.nullcontext) -> List["jax.Array"]:
        # Device chunks are pulled one bucket at a time, in issue order;
        # the host quantizer pulls nothing and takes no turn.
        with pull(), trace_span("torchft::collectives::quantize_pull", **tags):
            if host_quant:
                flat_host = np.asarray(flat, dtype=np.float32)
                n = flat_host.size
                q_host, s_host = quantize_blockwise(flat_host, bits)
            else:
                q_host, s_host, n = Q.pull_transfer_chunks(
                    q_chunks, n_elems, bits
                )
        with wire(), trace_span("torchft::collectives::wire", **tags):
            reduced = _quantized_wire_pipeline(pg, q_host, s_host, n, bits)
        with trace_span("torchft::collectives::dequant_push", **tags):
            if host_quant and not isinstance(reduced, np.ndarray):
                reduced = dequantize_blockwise(*reduced, n, bits)
            if isinstance(reduced, np.ndarray):
                # Already fp32 on the host: the host quantizer's decode,
                # or a tiny payload (the local reduce produced the full
                # sum; no second lossy round trip).
                outs = Q.cut_leaves(
                    jnp.asarray(reduced),
                    None if total_scale == 1.0 else np.float32(total_scale),
                    shapes=shapes,
                    dtypes=dtypes,
                )
            else:
                # Device-side dequantize (the sum stayed fp32 on the wire
                # pipeline so only one quantize->dequantize round trip of
                # error per value).
                outs = Q.dequantize_leaves_from_transfer(
                    *reduced, shapes, dtypes, total_scale, bits
                )
            # BOTH backends: leave the final device arrays async-dispatched.
            # On CPU the dequantize itself already ran on the host above, so
            # every real error class (wire, shape, quantize, reduce) has
            # latched by this point; the only thing a block_until_ready here
            # would add is latching execution faults of the trivial
            # elementwise rebuild ops — and on a 1-core box it DRAINS THE
            # DEVICE QUEUE through the caller's whole in-flight training
            # window (seen once on such a box: a 0.05 MB fragment's
            # "dequant_push" span at 14.7 s, with a 3.1 s exposed tail in
            # the caller's wait), turning the overlapped sync into a
            # serialized one.  The TPU rationale below applies everywhere.
            #
            # TPU: leave the dequantize async-dispatched. Its execution
            # naturally queues behind whatever window the caller has in
            # flight, and wait() returning a not-yet-executed array is
            # exactly XLA's async-dispatch contract — blocking here would
            # re-serialize the window we just overlapped.
            #
            # FT error-latch boundary under async dispatch: everything
            # DISPATCH-time still raises here on the collective thread and
            # latches (shape errors, and HBM OOM — PJRT allocates output
            # buffers at dispatch, so the leaves' fp32 allocation in
            # dequantize_leaves_from_transfer fails synchronously).  Only an
            # EXECUTION-time device fault defers to the caller's next
            # materialize, outside the latch — for static-shaped
            # elementwise kernels on TPU there is no analog of CUDA's
            # illegal-access class, so that residue is accepted as the
            # price of the overlap.
        return outs

    return FutureWork(
        _spawn_collective(pg, run, tags["bucket"], pulls=q_chunks is not None)
    )


def reduce_scatter_quantized(
    pg: ProcessGroup,
    arrays: Sequence[np.ndarray],
    op: ReduceOp = ReduceOp.SUM,
    bits: int = 8,
) -> Work:
    """Quantized reduce_scatter (reference: collectives.py:159-294): the
    alltoall + local-fp32-reduce half of the allreduce pipeline, WITHOUT the
    allgather — each rank keeps only its own reduced shard (block-aligned).

    Returns Work whose result is ``(shard, (start, end))``: this rank's
    fp32 reduced values covering flat elements ``[start, end)`` of the
    concatenated input.
    """
    if op not in (ReduceOp.SUM, ReduceOp.AVG):
        raise ValueError(f"reduce_scatter_quantized supports SUM/AVG, got {op}")
    ws = pg.size()
    arrays = list(arrays)

    def run(wire):
        flat, _sizes = _flatten(arrays)
        n = flat.size
        if ws <= 1:
            return flat, (0, n)
        q_host, s_host = quantize_blockwise(flat, bits)
        blocks = s_host.size
        me = pg.rank()
        counts = [len(c) for c in np.array_split(np.arange(blocks), ws)]
        starts = np.concatenate([[0], np.cumsum(counts)]) * BLOCK
        start, end = int(starts[me]), int(min(starts[me + 1], n))
        with wire():
            if blocks < ws:
                # Tiny payload: gather-all, reduce locally, slice my range.
                gathered = pg.allgather([q_host, s_host]).wait()
                acc = np.zeros(n, np.float32)
                for g_q, g_s in gathered:
                    acc += dequantize_blockwise(g_q, g_s, n, bits)
                shard = acc[start:end]
            else:
                acc = _alltoall_chunk_reduce(
                    pg, q_host, s_host, counts, bits, _wire_scratch(pg),
                    requantize=False,
                )
                # ``acc`` is the next wire turn's to overwrite: what the
                # caller keeps is a copy, made inside this turn.
                shard = acc[: end - start].copy()
        if op == ReduceOp.AVG:
            shard /= ws
        return shard, (start, end)

    return FutureWork(_spawn_collective(pg, run))


def bucketize(arrays: Sequence[np.ndarray], cap_bytes: int) -> List[List[int]]:
    """Greedy same-dtype buckets up to ``cap_bytes`` (reference: <=32 MiB
    flat buffers, local_sgd.py:466-560 / ddp bucketing). Returns index
    groups into ``arrays``."""
    by_dtype: dict = {}
    for i, a in enumerate(arrays):
        by_dtype.setdefault(a.dtype, []).append(i)
    buckets: List[List[int]] = []
    for idxs in by_dtype.values():
        cur: List[int] = []
        size = 0
        for i in idxs:
            nbytes = arrays[i].nbytes
            if cur and size + nbytes > cap_bytes:
                buckets.append(cur)
                cur, size = [], 0
            cur.append(i)
            size += nbytes
        if cur:
            buckets.append(cur)
    return buckets


class ErrorFeedback:
    """Replica-local error-feedback residual store for quantized
    collectives (host path).

    Each sync, the caller compensates its payload with the residual the
    previous sync's quantizer dropped, and the ``on_local_quantized``
    hook (running on the collective thread) records what THIS
    quantization drops.  Residuals never cross the wire — each replica
    ships its own compensated payload — so cross-replica bitwise
    equality of the reduced result is unaffected.

    Heal safety: ``clear()`` bumps a generation counter, and a hook
    created before the clear drops its write — an in-flight allreduce
    issued pre-heal cannot re-insert a stale pre-heal residual after
    the store was reset (the collective thread races the heal
    otherwise).  Reference ceiling is 8-bit fp8 with no feedback
    (torchft/collectives.py:297-415); feedback is what makes <=4-bit
    wire widths usable across many rounds.
    """

    def __init__(self, bits: int) -> None:
        self._bits = bits
        self._residuals: dict = {}
        self._generation = 0
        self._lock = threading.Lock()

    def compensate(self, key, flat: np.ndarray) -> np.ndarray:
        """Returns ``flat`` plus the stored residual for ``key`` (no-op
        when absent or shape-mismatched, e.g. after a re-bucketing)."""
        r = self._residuals.get(key)
        if r is not None and r.size == flat.size:
            return flat + r
        return flat

    def make_hook(self, key) -> Callable:
        """Builds the ``on_local_quantized(wire_flat, q, s)`` callback
        that stores the new residual, pinned to the CURRENT generation."""
        gen = self._generation

        def on_local_quantized(wire_flat, q, s):  # collective thread
            residual = wire_flat - dequantize_blockwise(
                q, s, wire_flat.size, self._bits
            )
            with self._lock:
                if self._generation == gen:
                    self._residuals[key] = residual

        return on_local_quantized

    def clear(self) -> None:
        """Drops all residuals AND invalidates in-flight hooks (heal)."""
        with self._lock:
            self._generation += 1
            self._residuals.clear()

    def __bool__(self) -> bool:
        return bool(self._residuals)


def _native_codec(
    peers: "Sequence[Tuple[np.ndarray, np.ndarray]]", blocks: int, bits: int
):
    """``_native`` where the library's one pass a block can reduce this
    turn's chunk, else None and the numpy passes run. Decided by what is
    there to see, never by a caller: 8-bit payloads, every peer's payload
    and scales of exactly ``blocks`` whole blocks, of their dtypes and
    contiguous (C reads what it is pointed at: a short message has to
    fail in numpy's reshape, as it always has), and a process that has
    the library. The two paths agree to the byte, so replicas that differ
    here still agree."""
    if bits != 8:
        return None
    for q, s in peers:
        if not (
            q.dtype == np.int8
            and s.dtype == np.float32
            and q.size == blocks * BLOCK
            and s.size == blocks
            and q.flags.c_contiguous
            and s.flags.c_contiguous
        ):
            return None
    from torchft_tpu import _native

    return _native if _native.is_available() else None


def _alltoall_chunk_reduce(
    pg: ProcessGroup,
    q_host: np.ndarray,
    s_host: np.ndarray,
    counts: "List[int]",
    bits: int,
    scratch: _WireScratch,
    requantize: bool,
):
    """Shared wire step of both quantized collectives: split the payload
    into per-rank block-aligned chunks, alltoall, and dequantize-accumulate
    every peer's contribution for MY chunk in fp32 (counts[rank] * BLOCK
    values, padded). With ``requantize`` the result is that sum's
    (payload, scales), else the fp32 sum itself; either way in buffers of
    ``scratch`` that the next wire turn overwrites.

    Where :func:`_native_codec` allows, a block is summed and requantized
    in one pass of the library, and a sum nobody asked for is never
    written to memory; else the numpy passes run, to the same bytes."""
    bpb = BLOCK // (8 // bits)  # payload bytes per block
    q_chunks, s_chunks = [], []
    off = 0
    for c in counts:
        q_chunks.append(q_host[off * bpb : (off + c) * bpb])
        s_chunks.append(s_host[off : off + c])
        off += c
    with trace_span("torchft::collectives::wire_alltoall"):
        # This rank's own chunk comes back as the views handed in
        # (``ProcessGroup.alltoall``): read inside the turn only.
        peers = pg.alltoall(
            [[q, s] for q, s in zip(q_chunks, s_chunks)]
        ).wait()
    mine = counts[pg.rank()]
    with trace_span("torchft::collectives::wire_reduce") as span:
        acc = rq = rs = None
        if requantize:
            rq = scratch.turn("rq", np.int8, mine * bpb)
            rs = scratch.turn("rs", np.float32, mine)
        codec = _native_codec(peers, mine, bits)
        if codec is not None:
            if not requantize:
                acc = scratch.turn("acc", np.float32, mine * BLOCK)
            _parallel_over_blocks(
                mine,
                codec.q8_reducer(peers, acc, rq, rs),
                _NATIVE_BLOCKS_PER_TASK,
            )
        else:
            acc = scratch.turn("acc", np.float32, mine * BLOCK)
            tmp = scratch.turn("tmp", np.float32, _task_tmp_shape(mine))
            _dequantize_sum(acc, peers, bits, tmp)
            if requantize:
                _quantize_into(acc, bits, rq, rs, tmp)
        span.attrs.update(
            scratch.counts(),
            native_blocks=mine if codec is not None else 0,
            numpy_blocks=0 if codec is not None else mine,
        )
    return (rq, rs) if requantize else acc


def _quantized_wire_pipeline(
    pg: ProcessGroup,
    q_host: np.ndarray,
    s_host: np.ndarray,
    n: int,
    bits: int = 8,
):
    """The shared quantized-allreduce wire protocol: block-aligned alltoall
    of int8 chunks + scales -> local fp32 reduce -> requantize -> allgather.
    BOTH entry points (jax-array and numpy) use this, so replicas may mix
    input types freely — the wire format never depends on the caller's local
    array type.

    Returns (q_final, s_final) int8+scales for the full buffer, lent by
    the group's :class:`_WireScratch` for as long as the caller (or a
    transfer it started) holds them, or, for tiny payloads (fewer blocks
    than ranks: allgather-all fallback, no chunking), the fully-reduced
    fp32 array of length ``n`` directly. The caller holds ``pg``'s wire
    turn.
    """
    ws = pg.size()
    blocks = s_host.size
    if blocks < ws:
        with trace_span("torchft::collectives::wire_allgather"):
            gathered = pg.allgather([q_host, s_host]).wait()
        with trace_span("torchft::collectives::wire_reduce") as span:
            acc = np.zeros(n, np.float32)
            for g_q, g_s in gathered:
                acc += dequantize_blockwise(g_q, g_s, n, bits)
            span.attrs.update(native_blocks=0, numpy_blocks=blocks)
        return acc
    # Contiguous block-aligned chunks so each chunk owns whole scales;
    # alltoall -> rank r reduces everyone's r-th chunk.
    counts = [len(c) for c in np.array_split(np.arange(blocks), ws)]
    scratch = _wire_scratch(pg)
    rq, rs = _alltoall_chunk_reduce(
        pg, q_host, s_host, counts, bits, scratch, requantize=True
    )
    bpb = BLOCK // (8 // bits)  # payload bytes per block
    with trace_span("torchft::collectives::wire_allgather"):
        # ``rq`` and ``rs`` come back as this rank's own entry: the
        # turn's buffers, read by the join below and by nothing later.
        gathered = pg.allgather([rq, rs]).wait()
    # Joining the ranks' chunks is a host copy of the whole payload, not
    # socket time: booked with the other numpy work of the stage.
    with trace_span("torchft::collectives::wire_reduce") as span:
        q_final, s_final = scratch.result(blocks * bpb, blocks)
        _join([g[0] for g in gathered], q_final)
        _join([g[1] for g in gathered], s_final)
        span.attrs.update(scratch.counts())
    return q_final, s_final


def allreduce_quantized(
    pg: ProcessGroup,
    arrays: Sequence[np.ndarray],
    op: ReduceOp = ReduceOp.SUM,
    bits: int = 8,
    on_local_quantized: "Callable | None" = None,
) -> Work:
    """Quantized SUM/AVG allreduce, in place (reference:
    collectives.py:297-415). Returns async Work whose result is ``arrays``.
    ``bits=4`` nibble-packs the wire payload (half the bytes of int8).

    ``on_local_quantized(flat, q, scales)`` is invoked on the collective
    thread right after THIS rank's payload is quantized — DiLoCo's
    error-feedback residual (flat - dequantize(q, s)) hooks in here, so
    the payload is quantized exactly once and the residual math stays off
    the training thread. The callback sees the flat that actually hit the
    wire (zeros on a non-participating replica)."""
    if op not in (ReduceOp.SUM, ReduceOp.AVG):
        raise ValueError(f"allreduce_quantized supports SUM/AVG, got {op}")
    ws = pg.size()
    if ws <= 1:
        return DummyWork(list(arrays))
    tags = _bucket_tags(arrays)

    def run(wire) -> List[np.ndarray]:
        # Same span names as the device (jax) path so bench/telemetry
        # consumers see one uniform phase decomposition: "quantize_pull"
        # is the host quantize here (there is no device pull), "wire" the
        # alltoall-reduce-allgather pipeline, "dequant_push" the decode +
        # write-back.
        with trace_span("torchft::collectives::quantize_pull", **tags):
            flat, sizes = _flatten(arrays)
            n = flat.size
            q_host, s_host = quantize_blockwise(flat, bits)
            if on_local_quantized is not None:
                on_local_quantized(flat, q_host, s_host)
        with wire(), trace_span("torchft::collectives::wire", **tags):
            reduced = _quantized_wire_pipeline(pg, q_host, s_host, n, bits)
        with trace_span("torchft::collectives::dequant_push", **tags):
            if isinstance(reduced, np.ndarray):
                result = reduced
            else:
                q_final, s_final = reduced
                result = dequantize_blockwise(q_final, s_final, n, bits)
            if op == ReduceOp.AVG:
                result /= ws
            _unflatten_into(arrays, result, sizes)
        return list(arrays)

    return FutureWork(_spawn_collective(pg, run, tags["bucket"]))
