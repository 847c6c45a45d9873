"""Fault-tolerant data parallelism across replica groups.

Reference: ``torchft/ddp.py:32-105`` routes each gradient bucket through
``manager.allreduce`` via a DDP comm hook. The JAX equivalent: the *inner*
data-parallel axis (within a replica group / pod) is a mesh axis whose
gradient psum is compiled into the step function and rides ICI; this module
averages the resulting gradients *across replica groups* over DCN, bucketed
in flat host buckets with async overlap (bucket N+1 transfers while N is
in flight — the comm-hook overlap analog).
"""

from __future__ import annotations

import collections
from typing import Any, Deque, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from torchft_tpu.manager import Manager
from torchft_tpu.telemetry import DDP_ROOT_SPAN, name_next_bucket, trace_span


def issue_order(arrays: Sequence[Any], buckets: List[List[int]]) -> List[int]:
    """The order in which a step's buckets are handed to
    ``Manager.allreduce``: smallest first, by element count, ties in
    layout order. ``buckets`` is ``bucketize``'s layout over ``arrays``;
    the answer is a permutation of its indices and depends on nothing
    else, so jax leaves and their numpy copies give the same one.

    Each bucket is pulled to the host and then put on the wire, one
    bucket at a time in either stage, and the pull is the shorter stage
    for every bucket: Johnson's rule for such a two-stage line puts the
    shortest first stage first, and the wire then starts after the
    smallest pull instead of the largest."""
    sizes = [sum(arrays[i].size for i in idx_list) for idx_list in buckets]
    return sorted(range(len(buckets)), key=lambda b: (sizes[b], b))


@jax.jit
def _join_leaves(leaves: Sequence[jax.Array]) -> jax.Array:
    """A bucket's flat payload on the device, as ``pack`` lays it out on
    the host: the leaves raveled in the order given, in their own dtype.
    One compiled program a bucket layout (the leaves' shapes and dtype)."""
    return jax.numpy.concatenate([jax.numpy.ravel(a) for a in leaves])


def _one_device(leaves: Sequence[jax.Array]) -> bool:
    """Whether every leaf lies whole on one and the same device: only then
    can one program join them without moving a byte between devices."""
    devices = {d for x in leaves for d in x.devices()}
    return len(devices) == 1


class _PackBuffers:
    """The flat bucket buffers one ``DistributedDataParallel`` keeps
    between calls: a gradient-sized numpy array made every step is
    mapped, page-faulted in as it is written and unmapped again, which
    cost ``pack`` more than its copy (the fault ``_WireScratch`` removed
    from the quantized wire stage). A set is valid for one bucket layout,
    ``(dtype, element count)`` per bucket; a call with another layout
    sizes a new set. ``np.empty`` touches no page: a buffer costs memory
    only once something is written into it, so on the line of buckets,
    where the landed copy is the bucket, a buffer is paid for only by a
    step that has to write (more than one rank, a scale, quantization)."""

    def __init__(self) -> None:
        self._layout: Optional[Tuple[Tuple[Any, int], ...]] = None
        self._flats: List[np.ndarray] = []

    def take(
        self, layout: Tuple[Tuple[Any, int], ...]
    ) -> Tuple[List[np.ndarray], bool]:
        """The set for ``layout``, one flat array a bucket, and whether it
        was kept from the last call (else: made now, pages untouched)."""
        kept = layout == self._layout
        if not kept:
            self._layout = layout
            self._flats = [np.empty(n, dtype=dtype) for dtype, n in layout]
        return self._flats, kept

    def retire(self) -> None:
        """Lets the set go; the next call sizes a new one. Whoever still
        holds the old arrays (a collective's thread, the caller's leaves)
        keeps them alive and to itself."""
        self._layout, self._flats = None, []


class DistributedDataParallel:
    """Averages gradient pytrees across the fault-tolerant replica axis.

    Usage::

        ddp = DistributedDataParallel(manager)
        grads = grad_fn(params, batch)          # inner-axis psum inside jit
        grads = ddp.allreduce_grads(grads)      # outer-axis average over DCN

    **Who owns what comes back** (host path: fp32, or quantized on the
    host). Two cases, by what was handed in.

    *Every leaf a ``jax.Array``*: new device arrays with each input
    leaf's sharding and dtype, landed when the call returns
    (``torchft::ddp::push_wait``), as the device (int8) path and
    ``PureDistributedDataParallel`` return new arrays. No view of a kept
    buffer leaves the wrapper, so its next call may write the buffers
    whether or not the step commits, and the wrapper keeps no reference
    to a device array past its return. The buckets form a line, and a
    gradient byte is written once on its way down and read once on its
    way up. A bucket comes down as ONE array: its one leaf, or its
    leaves joined on the device by one program a layout
    (``_join_leaves``; leaves that lie on several devices are packed on
    the host as below). jax caches a host copy on the array it was asked
    of, so a leaf alone is asked through a second handle on its buffer
    (``jax.make_array_from_single_device_arrays``, no device copy) that
    only the call holds: the landing is then freed when its push has
    read it, not in one gradient-sized piece when the caller drops its
    tree. Every copy starts at once, as soon as the gradients exist
    (after ``grads_wait``), and per bucket, in issue order, the caller
    waits for that bucket's copy (``torchft::ddp::pull``) and hands the
    landed array to ``Manager.allreduce`` as it is: a read-only array of
    host memory made for it this step, with the kept buffer as
    ``scratch``. The manager copies into the kept buffer only where
    something writes (more than one rank in the process group, a scale
    other than 1, quantization, a non-participant's zeros:
    ``torchft::manager::host_copy`` ``copied_bytes``); at a quorum of one
    nothing does, and the landed copy is what goes back up. Before the
    caller blocks on the next pull it sends back every earlier bucket
    whose collective is done (``torchft::ddp::push``: ``jax.device_put``
    of each leaf's slice of the reduced bucket, asynchronous). Off the
    TPU a ``device_put`` of a numpy view may alias the host memory rather
    than copy it, so there the slice is copied first. A bucket whose
    collective failed is not pushed (its buffer may still be written,
    below): its input leaves come back in its place, on a step that will
    not commit.

    *Any leaf a numpy array*: the tree is pulled whole, every bucket is
    packed into a flat buffer this wrapper owns, and the leaves that come
    back are views of those buffers, which the next call writes again:
    they are valid until the next ``allreduce_grads`` on the same
    wrapper, and a caller that keeps one longer copies it
    (``np.array(leaf)``; off-TPU a ``jnp.asarray(leaf)`` may alias the
    host memory rather than copy it).

    **A failed step retires the buffers.** If the call raises, or the
    manager holds an error when it ends (a bucket's work failed or timed
    out), the set is dropped and the next call sizes a new one: an aborted
    collective's thread may still hold, and write into, the array it was
    given (``ProcessGroupSocket.abort`` does not join it). A landed copy
    it holds instead is read-only and nobody's to write. The step after
    a failure pays for fresh pages once, if it writes at all.

    ``torchft::ddp::pack`` is recorded once a bucket either way and says
    what the host did: ``nbytes`` is what the pack copied (the bucket,
    concatenated into its kept buffer; **0** where the landed copy is the
    bucket; the compensated copy under error feedback on the line),
    ``fresh_bytes`` is host memory made for the bucket this call (a new
    set of buffers: the wrapper's first call, another layout, the call
    after a failed step; the landing itself on the line, every step; the
    compensated copy under error feedback), ``reused_bytes`` what was
    written into memory the wrapper already had. Steady state with a
    numpy leaf is ``fresh_bytes`` 0 and ``reused_bytes`` = ``nbytes``; on
    the line ``nbytes`` 0, ``fresh_bytes`` the bucket, ``reused_bytes`` 0.

    **The order of a step's collectives** is :func:`issue_order` of the
    bucket layout, ascending size, in both branches below, and it is part
    of the wire protocol: the process group pairs the ops of two replicas
    by a sequence number taken at the call, and the quantized collectives
    go to the wire in issue order (``collectives._WireOrder``), so every
    replica of one quorum must issue the same buckets in the same order,
    a device-path replica and a host-path one alike. Every replica of one
    version does; a quorum that mixes this version with one that issued
    in layout order would exchange each other's buckets and is not
    supported. The layout itself is ``bucketize``'s and a bucket keeps
    its layout index as its name: ``bucket=`` on its spans, the key of
    its error-feedback residual, its slot among the kept buffers.
    """

    def __init__(
        self,
        manager: Manager,
        bucket_cap_mb: float = 32.0,
        error_feedback: bool = False,
        quantize_bits: int = 8,
    ) -> None:
        self._manager = manager
        self._bucket_cap = int(bucket_cap_mb * 1024 * 1024)
        self._error_feedback = error_feedback
        self._quantize_bits = quantize_bits
        from torchft_tpu.collectives import ErrorFeedback

        self._residuals = ErrorFeedback(quantize_bits)
        self._pack_buffers = _PackBuffers()

    def allreduce_grads(
        self,
        grads: Any,
        should_quantize: bool = False,
        quantize_bits: Optional[int] = None,
    ) -> Any:
        """Flattens ``grads`` into <=bucket_cap flat buffers per dtype, issues
        async manager allreduces for all buckets, waits, and rebuilds the
        pytree (values averaged over live participants).

        With ``should_quantize=True``:

        - device-array grads on TPU ride the manager's DEVICE quantize
          path (Pallas kernels shrink the payload to int8/int4 *before*
          the device->host pull, so PCIe bytes drop 4-8x along
          with the wire) — but only when ``error_feedback`` is off: the
          device path has no host-side quantize moment to hook, so an
          EF-enabled DDP takes the host path everywhere rather than
          silently dropping the residual compensation the caller asked
          for;
        - otherwise the host path quantizes the flat buckets, and
          ``error_feedback=True`` (ctor) compensates each bucket with the
          residual the previous step's quantizer dropped
          (collectives.ErrorFeedback) — what makes a 4-bit per-step grad
          wire usable without accumulating bias.  DDP residuals are NOT
          cleared on heal: they compensate the very next step's payload
          and carry at most one step's replica-local quantization error,
          unlike DiLoCo's residuals which track a whole discarded local
          stream.
        """
        clean = False
        try:
            with trace_span(DDP_ROOT_SPAN) as root:
                compute_s, out = self._allreduce_grads(
                    grads, should_quantize, quantize_bits
                )
            clean = self._manager.errored() is None
        finally:
            if not clean:
                # A collective that failed or was aborted may still write
                # into the bucket it was given: never pack into it again.
                self._pack_buffers.retire()
        # The ledger's exposed_comm: the caller's time in here, less the
        # wait for the backward pass that produces the gradients.
        self._manager.note_exposed_comm(root.elapsed_s - compute_s)
        return out

    def _allreduce_grads(
        self,
        grads: Any,
        should_quantize: bool,
        quantize_bits: Optional[int],
    ) -> Tuple[float, Any]:
        """``allreduce_grads`` under its root span; returns the seconds of
        it that were compute (``grads_wait``) and the reduced pytree."""
        if quantize_bits is None:
            quantize_bits = self._quantize_bits
        elif (
            should_quantize
            and self._error_feedback
            and quantize_bits != self._quantize_bits
        ):
            # The residual hook decodes the wire payload with the CTOR
            # width; a divergent per-call width would mis-decode it.
            raise ValueError(
                f"quantize_bits={quantize_bits} differs from the "
                f"error-feedback width {self._quantize_bits} pinned at "
                "construction; pass the width once, in the ctor"
            )
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        if (
            should_quantize
            and not self._error_feedback
            and leaves
            and all(isinstance(x, jax.Array) for x in leaves)
            and jax.default_backend() == "tpu"
        ):
            # Same bucket layout as the host path below (bucketize keys
            # on dtype/nbytes, which jax arrays expose identically), so a
            # device-path replica stays collective-for-collective
            # symmetric with host-path replicas — the socket PG pairs
            # ops in issue order, and a single whole-pytree allreduce
            # against a peer's per-bucket ones would desync the wire.
            # Each bucket's leaves go down as a list: the quantized jax
            # collective joins them on device in ONE program, matching
            # the host path's flat bucket payload byte-for-byte. The
            # order of the calls is the host path's too (issue_order).
            buckets = self._bucketize(leaves)
            works = []
            for b_idx in issue_order(leaves, buckets):
                idx_list = buckets[b_idx]
                name_next_bucket(b_idx)
                work = self._manager.allreduce(
                    [leaves[i] for i in idx_list],
                    should_quantize=True,
                    quantize_bits=quantize_bits,
                )
                works.append((work, idx_list))
            out: List[Optional[Any]] = [None] * len(leaves)
            for work, idx_list in works:
                reduced = work.wait()
                for i, r in zip(idx_list, reduced):
                    out[i] = r
            return 0.0, jax.tree_util.tree_unflatten(treedef, out)
        dev_leaves = [x for x in leaves if isinstance(x, jax.Array)]
        # Device leaves only: the buckets form a line and device arrays go
        # back. A tree with a numpy leaf in it is pulled whole, then
        # packed, then waited for, and gets views of the kept buffers.
        line = bool(leaves) and len(dev_leaves) == len(leaves)
        compute_s = 0.0
        if dev_leaves:
            # Guard the device->host pull: if the device computation feeding
            # the grads never completes (wedged inner-mesh collective), the
            # timeout engine latches an error and aborts the outer pg so the
            # step fails fast instead of wedging the trainer (the reference
            # arms stream_timeout on every wrapped future, manager.py:473-515).
            from torchft_tpu import futures as ft_futures

            manager = self._manager

            def on_stall() -> None:
                manager.report_error(
                    TimeoutError("gradient device->host pull stalled")
                )
                abort = getattr(manager, "_abort_pg_on_stall", None)
                if abort is not None:
                    abort()

            ft_futures.array_timeout(
                dev_leaves, on_stall, getattr(manager, "_timeout", 60.0)
            )
            # The pull below blocks on the same event (the leaves are
            # outputs of one program), so this wait changes no timing; it
            # only separates the backward pass from the transfer.
            with trace_span("torchft::ddp::grads_wait") as waited:
                jax.block_until_ready(dev_leaves)
            compute_s = waited.elapsed_s
        host: List[Optional[np.ndarray]] = [None] * len(leaves)
        if not line:
            with trace_span(
                "torchft::ddp::pull", nbytes=sum(x.nbytes for x in dev_leaves)
            ):
                host = [np.asarray(x) for x in leaves]
        # The layout is the same read off jax leaves or their numpy copies.
        like: Sequence[Any] = leaves if line else host
        buckets = self._bucketize(like)
        order = issue_order(like, buckets)
        # A bucket whose leaves lie on one device comes down as ONE array
        # that only this call holds, {bucket: that array}: several leaves
        # joined, a leaf alone through a second handle on its buffer (jax
        # caches the host copy on the array it was asked of: see the class
        # docstring).
        staged: Dict[int, jax.Array] = {}
        if line:
            for b_idx in order:
                bucket_leaves = [leaves[i] for i in buckets[b_idx]]
                if not _one_device(bucket_leaves):
                    continue
                if len(bucket_leaves) > 1:
                    staged[b_idx] = _join_leaves(bucket_leaves)
                else:
                    (leaf,) = bucket_leaves
                    staged[b_idx] = jax.make_array_from_single_device_arrays(
                        leaf.shape, leaf.sharding, [leaf]
                    )
            # Copies asked of a program's pending outputs are not served in
            # the order asked (below): the joins are microseconds of device.
            jax.block_until_ready(list(staged.values()))
            # Every copy starts now, in the order the buckets will be
            # wanted, and they run back to back while the host reduces and
            # pushes the buckets that have arrived. Not before the
            # gradients exist: copies asked of a program's pending outputs
            # are not served in the order asked, and the first bucket then
            # waits for most of the gradient (PERF.md §6, PR 39).
            for b_idx in order:
                if b_idx in staged:
                    staged[b_idx].copy_to_host_async()
                    continue
                for i in buckets[b_idx]:
                    leaves[i].copy_to_host_async()
        # device_put of a numpy view copies on the TPU and may alias off it.
        copy_first = line and any(
            d.platform != "tpu" for d in leaves[0].devices()
        )

        flats, kept = self._pack_buffers.take(
            tuple(
                (like[idx_list[0]].dtype, sum(like[i].size for i in idx_list))
                for idx_list in buckets
            )
        )
        out: List[Any] = [None] * len(leaves)
        issued: Deque[Tuple[int, Any, List[int]]] = collections.deque()

        def land() -> None:
            """The oldest issued bucket: waits for its collective, slices
            the reduced buffer back into leaves and, on the line, sends
            them to the device."""
            b_idx, work, idx_list = issued.popleft()
            (reduced,) = work.wait()
            if line and self._manager.errored() is not None:
                # An aborted collective's thread may still write into this
                # bucket: nothing is read from it. The step will not
                # commit; the caller gets its own leaves back and drops them.
                for i in idx_list:
                    out[i] = leaves[i]
                return
            with trace_span("torchft::ddp::unpack", bucket=b_idx):
                offset = 0
                for i in idx_list:
                    n = like[i].size
                    out[i] = reduced[offset : offset + n].reshape(
                        like[i].shape
                    )
                    offset += n
            if not line:
                return
            # Asynchronous: the span is the dispatch, push_wait the landing.
            with trace_span(
                "torchft::ddp::push", bucket=b_idx, nbytes=reduced.nbytes
            ):
                for i in idx_list:
                    out[i] = jax.device_put(
                        np.array(out[i]) if copy_first else out[i],
                        leaves[i].sharding,
                    )

        for b_idx in order:
            idx_list = buckets[b_idx]
            # The bucket as the one flat, read-only array that landed,
            # where it came down as one piece.
            landed: Optional[np.ndarray] = None
            if line:
                # A finished bucket goes back while this one's copy arrives.
                while issued and issued[0][1].done():
                    land()
                with trace_span(
                    "torchft::ddp::pull",
                    bucket=b_idx,
                    nbytes=sum(leaves[i].nbytes for i in idx_list),
                ):
                    # pop: a join's device temporary goes as it lands
                    source = staged.pop(b_idx, None)
                    if source is None and len(idx_list) == 1:
                        source = leaves[idx_list[0]]  # over several devices
                    if source is not None:
                        landed = np.asarray(source).reshape(-1)
                    else:  # leaves on several devices: packed on the host
                        for i in idx_list:
                            host[i] = np.asarray(leaves[i])
            on_quantized = None
            with trace_span("torchft::ddp::pack", bucket=b_idx) as pack:
                flat, scratch = flats[b_idx], None
                if landed is None:
                    np.concatenate(
                        [host[i].reshape(-1) for i in idx_list], out=flat
                    )
                    copied = flat.nbytes
                    reused = flat.nbytes if kept else 0
                    fresh = flat.nbytes - reused
                else:
                    # The landing is the bucket, memory made this step; the
                    # kept buffer is the manager's to write if it must.
                    flat, scratch = landed, flat
                    copied, reused, fresh = 0, 0, landed.nbytes
                if should_quantize and self._error_feedback:
                    compensated = self._residuals.compensate(b_idx, flat)
                    if compensated is not flat:  # flat + residual: new memory
                        fresh += compensated.nbytes
                        copied = compensated.nbytes  # the bucket's, again
                        flat = compensated
                    on_quantized = self._residuals.make_hook(b_idx)
                pack.attrs.update(
                    nbytes=copied, fresh_bytes=fresh, reused_bytes=reused
                )
            name_next_bucket(b_idx)
            work = self._manager.allreduce(
                flat,
                should_quantize=should_quantize,
                quantize_bits=quantize_bits,
                on_local_quantized=on_quantized,
                # only the line has a kept buffer to lend
                **({} if scratch is None else {"scratch": scratch}),
            )
            issued.append((b_idx, work, idx_list))
        # The last landing is the push's to free once it has read it.
        landed = None
        while issued:
            land()
        if line:
            with trace_span("torchft::ddp::push_wait"):
                jax.block_until_ready(out)
        return compute_s, jax.tree_util.tree_unflatten(treedef, out)

    def _bucketize(self, arrays: List[np.ndarray]) -> List[List[int]]:
        from torchft_tpu.collectives import bucketize

        return bucketize(arrays, self._bucket_cap)


class PureDistributedDataParallel:
    """Naive per-leaf variant (reference: ddp.py:82-105) — one allreduce per
    gradient leaf, no bucketing. Useful for debugging numerics."""

    def __init__(self, manager: Manager) -> None:
        self._manager = manager

    def allreduce_grads(self, grads: Any) -> Any:
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        works = [self._manager.allreduce(np.asarray(g)) for g in leaves]
        out = [w.wait()[0] for w in works]
        return jax.tree_util.tree_unflatten(treedef, out)
