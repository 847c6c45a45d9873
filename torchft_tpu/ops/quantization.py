"""Pallas TPU kernels for blockwise int8 quantization of collective payloads.

Role of the reference's Triton fp8 kernels (``torchft/quantization.py:44-428``):
quantize-with-scales into a flat transfer buffer, dequantize back, and a
fused reduce of all ranks' chunks in full precision with requantization.
TPU port notes:

- int8 (not fp8e4nv): the payloads ride DCN host links, and int8 keeps exact
  parity with the host-side numpy path in ``torchft_tpu/collectives.py`` so
  either side of a transfer may (de)quantize.
- Block size 512 = 4 TPU lanes of 128; row tiles of 32 satisfy the int8
  (32, 128) min-tile constraint. Scales are computed rowwise in-kernel (one
  fp32 scale per 512-value block, broadcast across a 128-lane output row).
- ``interpret=True`` off-TPU: tests on the CPU backend execute the same
  kernels through the Pallas interpreter, so kernel logic is covered without
  a chip.

Numerics vs ``collectives.quantize_blockwise``: same formula (scale =
absmax/127, 1.0 for all-zero blocks, round-to-nearest-even, clip to ±127),
and DEQUANTIZE is bit-exact either side (int8·fp32 multiply is exact).
QUANTIZE is *not* bit-exact on real TPUs — the VPU divide is not
correctly-rounded IEEE, so round-boundary values can land one int8 level
off the host result (measured 7 per 4.2M on v5e; see bench_kernels.py).
That is within the quantization half-step and does not affect the wire
protocol's cross-replica bitwise guarantee: each wire chunk is requantized
by exactly one owner rank, and all replicas decode identical bytes.
"""

from __future__ import annotations

import functools
import math
from typing import Any, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

BLOCK = 512  # values per scale; multiple of the 128-lane width
_TILE = 32  # rows per kernel instance; int8 min sublane count


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _requantize(
    x: jax.Array, qmax: float = 127.0
) -> Tuple[jax.Array, jax.Array]:
    """Shared numerics for both kernels: rowwise absmax scale (1.0 for
    all-zero rows), round-to-nearest-even, clip to ±qmax. Must stay in
    parity with collectives.quantize_blockwise (see module docstring for
    the TPU-divide caveat). ``qmax`` 127 = int8, 7 = int4."""
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = jnp.where(absmax == 0.0, 1.0, absmax / qmax)
    q = jnp.clip(jnp.round(x / scale), -qmax, qmax).astype(jnp.int8)
    return q, jnp.broadcast_to(scale, (x.shape[0], 128))


def _quantize_kernel(x_ref, q_ref, s_ref, *, qmax: float):
    q_ref[...], s_ref[...] = _requantize(x_ref[...], qmax)


@functools.partial(jax.jit, static_argnames=("qmax",))
def _quantize_rows(
    x2d: jax.Array, qmax: float = 127.0
) -> Tuple[jax.Array, jax.Array]:
    rows = x2d.shape[0]
    grid = (rows // _TILE,)
    return pl.pallas_call(
        functools.partial(_quantize_kernel, qmax=qmax),
        grid=grid,
        in_specs=[pl.BlockSpec((_TILE, BLOCK), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((_TILE, BLOCK), lambda i: (i, 0)),
            pl.BlockSpec((_TILE, 128), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, BLOCK), jnp.int8),
            jax.ShapeDtypeStruct((rows, 128), jnp.float32),
        ],
        interpret=_interpret(),
    )(x2d)


def _pack_nibbles_jnp(q: jax.Array) -> jax.Array:
    """[rows, BLOCK] int8 in [-7,7] -> [rows, BLOCK//2] int8, flat layout
    identical to collectives.pack_nibbles (even flat index -> low nibble).
    Plain jnp ops OUTSIDE the Pallas kernel: XLA compiles int8 bitwise on
    TPU fine, and keeping the kernel int8-only avoids Mosaic strided-lane
    territory."""
    u = q.astype(jnp.uint8) & 0xF
    return (u[:, 0::2] | (u[:, 1::2] << 4)).astype(jnp.int8)


def _unpack_nibbles_jnp(p: jax.Array) -> jax.Array:
    """[rows, BLOCK//2] int8 -> [rows, BLOCK] int8 with sign extension."""
    u = p.astype(jnp.uint8)
    both = jnp.stack([u & 0xF, u >> 4], axis=-1).reshape(p.shape[0], -1)
    return (jnp.bitwise_xor(both, 8).astype(jnp.int8) - 8)


# Single source of truth for the bits->range policy lives in
# collectives._qmax (no import cycle: collectives only imports this
# module lazily, inside functions).
from torchft_tpu.collectives import _qmax as _bits_qmax  # noqa: E402


def _flat_f32(leaves: Sequence[jax.Array]) -> jax.Array:
    """The bucket's flat payload as the host path lays it out
    (``ddp``'s pack, ``collectives._flatten``): the leaves raveled in the
    order given, as float32. One float32 leaf is a reshape and nothing
    else."""
    parts = [jnp.ravel(a).astype(jnp.float32) for a in leaves]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def _tile_rows(n: int) -> int:
    """Rows of BLOCK values that hold ``n``, in whole kernel tiles."""
    blocks = max(-(-n // BLOCK), 1)
    return -(-blocks // _TILE) * _TILE


def _as_words(q: jax.Array) -> jax.Array:
    """int8 [rows, BLOCK] -> uint32 [rows, BLOCK/4], word j of a row its
    bytes 4j..4j+3, lowest first: on a little-endian host the words'
    int8 view IS the payload. Why: the TPU keeps int8 in tiles that pack
    four ROWS a word, and undoing that byte by byte is what a
    device-to-host copy of int8 pays for (0.65 GB/s where the same bytes
    as 32-bit values move 2.4; PERF.md section 6, PR 40). XLA has no
    cheap way to put four neighbouring COLUMNS into a word (a minor
    dimension of 4 is padded 32-fold, strided slices become gathers), but
    the MXU has: each byte as an unsigned bf16 value (0..255, exact) times
    a 0/1/256 selection matrix, accumulated in float32, gives the two
    16-bit halves of every word exactly (two nonzero terms a sum, each
    under 2**16)."""
    u = (q.astype(jnp.int32) & 0xFF).astype(jnp.bfloat16)
    col = jnp.arange(BLOCK)
    same_word = (col // 4)[:, None] == jnp.arange(BLOCK // 4)[None, :]
    weight = jnp.where(col % 2 == 0, 1.0, 256.0)[:, None]

    def half(which: int) -> jax.Array:
        sel = jnp.where(
            same_word & ((col % 4) // 2 == which)[:, None], weight, 0.0
        ).astype(jnp.bfloat16)
        return jnp.dot(u, sel, preferred_element_type=jnp.float32).astype(
            jnp.uint32
        )

    return half(0) | (half(1) << 16)


@functools.partial(jax.jit, static_argnames=("bits", "words"))
def _quantize_leaves(
    leaves: Sequence[jax.Array], bits: int = 8, words: bool = False
) -> Tuple[jax.Array, jax.Array]:
    """The way down, one compiled program a bucket layout (the leaves'
    shapes and dtypes, ``bits``): ravel and join the leaves, pad to whole
    tiles only where the element count is not one already, the quantize
    kernel over all rows, nibble packing for 4 bits. Returns (payload
    [rows, BLOCK or BLOCK/2], scales [rows]) with ``rows`` in whole tiles:
    the first ``ceil(n / BLOCK)`` rows, row-major, are the bytes of
    ``collectives.quantize_blockwise``. With ``words`` (int8 payloads
    only) the same bytes leave as uint32 [rows, BLOCK/4], the form that
    crosses to the host (:func:`_as_words`)."""
    flat = _flat_f32(leaves)
    rows = _tile_rows(flat.size)
    if rows * BLOCK != flat.size:
        flat = jnp.pad(flat, (0, rows * BLOCK - flat.size))
    q, s = _quantize_rows(flat.reshape(rows, BLOCK), _bits_qmax(bits))
    if bits == 4:
        q = _pack_nibbles_jnp(q)
    elif words:
        q = _as_words(q)
    return q, s[:, 0]


def fused_quantize(
    x: jax.Array, bits: int = 8
) -> Tuple[jax.Array, jax.Array, int]:
    """Quantizes a device array to (payload [rows, BLOCK or BLOCK/2], fp32
    scales [rows], element count), ``rows`` padded to whole kernel tiles,
    in one compiled program a shape (:func:`_quantize_leaves`, the program
    the quantized allreduce runs a bucket). Pull the first two to host for
    a ~4x (int8) or ~8x (int4 nibble-packed) smaller DCN transfer
    (reference: fused_quantize_into_fp8, quantization.py:531+)."""
    q, s = _quantize_leaves([x], bits)
    return q, s, x.size


def fused_quantize_int8(x: jax.Array) -> Tuple[jax.Array, jax.Array, int]:
    """int8 shorthand for :func:`fused_quantize` (the original API)."""
    return fused_quantize(x, 8)


def _dequantize_kernel(q_ref, s_ref, out_ref):
    out_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[..., 0:1]


def _pad_rows(x: jax.Array) -> jax.Array:
    """Pads the leading (row) dim up to a _TILE multiple so host-shaped
    payloads (exactly ``blocks`` rows) drive a full kernel grid — a
    non-multiple row count would otherwise truncate the grid and silently
    return unwritten (zero) outputs."""
    rows = x.shape[0]
    padded = ((rows + _TILE - 1) // _TILE) * _TILE
    if padded == rows:
        return x
    pad_widths = [(0, padded - rows)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad_widths)


@jax.jit
def _dequantize_rows(q2d: jax.Array, s2d: jax.Array) -> jax.Array:
    """The dequantize kernel over whole tiles: int8 [rows, BLOCK] times
    the scales [rows, 128] (a row's scale across its lanes)."""
    rows = q2d.shape[0]
    return pl.pallas_call(
        _dequantize_kernel,
        grid=(rows // _TILE,),
        in_specs=[
            pl.BlockSpec((_TILE, BLOCK), lambda i: (i, 0)),
            pl.BlockSpec((_TILE, 128), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((_TILE, BLOCK), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, BLOCK), jnp.float32),
        interpret=_interpret(),
    )(q2d, s2d)


def _cut_leaves(
    flat: jax.Array,
    scale: "jax.Array | None",
    shapes: Tuple[Tuple[int, ...], ...],
    dtypes: Tuple[Any, ...],
) -> List[jax.Array]:
    """The leaves of a flat float32 payload: times ``scale`` where one is
    given, then cut, reshaped and cast, in layout order."""
    if scale is not None:
        flat = flat * scale
    leaves = []
    offset = 0
    for shape, dtype in zip(shapes, dtypes):
        size = math.prod(shape)
        leaves.append(
            flat[offset : offset + size].reshape(shape).astype(dtype)
        )
        offset += size
    return leaves


cut_leaves = jax.jit(_cut_leaves, static_argnames=("shapes", "dtypes"))
flatten_leaves = jax.jit(_flat_f32)


@functools.partial(jax.jit, static_argnames=("shapes", "dtypes", "bits"))
def _dequantize_leaves(
    q: jax.Array,
    scales: jax.Array,
    scale: "jax.Array | None",
    shapes: Tuple[Tuple[int, ...], ...],
    dtypes: Tuple[Any, ...],
    bits: int = 8,
) -> List[jax.Array]:
    """The way up, one compiled program a bucket layout: the dequantize
    kernel over all rows of a payload in the wire layout (any shape, one
    scale a block of it), the result times ``scale`` where one is given
    (a traced scalar: a quorum that changes size compiles nothing), and
    the leaves cut, reshaped and cast. The program's outputs are the
    leaves; no payload-sized array outlives it."""
    if bits == 4:
        q = _unpack_nibbles_jnp(q.reshape(-1, BLOCK // 2))
    q = _pad_rows(q.reshape(-1, BLOCK))
    s2d = jnp.broadcast_to(
        _pad_rows(scales.reshape(-1, 1)).astype(jnp.float32),
        (q.shape[0], 128),
    )
    flat = _dequantize_rows(q, s2d).reshape(-1)
    return _cut_leaves(flat, scale, shapes, dtypes)


def fused_dequantize(
    q: jax.Array, scales: jax.Array, n: int, bits: int = 8
) -> jax.Array:
    """Inverse of :func:`fused_quantize`; returns a flat fp32 array of
    length ``n``. Accepts host-quantized payloads too (any row count).
    One compiled program a shape (:func:`_dequantize_leaves` with the one
    leaf ``[n]``)."""
    return _dequantize_leaves(
        q, scales, None, shapes=((n,),), dtypes=(jnp.float32,), bits=bits
    )[0]


def fused_dequantize_int8(
    q: jax.Array, scales: jax.Array, n: int
) -> jax.Array:
    """int8 shorthand for :func:`fused_dequantize` (the original API)."""
    return fused_dequantize(q, scales, n, 8)


def _reduce_kernel(q_ref, s_ref, qo_ref, so_ref, *, ranks: int, avg: bool):
    acc = jnp.zeros((q_ref.shape[1], BLOCK), jnp.float32)
    for r in range(ranks):  # static unroll: ranks is a compile-time constant
        acc = acc + q_ref[r].astype(jnp.float32) * s_ref[r, :, 0:1]
    if avg:
        acc = acc / ranks
    qo_ref[...], so_ref[...] = _requantize(acc)


def fused_reduce_int8(
    q: jax.Array, scales: jax.Array, avg: bool = False
) -> Tuple[jax.Array, jax.Array]:
    """Sums ``ranks`` quantized copies of the same chunk in fp32 and
    requantizes (reference: fused_reduce_fp8, quantization.py:261-376).

    Args: q [ranks, rows, BLOCK] int8; scales [ranks, rows] fp32.
    Returns (q_out [rows, BLOCK] int8, scales_out [rows] fp32).
    """
    ranks = q.shape[0]
    q = jnp.stack([_pad_rows(jnp.asarray(q[r])) for r in range(ranks)])
    rows = q.shape[1]
    scales = jnp.asarray(scales)
    s3d = jnp.broadcast_to(
        jnp.stack(
            [_pad_rows(scales[r].reshape(-1, 1)) for r in range(ranks)]
        ).astype(jnp.float32),
        (ranks, rows, 128),
    )
    kernel = functools.partial(_reduce_kernel, ranks=ranks, avg=avg)
    qo, so = pl.pallas_call(
        kernel,
        grid=(rows // _TILE,),
        in_specs=[
            pl.BlockSpec((ranks, _TILE, BLOCK), lambda i: (0, i, 0)),
            pl.BlockSpec((ranks, _TILE, 128), lambda i: (0, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((_TILE, BLOCK), lambda i: (i, 0)),
            pl.BlockSpec((_TILE, 128), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, BLOCK), jnp.int8),
            jax.ShapeDtypeStruct((rows, 128), jnp.float32),
        ],
        interpret=_interpret(),
    )(q, s3d)
    return qo, so[:, 0]


# The most elements one program takes down or up. The TPU lays a float32
# leaf out in (8, 128) tiles of ITS OWN last two dimensions, so the reshape
# to rows of BLOCK that the kernel reads is a relayout, not a bitcast: a
# float32 temporary of the payload's size beside the kernel's outputs
# (compiled for a described v5e: 524 + 131 MB for a 131M-element leaf,
# whole tiles or not, one dimension or two; PERF.md section 6, PR 40). Up
# to this size that stands, and a bucket costs one program each way: every
# bucket DDP makes (capped at 32 MiB but for a single larger leaf), the
# 131M-element embedding of a 7B model among them. A larger payload is
# worked through in pieces of this size, so the transient HBM stays
# ~6 bytes/elem of ONE piece (0.75 GiB) however large the payload.
_TRANSFER_CHUNK = 128 * 1024 * 1024  # 2**27 elems = 512 MiB fp32


def quantize_for_transfer(
    x: "jax.Array | Sequence[jax.Array]", bits: int = 8
) -> Tuple[np.ndarray, np.ndarray, int]:
    """Device-quantize then pull to host: the device->host (and then DCN)
    transfer moves the quantized payload + per-block scales instead of
    fp32. The returned (payload, scales, n) is exactly the layout of
    ``collectives.quantize_blockwise`` of the flat payload (``x``: one
    array, or the leaves of a bucket in layout order), so the receiving
    host (or device, via :func:`fused_dequantize`) can decode it directly.

    Composition of the async pair (one implementation; tests pin the two
    bit-identical): dispatch, then pull."""
    return pull_transfer_chunks(*quantize_for_transfer_async(x, bits), bits)


@functools.partial(jax.jit, static_argnames=("n_full", "bits"))
def _quantize_row(
    flat: jax.Array, row: jax.Array, n_full: int, bits: int = 8
):
    """One full-size chunk of the bounded path: slice + pad + quantize
    fused in ONE jitted computation (the slice never materializes as a
    standalone dispatched buffer — with many chunks enqueued at once,
    per-chunk fp32 slice copies would otherwise sum to a second full-size
    payload of queued HBM).  The chunk is addressed as a ROW of the
    (n_full, chunk) view rather than by flat element offset: the traced
    index stays a small int32 row number, so payloads past 2**31 elements
    can't silently slice the wrong region (jax x64 is disabled, so a
    traced element offset would wrap)."""
    body = flat[: n_full * _TRANSFER_CHUNK].reshape(n_full, _TRANSFER_CHUNK)
    piece = jax.lax.dynamic_slice(
        body, (row, 0), (1, _TRANSFER_CHUNK)
    ).reshape(-1)
    return _quantize_leaves.__wrapped__([piece], bits, words=True)


@functools.partial(jax.jit, static_argnames=("start", "m", "bits"))
def _quantize_tail(flat: jax.Array, start: int, m: int, bits: int = 8):
    """The final partial chunk. ``start`` is STATIC (one value per flat
    size, so no compile blowup) — a static basic-index slice carries
    64-bit offsets and is safe past 2**31 elements."""
    return _quantize_leaves.__wrapped__(
        [flat[start : start + m]], bits, words=True
    )


def quantize_for_transfer_async(
    x: "jax.Array | Sequence[jax.Array]", bits: int = 8
) -> Tuple[list, int]:
    """Dispatch-only half of :func:`quantize_for_transfer`: enqueues the
    quantize program(s) (async — returns as soon as XLA has the work)
    WITHOUT pulling anything to host. ``x`` is one array or the leaves of
    a bucket in layout order. Returns (chunks, n) where chunks is
    ``[(q, s, m), ...]`` of not-yet-materialized device arrays covering
    the flat payload in order; finish with :func:`pull_transfer_chunks`,
    possibly on another thread.

    Why two halves: the pull blocks until the kernels (and everything
    queued before them) execute. Dispatching the kernels on the CALLER's
    thread enqueues them immediately after the compute that produced
    ``x`` — before the caller's next training window — so a deferred pull
    overlaps that window instead of waiting behind it.

    One chunk, one cached compiled program (:func:`_quantize_leaves`),
    up to ``_TRANSFER_CHUNK`` elements. Past it the bounded path, chosen
    by the payload's size alone: the leaves are joined on the device and
    worked through in pieces of that size, at most two compilations a
    flat size (full rows, where only the row INDEX is traced, and the
    static tail); peak HBM beyond the joined input is the outputs (~1.25
    bytes/elem: they ARE the payload) plus ONE executing piece's fp32
    intermediates.
    """
    leaves = list(x) if isinstance(x, (list, tuple)) else [x]
    n = sum(a.size for a in leaves)
    if n <= _TRANSFER_CHUNK:
        return [(*_quantize_leaves(leaves, bits, words=True), n)], n
    flat = flatten_leaves(leaves)
    n_full = n // _TRANSFER_CHUNK
    chunks = []
    for i in range(n_full):
        q, s = _quantize_row(flat, i, n_full, bits)
        chunks.append((q, s, _TRANSFER_CHUNK))
    tail = n - n_full * _TRANSFER_CHUNK
    if tail:
        q, s = _quantize_tail(flat, n_full * _TRANSFER_CHUNK, tail, bits)
        chunks.append((q, s, tail))
    return chunks, n


def pull_transfer_chunks(
    chunks: list, n: int, bits: int = 8
) -> Tuple[np.ndarray, np.ndarray, int]:
    """Waits for the device chunks from :func:`quantize_for_transfer_async`
    on the host, returning the same (q, scales, n) layout — bit-identical —
    as :func:`quantize_for_transfer`. One chunk (one program) comes back
    as views of the arrays the transfer wrote, trimmed to whole blocks of
    ``n``; only the bounded path joins its pieces. The copies of a chunk's
    payload and scales are asked for together and here, one chunk at a
    time: copies asked of several payloads at once, or of a program's
    pending outputs, are not served in the order asked but share the path
    off the device (PERF.md section 6, PR 39 and PR 40)."""
    bpb = BLOCK // (8 // bits)
    q_parts = []
    s_parts = []
    for i, (q, s, m) in enumerate(chunks):
        blocks = (m + BLOCK - 1) // BLOCK
        q.copy_to_host_async()
        s.copy_to_host_async()
        # An int8 payload crosses as 32-bit words (:func:`_as_words`).
        q_parts.append(
            np.asarray(q).view(np.int8).reshape(-1)[: blocks * bpb]
        )
        s_parts.append(np.asarray(s)[:blocks])
        # Release the device buffers as they are consumed: the caller's
        # closure may keep `chunks` alive through the whole wire pipeline,
        # and these are the payload-sized HBM allocations.
        chunks[i] = None
    if len(q_parts) == 1:
        return q_parts[0], s_parts[0], n
    return np.concatenate(q_parts), np.concatenate(s_parts), n


@functools.partial(jax.jit, donate_argnums=(0,))
def _place_chunk(buf: jax.Array, piece: jax.Array, start) -> jax.Array:
    """Donated in-place write of a dequantized chunk into the output
    buffer — no second full-size copy is ever alive."""
    return jax.lax.dynamic_update_slice(buf, piece, (start,))


def _dequantize_flat_bounded(
    q: np.ndarray, scales: np.ndarray, n: int, bits: int
) -> jax.Array:
    """The bounded path up: each ``_TRANSFER_CHUNK`` piece is dequantized
    and written (buffer-donated) into a preallocated output, so peak
    transient HBM is output + one piece regardless of payload size."""
    bpb = BLOCK // (8 // bits)
    blocks_per_chunk = _TRANSFER_CHUNK // BLOCK
    q = np.asarray(q).reshape(-1)
    out = jnp.zeros((n,), jnp.float32)
    for start_blk in range(0, (n + BLOCK - 1) // BLOCK, blocks_per_chunk):
        start = start_blk * BLOCK
        q_piece = q[start_blk * bpb : (start_blk + blocks_per_chunk) * bpb]
        s_piece = scales[start_blk : start_blk + blocks_per_chunk]
        m = min(
            min(q_piece.size * (8 // bits), blocks_per_chunk * BLOCK),
            n - start,
        )
        piece = fused_dequantize(q_piece, s_piece, m, bits)
        out = _place_chunk(out, piece, jnp.asarray(start))
    return out


def dequantize_leaves_from_transfer(
    q: np.ndarray,
    scales: np.ndarray,
    shapes: Sequence[Tuple[int, ...]],
    dtypes: Sequence[Any],
    scale: float = 1.0,
    bits: int = 8,
) -> List[jax.Array]:
    """Host quantized payload (the wire layout, any shape) -> the leaves
    of its bucket on the device, each ``float32(q) * its block's scale``
    times ``scale`` (left out where it is 1), cut at ``shapes`` and cast
    to ``dtypes``. One host-to-device transfer and one cached compiled
    program (:func:`_dequantize_leaves`) up to ``_TRANSFER_CHUNK``
    elements; the bounded path past it. Asynchronously dispatched: the
    host arrays are held by the transfer until it is done."""
    shapes = tuple(tuple(int(d) for d in shape) for shape in shapes)
    dtypes = tuple(jnp.dtype(d) for d in dtypes)
    n = sum(math.prod(shape) for shape in shapes)
    factor = None if scale == 1.0 else np.float32(scale)
    if n <= _TRANSFER_CHUNK:
        bpb = BLOCK // (8 // bits)
        q_dev, s_dev = jax.device_put(
            (np.asarray(q).reshape(-1, bpb), np.asarray(scales))
        )
        return _dequantize_leaves(
            q_dev, s_dev, factor, shapes=shapes, dtypes=dtypes, bits=bits
        )
    flat = _dequantize_flat_bounded(q, scales, n, bits)
    return cut_leaves(flat, factor, shapes=shapes, dtypes=dtypes)


def dequantize_from_transfer(
    q: np.ndarray, scales: np.ndarray, n: int, bits: int = 8
) -> jax.Array:
    """Host quantized payload -> device fp32, flat, of length ``n``:
    :func:`dequantize_leaves_from_transfer` with the one leaf ``[n]``."""
    return dequantize_leaves_from_transfer(
        q, scales, [(n,)], [jnp.float32], 1.0, bits
    )[0]
