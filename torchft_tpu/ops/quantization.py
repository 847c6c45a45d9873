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
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

BLOCK = 512  # values per scale; multiple of the 128-lane width
_TILE = 32  # rows per kernel instance; int8 min sublane count


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_blocks(x: jax.Array) -> Tuple[jax.Array, int]:
    n = x.size
    blocks = max((n + BLOCK - 1) // BLOCK, 1)
    # Row count padded to the tile so the grid divides evenly.
    rows = ((blocks + _TILE - 1) // _TILE) * _TILE
    padded = jnp.zeros((rows * BLOCK,), jnp.float32)
    padded = padded.at[:n].set(x.reshape(-1).astype(jnp.float32))
    return padded.reshape(rows, BLOCK), n


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


def fused_quantize(
    x: jax.Array, bits: int = 8
) -> Tuple[jax.Array, jax.Array, int]:
    """Quantizes a device array to (payload [rows, BLOCK or BLOCK/2], fp32
    scales [rows], element count). Pull the first two to host for a ~4x
    (int8) or ~8x (int4 nibble-packed) smaller DCN transfer (reference:
    fused_quantize_into_fp8, quantization.py:531+)."""
    x2d, n = _pad_blocks(x)
    q, s = _quantize_rows(x2d, _bits_qmax(bits))
    if bits == 4:
        q = _pack_nibbles_jnp(q)
    return q, s[:, 0], n


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


def fused_dequantize(
    q: jax.Array, scales: jax.Array, n: int, bits: int = 8
) -> jax.Array:
    """Inverse of :func:`fused_quantize`; returns a flat fp32 array of
    length ``n``. Accepts host-quantized payloads too (any row count)."""
    if bits == 4:
        q = _unpack_nibbles_jnp(jnp.asarray(q).reshape(-1, BLOCK // 2))
    q = _pad_rows(jnp.asarray(q).reshape(-1, BLOCK))
    rows = q.shape[0]
    scales = jnp.asarray(scales).reshape(-1)
    s2d = jnp.broadcast_to(
        _pad_rows(scales.reshape(-1, 1)).astype(jnp.float32), (rows, 128)
    )
    out = pl.pallas_call(
        _dequantize_kernel,
        grid=(rows // _TILE,),
        in_specs=[
            pl.BlockSpec((_TILE, BLOCK), lambda i: (i, 0)),
            pl.BlockSpec((_TILE, 128), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((_TILE, BLOCK), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, BLOCK), jnp.float32),
        interpret=_interpret(),
    )(q, s2d)
    return out.reshape(-1)[:n]


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


# Elements per quantize-and-pull chunk. Bounds peak device memory at
# ~5 bytes/elem of extra HBM (padded fp32 copy + int8 + scales) no matter
# how large the payload: a 500 MB pseudograd otherwise needs >1 GB of
# transient HBM on top of the train state.
_TRANSFER_CHUNK = 16 * 1024 * 1024  # 16M elems = 64 MB fp32 per chunk


def quantize_for_transfer(
    x: jax.Array, bits: int = 8
) -> Tuple[np.ndarray, np.ndarray, int]:
    """Device-quantize then pull to host: the device->host (and then DCN)
    transfer moves the quantized payload + per-block scales instead of
    fp32. The returned (payload, scales, n) is exactly the layout of
    ``collectives.quantize_blockwise``, so the receiving host (or device,
    via :func:`fused_dequantize`) can decode it directly.

    Composition of the async pair (one implementation of the chunking /
    trimming logic; tests pin the two paths bit-identical): dispatch all
    chunk kernels, then pull. Per-chunk double buffering emerges from the
    same structure — every kernel is enqueued before the first pull
    blocks."""
    return pull_transfer_chunks(*quantize_for_transfer_async(x, bits), bits)


@functools.partial(jax.jit, static_argnames=("n_full", "bits"))
def _quantize_row(
    flat: jax.Array, row: jax.Array, n_full: int, bits: int = 8
):
    """One full-size chunk: slice + pad + quantize fused in ONE jitted
    computation (the slice never materializes as a standalone dispatched
    buffer — with many chunks enqueued at once, per-chunk fp32 slice
    copies would otherwise sum to a second full-size payload of queued
    HBM).  The chunk is addressed as a ROW of the (n_full, chunk) view
    rather than by flat element offset: the traced index stays a small
    int32 row number, so payloads past 2**31 elements can't silently
    slice the wrong region (jax x64 is disabled, so a traced element
    offset would wrap)."""
    body = flat[: n_full * _TRANSFER_CHUNK].reshape(n_full, _TRANSFER_CHUNK)
    piece = jax.lax.dynamic_slice(
        body, (row, 0), (1, _TRANSFER_CHUNK)
    ).reshape(-1)
    x2d, _ = _pad_blocks(piece)
    q, s = _quantize_rows(x2d, _bits_qmax(bits))
    if bits == 4:
        q = _pack_nibbles_jnp(q)
    return q, s[:, 0]


@functools.partial(jax.jit, static_argnames=("start", "m", "bits"))
def _quantize_tail(flat: jax.Array, start: int, m: int, bits: int = 8):
    """The final partial chunk. ``start`` is STATIC (one value per flat
    size, so no compile blowup) — a static basic-index slice carries
    64-bit offsets and is safe past 2**31 elements."""
    piece = flat[start : start + m]
    x2d, _ = _pad_blocks(piece)
    q, s = _quantize_rows(x2d, _bits_qmax(bits))
    if bits == 4:
        q = _pack_nibbles_jnp(q)
    return q, s[:, 0]


def quantize_for_transfer_async(
    x: jax.Array, bits: int = 8
) -> Tuple[list, int]:
    """Dispatch-only half of :func:`quantize_for_transfer`: enqueues every
    chunk's quantize kernel (async — returns as soon as XLA has the work)
    WITHOUT pulling anything to host. Returns (chunks, n) where chunks is
    ``[(q, s, m), ...]`` of not-yet-materialized device arrays; finish with
    :func:`pull_transfer_chunks`, possibly on another thread.

    Why two halves: the pull blocks until the kernels (and everything
    queued before them) execute. Dispatching the kernels on the CALLER's
    thread enqueues them immediately after the compute that produced
    ``x`` — before the caller's next training window — so a deferred pull
    overlaps that window instead of waiting behind it.

    Peak queued HBM beyond the input: the int8+scales outputs (~1.25
    bytes/elem total — they must coexist anyway, they ARE the payload)
    plus ONE executing chunk's fp32 intermediates (slice/pad live only
    inside `_quantize_row`'s execution, not per queued chunk). At most
    two slice-size compilations exist per flat size (full-chunk rows,
    where only the row INDEX is traced, + the static tail).
    """
    flat = x.reshape(-1)
    n = flat.size
    if n <= _TRANSFER_CHUNK:
        return [fused_quantize(flat, bits)], n
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
    """Pulls the device chunks from :func:`quantize_for_transfer_async` to
    host, returning the same (q, scales, n) layout — bit-identical — as
    :func:`quantize_for_transfer`."""
    bpb = BLOCK // (8 // bits)
    q_parts = []
    s_parts = []
    for i, (q, s, m) in enumerate(chunks):
        blocks = (m + BLOCK - 1) // BLOCK
        q_parts.append(np.asarray(q).reshape(-1)[: blocks * bpb])
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


def dequantize_from_transfer(
    q: np.ndarray, scales: np.ndarray, n: int, bits: int = 8
) -> jax.Array:
    """Host quantized payload -> device fp32, chunked like
    :func:`quantize_for_transfer`: each chunk is dequantized and written
    (buffer-donated) into a preallocated output, so peak transient HBM is
    output + one chunk regardless of payload size."""
    if n <= _TRANSFER_CHUNK:
        return fused_dequantize(q, scales, n, bits)
    bpb = BLOCK // (8 // bits)
    blocks_per_chunk = _TRANSFER_CHUNK // BLOCK
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
