"""Pallas TPU flash attention (GQA, forward and backward, four masks;
latent attention's two-part heads under the causal one).

The reference has no attention kernel of its own (it delegates compute to
torchtitan); this kernel exists because the flagship bench model's dense
attention materializes the full [B,H,S,S] score matrix in fp32 — an HBM
round trip that dominates step time as S grows. Flash attention streams
K/V blocks through VMEM with an online softmax so scores never leave
the chip (reference for the FLOPs budget: SURVEY.md §6; technique:
Dao et al. 2022, standard TPU formulation as in jax's pallas examples).

Five families of kernels in this file, four masks, each a mask closure
and a skip predicate (or a sweep of its own) around the one step math
(``_fwd_step``, ``_bwd_dq_step``, ``_bwd_dkv_step``):

- ``flash_attention``: causal (or none) over one sequence, static.
- ``flash_attention_block``: causal at GLOBAL offsets that are dynamic
  scalars, for the ring (one streamed k/v block a call, merged by lse).
- ``flash_attention_block_diffusion``: the training mask of block
  diffusion (arXiv:2503.09573) over two streams of L positions laid end
  to end, a noisy x_t then the clean x_0, with blocks of ``b`` positions:
  a clean query sees the clean keys of its own and earlier blocks, a noisy
  query the clean keys of strictly earlier blocks and the noisy keys of
  its own block, and nothing sees otherwise. L^2 + L*b score entries are
  kept of the 4 L^2 of the square; the sweeps visit the kept tiles only.
- ``flash_attention_window``: causal with a band, row i keeping the
  ``window`` columns j <= i with i - j < window, static; the sweeps visit
  only the tiles the band touches (70 of the causal triangle's 136 a head
  at 16,384 positions, a window of 4,096 and tiles of 1,024).
- ``flash_attention_mla``: the causal mask again over heads of another
  shape: a query and key of two parts (rope-free and rotary, the rotary
  key one a position for all heads) and values of a width of their own.

Layout: model-native [B, S, H, D] in/out (matching
``models/llama.py:dense_attention``); internally transposed to
[B, H, S, D] so the S×D blocks are MXU-shaped. GQA folds the q-head →
kv-head mapping into the K/V BlockSpec index maps — no K/V replication
in HBM or VMEM.

Grid = (B, Hq, q tiles, kv steps), kv innermost: TPU grids execute
sequentially, so the fp32 accumulator + online-softmax stats live in VMEM
scratch across the kv sweep and the output block is written once at the
final kv step. A step whose tile the mask empties is skipped via
``pl.when`` (no compute), and under the static masks its index map names
the tile the sweep already holds, so nothing is fetched for it either;
the ring's offset kernels, whose skip is decided by a dynamic scalar,
still fetch the tile they skip. The forward keeps its softmax state by
the lane (``_fwd_step``): the row max replicated across 128 lanes, the
row sum as 128 partial sums reduced once at the sweep's end, so a step
has one cross-lane reduction (the max) and broadcasts nothing to store.

Tiles: no caller names the tile. ``choose_tiles`` takes it from the shape
a call is given, 1,024 x 1,024 wherever 1,024 divides the sequence (6 to
12% under 512 x 512 forward and backward in every family and at both head
widths on a v5e), else 512, else what the caller's bound admits; the
entries' ``block_q`` / ``block_k`` are that bound.

Numerics: scores and softmax accumulate in fp32 regardless of input
dtype; output is cast back to the input dtype. Tested bitwise-free
against ``dense_attention`` to ≤2e-2 in bf16 and ≤1e-5 in fp32 (the
usual flash-vs-dense reassociation tolerance).

``interpret=True`` off-TPU: CPU tests execute the same kernel through the
Pallas interpreter (same gating as ``ops/quantization.py``).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "flash_attention",
    "flash_attention_block",
    "flash_attention_block_diffusion",
    "flash_attention_mla",
    "flash_attention_window",
    "block_diffusion_tiles",
    "window_kept",
    "window_tiles",
    "choose_tiles",
    "supports",
    "supports_block_diffusion",
    "supports_mla",
    "supports_window",
]

_NEG_INF = -1e30
_LANES = 128


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# The tiles measured good on a v5e, best first, and so the largest a call
# takes unless its caller names a smaller one (PERF.md section 6, PR 52: the
# family x shape x tile sweep of ``bench_kernels --tiles``).
_GOOD_TILES = (1024, 512)
_MAX_TILE = _GOOD_TILES[0]


def _tile(length: int, largest: int, multiple: int = 16) -> Optional[int]:
    """The tile one side of a sweep over ``length`` positions takes under
    ``largest``: the first of the measured-good tiles, then ``largest``
    itself (what the kernels took before they chose), that cuts the length
    into whole tiles of whole ``multiple`` rows. A length under a tile is
    one tile of its own; past 512 rows only in whole lane tiles (1,008
    rows, 63 groups of 16 lanes, do not fit the scoped VMEM). None: no
    tiling."""
    for t in (*(t for t in _GOOD_TILES if t <= largest), largest):
        t = min(t, length)
        if (
            t > 0 and length % t == 0 and t % multiple == 0
            and (t <= _GOOD_TILES[-1] or t % _LANES == 0)
        ):
            return t
    return None


def choose_tiles(
    family: str,
    seq_len: int,
    widths: tuple = (),
    block_q: int = _MAX_TILE,
    block_k: int = _MAX_TILE,
    kv_len: Optional[int] = None,
    block_length: int = 0,
) -> Optional[tuple]:
    """The (q rows, kv columns) a call's VMEM tiles hold, from what the
    call can see of its input, or None where the kernels do not take the
    shape (the caller then runs dense attention). The one rule of every
    entry and ``supports*`` predicate below.

    ``family``: 'causal', 'window' (the band's tiles are the causal
    family's: the window decides the sweep, not the tile), 'block' (the
    ring's offset block: ``kv_len``
    keys against ``seq_len`` queries), 'block_diffusion' (``seq_len`` the
    length of ONE stream, ``block_length`` its blocks) or 'mla'
    (``widths`` = rope-free, rotary and value channels; the other
    families' one head width decides nothing today: 64 and 128 measured
    best at the same tiles). ``block_q`` and ``block_k`` are the LARGEST
    tile the caller admits (``LlamaConfig.flash_block_q`` /
    ``flash_block_k``), not the tile.

    Alignment: tiles of whole 16-row groups (fp32 tiles are 8 rows; bf16
    blocks enter VMEM in their own dtype, so the stricter multiple).
    Block diffusion takes one square tile that lies in one stream and cuts
    no block and, compiled, is whole lane tiles (a stream is half of the
    array, so the per-row residuals' blocks cannot be the array's own last
    dimension as a short causal sequence's are). The latent kernels have
    been compiled for a rope-free part and values of whole lane tiles and
    a rotary part of half a tile or whole ones; the interpreter takes any.
    """
    compiled = not _interpret()
    if family == "block_diffusion":
        if block_length <= 0:
            return None
        rows = math.lcm(16, block_length, _LANES if compiled else 1)
        tile = _tile(seq_len, min(block_q, block_k), rows)
        return None if tile is None else (tile, tile)
    if family == "mla" and compiled:
        nope, rope, v_dim = widths
        if nope % _LANES or v_dim % _LANES or rope % (_LANES // 2):
            return None
    bq = _tile(seq_len, block_q)
    bk = _tile(seq_len if kv_len is None else kv_len, block_k)
    return None if bq is None or bk is None else (bq, bk)


def supports(
    seq_len: int, block_q: int = _MAX_TILE, block_k: int = _MAX_TILE
) -> bool:
    """Whether the kernel path handles this sequence length under these
    largest tiles (the caller falls back to dense attention otherwise)."""
    return choose_tiles("causal", seq_len, (), block_q, block_k) is not None


# ---------------------------------------------------------------------------
# Shared per-block step math. Every kernel below (causal and offset-block,
# forward and backward) delegates here so the numerics live in exactly one
# place; kernels differ only in their mask closure and skip predicate.
# All matmuls run in the INPUT dtype (bf16 hits the MXU at full rate; fp32
# would be emulated) with fp32 accumulation; softmax math stays fp32.
# ---------------------------------------------------------------------------


def _parts(x) -> tuple:
    """The latent family's score is a sum of two contractions (a rope-free
    and a rotary part of each query and key), so its kernels hand the step
    math TUPLES of refs and of accumulators, a part each; every other
    family hands one ref, which is a tuple of one."""
    return x if isinstance(x, tuple) else (x,)


def _scores(q_ref, k_ref, scale, mask_fn):
    s = functools.reduce(jnp.add, [
        jax.lax.dot_general(
            q[0, 0], k[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        for q, k in zip(_parts(q_ref), _parts(k_ref))
    ]) * scale  # [block_q, block_k] fp32
    return mask_fn(s)


def _lanes_to(x, width: int):
    """A lane-replicated [rows, 128] value at an accumulator's width."""
    if width <= _LANES:
        return x[:, :width]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], width))


def _fwd_step(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref, scale, mask_fn):
    """One online-softmax accumulation of a kv block into the scratch.
    The softmax state is kept by the lane: ``m_ref`` holds the running row
    max replicated across its 128 lanes, ``l_ref`` PARTIAL row sums a row,
    the tile's column groups folded in with elementwise adds (groups of
    128 lanes in every compiled tile; of gcd(block_k, 128) under the CPU
    tests' small tiles, the lanes past them staying 0), and
    ``_fwd_finish`` reduces them across the lanes once. alpha is computed
    on the replicated form, so the max is the one cross-lane reduction a
    tile and nothing is broadcast to be stored."""
    s = _scores(q_ref, k_ref, scale, mask_fn)
    block_k = s.shape[1]
    w = math.gcd(block_k, _LANES)
    v = v_ref[0, 0]
    m_prev = m_ref[:]  # [block_q, 128]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    groups = [
        jnp.exp(s[:, g:g + w] - m_new[:, :w]) for g in range(0, block_k, w)
    ]
    p = jnp.concatenate(groups, axis=1)
    l_ref[:, :w] = (
        alpha[:, :w] * l_ref[:, :w] + functools.reduce(jnp.add, groups)
    )
    m_ref[:] = m_new
    acc = acc_ref[:] * _lanes_to(alpha, acc_ref.shape[1])
    acc_ref[:] = acc + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _fwd_finish(o_ref, lse_ref, acc_ref, m_ref, l_ref):
    """Final normalization + logsumexp residual write."""
    # A row with every entry masked cannot happen under the causal mask
    # (the diagonal is always kept) nor under the block-diffusion mask (a
    # row's own block is always kept, and its sweep starts on a tile that
    # holds it), but CAN in an offset block entirely in the future: the
    # denom guard makes out 0 and lse ~ -1e30, which the block merge
    # weighs to zero.
    denom = jnp.maximum(jnp.sum(l_ref[:], axis=-1, keepdims=True), 1e-30)
    o_ref[0, 0] = (acc_ref[:] / denom).astype(o_ref.dtype)
    # TPU tiles need the last two block dims (sublane, lane) aligned, so
    # the per-row LSE is broadcast across 8 sublanes: array [B,H,8,S].
    lse = (m_ref[:, :1] + jnp.log(denom))[:, 0]  # [block_q]
    lse_ref[0, 0] = jnp.broadcast_to(lse[None, :], (8, lse.shape[0]))


def _bwd_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dlse_ref,
            scale, mask_fn):
    """Recomputes P and the softmax-jacobian term dS for a block.
    ``dlse_ref`` is None when the caller's lse output carries no cotangent
    (plain flash_attention returns only out); for the block variant
    d lse_i / d s_ij = p_ij folds the lse cotangent straight into dS."""
    s = _scores(q_ref, k_ref, scale, mask_fn)
    lse = lse_ref[0, 0, 0][:, None]  # [block_q, 1]
    delta = delta_ref[0, 0, 0][:, None]
    p = jnp.exp(s - lse)  # [block_q, block_k] fp32 (normalized)
    do = do_ref[0, 0]
    v = v_ref[0, 0]
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dsum = dp - delta
    if dlse_ref is not None:
        dsum = dsum + dlse_ref[0, 0, 0][:, None]
    return p, p * dsum


def _bwd_dq_step(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dlse_ref,
                 dq_acc, scale, mask_fn):
    _, ds = _bwd_ds(
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dlse_ref,
        scale, mask_fn,
    )
    for k_part, acc in zip(_parts(k_ref), _parts(dq_acc)):
        k = k_part[0, 0]
        acc[:] = acc[:] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale


def _bwd_dkv_step(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dlse_ref,
                  dk_acc, dv_acc, scale, mask_fn):
    p, ds = _bwd_ds(
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dlse_ref,
        scale, mask_fn,
    )
    qs = [q[0, 0] for q in _parts(q_ref)]
    do = do_ref[0, 0]
    # dv += P^T @ dO
    dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    # dk += dS^T @ Q * scale, a part of the key each
    for q, acc in zip(qs, _parts(dk_acc)):
        acc[:] = acc[:] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale


def _static_mask(causal, q_start, k_start):
    def mask_fn(s):
        if not causal:
            return s
        rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + q_start
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + k_start
        return jnp.where(rows >= cols, s, _NEG_INF)

    return mask_fn


def _dynamic_mask(q_start, k_start, qoff, koff):
    def mask_fn(s):
        return _offset_mask(s, q_start, k_start, qoff, koff)

    return mask_fn


def _offset_mask(s, q_start, k_start, qoff, koff):
    rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + q_start + qoff
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + k_start + koff
    return jnp.where(rows >= cols, s, _NEG_INF)


def _flash_kernel(
    q_ref,  # [1, 1, block_q, D]
    k_ref,  # [1, 1, block_k, D]
    v_ref,  # [1, 1, block_k, D]
    o_ref,  # [1, 1, block_q, D]
    lse_ref,  # [1, 1, 8, block_q] f32 (logsumexp residual)
    acc_ref,  # VMEM [block_q, D] f32
    m_ref,  # VMEM [block_q, 128] f32 (row max, lane-broadcast)
    l_ref,  # VMEM [block_q, 128] f32 (partial row sums by the lane)
    *,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # Causal: skip blocks strictly above the diagonal (no q row attends
    # into them; their DMA is elided by the clamped index maps).
    q_start = iq * block_q
    k_start = ik * block_k
    run = (not causal) or (k_start <= q_start + block_q - 1)

    @pl.when(run)
    def _step():
        _fwd_step(
            q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref, scale,
            _static_mask(causal, q_start, k_start),
        )

    @pl.when(ik == nk - 1)
    def _finish():
        _fwd_finish(o_ref, lse_ref, acc_ref, m_ref, l_ref)


def _flash_bwd_dq_kernel(
    q_ref,  # [1, 1, block_q, D]
    k_ref,  # [1, 1, block_k, D]
    v_ref,  # [1, 1, block_k, D]
    do_ref,  # [1, 1, block_q, D]
    lse_ref,  # [1, 1, 8, block_q] (sublane-broadcast)
    delta_ref,  # [1, 1, 8, block_q]
    dq_ref,  # out [1, 1, block_q, D]
    dq_acc,  # VMEM [block_q, D] f32
    *,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        for acc in _parts(dq_acc):
            acc[:] = jnp.zeros_like(acc)

    q_start = iq * block_q
    k_start = ik * block_k
    run = (not causal) or (k_start <= q_start + block_q - 1)

    @pl.when(run)
    def _step():
        _bwd_dq_step(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, None,
            dq_acc, scale, _static_mask(causal, q_start, k_start),
        )

    @pl.when(ik == nk - 1)
    def _finish():
        for out, acc in zip(_parts(dq_ref), _parts(dq_acc)):
            out[0, 0] = acc[:].astype(out.dtype)


def _flash_bwd_dkv_kernel(
    q_ref,  # [1, 1, block_q, D]
    k_ref,  # [1, 1, block_k, D]
    v_ref,  # [1, 1, block_k, D]
    do_ref,  # [1, 1, block_q, D]
    lse_ref,  # [1, 1, 8, block_q] (sublane-broadcast)
    delta_ref,  # [1, 1, 8, block_q]
    dk_ref,  # out [1, 1, block_k, D] (kv-head indexed)
    dv_ref,  # out [1, 1, block_k, D]
    dk_acc,  # VMEM [block_k, D] f32
    dv_acc,  # VMEM [block_k, D] f32
    *,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    nq: int,
    q_per_kv: int,
):
    # Grid = (B, Hkv, nk, q_per_kv * nq): everything that accumulates into
    # THIS kv block — the q-head group and the q-block sweep — is the
    # single innermost dimension, so the output block's VMEM residency is
    # one consecutive run and the scratch init/flush brackets exactly it.
    ik = pl.program_id(2)
    inner = pl.program_id(3)
    n_inner = pl.num_programs(3)
    iq = inner % nq

    @pl.when(inner == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_start = iq * block_q
    k_start = ik * block_k
    run = (not causal) or (k_start <= q_start + block_q - 1)

    @pl.when(run)
    def _step():
        _bwd_dkv_step(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, None,
            dk_acc, dv_acc, scale, _static_mask(causal, q_start, k_start),
        )

    @pl.when(inner == n_inner - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call drivers ([B,H,S,D] layout) + custom_vjp plumbing
# ---------------------------------------------------------------------------


def _forward_impl(qt, kt, vt, causal, block_q, block_k, interpret):
    B, Hq, S, D = qt.shape
    Hkv = kt.shape[1]
    q_per_kv = Hq // Hkv
    scale = 1.0 / math.sqrt(D)
    grid = (B, Hq, S // block_q, S // block_k)

    if causal:
        # Blocks strictly above the causal diagonal are pl.when-skipped in
        # the kernel; CLAMP their kv index to the diagonal block so the
        # index map repeats and pallas elides the (otherwise wasted) DMA.
        def kv_idx(b, h, iq, ik):
            lim = (iq * block_q + block_q - 1) // block_k
            return (b, h // q_per_kv, jnp.minimum(ik, lim), 0)
    else:
        def kv_idx(b, h, iq, ik):
            return (b, h // q_per_kv, ik, 0)

    out, lse = pl.pallas_call(
        functools.partial(
            _flash_kernel,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k,
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, S, D), qt.dtype),
            jax.ShapeDtypeStruct((B, Hq, 8, S), jnp.float32),
        ],
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            # GQA: q head h reads kv head h // q_per_kv.
            pl.BlockSpec((1, 1, block_k, D), kv_idx),
            pl.BlockSpec((1, 1, block_k, D), kv_idx),
        ],
        # Constant in ik: blocks stay resident in VMEM across the kv sweep
        # and are flushed once.
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, 8, block_q), lambda b, h, iq, ik: (b, h, 0, iq)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return out, lse


def _backward_impl(qt, kt, vt, do, lse, delta, causal, block_q, block_k,
                   interpret):
    B, Hq, S, D = qt.shape
    Hkv = kt.shape[1]
    q_per_kv = Hq // Hkv
    scale = 1.0 / math.sqrt(D)

    q_spec = pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0))
    if causal:
        def kv_idx(b, h, iq, ik):
            lim = (iq * block_q + block_q - 1) // block_k
            return (b, h // q_per_kv, jnp.minimum(ik, lim), 0)
    else:
        def kv_idx(b, h, iq, ik):
            return (b, h // q_per_kv, ik, 0)
    kv_spec = pl.BlockSpec((1, 1, block_k, D), kv_idx)
    row_spec = pl.BlockSpec(
        (1, 1, 8, block_q), lambda b, h, iq, ik: (b, h, 0, iq)
    )

    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k,
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, S, D), qt.dtype),
        grid=(B, Hq, S // block_q, S // block_k),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)
        ),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
    )(qt, kt, vt, do, lse, delta)

    # dk/dv: one kv block per (b, hkv, ik); its full accumulation sweep
    # (q heads in the GQA group x q blocks) is the innermost grid dim.
    nq = S // block_q

    def q_blk(ik, inner):
        iq = inner % nq
        if not causal:
            return iq
        # q blocks fully above the diagonal contribute nothing; clamp to
        # the diagonal block so the repeated index elides their DMA.
        lo = (ik * block_k) // block_q
        return jnp.maximum(iq, lo)

    q_spec2 = pl.BlockSpec(
        (1, 1, block_q, D),
        lambda b, hk, ik, inner: (
            b, hk * q_per_kv + inner // nq, q_blk(ik, inner), 0
        ),
    )
    kv_spec2 = pl.BlockSpec(
        (1, 1, block_k, D), lambda b, hk, ik, inner: (b, hk, ik, 0)
    )
    row_spec2 = pl.BlockSpec(
        (1, 1, 8, block_q),
        lambda b, hk, ik, inner: (
            b, hk * q_per_kv + inner // nq, 0, q_blk(ik, inner)
        ),
    )
    dkv_out = pl.BlockSpec(
        (1, 1, block_k, D), lambda b, hk, ik, inner: (b, hk, ik, 0)
    )
    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k,
            nq=nq, q_per_kv=q_per_kv,
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, S, D), kt.dtype),
            jax.ShapeDtypeStruct((B, Hkv, S, D), vt.dtype),
        ],
        grid=(B, Hkv, S // block_k, q_per_kv * nq),
        in_specs=[q_spec2, kv_spec2, kv_spec2, q_spec2, row_spec2, row_spec2],
        out_specs=[dkv_out, dkv_out],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt, do, lse, delta)
    return dq, dk, dv


def _row_delta(do, out):
    """Delta_i = rowsum(dO_i * O_i) [B, Hq, S] (a tiny elementwise + reduce
    that XLA fuses), sublane-broadcast to the lse residual's layout
    [B, Hq, 8, S]."""
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    return jnp.broadcast_to(
        delta[:, :, None, :], (*delta.shape[:2], 8, delta.shape[-1])
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(qt, kt, vt, causal, block_q, block_k, interpret):
    out, _ = _forward_impl(qt, kt, vt, causal, block_q, block_k, interpret)
    return out


def _flash_fwd(qt, kt, vt, causal, block_q, block_k, interpret):
    out, lse = _forward_impl(qt, kt, vt, causal, block_q, block_k, interpret)
    return out, (qt, kt, vt, out, lse)


def _flash_bwd(causal, block_q, block_k, interpret, res, do):
    qt, kt, vt, out, lse = res
    return _backward_impl(
        qt, kt, vt, do, lse, _row_delta(do, out), causal, block_q, block_k,
        interpret,
    )


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret")
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    block_q: int = _MAX_TILE,
    block_k: int = _MAX_TILE,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Causal GQA flash attention, differentiable. q: [B,S,Hq,D]; k/v:
    [B,S,Hkv,D] with Hq % Hkv == 0. Returns [B,S,Hq,D] in q's dtype.
    ``block_q``, ``block_k``: the largest tiles to take (``choose_tiles``)."""
    B, S, Hq, D = q.shape
    _, _, Hkv, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    tiles = choose_tiles("causal", S, (D,), block_q, block_k)
    if tiles is None:
        raise ValueError(
            f"flash_attention: seq_len {S} not divisible by blocks "
            f"({block_q},{block_k}); use dense_attention"
        )
    block_q, block_k = tiles
    itp = _interpret() if interpret is None else interpret
    # [B,S,H,D] -> [B,H,S,D]: S x D blocks are MXU-shaped.
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = _flash(qt, kt, vt, causal, block_q, block_k, itp)
    return jnp.swapaxes(out, 1, 2)


# ---------------------------------------------------------------------------
# Offset-aware block variant for ring attention (parallel/ring_attention.py):
# full attention of a local q shard against one streamed k/v block, with the
# causal mask evaluated at GLOBAL positions (q_offset / k_offset are dynamic
# SMEM scalars — each ring step sees a different source block). Returns
# (out, lse) so the caller can merge blocks with the standard online-softmax
# combination.
# ---------------------------------------------------------------------------


def _flash_block_fwd_kernel(
    qoff_ref,  # SMEM [1, 1] i32
    koff_ref,  # SMEM [1, 1] i32
    q_ref, k_ref, v_ref,  # [1, 1, block, D]
    o_ref,  # [1, 1, block_q, D]
    lse_ref,  # [1, 1, 8, block_q]
    acc_ref, m_ref, l_ref,  # VMEM scratch
    *, scale: float, block_q: int, block_k: int,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)
    qoff = qoff_ref[0, 0]
    koff = koff_ref[0, 0]

    @pl.when(ik == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    q_start = iq * block_q
    k_start = ik * block_k
    # Dynamic skip: this kv block is entirely in this q block's future.
    run = (k_start + koff) <= (q_start + qoff + block_q - 1)

    @pl.when(run)
    def _step():
        _fwd_step(
            q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref, scale,
            _dynamic_mask(q_start, k_start, qoff, koff),
        )

    @pl.when(ik == nk - 1)
    def _finish():
        _fwd_finish(o_ref, lse_ref, acc_ref, m_ref, l_ref)


def _flash_block_bwd_dq_kernel(
    qoff_ref, koff_ref,
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dlse_ref,
    dq_ref,
    dq_acc,
    *, scale: float, block_q: int, block_k: int,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)
    qoff = qoff_ref[0, 0]
    koff = koff_ref[0, 0]

    @pl.when(ik == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q_start = iq * block_q
    k_start = ik * block_k
    run = (k_start + koff) <= (q_start + qoff + block_q - 1)

    @pl.when(run)
    def _step():
        _bwd_dq_step(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dlse_ref,
            dq_acc, scale, _dynamic_mask(q_start, k_start, qoff, koff),
        )

    @pl.when(ik == nk - 1)
    def _finish():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_block_bwd_dkv_kernel(
    qoff_ref, koff_ref,
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dlse_ref,
    dk_ref, dv_ref,
    dk_acc, dv_acc,
    *, scale: float, block_q: int, block_k: int, nq: int, q_per_kv: int,
):
    ik = pl.program_id(2)
    inner = pl.program_id(3)
    n_inner = pl.num_programs(3)
    iq = inner % nq
    qoff = qoff_ref[0, 0]
    koff = koff_ref[0, 0]

    @pl.when(inner == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_start = iq * block_q
    k_start = ik * block_k
    run = (k_start + koff) <= (q_start + qoff + block_q - 1)

    @pl.when(run)
    def _step():
        _bwd_dkv_step(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dlse_ref,
            dk_acc, dv_acc, scale,
            _dynamic_mask(q_start, k_start, qoff, koff),
        )

    @pl.when(inner == n_inner - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)



def _smem_spec():
    return pl.BlockSpec(
        (1, 1), lambda *_: (0, 0), memory_space=pltpu.SMEM
    )


def _block_forward_impl(qt, kt, vt, qoff, koff, block_q, block_k, interpret):
    B, Hq, Sq, D = qt.shape
    Hkv, Skv = kt.shape[1], kt.shape[2]
    q_per_kv = Hq // Hkv
    scale = 1.0 / math.sqrt(D)
    grid = (B, Hq, Sq // block_q, Skv // block_k)
    kv_idx = lambda b, h, iq, ik: (b, h // q_per_kv, ik, 0)  # noqa: E731
    out, lse = pl.pallas_call(
        functools.partial(
            _flash_block_fwd_kernel,
            scale=scale, block_q=block_q, block_k=block_k,
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, Sq, D), qt.dtype),
            jax.ShapeDtypeStruct((B, Hq, 8, Sq), jnp.float32),
        ],
        grid=grid,
        in_specs=[
            _smem_spec(),
            _smem_spec(),
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, D), kv_idx),
            pl.BlockSpec((1, 1, block_k, D), kv_idx),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, 8, block_q), lambda b, h, iq, ik: (b, h, 0, iq)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=interpret,
    )(qoff, koff, qt, kt, vt)
    return out, lse


def _block_backward_impl(
    qt, kt, vt, qoff, koff, do, lse, delta, dlse, block_q, block_k, interpret
):
    B, Hq, Sq, D = qt.shape
    Hkv, Skv = kt.shape[1], kt.shape[2]
    q_per_kv = Hq // Hkv
    scale = 1.0 / math.sqrt(D)

    q_spec = pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, block_k, D), lambda b, h, iq, ik: (b, h // q_per_kv, ik, 0)
    )
    row_spec = pl.BlockSpec(
        (1, 1, 8, block_q), lambda b, h, iq, ik: (b, h, 0, iq)
    )
    dq = pl.pallas_call(
        functools.partial(
            _flash_block_bwd_dq_kernel,
            scale=scale, block_q=block_q, block_k=block_k,
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, D), qt.dtype),
        grid=(B, Hq, Sq // block_q, Skv // block_k),
        in_specs=[_smem_spec(), _smem_spec(),
                  q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec,
                  row_spec],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)
        ),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
    )(qoff, koff, qt, kt, vt, do, lse, delta, dlse)

    nq = Sq // block_q
    q_spec2 = pl.BlockSpec(
        (1, 1, block_q, D),
        lambda b, hk, ik, inner: (b, hk * q_per_kv + inner // nq, inner % nq, 0),
    )
    kv_spec2 = pl.BlockSpec(
        (1, 1, block_k, D), lambda b, hk, ik, inner: (b, hk, ik, 0)
    )
    row_spec2 = pl.BlockSpec(
        (1, 1, 8, block_q),
        lambda b, hk, ik, inner: (b, hk * q_per_kv + inner // nq, 0, inner % nq),
    )
    dkv_out = pl.BlockSpec(
        (1, 1, block_k, D), lambda b, hk, ik, inner: (b, hk, ik, 0)
    )
    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_block_bwd_dkv_kernel,
            scale=scale, block_q=block_q, block_k=block_k,
            nq=nq, q_per_kv=q_per_kv,
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, Skv, D), kt.dtype),
            jax.ShapeDtypeStruct((B, Hkv, Skv, D), vt.dtype),
        ],
        grid=(B, Hkv, Skv // block_k, q_per_kv * nq),
        in_specs=[_smem_spec(), _smem_spec(),
                  q_spec2, kv_spec2, kv_spec2, q_spec2, row_spec2, row_spec2,
                  row_spec2],
        out_specs=[dkv_out, dkv_out],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        interpret=interpret,
    )(qoff, koff, qt, kt, vt, do, lse, delta, dlse)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _flash_block(qt, kt, vt, qoff, koff, block_q, block_k, interpret):
    return _block_forward_impl(
        qt, kt, vt, qoff, koff, block_q, block_k, interpret
    )


def _flash_block_fwd(qt, kt, vt, qoff, koff, block_q, block_k, interpret):
    out, lse = _block_forward_impl(
        qt, kt, vt, qoff, koff, block_q, block_k, interpret
    )
    return (out, lse), (qt, kt, vt, qoff, koff, out, lse)


def _flash_block_bwd(block_q, block_k, interpret, res, cts):
    qt, kt, vt, qoff, koff, out, lse = res
    do, dlse = cts  # BOTH outputs carry cotangents (the ring merge uses lse)
    delta = _row_delta(do, out)
    # dlse is already in the raw [B,Hq,8,S] kernel layout (the sublane
    # slice happens in the public wrapper, outside this vjp); the kernels
    # read sublane 0, which is exactly where the slice cotangent lands.
    dq, dk, dv = _block_backward_impl(
        qt, kt, vt, qoff, koff, do, lse, delta,
        dlse.astype(jnp.float32), block_q, block_k, interpret,
    )
    return dq, dk, dv, None, None


_flash_block.defvjp(_flash_block_fwd, _flash_block_bwd)


def flash_attention_block(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_offset: jax.Array,
    k_offset: jax.Array,
    block_q: int = _MAX_TILE,
    block_k: int = _MAX_TILE,
    interpret: Optional[bool] = None,
) -> tuple:
    """One causal-at-global-positions attention block: q [B,Sq,Hq,D]
    against k/v [B,Skv,Hkv,D], where q row i has global position
    ``q_offset + i`` and k col j has ``k_offset + j`` (both dynamic int32
    scalars). Returns ``(out [B,Sq,Hq,D], lse [B,Hq,Sq] fp32)`` — merge
    streamed blocks with the online-softmax combine (see
    parallel/ring_attention.py). Differentiable (offsets get no grad)."""
    B, Sq, Hq, D = q.shape
    Skv = k.shape[1]
    tiles = choose_tiles("block", Sq, (D,), block_q, block_k, kv_len=Skv)
    if tiles is None:
        raise ValueError(
            f"flash_attention_block: shapes (Sq={Sq}, Skv={Skv}) not "
            f"block-divisible; use the dense fold"
        )
    block_q, block_k = tiles
    qoff = jnp.asarray(q_offset, jnp.int32).reshape(1, 1)
    koff = jnp.asarray(k_offset, jnp.int32).reshape(1, 1)
    itp = _interpret() if interpret is None else interpret
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out, lse = _flash_block(qt, kt, vt, qoff, koff, block_q, block_k, itp)
    # lse is sublane-broadcast [B,Hq,8,Sq]; take one sublane.
    return jnp.swapaxes(out, 1, 2), lse[:, :, 0, :]


# ---------------------------------------------------------------------------
# Block-diffusion variant (models/llama.py:Attention under
# ``objective="block_diffusion"``): q/k/v hold two streams of L positions
# end to end, rows 0..L-1 the noisy x_t and L..2L-1 the clean x_0, both at
# positions 0..L-1, in tiles of ``block`` (a multiple of the block length
# ``b``, so a tile lies in one stream and cuts no block). With n = L/block
# the 2n x 2n tiles hold n(n+1)/2 kept clean-on-clean tiles, as many
# noisy-on-clean and n noisy-on-noisy: n^2 + 2n of 4n^2. No kernel's grid
# walks the square: a q tile sweeps its own kept kv tiles (n + 1 steps at
# most, as a causal sweep over L would take n), a clean kv tile the q tiles
# of both streams from its own on, a noisy kv tile its one q tile. A step
# past the end of a sweep repeats the sweep's last tile, so nothing is
# fetched for it. Everything is static: no scalar reaches the kernels.
# ---------------------------------------------------------------------------


def supports_block_diffusion(
    stream_len: int, block_length: int, block: int = _MAX_TILE
) -> bool:
    """Whether the block-diffusion kernels handle two streams of
    ``stream_len`` positions under blocks of ``block_length`` in tiles of
    at most ``block`` (``choose_tiles`` has the rules)."""
    return _bd_tile(stream_len, block_length, block) is not None


def _bd_tile(stream_len: int, block_length: int, block: int) -> Optional[int]:
    tiles = choose_tiles(
        "block_diffusion", stream_len, (), block, block, block_length=block_length
    )
    return None if tiles is None else tiles[0]


def block_diffusion_tiles(
    stream_len: int, block_length: int, block: int = _MAX_TILE
):
    """(kept score entries, score entries of the tiles a sweep runs) a
    head and sequence, forward, at the tile the kernels choose under
    ``block``; the backward kernels run the same tiles."""
    blk = _bd_tile(stream_len, block_length, block)
    if blk is None:
        raise ValueError(
            f"block_diffusion_tiles: streams of {stream_len} positions in "
            f"blocks of {block_length} do not tile under {block}"
        )
    n = stream_len // blk
    kept = stream_len * stream_len + stream_len * block_length
    return kept, (n * n + 2 * n) * blk * blk


def _bd_kv_sweep(n, iq, j):
    """Step ``j`` of q tile ``iq``'s sweep over the kv tiles: (tile,
    whether it runs). A noisy tile i starts on the noisy tile i, which
    holds every row's own block (so the running max is finite from the
    first step on), then takes the clean tiles 0..i; a clean tile i takes
    the clean tiles 0..i."""
    noisy = iq < n
    i = jnp.where(noisy, iq, iq - n)
    last = jnp.where(noisy, i + 1, i)
    jj = jnp.minimum(j, last)
    clean_tile = n + jnp.where(noisy, jj - 1, jj)
    return jnp.where(noisy & (jj == 0), i, clean_tile), j <= last


def _bd_q_sweep(n, ik, s):
    """Step ``s`` of the clean kv tile ``ik``'s sweep over the q tiles:
    the noisy tiles ik..n-1 (steps ik..n-1), then the clean ones (steps
    n+ik..2n-1); a step before them repeats the first."""
    i = s % n
    return jnp.where(s >= n, n, 0) + jnp.maximum(i, ik), i >= ik


def _bd_mask(n, b, block, iq, ikv):
    """The mask of q tile ``iq`` on kv tile ``ikv`` (tiles of the 2n): a
    row at position p of block first(p)..first(p)+b-1 keeps the columns in
    [lo, hi), by the two tiles' streams. A sweep never pairs a clean q
    tile with a noisy kv tile."""

    def mask_fn(s):
        q_noisy, k_noisy = iq < n, ikv < n
        rows = jax.lax.broadcasted_iota(jnp.int32, (s.shape[0], 1), 0)
        qpos = rows + (iq - jnp.where(q_noisy, 0, n)) * block
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        kpos = cols + (ikv - jnp.where(k_noisy, 0, n)) * block
        # b a power of two: a bitwise and (the VPU has no integer divide).
        first = (qpos & -b) if b & (b - 1) == 0 else qpos - qpos % b
        lo = first * k_noisy.astype(jnp.int32)  # noisy keys: the own block only
        # Noisy on clean: strictly earlier blocks. Else up to the own block's end.
        hi = first + jnp.where(q_noisy & jnp.logical_not(k_noisy), 0, b)
        return jnp.where((kpos >= lo) & (kpos < hi), s, _NEG_INF)

    return mask_fn


def _flash_bd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
    *, scale: float, n: int, b: int, block: int,
):
    iq = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    ikv, run = _bd_kv_sweep(n, iq, j)

    @pl.when(run)
    def _step():
        _fwd_step(
            q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref, scale,
            _bd_mask(n, b, block, iq, ikv),
        )

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        _fwd_finish(o_ref, lse_ref, acc_ref, m_ref, l_ref)


def _flash_bd_bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc,
    *, scale: float, n: int, b: int, block: int,
):
    iq = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    ikv, run = _bd_kv_sweep(n, iq, j)

    @pl.when(run)
    def _step():
        _bwd_dq_step(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, None,
            dq_acc, scale, _bd_mask(n, b, block, iq, ikv),
        )

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_bd_bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc, dv_acc,
    *, scale: float, n: int, b: int, block: int, clean: bool, sweep: int,
):
    # Grid = (B, Hkv, n, q_per_kv * sweep) over the kv tiles of ONE stream:
    # the clean ones (``sweep`` = 2n q tiles a head) or the noisy ones (1).
    ik = pl.program_id(2)
    inner = pl.program_id(3)

    @pl.when(inner == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    if clean:
        ikv = n + ik
        iq, run = _bd_q_sweep(n, ik, inner % sweep)
    else:
        ikv, iq, run = ik, ik, True

    @pl.when(run)
    def _step():
        _bwd_dkv_step(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, None,
            dk_acc, dv_acc, scale, _bd_mask(n, b, block, iq, ikv),
        )

    @pl.when(inner == pl.num_programs(3) - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _bd_q_sweep_specs(n, q_per_kv, block, D):
    """Block specs of the kernels whose grid is (B, Hq, 2n q tiles, n + 1
    sweep steps), forward and dq: a q tile's own block (q, o, do, dq), the
    kv tile its sweep is at (GQA: q head h reads kv head h // q_per_kv),
    its rows' residuals (lse, delta)."""
    q_spec = pl.BlockSpec((1, 1, block, D), lambda bb, h, iq, j: (bb, h, iq, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, block, D),
        lambda bb, h, iq, j: (bb, h // q_per_kv, _bd_kv_sweep(n, iq, j)[0], 0),
    )
    row_spec = pl.BlockSpec((1, 1, 8, block), lambda bb, h, iq, j: (bb, h, 0, iq))
    return q_spec, kv_spec, row_spec


def _bd_forward_impl(qt, kt, vt, b, block, interpret):
    B, Hq, S, D = qt.shape
    n = S // 2 // block
    q_spec, kv_spec, row_spec = _bd_q_sweep_specs(n, Hq // kt.shape[1], block, D)
    return pl.pallas_call(
        functools.partial(
            _flash_bd_kernel, scale=1.0 / math.sqrt(D), n=n, b=b, block=block
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, S, D), qt.dtype),
            jax.ShapeDtypeStruct((B, Hq, 8, S), jnp.float32),
        ],
        grid=(B, Hq, 2 * n, n + 1),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, row_spec],
        scratch_shapes=[
            pltpu.VMEM((block, D), jnp.float32),
            pltpu.VMEM((block, 128), jnp.float32),
            pltpu.VMEM((block, 128), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)


def _bd_backward_impl(qt, kt, vt, do, lse, delta, b, block, interpret):
    B, Hq, S, D = qt.shape
    Hkv = kt.shape[1]
    q_per_kv = Hq // Hkv
    n = S // 2 // block
    static = dict(scale=1.0 / math.sqrt(D), n=n, b=b, block=block)

    q_spec, kv_spec, row_spec = _bd_q_sweep_specs(n, q_per_kv, block, D)
    dq = pl.pallas_call(
        functools.partial(_flash_bd_bwd_dq_kernel, **static),
        out_shape=jax.ShapeDtypeStruct((B, Hq, S, D), qt.dtype),
        grid=(B, Hq, 2 * n, n + 1),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((block, D), jnp.float32)],
        interpret=interpret,
    )(qt, kt, vt, do, lse, delta)

    def dkv(clean: bool):
        """dk, dv of one stream's kv tiles, [B, Hkv, L, D] each."""
        sweep = 2 * n if clean else 1

        def q_at(hk, ik, inner):
            iq = _bd_q_sweep(n, ik, inner % sweep)[0] if clean else ik
            return hk * q_per_kv + inner // sweep, iq

        def q_idx(bb, hk, ik, inner):
            head, iq = q_at(hk, ik, inner)
            return bb, head, iq, 0

        def row_idx(bb, hk, ik, inner):
            head, iq = q_at(hk, ik, inner)
            return bb, head, 0, iq

        first = n if clean else 0
        q_spec2 = pl.BlockSpec((1, 1, block, D), q_idx)
        row_spec2 = pl.BlockSpec((1, 1, 8, block), row_idx)
        kv_spec2 = pl.BlockSpec(
            (1, 1, block, D), lambda bb, hk, ik, inner: (bb, hk, first + ik, 0)
        )
        out_spec = pl.BlockSpec(
            (1, 1, block, D), lambda bb, hk, ik, inner: (bb, hk, ik, 0)
        )
        return pl.pallas_call(
            functools.partial(
                _flash_bd_bwd_dkv_kernel, clean=clean, sweep=sweep, **static
            ),
            out_shape=[
                jax.ShapeDtypeStruct((B, Hkv, S // 2, D), kt.dtype),
                jax.ShapeDtypeStruct((B, Hkv, S // 2, D), vt.dtype),
            ],
            grid=(B, Hkv, n, q_per_kv * sweep),
            in_specs=[q_spec2, kv_spec2, kv_spec2, q_spec2, row_spec2, row_spec2],
            out_specs=[out_spec, out_spec],
            scratch_shapes=[
                pltpu.VMEM((block, D), jnp.float32),
                pltpu.VMEM((block, D), jnp.float32),
            ],
            interpret=interpret,
        )(qt, kt, vt, do, lse, delta)

    (dk_noisy, dv_noisy), (dk_clean, dv_clean) = dkv(False), dkv(True)
    return (
        dq,
        jnp.concatenate([dk_noisy, dk_clean], axis=2),
        jnp.concatenate([dv_noisy, dv_clean], axis=2),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_bd(qt, kt, vt, b, block, interpret):
    return _bd_forward_impl(qt, kt, vt, b, block, interpret)[0]


def _flash_bd_fwd(qt, kt, vt, b, block, interpret):
    out, lse = _bd_forward_impl(qt, kt, vt, b, block, interpret)
    return out, (qt, kt, vt, out, lse)


def _flash_bd_bwd(b, block, interpret, res, do):
    qt, kt, vt, out, lse = res
    return _bd_backward_impl(
        qt, kt, vt, do, lse, _row_delta(do, out), b, block, interpret
    )


_flash_bd.defvjp(_flash_bd_fwd, _flash_bd_bwd)


@functools.partial(
    jax.jit, static_argnames=("block_length", "block", "interpret")
)
def flash_attention_block_diffusion(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    block_length: int,
    block: int = _MAX_TILE,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """GQA flash attention under the block-diffusion training mask,
    differentiable. q: [B,2L,Hq,D]; k/v: [B,2L,Hkv,D]: rows 0..L-1 the
    noisy stream, L..2L-1 the clean one. Returns [B,2L,Hq,D] in q's dtype.
    ``block``: the largest tile to take (``choose_tiles``).
    The kernels' trace names start ``flash_attention``, as the causal
    ones' do (a kernel is named for the jit around it)."""
    B, S, Hq, D = q.shape
    assert Hq % k.shape[2] == 0, (Hq, k.shape[2])
    L = S // 2
    tile = None if S % 2 else _bd_tile(L, block_length, block)
    if tile is None:
        raise ValueError(
            f"flash_attention_block_diffusion: two streams of {L} positions "
            f"in blocks of {block_length} do not tile by {min(block, L)}"
        )
    itp = _interpret() if interpret is None else interpret
    out = _flash_bd(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
        block_length, tile, itp,
    )
    return jnp.swapaxes(out, 1, 2)


# ---------------------------------------------------------------------------
# Latent-attention variant (models/mla.py, DeepSeek-V3 arXiv:2412.19437
# section 2.1.1 in its training form): a head's query and key are a
# rope-free part of ``Dn`` channels and a rotary part of ``Dr``, the score
# (q_nope . k_nope + q_rope . k_rope) / sqrt(Dn + Dr), the values ``Dv``
# wide, and ONE rotary key a position serves every head. The rotary key
# stays one head in HBM, [B, 1, S, Dr]: every head's index map names that
# head, and its gradient is accumulated over the heads inside the dkv
# kernel. Causal over one sequence, static; the causal kernels' bodies,
# their refs and accumulators handed in parts (``_parts``).
#
# The score is TWO contractions a tile, Dn deep and Dr deep, summed. The
# other form, one contraction over the parts joined in VMEM (Dn + Dr = 192
# lanes, padded to 256), was measured beside it on a v5e at the cell's
# shapes ([2,8192,32] heads of 128 + 64 | 128, tiles of 512; PERF.md
# section 6, PR 51): forward 15.9 ms against 16.1, forward and backward
# 61.0 against 60.8, within 1.2% at every tiling tried. Either is two
# passes of a 128-deep MXU, so nothing is won by joining, and the joined
# form copies both operands inside the kernel every tile: the two
# contractions stay. What the 64-deep pass costs is seen against head width
# 128's kernels: 15.9 ms forward for 1.6 times the work of their 12.0.
# ---------------------------------------------------------------------------


def supports_mla(
    seq_len: int, nope: int, rope: int, v_dim: int,
    block_q: int = _MAX_TILE, block_k: int = _MAX_TILE,
) -> bool:
    """Whether the latent kernels handle this sequence and these widths
    under these largest tiles (``choose_tiles`` has the rules)."""
    return choose_tiles("mla", seq_len, (nope, rope, v_dim), block_q, block_k) is not None


def _mla_fwd_kernel(
    qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
    **static,
):
    _flash_kernel(
        (qn_ref, qr_ref), (kn_ref, kr_ref), v_ref, o_ref, lse_ref,
        acc_ref, m_ref, l_ref, causal=True, **static,
    )


def _mla_bwd_dq_kernel(
    qn_ref, qr_ref, kn_ref, kr_ref, v_ref, do_ref, lse_ref, delta_ref,
    dqn_ref, dqr_ref, dqn_acc, dqr_acc, **static,
):
    _flash_bwd_dq_kernel(
        (qn_ref, qr_ref), (kn_ref, kr_ref), v_ref, do_ref, lse_ref, delta_ref,
        (dqn_ref, dqr_ref), (dqn_acc, dqr_acc), causal=True, **static,
    )


def _mla_bwd_dkv_kernel(
    qn_ref, qr_ref, kn_ref, kr_ref, v_ref, do_ref, lse_ref, delta_ref,
    dkn_ref, dkr_ref, dv_ref,  # out: [1,1,block_k,Dn], [1,1,block_k,Dr] (head 0), [1,1,block_k,Dv]
    dkn_acc, dkr_acc, dv_acc,
    *, scale: float, block_q: int, block_k: int, nq: int,
):
    # Grid = (B, nk, H * nq): everything that accumulates into THIS kv tile
    # is the innermost dimension, head after head. A head's dk_nope and dv
    # are bracketed by its own nq steps (their output blocks move on with
    # the head); the shared rotary key's gradient by the whole sweep.
    ik = pl.program_id(1)
    inner = pl.program_id(2)
    iq = inner % nq

    @pl.when(inner == 0)
    def _init_shared():
        dkr_acc[:] = jnp.zeros_like(dkr_acc)

    @pl.when(iq == 0)
    def _init_head():
        dkn_acc[:] = jnp.zeros_like(dkn_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_start = iq * block_q
    k_start = ik * block_k

    @pl.when(k_start <= q_start + block_q - 1)
    def _step():
        _bwd_dkv_step(
            (qn_ref, qr_ref), (kn_ref, kr_ref), v_ref, do_ref, lse_ref,
            delta_ref, None, (dkn_acc, dkr_acc), dv_acc, scale,
            _static_mask(True, q_start, k_start),
        )

    @pl.when(iq == nq - 1)
    def _finish_head():
        dkn_ref[0, 0] = dkn_acc[:].astype(dkn_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)

    @pl.when(inner == pl.num_programs(2) - 1)
    def _finish_shared():
        dkr_ref[0, 0] = dkr_acc[:].astype(dkr_ref.dtype)


def _mla_q_sweep_specs(dims, block_q, block_k):
    """Block specs of the kernels whose grid is (B, H, q tiles, kv tiles),
    forward and dq: (q_nope, q_rope, k_nope, k_rope, v, out/do, rows). A
    kv tile above the diagonal is skipped and its index clamped to the
    diagonal's, as in the causal kernels; the rotary key is head 0's."""
    dn, dr, dv = dims

    def q_idx(b, h, iq, ik):
        return (b, h, iq, 0)

    def kv_at(iq, ik):
        return jnp.minimum(ik, (iq * block_q + block_q - 1) // block_k)

    def kv_idx(b, h, iq, ik):
        return (b, h, kv_at(iq, ik), 0)

    def shared_idx(b, h, iq, ik):
        return (b, 0, kv_at(iq, ik), 0)

    return (
        pl.BlockSpec((1, 1, block_q, dn), q_idx),
        pl.BlockSpec((1, 1, block_q, dr), q_idx),
        pl.BlockSpec((1, 1, block_k, dn), kv_idx),
        pl.BlockSpec((1, 1, block_k, dr), shared_idx),
        pl.BlockSpec((1, 1, block_k, dv), kv_idx),
        pl.BlockSpec((1, 1, block_q, dv), q_idx),
        pl.BlockSpec((1, 1, 8, block_q), lambda b, h, iq, ik: (b, h, 0, iq)),
    )


def _mla_forward_impl(qn, qr, kn, kr, vt, block_q, block_k, interpret):
    B, H, S, dn = qn.shape
    dr, dv = qr.shape[-1], vt.shape[-1]
    qn_spec, qr_spec, kn_spec, kr_spec, v_spec, o_spec, row_spec = _mla_q_sweep_specs(
        (dn, dr, dv), block_q, block_k
    )
    return pl.pallas_call(
        functools.partial(
            _mla_fwd_kernel, scale=1.0 / math.sqrt(dn + dr),
            block_q=block_q, block_k=block_k,
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, dv), vt.dtype),
            jax.ShapeDtypeStruct((B, H, 8, S), jnp.float32),
        ],
        grid=(B, H, S // block_q, S // block_k),
        in_specs=[qn_spec, qr_spec, kn_spec, kr_spec, v_spec],
        out_specs=[o_spec, row_spec],
        scratch_shapes=[
            pltpu.VMEM((block_q, dv), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=interpret,
    )(qn, qr, kn, kr, vt)


def _mla_backward_impl(qn, qr, kn, kr, vt, do, lse, delta, block_q, block_k, interpret):
    B, H, S, dn = qn.shape
    dr, dv = qr.shape[-1], vt.shape[-1]
    scale = 1.0 / math.sqrt(dn + dr)
    qn_spec, qr_spec, kn_spec, kr_spec, v_spec, o_spec, row_spec = _mla_q_sweep_specs(
        (dn, dr, dv), block_q, block_k
    )
    dqn, dqr = pl.pallas_call(
        functools.partial(
            _mla_bwd_dq_kernel, scale=scale, block_q=block_q, block_k=block_k
        ),
        out_shape=[
            jax.ShapeDtypeStruct(qn.shape, qn.dtype),
            jax.ShapeDtypeStruct(qr.shape, qr.dtype),
        ],
        grid=(B, H, S // block_q, S // block_k),
        in_specs=[qn_spec, qr_spec, kn_spec, kr_spec, v_spec, o_spec, row_spec, row_spec],
        out_specs=[qn_spec, qr_spec],
        scratch_shapes=[
            pltpu.VMEM((block_q, dn), jnp.float32),
            pltpu.VMEM((block_q, dr), jnp.float32),
        ],
        interpret=interpret,
    )(qn, qr, kn, kr, vt, do, lse, delta)

    nq = S // block_q

    def q_at(ik, inner):
        # q tiles wholly above the diagonal add nothing: clamp to the
        # diagonal's so that the repeated index fetches nothing.
        return inner // nq, jnp.maximum(inner % nq, (ik * block_k) // block_q)

    def q_idx(b, ik, inner):
        head, iq = q_at(ik, inner)
        return (b, head, iq, 0)

    def row_idx(b, ik, inner):
        head, iq = q_at(ik, inner)
        return (b, head, 0, iq)

    def kv_idx(b, ik, inner):
        return (b, inner // nq, ik, 0)

    def shared_idx(b, ik, inner):
        return (b, 0, ik, 0)

    q_specs = [pl.BlockSpec((1, 1, block_q, d), q_idx) for d in (dn, dr)]
    kv_specs = [
        pl.BlockSpec((1, 1, block_k, dn), kv_idx),
        pl.BlockSpec((1, 1, block_k, dr), shared_idx),
        pl.BlockSpec((1, 1, block_k, dv), kv_idx),
    ]
    rows = pl.BlockSpec((1, 1, 8, block_q), row_idx)
    dkn, dkr, dvt = pl.pallas_call(
        functools.partial(
            _mla_bwd_dkv_kernel, scale=scale, block_q=block_q, block_k=block_k, nq=nq
        ),
        out_shape=[
            jax.ShapeDtypeStruct(kn.shape, kn.dtype),
            jax.ShapeDtypeStruct(kr.shape, kr.dtype),
            jax.ShapeDtypeStruct(vt.shape, vt.dtype),
        ],
        grid=(B, S // block_k, H * nq),
        in_specs=[
            *q_specs, *kv_specs, pl.BlockSpec((1, 1, block_q, dv), q_idx), rows, rows,
        ],
        out_specs=kv_specs,
        scratch_shapes=[
            pltpu.VMEM((block_k, dn), jnp.float32),
            pltpu.VMEM((block_k, dr), jnp.float32),
            pltpu.VMEM((block_k, dv), jnp.float32),
        ],
        interpret=interpret,
    )(qn, qr, kn, kr, vt, do, lse, delta)
    return dqn, dqr, dkn, dkr, dvt


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _flash_mla(qn, qr, kn, kr, vt, block_q, block_k, interpret):
    return _mla_forward_impl(qn, qr, kn, kr, vt, block_q, block_k, interpret)[0]


def _flash_mla_fwd(qn, qr, kn, kr, vt, block_q, block_k, interpret):
    out, lse = _mla_forward_impl(qn, qr, kn, kr, vt, block_q, block_k, interpret)
    return out, (qn, qr, kn, kr, vt, out, lse)


def _flash_mla_bwd(block_q, block_k, interpret, res, do):
    qn, qr, kn, kr, vt, out, lse = res
    return _mla_backward_impl(
        qn, qr, kn, kr, vt, do, lse, _row_delta(do, out), block_q, block_k, interpret
    )


_flash_mla.defvjp(_flash_mla_fwd, _flash_mla_bwd)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k", "interpret"))
def flash_attention_mla(
    q_nope: jax.Array,
    q_rope: jax.Array,
    k_nope: jax.Array,
    k_rope: jax.Array,
    v: jax.Array,
    block_q: int = _MAX_TILE,
    block_k: int = _MAX_TILE,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Causal latent attention in its training form, differentiable.
    q_nope, k_nope: [B,S,H,Dn]; q_rope: [B,S,H,Dr]; k_rope: [B,S,Dr], the
    one rotary key a position that every head reads; v: [B,S,H,Dv].
    Returns [B,S,H,Dv] in v's dtype. k_rope's gradient is the sum over
    the heads. The kernels' trace names start ``flash_attention_mla`` (a
    kernel is named for the jit around it)."""
    B, S, H, dn = q_nope.shape
    dr, dv = q_rope.shape[-1], v.shape[-1]
    tiles = choose_tiles("mla", S, (dn, dr, dv), block_q, block_k)
    if tiles is None:
        raise ValueError(
            f"flash_attention_mla: seq_len {S} in blocks ({block_q},{block_k}) "
            f"at widths {dn}+{dr}|{dv}: use latent_dense_attention"
        )
    block_q, block_k = tiles
    itp = _interpret() if interpret is None else interpret
    to_heads = lambda x: jnp.swapaxes(x, 1, 2)  # noqa: E731
    out = _flash_mla(
        to_heads(q_nope), to_heads(q_rope), to_heads(k_nope), k_rope[:, None],
        to_heads(v), block_q, block_k, itp,
    )
    return jnp.swapaxes(out, 1, 2)


# ---------------------------------------------------------------------------
# Banded variant (models/llama.py:Attention of the windowed kind 'W'): causal
# with a sliding window, row i keeps the columns j <= i with i - j < window,
# the position itself counted (``window`` keys at most). Static, over one
# sequence. No kernel's grid walks the causal triangle: a q tile sweeps the
# kv tiles from the one that holds its first row's oldest key to its
# diagonal, a kv tile the q tiles from its diagonal to the one that holds the
# last row that still sees its last column (the mirrored sweep), and the
# grid's innermost dimension is the LONGEST such sweep (ceil((window - 1) /
# tile) + 1 steps where the tiles are square). A step past the end of a
# sweep repeats the sweep's last tile, so nothing is fetched for it. The
# first tile of a q tile's sweep can hold rows that keep none of it: their
# running max stays at the mask's value and what they accumulate there is
# scaled to exactly 0 by the diagonal tile's first finite max.
# ---------------------------------------------------------------------------


def supports_window(
    seq_len: int, window: int, block_q: int = _MAX_TILE, block_k: int = _MAX_TILE
) -> bool:
    """Whether the banded kernels handle this sequence under a window of
    ``window`` positions and these largest tiles (by shape alone; the
    caller falls back to dense attention under the band mask otherwise)."""
    return window >= 1 and choose_tiles("window", seq_len, (), block_q, block_k) is not None


def _band_span(i, a, c, back, ahead, n, lo=jnp.maximum, hi=jnp.minimum):
    """(first, last) of the ``n`` tiles of ``c`` positions that hold any of
    the positions i*a - back .. i*a + a - 1 + ahead: the kv tiles of q tile
    ``i`` (``back`` = window - 1, ``ahead`` = 0) or the q tiles of kv tile
    ``i`` (the mirror). On traced indices, or on Python ints with
    ``lo=max, hi=min``."""
    return lo(i * a - back, 0) // c, hi((i * a + a - 1 + ahead) // c, n - 1)


def _band_sweeps(seq_len: int, window: int, block_q: int, block_k: int):
    """Per q tile the kv tiles its sweep runs, per kv tile the q tiles
    (two lists of Python ints): what the grids are sized from."""
    nq, nk = seq_len // block_q, seq_len // block_k
    span = lambda *a: _band_span(*a, lo=max, hi=min)  # noqa: E731
    kv = [span(i, block_q, block_k, window - 1, 0, nk) for i in range(nq)]
    q = [span(i, block_k, block_q, 0, window - 1, nq) for i in range(nk)]
    return [b - a + 1 for a, b in kv], [b - a + 1 for a, b in q]


def window_kept(seq_len: int, window: int) -> int:
    """Score entries the band keeps a head and sequence: row i keeps
    min(i + 1, window) keys."""
    w = min(window, seq_len)
    return w * (w + 1) // 2 + (seq_len - w) * w


def window_tiles(
    seq_len: int, window: int, block_q: int = _MAX_TILE, block_k: int = _MAX_TILE
):
    """(kept score entries, score entries of the tiles a sweep runs) a head
    and sequence, forward, at the tiles the kernels choose under the
    bounds; the backward kernels run the same tiles. A window of at least
    the sequence runs the causal schedule."""
    tiles = choose_tiles("window", seq_len, (), block_q, block_k)
    if tiles is None:
        raise ValueError(
            f"window_tiles: {seq_len} positions do not tile under ({block_q},{block_k})"
        )
    sweeps = _band_sweeps(seq_len, min(window, seq_len), *tiles)[0]
    return window_kept(seq_len, window), sum(sweeps) * tiles[0] * tiles[1]


def _window_mask(window, q_start, k_start):
    def mask_fn(s):
        rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + q_start
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + k_start
        back = rows - cols
        return jnp.where((back >= 0) & (back < window), s, _NEG_INF)

    return mask_fn


def _flash_window_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
    *, scale: float, window: int, block_q: int, block_k: int, nk: int,
):
    iq = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    first, last = _band_span(iq, block_q, block_k, window - 1, 0, nk)
    ik = first + j

    @pl.when(ik <= last)
    def _step():
        _fwd_step(
            q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref, scale,
            _window_mask(window, iq * block_q, ik * block_k),
        )

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        _fwd_finish(o_ref, lse_ref, acc_ref, m_ref, l_ref)


def _flash_window_bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc,
    *, scale: float, window: int, block_q: int, block_k: int, nk: int,
):
    iq = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    first, last = _band_span(iq, block_q, block_k, window - 1, 0, nk)
    ik = first + j

    @pl.when(ik <= last)
    def _step():
        _bwd_dq_step(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, None,
            dq_acc, scale, _window_mask(window, iq * block_q, ik * block_k),
        )

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_window_bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc, dv_acc,
    *, scale: float, window: int, block_q: int, block_k: int, nq: int, sweep: int,
):
    # Grid = (B, Hkv, nk, q_per_kv * sweep): the q-head group and each
    # head's sweep over the q tiles the band pairs with THIS kv tile are the
    # one innermost dimension, as in the causal dkv kernel.
    ik = pl.program_id(2)
    inner = pl.program_id(3)

    @pl.when(inner == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    first, last = _band_span(ik, block_k, block_q, 0, window - 1, nq)
    iq = first + inner % sweep

    @pl.when(iq <= last)
    def _step():
        _bwd_dkv_step(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, None,
            dk_acc, dv_acc, scale,
            _window_mask(window, iq * block_q, ik * block_k),
        )

    @pl.when(inner == pl.num_programs(3) - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _window_q_sweep_specs(window, q_per_kv, block_q, block_k, nk, D):
    """Block specs of the kernels whose grid is (B, Hq, q tiles, sweep
    steps), forward and dq: a q tile's own block (q, o, do, dq), the kv
    tile its sweep is at (GQA: q head h reads kv head h // q_per_kv), its
    rows' residuals (lse, delta)."""

    def kv_idx(b, h, iq, j):
        first, last = _band_span(iq, block_q, block_k, window - 1, 0, nk)
        return (b, h // q_per_kv, jnp.minimum(first + j, last), 0)

    q_spec = pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, j: (b, h, iq, 0))
    kv_spec = pl.BlockSpec((1, 1, block_k, D), kv_idx)
    row_spec = pl.BlockSpec((1, 1, 8, block_q), lambda b, h, iq, j: (b, h, 0, iq))
    return q_spec, kv_spec, row_spec


def _window_forward_impl(qt, kt, vt, window, block_q, block_k, interpret):
    B, Hq, S, D = qt.shape
    nk = S // block_k
    steps = max(_band_sweeps(S, window, block_q, block_k)[0])
    q_spec, kv_spec, row_spec = _window_q_sweep_specs(
        window, Hq // kt.shape[1], block_q, block_k, nk, D
    )
    return pl.pallas_call(
        functools.partial(
            _flash_window_kernel, scale=1.0 / math.sqrt(D), window=window,
            block_q=block_q, block_k=block_k, nk=nk,
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, S, D), qt.dtype),
            jax.ShapeDtypeStruct((B, Hq, 8, S), jnp.float32),
        ],
        grid=(B, Hq, S // block_q, steps),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, row_spec],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)


def _window_backward_impl(
    qt, kt, vt, do, lse, delta, window, block_q, block_k, interpret
):
    B, Hq, S, D = qt.shape
    Hkv = kt.shape[1]
    q_per_kv = Hq // Hkv
    nq, nk = S // block_q, S // block_k
    kv_sweeps, q_sweeps = _band_sweeps(S, window, block_q, block_k)
    static = dict(
        scale=1.0 / math.sqrt(D), window=window, block_q=block_q, block_k=block_k
    )

    q_spec, kv_spec, row_spec = _window_q_sweep_specs(
        window, q_per_kv, block_q, block_k, nk, D
    )
    dq = pl.pallas_call(
        functools.partial(_flash_window_bwd_dq_kernel, nk=nk, **static),
        out_shape=jax.ShapeDtypeStruct((B, Hq, S, D), qt.dtype),
        grid=(B, Hq, nq, max(kv_sweeps)),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
    )(qt, kt, vt, do, lse, delta)

    sweep = max(q_sweeps)

    def q_at(hk, ik, inner):
        first, last = _band_span(ik, block_k, block_q, 0, window - 1, nq)
        return hk * q_per_kv + inner // sweep, jnp.minimum(first + inner % sweep, last)

    def q_idx(b, hk, ik, inner):
        head, iq = q_at(hk, ik, inner)
        return b, head, iq, 0

    def row_idx(b, hk, ik, inner):
        head, iq = q_at(hk, ik, inner)
        return b, head, 0, iq

    q_spec2 = pl.BlockSpec((1, 1, block_q, D), q_idx)
    row_spec2 = pl.BlockSpec((1, 1, 8, block_q), row_idx)
    kv_spec2 = pl.BlockSpec(
        (1, 1, block_k, D), lambda b, hk, ik, inner: (b, hk, ik, 0)
    )
    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_window_bwd_dkv_kernel, nq=nq, sweep=sweep, **static
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, S, D), kt.dtype),
            jax.ShapeDtypeStruct((B, Hkv, S, D), vt.dtype),
        ],
        grid=(B, Hkv, nk, q_per_kv * sweep),
        in_specs=[q_spec2, kv_spec2, kv_spec2, q_spec2, row_spec2, row_spec2],
        out_specs=[kv_spec2, kv_spec2],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_window(qt, kt, vt, window, block_q, block_k, interpret):
    return _window_forward_impl(qt, kt, vt, window, block_q, block_k, interpret)[0]


def _flash_window_fwd(qt, kt, vt, window, block_q, block_k, interpret):
    out, lse = _window_forward_impl(qt, kt, vt, window, block_q, block_k, interpret)
    return out, (qt, kt, vt, out, lse)


def _flash_window_bwd(window, block_q, block_k, interpret, res, do):
    qt, kt, vt, out, lse = res
    return _window_backward_impl(
        qt, kt, vt, do, lse, _row_delta(do, out), window, block_q, block_k,
        interpret,
    )


_flash_window.defvjp(_flash_window_fwd, _flash_window_bwd)


@functools.partial(
    jax.jit, static_argnames=("window", "block_q", "block_k", "interpret")
)
def flash_attention_window(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    window: int,
    block_q: int = _MAX_TILE,
    block_k: int = _MAX_TILE,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Causal GQA flash attention under a sliding window, differentiable:
    row i keeps the columns j <= i with i - j < ``window``. q: [B,S,Hq,D];
    k/v: [B,S,Hkv,D] with Hq % Hkv == 0. Returns [B,S,Hq,D] in q's dtype.
    A window of at least the sequence is the causal mask, and runs the
    causal kernels. ``block_q``, ``block_k``: the largest tiles to take
    (``choose_tiles``). The kernels' trace names start
    ``flash_attention_window`` (a kernel is named for the jit around it)."""
    B, S, Hq, D = q.shape
    assert Hq % k.shape[2] == 0, (Hq, k.shape[2])
    if window < 1:
        raise ValueError(f"flash_attention_window: window {window} keeps nothing")
    tiles = choose_tiles("window", S, (D,), block_q, block_k)
    if tiles is None:
        raise ValueError(
            f"flash_attention_window: seq_len {S} not divisible by blocks "
            f"({block_q},{block_k}); use dense_attention under the band mask"
        )
    itp = _interpret() if interpret is None else interpret
    qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    if window >= S:
        out = _flash(qt, kt, vt, True, *tiles, itp)
    else:
        out = _flash_window(qt, kt, vt, window, *tiles, itp)
    return jnp.swapaxes(out, 1, 2)
