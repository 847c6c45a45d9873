"""Pallas TPU flash attention (GQA, forward and backward, five masks, one
of them data; latent attention's two-part heads under the causal one).

The reference has no attention kernel of its own (it delegates compute to
torchtitan); this kernel exists because the flagship bench model's dense
attention materializes the full [B,H,S,S] score matrix in fp32 — an HBM
round trip that dominates step time as S grows. Flash attention streams
K/V blocks through VMEM with an online softmax so scores never leave
the chip (reference for the FLOPs budget: SURVEY.md §6; technique:
Dao et al. 2022, standard TPU formulation as in jax's pallas examples).

One scaffold, six families. The step math (``_fwd_step``, ``_bwd_step``),
the two kernel bodies around it (``_fwd_kernel``, ``_bwd_kernel``: init at a
sweep's first step, the step under ``pl.when(run)``, finish at its last),
the two drivers that build block specs, out shapes and scratch
(``_forward_impl``, ``_backward_impl``) and the ``custom_vjp`` (``_flash``)
are written once. A family is a small frozen value (``_Family``) that
answers three questions, and is the only thing that answers them:

- the sweep (forward and backward alike): a q tile's span (``q_span``: what
  the steps of its sweep share), then for step ``j`` the kv tile and
  whether the step runs (``q_sweep``) or the tile the index map names,
  which repeats a tile the sweep holds where the step does not run, so
  nothing is fetched for it (``q_fetch``); ``q_steps`` sizes the grid;
- the mask closure of a (q tile, kv tile) pair (``mask``);
- its operands: how many operands lead a call ahead of q (``scalars``:
  SMEM scalars, of which the kernels hand the sweep and the mask the values;
  a family whose mask is data says itself what leads, ``lead_specs``, and
  what of it the sweep and the mask are handed, ``at``), whether ``lse`` is
  an output (``lse_out``) and whether it then has a cotangent
  (``lse_cotangent``). A tensor is a tuple of parts (the latent family's
  query and key are two).

A seventh family is a seventh subclass beside these and an entry that
builds its value; nothing in the frame names a family.

- ``flash_attention`` (``_Causal``): causal (or none) over one sequence,
  static.
- ``flash_attention_block`` (``_Offset``): causal at GLOBAL offsets that
  are dynamic scalars, for the ring (one streamed k/v block a call, merged
  by lse).
- ``flash_attention_block_diffusion`` (``_BlockDiffusion``): the training
  mask of block diffusion (arXiv:2503.09573) over two streams of L
  positions laid end to end, a noisy x_t then the clean x_0, with blocks
  of ``b`` positions: a clean query sees the clean keys of its own and
  earlier blocks, a noisy query the clean keys of strictly earlier blocks
  and the noisy keys of its own block, and nothing sees otherwise.
  L^2 + L*b score entries are kept of the 4 L^2 of the square; the sweeps
  visit the kept tiles only.
- ``flash_attention_window`` (``_Window``): causal with a band, row i
  keeping the ``window`` columns j <= i with i - j < window, static; the
  sweeps visit only the tiles the band touches (70 of the causal
  triangle's 136 a head at 16,384 positions, a window of 4,096 and tiles
  of 1,024).
- ``flash_attention_mla`` (``_Causal`` again): the causal mask over heads
  of another shape: a query and key of two parts (rope-free and rotary,
  the rotary key one a position for all heads) and values of a width of
  their own.
- ``flash_attention_selected`` (``_Selected``): causal, and of a row's
  earlier keys those an indexer SELECTED (a learned sparse attention in its
  masked form, ``ops/sparse_index.py``): the one mask that is data. Two
  operands lead the call: the selection packed a bit an entry, of which a
  q tile's rows are one VMEM block for its whole sweep and a kv tile's
  columns an elementwise shift of it, and the table of tile pairs that hold
  any selected entry, in SMEM, which gates the step forward and backward.
  ``lse`` is an output (the indexer's loss reads it), a constant to
  differentiation.

Layout: model-native [B, S, H, D] in/out (matching
``models/llama.py:dense_attention``); internally transposed to
[B, H, S, D] so the S×D blocks are MXU-shaped. GQA folds the q-head →
kv-head mapping into the K/V BlockSpec index maps — no K/V replication
in HBM or VMEM.

Grid = (B, Hq, q tiles, kv steps), kv innermost, of the forward and of the
backward: TPU grids execute sequentially, so the fp32 accumulator +
online-softmax stats live in VMEM scratch across the kv sweep and the
output block is written once at the final kv step. A step whose tile the
mask empties is skipped via ``pl.when`` (no compute), and under the static
masks its index map names the tile the sweep already holds, so nothing is
fetched for it either;
the ring's offset kernels and the selected family's, whose skip is decided
by a dynamic scalar, still fetch the tile they skip. The forward keeps its softmax state by
the lane (``_fwd_step``): the row max replicated across 128 lanes, the
row sum as 128 partial sums reduced once at the sweep's end, so a step
has one cross-lane reduction (the max) and broadcasts nothing to store.

One backward: every kept (q tile, kv tile) pair is visited once, and the
step computes S, the mask, P = exp(S - lse), dP = dO V^T and dS once
(``_bwd_ds``) and adds the pair's three products from them: dq += dS K,
dv += P^T dO, dk += dS^T Q (five matmuls and one exponential a pair; a dq
call and a dkv call ran seven and two). dq accumulates with the q tile, as
the forward's output does. dk's parts and dv are RESIDENT for a kv head:
float32 scratch of [kv_len, D] a part, zeroed at the first step of the kv
head's first q head, added to at the step's kv tile, cast and flushed at the
last step of its last q head into an output block of the whole [kv_len, D],
whose index is constant over that bracket so it is written once
(``_bracket``). The q heads of a group are consecutive in Hq, so GQA's sum
happens in VMEM; a key part that all heads share (the latent family's rotary
key) is a bracket of a whole batch row. For one kv tile the contributions
arrive head by head, q tile ascending, and dq's kv tile ascending: the
order, the operand dtypes and the casts of the dq and dkv kernels this
replaced, whose dq, dk and dv the interpreter reproduces to the bit.

VMEM: the residents cost ``kv_len`` x (dk's widths + dv's, in whole lane
tiles) x (4 bytes of accumulator + two buffers of the output block), 32 MiB
at 16,384 keys of head width 128 in bf16. That is past the compiler's
default scoped limit (16 MiB) and inside a v5e core's 128 MiB, so the call
states ``vmem_limit_bytes`` from its own shapes (``_backward_vmem_bytes``),
and ``choose_tiles``, which every entry and ``supports*`` predicate asks,
answers None for a key length whose residents would not fit
(``_residents_fit``: past 37,888 keys at head widths 128 and 64, 24,576 at
the latent widths). The ring's ``flash_attention_block`` sees one streamed
block a call, so longer sequences reach the kernel in bounded pieces.

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

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from torchft_tpu.ops.sparse_index import mask_width, tile_bits

__all__ = [
    "flash_attention",
    "flash_attention_block",
    "flash_attention_block_diffusion",
    "flash_attention_mla",
    "flash_attention_selected",
    "flash_attention_window",
    "block_diffusion_tiles",
    "window_kept",
    "window_tiles",
    "choose_tiles",
    "supports",
    "supports_block_diffusion",
    "supports_mla",
    "supports_selected",
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


# A banded sweep's first and last tile are crossed by a mask edge, whatever
# the tile: a band narrower than this many of a tile takes the next
# measured-good tile down. On a v5e at S = 16,384, 32 heads on 4 of width 128
# (forward + dq + dkv, ms): a window of 2,048, two tiles of 1,024 wide, a
# sweep of three, 23.34 at 1,024 against 21.60 at 512 (38.8 at 256), the
# same to 0.1% on three repeats; a window of 4,096, four wide, a sweep of
# five, 31.0 at 1,024 against 31.5 at 512 (PERF.md section 6, PR 65).
_BAND_TILES = 4


def _band_tile(window: int, largest: int) -> int:
    """The largest tile a band of ``window`` positions admits under
    ``largest``: the first of the measured-good tiles the band is at least
    ``_BAND_TILES`` wide in, else the smallest of them; ``largest`` itself
    where it is under them all (the CPU tests' tiles, the reference
    check's 128)."""
    good = [t for t in _GOOD_TILES if t <= largest]
    if not good:
        return largest
    return next((t for t in good if window >= _BAND_TILES * t), good[-1])


# The backward holds a kv head's dk parts and dv in VMEM for the head's whole
# bracket (``_bwd_kernel``), so a key length has a price in VMEM that the
# tiles do not bound. A v5e core has 128 MiB (``pltpu.get_tpu_info()`` on the
# chip, PERF.md section 6, PR 68); the compiler's default scoped limit is 16 MiB
# of it, so the call states its own.
_VMEM_BYTES = 128 * 2**20
# What a step works in beside the residents (the streamed blocks, double-
# buffered, dq's accumulators and the compiler's own scratch): that default,
# in which the kernels of every tile the chooser takes have always compiled.
# Compiled for a described v5e at tiles of 1,024 the call needs the residents
# and 1.7 MiB more in bf16, 11.5 MiB more in float32.
_STEP_VMEM_BYTES = 16 * 2**20


def _backward_vmem_bytes(kv_len: int, widths, itemsize: int = 4) -> int:
    """VMEM bytes the backward call asks for at ``kv_len`` keys: a float32
    accumulator and two buffers of the output block (the pipeline's, of
    ``itemsize`` bytes an entry) for each of dk's parts and dv, ``widths``
    their channels (whole lane tiles in VMEM), and a step's working set."""
    lanes = sum(-(-w // _LANES) * _LANES for w in widths)
    return kv_len * lanes * (4 + 2 * itemsize) + _STEP_VMEM_BYTES


def _residents_fit(kv_len: int, widths) -> bool:
    """The one rule of what key length the backward holds: its VMEM bytes
    at float32 outputs, the widest the kernels take, within the core's."""
    return _backward_vmem_bytes(kv_len, widths) <= _VMEM_BYTES


def choose_tiles(
    family: str,
    seq_len: int,
    widths: tuple = (),
    block_q: int = _MAX_TILE,
    block_k: int = _MAX_TILE,
    kv_len: Optional[int] = None,
    block_length: int = 0,
    window: int = 0,
) -> Optional[tuple]:
    """The (q rows, kv columns) a call's VMEM tiles hold, from what the
    call can see of its input, or None where the kernels do not take the
    shape (the caller then runs dense attention). The one rule of every
    entry and ``supports*`` predicate below.

    ``family``: 'causal', 'window' (a band of ``window`` positions inside
    the sequence: the causal family's tiles where the band is at least
    ``_BAND_TILES`` of them wide, else the next measured-good tile down,
    ``_band_tile``; a window of the whole sequence or none given: the
    causal family's), 'block' (the
    ring's offset block: ``kv_len``
    keys against ``seq_len`` queries), 'block_diffusion' (``seq_len`` the
    length of ONE stream, ``block_length`` its blocks) or 'mla'
    (``widths`` = rope-free, rotary and value channels; the other
    families' one head width decides nothing today: 64 and 128 measured
    best at the same tiles). ``block_q`` and ``block_k`` are the LARGEST
    tile the caller admits (``LlamaConfig.flash_block_q`` /
    ``flash_block_k``), not the tile.

    Length: the backward holds dk's parts and dv of a kv head's every key
    in VMEM, so a key length whose residents do not fit the core is refused
    whatever its tiles (``_residents_fit``; ``widths`` the latent family's
    three, else the one head width for keys and values, 128 where none is
    given).

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
    keys = 2 * seq_len if family == "block_diffusion" else kv_len or seq_len
    held = widths if family == "mla" else 2 * (tuple(widths) or (_LANES,))
    if not _residents_fit(keys, held):
        return None
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
    if family == "window" and 0 < window < seq_len:
        block_q, block_k = _band_tile(window, block_q), _band_tile(window, block_k)
    bq = _tile(seq_len, block_q)
    bk = _tile(seq_len if kv_len is None else kv_len, block_k)
    if bq is None or bk is None:
        return None
    if family == "selected":
        # A kv tile is whole groups of the packed selection's columns or a
        # group whole tiles; compiled, both in whole lane tiles.
        width = mask_width(seq_len)
        if bk % width and width % bk or compiled and (width % _LANES or bk % _LANES):
            return None
    return bq, bk


def supports(
    seq_len: int, block_q: int = _MAX_TILE, block_k: int = _MAX_TILE
) -> bool:
    """Whether the kernel path handles this sequence length under these
    largest tiles (the caller falls back to dense attention otherwise)."""
    return choose_tiles("causal", seq_len, (), block_q, block_k) is not None


# ---------------------------------------------------------------------------
# Shared per-block step math. Every kernel below (forward and backward, every
# family) delegates here so the numerics live in exactly one place; a family
# differs only in the mask closure it hands over. A tensor arrives as a TUPLE
# of refs, a part each: the latent family's score is a sum of two
# contractions (a rope-free and a rotary part of each query and key), every
# other family's tensors are tuples of one.
# All matmuls run in the INPUT dtype (bf16 hits the MXU at full rate; fp32
# would be emulated) with fp32 accumulation; softmax math stays fp32.
# ---------------------------------------------------------------------------


def _scores(q, k, scale, mask_fn):
    s = functools.reduce(jnp.add, [
        jax.lax.dot_general(
            q_part[0, 0], k_part[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        for q_part, k_part in zip(q, k)
    ]) * scale  # [block_q, block_k] fp32
    return mask_fn(s)


def _lanes_to(x, width: int):
    """A lane-replicated [rows, 128] value at an accumulator's width."""
    if width <= _LANES:
        return x[:, :width]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], width))


def _fwd_step(q, k, v_ref, acc_ref, m_ref, l_ref, scale, mask_fn):
    """One online-softmax accumulation of a kv block into the scratch.
    The softmax state is kept by the lane: ``m_ref`` holds the running row
    max replicated across its 128 lanes, ``l_ref`` PARTIAL row sums a row,
    the tile's column groups folded in with elementwise adds (groups of
    128 lanes in every compiled tile; of gcd(block_k, 128) under the CPU
    tests' small tiles, the lanes past them staying 0), and
    ``_fwd_finish`` reduces them across the lanes once. alpha is computed
    on the replicated form, so the max is the one cross-lane reduction a
    tile and nothing is broadcast to be stored."""
    s = _scores(q, k, scale, mask_fn)
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


def _bwd_ds(q, k, v_ref, do_ref, lse_ref, delta_ref, dlse_ref, scale, mask_fn):
    """Recomputes P and the softmax-jacobian term dS for a block.
    ``dlse_ref`` is None when the family's lse output carries no cotangent
    (plain flash_attention returns only out); for the offset block
    d lse_i / d s_ij = p_ij folds the lse cotangent straight into dS."""
    s = _scores(q, k, scale, mask_fn)
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


def _bwd_step(q, k, residuals, dq_acc, dk_acc, dv_acc, keys, scale, mask_fn):
    """One tile pair of the backward: P and dS once, then the pair's three
    products from them. dq's accumulators hold the q tile, dk's and dv's a
    kv head's every key: ``keys`` is the pair's kv tile in them."""
    p, ds = _bwd_ds(q, k, *residuals, scale, mask_fn)
    do = residuals[1][0, 0]
    # dq += dS @ K * scale, a part of the query each
    for k_part, acc in zip(k, dq_acc):
        kk = k_part[0, 0]
        acc[:] = acc[:] + jax.lax.dot_general(
            ds.astype(kk.dtype), kk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
    # dv += P^T @ dO
    dv_acc[keys, :] = dv_acc[keys, :] + jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    # dk += dS^T @ Q * scale, a part of the key each
    for q_part, acc in zip(q, dk_acc):
        qq = q_part[0, 0]
        acc[keys, :] = acc[keys, :] + jax.lax.dot_general(
            ds.astype(qq.dtype), qq, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale


# ---------------------------------------------------------------------------
# The families. Tile indices are traced scalars inside a kernel or an index
# map and plain ints in the tests, which walk the sweeps as the grids do.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _Family:
    """What the frame asks of a family (the module's docstring has the
    three questions). Hashable and of static ints: it is a
    non-differentiable argument of the ``custom_vjp``. The sweep is asked in
    two steps: a q tile's span (``q_span``: what its steps share, by default
    the tile itself), then step by step the kv tile the sweep is at and
    whether it runs (``q_sweep``) or the tile to fetch (``q_fetch``).
    ``scalars`` SMEM scalars lead a call's operands and are handed to
    ``q_sweep`` and ``mask`` after the tiles; the index maps do not see
    them. This base is the full rectangle of tiles, every step run."""
    q_len: int
    kv_len: int
    block_q: int
    block_k: int

    scalars = 0
    lse_cotangent = False

    @property
    def lse_out(self) -> bool:
        """Whether ``lse`` is an output of the entry: where it carries a
        cotangent, and where a family says so itself."""
        return self.lse_cotangent

    def lead_specs(self) -> list:
        """Block specs of the ``scalars`` operands that lead a call."""
        return [_smem_spec()] * self.scalars

    def at(self, refs) -> list:
        """What a kernel hands ``q_sweep`` and ``mask`` of those operands'
        refs: the scalars' values."""
        return [ref[0, 0] for ref in refs]

    @property
    def nq(self) -> int:
        return self.q_len // self.block_q

    @property
    def nk(self) -> int:
        return self.kv_len // self.block_k

    @property
    def q_steps(self) -> int:
        return self.nk

    def q_span(self, iq):
        return iq

    def q_sweep(self, span, j, *scalars):
        return j, True

    def q_fetch(self, span, j):
        return j

    def mask(self, iq, ikv, *scalars):
        return lambda s: s

    def _starts(self, iq, ikv):
        return iq * self.block_q, ikv * self.block_k


def _keep(s, kept):
    return jnp.where(kept, s, _NEG_INF)


def _positions(s, q_start, k_start):
    """(row, column) positions of a score tile that starts at these."""
    rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + q_start
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + k_start
    return rows, cols


@dataclasses.dataclass(frozen=True)
class _Causal(_Family):
    """Causal over one sequence, the grids the whole (nq, nk) rectangle: a
    tile strictly above the diagonal is skipped (no q row attends into it)
    and its index CLAMPED to the diagonal tile's, so the index map repeats
    and the (otherwise wasted) DMA is elided. ``causal=False``: the base."""
    causal: bool = True

    def _runs(self, iq, ikv):
        q_start, k_start = self._starts(iq, ikv)
        return (not self.causal) or (k_start <= q_start + self.block_q - 1)

    def q_span(self, iq):
        if not self.causal:
            return iq, None
        return iq, (iq * self.block_q + self.block_q - 1) // self.block_k

    def q_sweep(self, span, j):
        # On positions, as the kernels have always traced it (j <= the
        # span's last tile says the same; the compiler drops what is unused).
        return j, self._runs(span[0], j)

    def q_fetch(self, span, j):
        return j if span[1] is None else jnp.minimum(j, span[1])

    def mask(self, iq, ikv):
        if not self.causal:
            return lambda s: s
        starts = self._starts(iq, ikv)

        def mask_fn(s):
            rows, cols = _positions(s, *starts)
            return _keep(s, rows >= cols)

        return mask_fn


@dataclasses.dataclass(frozen=True)
class _Offset(_Family):
    """The ring's fold (parallel/ring_attention.py): full attention of a
    local q shard against one streamed k/v block, with the causal mask
    evaluated at GLOBAL positions (``qoff``, ``koff`` are dynamic SMEM
    scalars: each ring step sees a different source block). A skip decided
    by a dynamic scalar still fetches the tile it skips. ``lse`` is an
    output, so the caller can merge blocks with the standard online-softmax
    combination, and carries a cotangent."""
    scalars = 2
    lse_cotangent = True

    def _runs(self, iq, ikv, qoff, koff):
        q_start, k_start = self._starts(iq, ikv)
        # False: this kv block is entirely in this q block's future.
        return (k_start + koff) <= (q_start + qoff + self.block_q - 1)

    def q_sweep(self, iq, j, qoff, koff):
        return j, self._runs(iq, j, qoff, koff)

    def mask(self, iq, ikv, qoff, koff):
        q_start, k_start = self._starts(iq, ikv)

        def mask_fn(s):  # a tile's start, then the offset: two adds a side, as ever
            rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + q_start + qoff
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + k_start + koff
            return _keep(s, rows >= cols)

        return mask_fn


@dataclasses.dataclass(frozen=True)
class _BlockDiffusion(_Family):
    """models/llama.py:Attention under ``objective="block_diffusion"``:
    q/k/v hold two streams of L positions end to end, rows 0..L-1 the noisy
    x_t and L..2L-1 the clean x_0, both at positions 0..L-1, in square
    tiles (a multiple of the block length ``b``, so a tile lies in one
    stream and cuts no block). With n = L/tile the 2n x 2n tiles hold
    n(n+1)/2 kept clean-on-clean tiles, as many noisy-on-clean and n
    noisy-on-noisy: n^2 + 2n of 4n^2. No kernel's grid walks the square: a
    q tile sweeps its own kept kv tiles (n + 1 steps at most, as a causal
    sweep over L would take n). A step past the end of a sweep repeats the
    sweep's last tile, so nothing is fetched for it. Everything is static:
    no scalar reaches the kernels."""
    b: int = 0

    @property
    def n(self) -> int:
        return self.nq // 2

    @property
    def q_steps(self) -> int:
        return self.n + 1

    def q_sweep(self, iq, j):
        """A noisy tile i starts on the noisy tile i, which holds every
        row's own block (so the running max is finite from the first step
        on), then takes the clean tiles 0..i; a clean tile i takes the
        clean tiles 0..i."""
        n = self.n
        noisy = iq < n
        i = jnp.where(noisy, iq, iq - n)
        last = jnp.where(noisy, i + 1, i)
        jj = jnp.minimum(j, last)
        clean_tile = n + jnp.where(noisy, jj - 1, jj)
        return jnp.where(noisy & (jj == 0), i, clean_tile), j <= last

    def q_fetch(self, iq, j):
        return self.q_sweep(iq, j)[0]

    def mask(self, iq, ikv):
        """A row at position p of block first(p)..first(p)+b-1 keeps the
        columns in [lo, hi), by the two tiles' streams. A sweep never pairs
        a clean q tile with a noisy kv tile."""
        n, b, block = self.n, self.b, self.block_q

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
            return _keep(s, (kpos >= lo) & (kpos < hi))

        return mask_fn


def _band_span(i, a, c, back, n, lo=jnp.maximum, hi=jnp.minimum):
    """(first, last) of the ``n`` kv tiles of ``c`` positions that hold any
    of the positions i*a - back .. i*a + a - 1, q tile ``i``'s rows and the
    ``back`` = window - 1 keys behind its first. On traced indices, or on
    Python ints with ``lo=max, hi=min``."""
    return lo(i * a - back, 0) // c, hi((i * a + a - 1) // c, n - 1)


def _band_sweeps(seq_len: int, window: int, block_q: int, block_k: int) -> list:
    """Per q tile the kv tiles its sweep runs (Python ints): what the grids
    are sized from."""
    spans = [
        _band_span(i, block_q, block_k, window - 1, seq_len // block_k, lo=max, hi=min)
        for i in range(seq_len // block_q)
    ]
    return [b - a + 1 for a, b in spans]


@dataclasses.dataclass(frozen=True)
class _Window(_Family):
    """models/llama.py:Attention of the windowed kind 'W': causal with a
    sliding window, row i keeps the columns j <= i with i - j < window, the
    position itself counted (``window`` keys at most). Static, over one
    sequence. No kernel's grid walks the causal triangle: a q tile sweeps
    the kv tiles from the one that holds its first row's oldest key to its
    diagonal, and the grid's innermost dimension is the LONGEST such sweep
    (ceil((window - 1) / tile) + 1 steps where the tiles are square). A
    step past the end of a sweep repeats the sweep's last tile, so nothing
    is fetched for it. The first tile of a q tile's sweep can hold rows
    that keep none of it: their running max stays at the mask's value and
    what they accumulate there is scaled to exactly 0 by the diagonal
    tile's first finite max."""
    window: int = 0

    @property
    def q_steps(self) -> int:
        return max(_band_sweeps(self.q_len, self.window, self.block_q, self.block_k))

    def q_span(self, iq):
        return _band_span(iq, self.block_q, self.block_k, self.window - 1, self.nk)

    def q_sweep(self, span, j):
        ik = span[0] + j
        return ik, ik <= span[1]

    def q_fetch(self, span, j):
        return jnp.minimum(span[0] + j, span[1])

    def mask(self, iq, ikv):
        starts = self._starts(iq, ikv)

        def mask_fn(s):
            rows, cols = _positions(s, *starts)
            back = rows - cols
            return _keep(s, (back >= 0) & (back < self.window))

        return mask_fn


@dataclasses.dataclass(frozen=True)
class _Selected(_Causal):
    """models/llama.py:Attention's selected kind (a learned sparse
    attention in its masked form): causal, and of the causal entries a row
    keeps those its indexer SELECTED, which is data. Two operands lead the
    call beside no scalar: ``runs``, int32 [B * nq * nk] in SMEM, 1 where a
    tile pair holds any selected entry (``ops/sparse_index.py:tile_runs``),
    which gates the step under ``pl.when``, forward and backward alike; and
    ``words``, the selection packed as int32 [B, S, width] (bit g of word c
    of a row: column g * width + c), of which a q tile's rows [block_q,
    width] are ONE block for its whole kv sweep, so it is fetched once a
    head and q tile and a kv tile's columns are those words shifted by the
    tile's own g: an elementwise unpack. The causal edge stays this
    family's own position test (and its sweep and fetch the causal
    family's: a tile the table skips is still fetched, its index is not a
    scalar's to move). A row may keep nothing of a tile that runs, or of
    every tile before its last: what it accumulates there is scaled to
    exactly 0 by its first finite max, as under the band. ``lse`` is an
    output (the indexer's loss reads it) and carries no cotangent."""
    width: int = 0

    scalars = 2
    lse_out = True

    def lead_specs(self):
        return [
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(
                (1, self.block_q, self.width), lambda b, h, iq, j: (b, iq, 0)
            ),
        ]

    def at(self, refs):
        return list(refs)

    def q_sweep(self, span, j, runs, words):
        ikv, causal = super().q_sweep(span, j)
        pair = (pl.program_id(0) * self.nq + span[0]) * self.nk + ikv
        return ikv, causal & (runs[pair] != 0)

    def mask(self, iq, ikv, runs, words):
        starts = self._starts(iq, ikv)
        width, bk = self.width, self.block_k

        def mask_fn(s):
            bits = tile_bits(words, ikv, bk, width)
            rows, cols = _positions(s, *starts)
            return _keep(s, (bits != 0) & (rows >= cols))

        return mask_fn


# ---------------------------------------------------------------------------
# The frame: two kernel bodies, two drivers ([B,H,S,D] layout) and the
# custom_vjp.
#
# Forward and backward: grid = (B, Hq, q tiles, family.q_steps). The forward's
# output block and the backward's dq stay with the q tile: scratch zeroed at
# the sweep's first step, flushed at its last. The backward's dk parts and dv
# are RESIDENT for a kv head: float32 scratch of [kv_len, D] a part, whose
# bracket is every grid step of the q heads that read the head (consecutive
# in Hq, so GQA's sum over the group happens in VMEM; a key part that all
# heads share is the same with a bracket of a whole batch row), and an output
# block of the whole [kv_len, D] whose index is constant over the bracket, so
# it is written once. A kernel's refs are its operands in the order the
# drivers pass them: the family's SMEM scalars, q's parts, k's parts, v,
# (backward: do, lse, delta and, where lse has a cotangent, dlse), the
# outputs, the scratch.
# ---------------------------------------------------------------------------


def _cut(refs, *counts):
    """``refs`` as tuples of ``counts`` refs each, then what is left."""
    cuts, at = [], 0
    for n in counts:
        cuts.append(tuple(refs[at:at + n]))
        at += n
    return (*cuts, tuple(refs[at:]))


def _zero(*accs):
    for acc in accs:
        acc[:] = jnp.zeros_like(acc)


def _flush(outs, accs):
    for out, acc in zip(outs, accs):
        out[0, 0] = acc[:].astype(out.dtype)


def _fwd_kernel(*refs, family, parts, scale):
    # q [1,1,block_q,D], k/v [1,1,block_k,D] a part; o [1,1,block_q,Dv];
    # lse [1,1,8,block_q] f32 (logsumexp residual); VMEM acc [block_q,Dv],
    # m and l [block_q,128] f32 (row max lane-broadcast, partial row sums).
    scalars, q, k, (v, o, lse, acc, m, l) = _cut(refs, family.scalars, parts, parts)
    iq, j = pl.program_id(2), pl.program_id(3)
    at = family.at(scalars)

    @pl.when(j == 0)
    def _init():
        _zero(acc)
        m[:] = jnp.full_like(m, _NEG_INF)
        _zero(l)

    ikv, run = family.q_sweep(family.q_span(iq), j, *at)

    @pl.when(run)
    def _step():
        _fwd_step(q, k, v, acc, m, l, scale, family.mask(iq, ikv, *at))

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        _fwd_finish(o, lse, acc, m, l)


def _backward_refs(refs, family, parts):
    """A backward kernel's refs: (scalars' values, q, k, (v, do, lse,
    delta, dlse or None), the rest: outputs then scratch)."""
    scalars, q, k, residuals, rest = _cut(
        refs, family.scalars, parts, parts, 4 + family.lse_cotangent
    )
    return family.at(scalars), q, k, (*residuals, None)[:5], rest


def _bracket(family, group, h, iq, j):
    """(opens, closes): whether grid step (h, iq, j) of a batch row is the
    first, the last of the bracket of a kv head that ``group`` consecutive
    q heads add to (all of them: a part every head shares)."""
    opens = (h % group == 0) & (iq == 0) & (j == 0)
    closes = (
        (h % group == group - 1) & (iq == family.nq - 1) & (j == family.q_steps - 1)
    )
    return opens, closes


def _bwd_kernel(*refs, family, parts, groups, scale):
    # dq [1,1,block_q,D] a part, dk's parts and dv [1,1,kv_len,D]; VMEM
    # accumulators of the same shapes in f32. ``groups``: for each of dk's
    # parts and dv, the consecutive q heads that add to one of its heads.
    h, iq, j = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    at, q, k, residuals, rest = _backward_refs(refs, family, parts)
    dq, dkv, dq_acc, dkv_acc = _cut(rest, parts, parts + 1, parts)
    bk = family.block_k
    tile = lambda i: pl.ds(pl.multiple_of(i * bk, bk), bk)  # noqa: E731
    # The residents are zeroed and flushed a tile at a time (the whole
    # [kv_len, D] as one value would be unrolled, thousands of vregs).
    brackets = [
        (_bracket(family, group, h, iq, j),
         [pair for pair, g in zip(zip(dkv, dkv_acc), groups) if g == group])
        for group in sorted(set(groups))
    ]

    @pl.when(j == 0)
    def _init():
        _zero(*dq_acc)

    for (opens, _), held in brackets:
        @pl.when(opens)
        def _open(held=held):
            @pl.loop(0, family.nk)
            def _(i):
                for _, acc in held:
                    acc[tile(i), :] = jnp.zeros((bk, acc.shape[1]), acc.dtype)

    ikv, run = family.q_sweep(family.q_span(iq), j, *at)

    @pl.when(run)
    def _step():
        _bwd_step(
            q, k, residuals, dq_acc, dkv_acc[:parts], dkv_acc[parts], tile(ikv),
            scale, family.mask(iq, ikv, *at),
        )

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        _flush(dq, dq_acc)

    for (_, closes), held in brackets:
        @pl.when(closes)
        def _close(held=held):
            @pl.loop(0, family.nk)
            def _(i):
                for out, acc in held:
                    out[0, 0, tile(i), :] = acc[tile(i), :].astype(out.dtype)


def _smem_spec():
    return pl.BlockSpec(
        (1, 1), lambda *_: (0, 0), memory_space=pltpu.SMEM
    )


def _blocks(rows: int, idx, xs) -> list:
    return [pl.BlockSpec((1, 1, rows, x.shape[-1]), idx) for x in xs]


def _shared(k) -> tuple:
    """Which of a key's parts all heads share: one of ONE head beside a
    first part of more (the latent family's rotary key, [B,1,S,Dr]; it
    stays one head in HBM, every head's index map names that head, and its
    gradient is accumulated over the heads inside the backward kernel)."""
    return tuple(part.shape[1] != k[0].shape[1] for part in k)


def _q_sweep_specs(family, q, k, v):
    """Block specs of the calls, whose grid is (B, Hq, q tiles, sweep
    steps): q's parts, k's parts, v, a q tile's own block
    of values' width (o, do), its rows' residuals (lse, delta, dlse). GQA
    folds into the index maps: q head h reads kv head h // q_per_kv."""
    bq, bk = family.block_q, family.block_k
    q_per_kv = q[0].shape[1] // k[0].shape[1]

    def q_idx(b, h, iq, j):
        return (b, h, iq, 0)

    def kv_idx(b, h, iq, j):
        span = family.q_span(iq)
        return (b, h // q_per_kv, family.q_fetch(span, j), 0)

    def shared_idx(b, h, iq, j):
        return (b, 0, family.q_fetch(family.q_span(iq), j), 0)

    return (
        _blocks(bq, q_idx, q),
        [
            pl.BlockSpec((1, 1, bk, x.shape[-1]), shared_idx if one else kv_idx)
            for x, one in zip((*k, v), (*_shared(k), False))
        ],
        *_blocks(bq, q_idx, [v]),
        pl.BlockSpec((1, 1, 8, bq), lambda b, h, iq, j: (b, h, 0, iq)),
    )


def _scale(q) -> float:
    return 1.0 / math.sqrt(sum(part.shape[-1] for part in q))


def _forward_impl(family, scalars, q, k, v, interpret):
    """(out [B,Hq,Sq,Dv] in q's dtype, lse [B,Hq,8,Sq] f32) of one family
    value: ``scalars`` its SMEM scalars ([1,1] i32 each), ``q`` and ``k``
    tuples of parts [B,H,S,D], ``v`` one array."""
    B, Hq = q[0].shape[:2]
    bq, dv = family.block_q, v.shape[-1]
    q_specs, kv_specs, o_spec, row_spec = _q_sweep_specs(family, q, k, v)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, family=family, parts=len(q), scale=_scale(q)),
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, family.q_len, dv), q[0].dtype),
            jax.ShapeDtypeStruct((B, Hq, 8, family.q_len), jnp.float32),
        ],
        grid=(B, Hq, family.nq, family.q_steps),
        in_specs=[*family.lead_specs(), *q_specs, *kv_specs],
        # Constant in the sweep's step: blocks stay resident in VMEM across
        # the kv sweep and are flushed once.
        out_specs=[o_spec, row_spec],
        scratch_shapes=[
            pltpu.VMEM((bq, dv), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
        ],
        interpret=interpret,
    )(*scalars, *q, *k, v)


def _backward_impl(family, scalars, q, k, v, do, lse, delta, dlse, interpret):
    """(dq's parts, dk's parts, dv) given the forward's residuals, in one
    call; ``dlse`` is None unless the family's lse carries a cotangent."""
    B, Hq = q[0].shape[:2]
    bq = family.block_q
    rows = (lse, delta) if dlse is None else (lse, delta, dlse)
    q_specs, kv_specs, o_spec, row_spec = _q_sweep_specs(family, q, k, v)
    groups = tuple(Hq // x.shape[1] for x in (*k, v))
    held = [(family.kv_len, x.shape[-1]) for x in (*k, v)]
    return _cut(pl.pallas_call(
        functools.partial(
            _bwd_kernel, family=family, parts=len(q), groups=groups, scale=_scale(q)
        ),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (*q, *k, v)],
        grid=(B, Hq, family.nq, family.q_steps),
        in_specs=[
            *family.lead_specs(), *q_specs, *kv_specs, o_spec,
            *[row_spec] * len(rows),
        ],
        # dk's parts and dv: the whole of a head, constant over its bracket.
        out_specs=[*q_specs, *[
            pl.BlockSpec((1, 1, *shape), lambda b, h, iq, j, g=g: (b, h // g, 0, 0))
            for shape, g in zip(held, groups)
        ]],
        scratch_shapes=[
            *[pltpu.VMEM((bq, x.shape[-1]), jnp.float32) for x in q],
            *[pltpu.VMEM(shape, jnp.float32) for shape in held],
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_backward_vmem_bytes(
            family.kv_len, [d for _, d in held], v.dtype.itemsize
        )),
        interpret=interpret,
    )(*scalars, *q, *k, v, do, *rows), len(q), len(k))


def _row_delta(do, out):
    """Delta_i = rowsum(dO_i * O_i) [B, Hq, S] (a tiny elementwise + reduce
    that XLA fuses), sublane-broadcast to the lse residual's layout
    [B, Hq, 8, S]."""
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    return jnp.broadcast_to(
        delta[:, :, None, :], (*delta.shape[:2], 8, delta.shape[-1])
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 5))
def _flash(family, scalars, q, k, v, interpret):
    """The attention of one family value, differentiable in q's parts, k's
    parts and v: out, or (out, lse) where the family's lse is an output."""
    return _flash_fwd(family, scalars, q, k, v, interpret)[0]


def _flash_fwd(family, scalars, q, k, v, interpret):
    out, lse = _forward_impl(family, scalars, q, k, v, interpret)
    primal = (out, lse) if family.lse_out else out
    return primal, (scalars, q, k, v, out, lse)


def _flash_bwd(family, interpret, res, ct):
    scalars, q, k, v, out, lse = res
    # Where lse is an output BOTH carry cotangents (the ring merge uses
    # lse). dlse is already in the raw [B,Hq,8,S] kernel layout (the
    # sublane slice happens in the public wrapper, outside this vjp); the
    # kernels read sublane 0, which is exactly where the slice cotangent
    # lands.
    do, dlse = ct if family.lse_out else (ct, None)
    if not family.lse_cotangent:
        dlse = None  # an output that is read as a constant
    dq, dk, (dv,) = _backward_impl(
        family, scalars, q, k, v, do, lse, _row_delta(do, out),
        None if dlse is None else dlse.astype(jnp.float32), interpret,
    )
    return tuple(None for _ in scalars), dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def _heads_first(*xs):
    """[B,S,H,D] -> [B,H,S,D]: S x D blocks are MXU-shaped."""
    return [jnp.swapaxes(x, 1, 2) for x in xs]


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
    itp = _interpret() if interpret is None else interpret
    qt, kt, vt = _heads_first(q, k, v)
    out = _flash(_Causal(S, S, *tiles, causal), (), (qt,), (kt,), vt, itp)
    return jnp.swapaxes(out, 1, 2)


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
    qoff = jnp.asarray(q_offset, jnp.int32).reshape(1, 1)
    koff = jnp.asarray(k_offset, jnp.int32).reshape(1, 1)
    itp = _interpret() if interpret is None else interpret
    qt, kt, vt = _heads_first(q, k, v)
    out, lse = _flash(
        _Offset(Sq, Skv, *tiles), (qoff, koff), (qt,), (kt,), vt, itp
    )
    # lse is sublane-broadcast [B,Hq,8,Sq]; take one sublane.
    return jnp.swapaxes(out, 1, 2), lse[:, :, 0, :]


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
    qt, kt, vt = _heads_first(q, k, v)
    family = _BlockDiffusion(S, S, tile, tile, block_length)
    return jnp.swapaxes(_flash(family, (), (qt,), (kt,), vt, itp), 1, 2)


# ---------------------------------------------------------------------------
# Latent attention (models/mla.py, DeepSeek-V3 arXiv:2412.19437 section
# 2.1.1 in its training form): a head's query and key are a rope-free part
# of ``Dn`` channels and a rotary part of ``Dr``, the score
# (q_nope . k_nope + q_rope . k_rope) / sqrt(Dn + Dr), the values ``Dv``
# wide, and ONE rotary key a position serves every head (``_shared``).
# Causal over one sequence, static: the causal family over tensors of two
# parts.
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
    Returns [B,S,H,Dv] in q_nope's dtype. k_rope's gradient is the sum over
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
    itp = _interpret() if interpret is None else interpret
    qn, qr, kn = _heads_first(q_nope, q_rope, k_nope)
    k = (kn, k_rope[:, None])
    out = _flash(_Causal(S, S, *tiles), (), (qn, qr), k, *_heads_first(v), itp)
    return jnp.swapaxes(out, 1, 2)


def supports_window(
    seq_len: int, window: int, block_q: int = _MAX_TILE, block_k: int = _MAX_TILE
) -> bool:
    """Whether the banded kernels handle this sequence under a window of
    ``window`` positions and these largest tiles (by shape alone; the
    caller falls back to dense attention under the band mask otherwise)."""
    return window >= 1 and choose_tiles(
        "window", seq_len, (), block_q, block_k, window=window
    ) is not None


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
    tiles = choose_tiles("window", seq_len, (), block_q, block_k, window=window)
    if tiles is None:
        raise ValueError(
            f"window_tiles: {seq_len} positions do not tile under ({block_q},{block_k})"
        )
    sweeps = _band_sweeps(seq_len, min(window, seq_len), *tiles)
    return window_kept(seq_len, window), sum(sweeps) * tiles[0] * tiles[1]


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
    causal family. ``block_q``, ``block_k``: the largest tiles to take
    (``choose_tiles``). The kernels' trace names start
    ``flash_attention_window`` (a kernel is named for the jit around it)."""
    B, S, Hq, D = q.shape
    assert Hq % k.shape[2] == 0, (Hq, k.shape[2])
    if window < 1:
        raise ValueError(f"flash_attention_window: window {window} keeps nothing")
    tiles = choose_tiles("window", S, (D,), block_q, block_k, window=window)
    if tiles is None:
        raise ValueError(
            f"flash_attention_window: seq_len {S} not divisible by blocks "
            f"({block_q},{block_k}); use dense_attention under the band mask"
        )
    itp = _interpret() if interpret is None else interpret
    qt, kt, vt = _heads_first(q, k, v)
    family = _Causal(S, S, *tiles) if window >= S else _Window(S, S, *tiles, window)
    return jnp.swapaxes(_flash(family, (), (qt,), (kt,), vt, itp), 1, 2)


def supports_selected(
    seq_len: int, block_q: int = _MAX_TILE, block_k: int = _MAX_TILE
) -> bool:
    """Whether the selected kernels handle this sequence under these
    largest tiles (``choose_tiles`` has the rules; the caller runs dense
    attention under the unpacked selection otherwise)."""
    return choose_tiles("selected", seq_len, (), block_q, block_k) is not None


@functools.partial(jax.jit, static_argnames=("block_q", "block_k", "interpret"))
def flash_attention_selected(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    words: jax.Array,
    runs: jax.Array,
    block_q: int = _MAX_TILE,
    block_k: int = _MAX_TILE,
    interpret: Optional[bool] = None,
) -> tuple:
    """Causal GQA flash attention over a SELECTION of each row's earlier
    keys, differentiable in q, k and v. q: [B,S,Hq,D]; k/v: [B,S,Hkv,D];
    ``words`` int32 [B,S,W], the selection packed as
    ``ops/sparse_index.py`` packs it (W its ``mask_width(S)``), and
    ``runs`` int32 [B,nq,nk], 1 where a tile pair of the tiles this call
    chooses (``choose_tiles("selected", ...)``) holds any selected entry.
    Returns ``(out [B,S,Hq,D], lse [B,Hq,S] fp32)``; lse is a constant to
    differentiation. The kernels' trace names start
    ``flash_attention_selected`` (a kernel is named for the jit around it)."""
    B, S, Hq, D = q.shape
    assert Hq % k.shape[2] == 0, (Hq, k.shape[2])
    tiles = choose_tiles("selected", S, (D,), block_q, block_k)
    if tiles is None or words.shape[-1] != mask_width(S):
        raise ValueError(
            f"flash_attention_selected: seq_len {S} under blocks ({block_q},"
            f"{block_k}) with words of {words.shape[-1]}: use dense attention "
            "under the unpacked selection"
        )
    assert runs.shape == (B, S // tiles[0], S // tiles[1]), (runs.shape, tiles)
    itp = _interpret() if interpret is None else interpret
    qt, kt, vt = _heads_first(q, k, v)
    family = _Selected(S, S, *tiles, width=words.shape[-1])
    lead = (runs.reshape(-1).astype(jnp.int32), words)
    out, lse = _flash(family, lead, (qt,), (kt,), vt, itp)
    return jnp.swapaxes(out, 1, 2), jax.lax.stop_gradient(lse[:, :, 0, :])
