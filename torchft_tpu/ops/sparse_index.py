"""A learned sparse attention's indexer (DeepSeek Sparse Attention's
lightning indexer, DeepSeek-V3.2, in its masked training form): the score
pass, the exact selection of each query's ``topk`` best earlier keys, and
the loss the indexer learns from.

For a sequence of S positions, ``J`` index heads of width ``D`` and one
index key a position:

- ``index_scores``: I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s]),
  operands in their own dtype, the products and the sum over the heads in
  float32. Entries above the diagonal are not specified: every reader
  tests positions itself.
- ``select``: S_t = the ``topk`` keys s <= t of largest I[t, s] (all of
  them where t < topk; equal scores go to the lower index, as
  ``lax.top_k`` orders them), found exactly with no sort: a bisection
  over the ordered bits of the float finds a row's k-th largest score (32
  counts of a row), a second over the columns finds where the ties at
  that score stop (log2 S counts, only where a chunk holds such a tie).
  It returns the selection PACKED (``words``), the table of tile pairs
  that hold any selected entry (``runs``) and each row's log-sum-exp of I
  over S_t (``lse_index``).
- ``index_kl``: L_I = mean_t KL(p[t] || softmax_{S_t}(I[t])), p the main
  attention's probabilities summed over its heads and L1-normalised over
  S_t, a constant. Differentiable in the indexer's three operands only,
  by a ``custom_vjp`` that forms G = (softmax(I) - p) / rows on S_t a
  chunk of queries at a time and hands it to the score pass's own
  transpose: no [S, S] tensor is a residual.

The packed selection. ``words`` is int32 [B, S, W], W = S / n for n <= 32
bits a word (``mask_width``): bit g of word c of row t says whether t
keeps column g * W + c. So the columns of one kv tile are the SAME words
shifted by the tile's own g, an elementwise unpack with no lane shuffle,
and a q tile's rows of words are one block [block_q, W] that stays in
VMEM for its whole kv sweep (2 MiB at S = 16,384 and tiles of 1,024,
fetched once a head and q tile; the selection is 32 MiB a layer and
sequence where a byte an entry would be 256).

Two forms. Each pass is written in ``jax.numpy`` over chunks of ``CHUNK``
query rows (the published ``q_chunk_size``), so that the largest temporary
is a chunk's [J, CHUNK, S] float32 and never a head's [S, S]: that is what
runs off the TPU and what the CPU tests hold the kernels to. On the TPU,
for sequences of whole tiles of ``CHUNK``, three Pallas kernels take the
passes the device trace showed above a tenth of the step (PERF.md section
6, PR 69; the jit around each names it in the trace): ``dsa_index_scores``
(a tile pair's 16 small matmuls, ReLU and weighted sum in VMEM, I written
once), ``dsa_index_kl`` (a grid step is a causal tile pair and a kv head's
GROUP of query heads: the grid walks the n(n+1)/2 pairs on or under the
diagonal from two prefetched tables, the kv heads innermost, so the key
tile is fetched once a group, the group's probabilities are summed before
they touch the accumulator, and a pair's head-summed probabilities stay in
VMEM; at the last kv head the tile's I is recomputed, and the tile's share
of L_I or of G comes out) and ``dsa_index_scores_bwd`` (G's transpose
through the score pass: dqI a q tile, dkI resident for the sequence, dw).
The selection stays ``jax.numpy``: its bisections are 32 fused counts of a
chunk.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "CHUNK",
    "index_kl",
    "index_scores",
    "mask_width",
    "pack",
    "select",
    "tile_bits",
    "tile_runs",
    "unpack",
]

# Query rows a pass works on at a time: the published ``q_chunk_size``.
CHUNK = 512
_INT_MIN = -(2**31)
_LANES = 128


def _chunk(seq_len: int) -> int:
    return CHUNK if seq_len % CHUNK == 0 else seq_len


def mask_width(seq_len: int) -> int:
    """W, the words a row of the packed selection has: S / n for the
    largest n <= 32 that leaves whole lane tiles (W % 128 == 0: what the
    chip's compiler takes), else the largest n <= 32 that divides S (the
    CPU tests' short sequences). 512 at S = 16,384, 128 at 1,024."""
    fits = [n for n in range(32, 0, -1) if seq_len % n == 0]
    lanes = [n for n in fits if (seq_len // n) % _LANES == 0]
    return seq_len // (lanes or fits)[0]


def pack(keep: jax.Array) -> jax.Array:
    """Boolean [..., S] -> int32 words [..., W] (the module's layout)."""
    seq_len = keep.shape[-1]
    width = mask_width(seq_len)
    bits = keep.reshape(*keep.shape[:-1], seq_len // width, width).astype(jnp.int32)
    shifts = jnp.arange(seq_len // width, dtype=jnp.int32)[:, None]
    # Distinct bits: the sum carries nothing, bit 31 wraps to the sign.
    return jnp.sum(bits << shifts, axis=-2, dtype=jnp.int32)


def unpack(words: jax.Array, seq_len: int) -> jax.Array:
    """int32 words [..., W] -> boolean [..., S]."""
    shifts = jnp.arange(seq_len // words.shape[-1], dtype=jnp.int32)[:, None]
    bits = (words[..., None, :] >> shifts) & 1
    return bits.reshape(*words.shape[:-1], seq_len) != 0


def _by_chunk(x: jax.Array, chunk: int) -> jax.Array:
    """[B, S, ...] -> [S / chunk, B, chunk, ...]: what a scan walks."""
    b, s = x.shape[:2]
    return jnp.moveaxis(x.reshape(b, s // chunk, chunk, *x.shape[2:]), 1, 0)


def _whole(x: jax.Array) -> jax.Array:
    """``_by_chunk``'s inverse: [n, B, chunk, ...] -> [B, n * chunk, ...]."""
    n, b, c = x.shape[:3]
    return jnp.moveaxis(x, 0, 1).reshape(b, n * c, *x.shape[3:])


def _chunk_scores(q_index, k_index, weights):
    """A chunk's I: q_index [B, C, J, D], k_index [B, S, D], weights
    [B, C, J] float32 -> [B, C, S] float32."""
    s = jnp.einsum(
        "bcjd,bsd->bcjs", q_index, k_index, preferred_element_type=jnp.float32
    )
    # On the VPU in float32: an einsum of float32 operands would go through
    # the MXU at the backend's default precision.
    return jnp.sum(jax.nn.relu(s) * weights[..., None], axis=2)


def index_scores(q_index: jax.Array, k_index: jax.Array, weights: jax.Array) -> jax.Array:
    """I [B, S, S] float32 of q_index [B, S, J, D], k_index [B, S, D] and
    weights [B, S, J] (float32)."""
    c = _chunk(q_index.shape[1])
    with jax.named_scope("dsa/index_scores"):
        if _kernels(q_index.shape[1]):
            return dsa_index_scores(q_index, k_index, weights)
        rows = jax.lax.map(
            lambda xs: _chunk_scores(xs[0], k_index, xs[1]),
            (_by_chunk(q_index, c), _by_chunk(weights, c)),
        )
        return _whole(rows)


def _ordered(scores: jax.Array) -> jax.Array:
    """float32 -> int32 that compares as the floats do."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def _select_rows(scores: jax.Array, pos: jax.Array, topk: int):
    """(keep [R, S] bool, lse [R] float32) of rows ``scores`` [R, S] at
    positions ``pos`` [R]: a row keeps min(topk, pos + 1) of its columns
    <= pos."""
    rows, seq_len = scores.shape
    cols = jnp.arange(seq_len, dtype=jnp.int32)[None, :]
    causal = cols <= pos[:, None]
    key = jnp.where(causal, _ordered(scores), _INT_MIN)
    want = jnp.minimum(topk, pos + 1)

    def count(hit):
        return jnp.sum(hit, axis=1, dtype=jnp.int32)

    def value_bit(i, found):
        # ``found`` holds the threshold's bits in offset binary (the sign
        # bit flipped), so that setting bits high to low only ever raises it.
        bit = jnp.int32(1) << (31 - i)
        cand = (found | bit) ^ _INT_MIN
        enough = count(key >= cand[:, None]) >= want
        return jnp.where(enough, found | bit, found)

    found = jax.lax.fori_loop(0, 32, value_bit, jnp.zeros((rows,), jnp.int32))
    kth = (found ^ _INT_MIN)[:, None]  # each row's want-th largest key
    above, tied = key > kth, key == kth
    need = want - count(above)  # >= 1 of the tied columns, the lowest first

    def first_tied(_):
        def column_bit(i, last):
            bit = jnp.int32(1) << (steps - 1 - i)
            cand = last | bit
            short = count(tied & (cols < cand[:, None])) < need
            return jnp.where(short, cand, last)

        steps = max(1, math.ceil(math.log2(seq_len)))
        # The largest column before which fewer than ``need`` ties lie: the
        # column of the need-th tie.
        return jax.lax.fori_loop(0, steps, column_bit, jnp.zeros((rows,), jnp.int32))

    last = jax.lax.cond(
        jnp.any(count(tied) != need), first_tied,
        lambda _: jnp.full((rows,), seq_len, jnp.int32), None,
    )
    keep = above | (tied & (cols <= last[:, None]))
    top = jnp.max(jnp.where(causal, scores, -jnp.inf), axis=1)
    total = jnp.sum(jnp.where(keep, jnp.exp(scores - top[:, None]), 0.0), axis=1)
    return keep, top + jnp.log(total)


def tile_runs(words: jax.Array, seq_len: int, block_q: int, block_k: int) -> jax.Array:
    """int32 [B, S / block_q, S / block_k]: 1 where a tile pair holds any
    selected entry (the causal edge is the reader's own test)."""
    b = words.shape[0]
    any_row = jax.lax.reduce(
        words.reshape(b, seq_len // block_q, block_q, -1), jnp.int32(0),
        jax.lax.bitwise_or, (2,),
    )
    cols = unpack(any_row, seq_len)
    return jnp.any(
        cols.reshape(b, seq_len // block_q, seq_len // block_k, block_k), axis=-1
    ).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("topk", "block_q", "block_k"))
def select(scores: jax.Array, topk: int, block_q: int, block_k: int):
    """(words int32 [B, S, W], runs int32 [B, nq, nk], lse_index [B, S]
    float32) of I ``scores`` [B, S, S] (the module's docstring)."""
    b, seq_len, _ = scores.shape
    c = _chunk(seq_len)

    def chunk(xs):
        rows, first = xs  # [B, C, S], the chunk's first position
        pos = jnp.tile(first + jnp.arange(c, dtype=jnp.int32), b)
        keep, lse = _select_rows(rows.reshape(b * c, seq_len), pos, topk)
        return pack(keep).reshape(b, c, -1), lse.reshape(b, c)

    with jax.named_scope("dsa/select"):
        starts = jnp.arange(0, seq_len, c, dtype=jnp.int32)
        words, lse = jax.lax.map(chunk, (_by_chunk(scores, c), starts))
        words, lse = _whole(words), _whole(lse)
        return words, tile_runs(words, seq_len, block_q, block_k), lse


def _chunk_probs(q, k, lse):
    """The main attention's probabilities summed over its heads for a
    chunk of queries: q [B, C, Hq, D], k [B, S, Hkv, D], lse [B, Hq, C]
    -> [B, C, S] float32, a kv head's group of query heads at a time so
    that the temporary is the group's [G, C, S]."""
    b, c, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    scale = d ** -0.5

    def group(total, xs):
        qg, kg, lg = xs  # [B, C, G, D], [B, S, D], [B, G, C]
        s = jnp.einsum("bcgd,bsd->bgcs", qg, kg, preferred_element_type=jnp.float32)
        return total + jnp.sum(jnp.exp(s * scale - lg[..., None]), axis=1), None

    groups = (
        jnp.moveaxis(q.reshape(b, c, hkv, g, d), 2, 0),
        jnp.moveaxis(k, 2, 0),
        jnp.moveaxis(lse.reshape(b, hkv, g, c), 1, 0),
    )
    zero = jnp.zeros((b, c, k.shape[1]), jnp.float32)
    return jax.lax.scan(group, zero, groups)[0]


def _kl_chunks(q_index, k_index, weights, q, k, lse, words, lse_index, each):
    """Walks the chunks of queries: ``each(I, vjp of I in the chunk's
    operands, p, softmax(I), the rows' lse of I)`` a chunk, its results
    stacked; p and softmax(I) are 0 off the selection."""
    seq_len, heads = q.shape[1], q.shape[2]
    c = _chunk(seq_len)

    def chunk(xs):
        qi, w, qc, lc, wc, li = xs
        scores, vjp = jax.vjp(_chunk_scores, qi, k_index, w)
        kept = unpack(wc, seq_len)
        # Over S_t each head's probabilities sum to 1, so the heads' to Hq.
        p = jnp.where(kept, _chunk_probs(qc, k, lc) / heads, 0.0)
        soft = jnp.where(kept, jnp.exp(scores - li[..., None]), 0.0)
        return each(scores, vjp, p, soft, li)

    return jax.lax.map(chunk, (
        _by_chunk(q_index, c), _by_chunk(weights, c), _by_chunk(q, c),
        jnp.moveaxis(lse.reshape(*lse.shape[:2], seq_len // c, c), 2, 0),
        _by_chunk(words, c), _by_chunk(lse_index, c),
    ))


@jax.custom_vjp
def index_kl(q_index, k_index, weights, q, k, lse, words, lse_index):
    """L_I, a scalar: the mean over the B * S rows of KL(p || softmax_{S_t}
    (I)). q_index [B, S, J, D], k_index [B, S, D], weights [B, S, J]
    float32 (the indexer's, differentiable); q [B, S, Hq, Dh], k
    [B, S, Hkv, Dh] and lse [B, Hq, S] the main attention's (constants);
    ``words`` and ``lse_index`` as ``select`` gave them."""
    def each(scores, vjp, p, soft, li):
        log_q = scores - li[..., None]
        return jnp.sum(
            jnp.where(p > 0, p * (jnp.log(jnp.where(p > 0, p, 1.0)) - log_q), 0.0)
        )

    with jax.named_scope("dsa/index_kl"):
        if _kernels(q.shape[1]):
            sums = dsa_index_kl(q_index, k_index, weights, q, k, lse, words, lse_index)
            return jnp.sum(sums) / (q.shape[0] * q.shape[1])
        sums = _kl_chunks(q_index, k_index, weights, q, k, lse, words, lse_index, each)
        return jnp.sum(sums) / (q.shape[0] * q.shape[1])


def _index_kl_fwd(q_index, k_index, weights, q, k, lse, words, lse_index):
    out = index_kl(q_index, k_index, weights, q, k, lse, words, lse_index)
    return out, (q_index, k_index, weights, q, k, lse, words, lse_index)


def _index_kl_bwd(res, g):
    q_index, k_index, weights, q, k, lse, words, lse_index = res
    rows = q.shape[0] * q.shape[1]

    def each(scores, vjp, p, soft, li):
        return vjp((soft - p) / rows)

    with jax.named_scope("dsa/index_kl_bwd"):
        if _kernels(q.shape[1]):
            dq, dk, dw = dsa_index_scores_bwd(
                dsa_index_kl(
                    q_index, k_index, weights, q, k, lse, words, lse_index, grad=True
                ),
                q_index, k_index, weights,
            )
            return (
                (dq * g).astype(q_index.dtype), (dk * g).astype(k_index.dtype), dw * g,
                None, None, None, None, None,
            )
        dq, dk, dw = _kl_chunks(
            q_index, k_index, weights, q, k, lse, words, lse_index, each
        )
        return (
            (_whole(dq).astype(jnp.float32) * g).astype(q_index.dtype),
            (jnp.sum(dk.astype(jnp.float32), axis=0) * g).astype(k_index.dtype),
            _whole(dw) * g,
            None, None, None, None, None,
        )


index_kl.defvjp(_index_kl_fwd, _index_kl_bwd)


# ---------------------------------------------------------------------------
# The Pallas kernels of the passes above. Tiles are CHUNK x CHUNK (the
# published q_chunk_size x kv_chunk_size). The score pass and its transpose
# step through every tile pair: one above the diagonal is skipped and its
# blocks' indices clamped to the diagonal pair's, so nothing is fetched or
# written for it. The probabilities' pass steps through the causal pairs only.
# ---------------------------------------------------------------------------

_VMEM_LIMIT = 64 * 2**20


def _kernels(seq_len: int) -> bool:
    """Whether the passes run as kernels: on the TPU, over whole tiles."""
    return jax.default_backend() == "tpu" and seq_len % CHUNK == 0


def tile_bits(words, ikv, block_k: int, width: int):
    """The packed selection's bits for kv tile ``ikv`` of ``block_k``
    columns, int32 [rows, block_k] of 0 / 1, from the q tile's block of
    words ``words`` (a ref [1, rows, width]): whole groups of columns, each
    the same words shifted by its own g, or a slice of one group's."""
    if block_k % width == 0:
        per = block_k // width
        rows = words[0]
        bits = [(rows >> (ikv * per + i)) & 1 for i in range(per)]
        return bits[0] if per == 1 else jnp.concatenate(bits, axis=1)
    per = width // block_k
    at = pl.ds(pl.multiple_of((ikv % per) * block_k, block_k), block_k)
    return (words[0, :, at] >> (ikv // per)) & 1


def _nt(a, b):
    """a [m, d] x b [n, d] -> [m, n] float32."""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )


def _scores_tile(q_ref, k_ref, w_ref):
    """A tile pair's I [tq, tk] float32: q_ref [1, J, tq, D], k_ref
    [1, tk, D], w_ref [1, tq, J]."""
    keys, w = k_ref[0], w_ref[0]
    total = None
    for j in range(q_ref.shape[1]):
        term = jnp.maximum(_nt(q_ref[0, j], keys), 0.0) * w[:, j:j + 1]
        total = term if total is None else total + term
    return total


def _scores_kernel(q_ref, k_ref, w_ref, o_ref):
    @pl.when(pl.program_id(2) <= pl.program_id(1))
    def _():
        o_ref[0] = _scores_tile(q_ref, k_ref, w_ref)


def _diag(iq, ik):
    return jnp.minimum(ik, iq)


def _index_specs(heads: int, width: int):
    """Block specs of the indexer's operands on a grid whose dimensions 1
    and 2 are the q tile and the kv tile: q_index [B, J, S, D], k_index
    [B, S, D], weights [B, S, J]."""
    return [
        pl.BlockSpec((1, heads, CHUNK, width), lambda b, iq, ik, *_: (b, 0, iq, 0)),
        pl.BlockSpec((1, CHUNK, width), lambda b, iq, ik, *_: (b, _diag(iq, ik), 0)),
        pl.BlockSpec((1, CHUNK, heads), lambda b, iq, ik, *_: (b, iq, 0)),
    ]


@functools.partial(jax.jit, static_argnames="interpret")
def dsa_index_scores(q_index, k_index, weights, interpret=False):
    """``index_scores`` as a kernel."""
    b, seq_len, heads, width = q_index.shape
    n = seq_len // CHUNK
    return pl.pallas_call(
        _scores_kernel,
        out_shape=jax.ShapeDtypeStruct((b, seq_len, seq_len), jnp.float32),
        grid=(b, n, n),
        in_specs=_index_specs(heads, width),
        out_specs=pl.BlockSpec((1, CHUNK, CHUNK), lambda b, iq, ik: (b, iq, _diag(iq, ik))),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(jnp.swapaxes(q_index, 1, 2), k_index, weights)


def _kl_kernel(
    iq_ref, ik_ref, words_ref, q_ref, k_ref, lse_ref, qi_ref, ki_ref, w_ref, lsei_ref,
    out_ref, acc_ref, *, heads, scale, width, rows,
):
    """One (causal tile pair, kv head) step: the pair is ``iq_ref``'s and
    ``ik_ref``'s entry (SMEM), q_ref [1, G, tq, d] the kv head's group of
    query heads. ``rows`` None: the tile's share of the rows' KL sums,
    folded into 128 lanes and added to ``out_ref`` [1, tq, 128]; else G's
    tile, (softmax(I) - p) / rows, to ``out_ref`` [1, tq, tk]."""
    pair, kv = pl.program_id(1), pl.program_id(2)
    iq, ik = iq_ref[pair], ik_ref[pair]

    @pl.when(kv == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    if rows is None:
        @pl.when((ik == 0) & (kv == 0))
        def _():
            out_ref[:] = jnp.zeros_like(out_ref)

    keys, group = k_ref[0, 0], None
    for g in range(q_ref.shape[1]):
        s = _nt(q_ref[0, g], keys) * scale
        probs = jnp.exp(s - lse_ref[0, g, 0][:, None])
        group = probs if group is None else group + probs
    acc_ref[:] = acc_ref[:] + group

    @pl.when(kv == pl.num_programs(2) - 1)
    def _():
        scores = _scores_tile(qi_ref, ki_ref, w_ref)
        at_row = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0) + iq * CHUNK
        at_col = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1) + ik * CHUNK
        kept = (tile_bits(words_ref, ik, CHUNK, width) != 0) & (at_row >= at_col)
        log_q = scores - lsei_ref[0, 0, 0][:, None]
        p = jnp.where(kept, acc_ref[:] * (1.0 / heads), 0.0)
        if rows is not None:
            soft = jnp.where(kept, jnp.exp(log_q), 0.0)
            out_ref[0] = ((soft - p) * (1.0 / rows)).astype(out_ref.dtype)
        else:
            some = p > 0
            term = jnp.where(some, p * (jnp.log(jnp.where(some, p, 1.0)) - log_q), 0.0)
            out_ref[0] = out_ref[0] + functools.reduce(
                jnp.add, [term[:, g:g + _LANES] for g in range(0, CHUNK, _LANES)]
            )


def _causal_pairs(n: int):
    """The n(n+1)/2 tile pairs on or under the diagonal, a q tile's pairs
    consecutive: (iq, ik), int32 each."""
    iq, ik = np.tril_indices(n)
    return jnp.asarray(iq, jnp.int32), jnp.asarray(ik, jnp.int32)


@functools.partial(jax.jit, static_argnames=("grad", "interpret"))
def dsa_index_kl(
    q_index, k_index, weights, q, k, lse, words, lse_index, grad=False, interpret=False
):
    """The probabilities' pass as a kernel: the rows' KL sums [B, S, 128]
    (their sum over the last axis), or under ``grad`` G [B, S, S] in the
    indexer's dtype. The grid is (B, causal tile pairs, kv heads): a q
    tile's pairs are consecutive, which the rows' sums rely on."""
    b, seq_len, hq, d = q.shape
    hkv = k.shape[2]
    heads, width = q_index.shape[2:]
    mask_w = words.shape[-1]
    group, pairs = hq // hkv, _causal_pairs(seq_len // CHUNK)

    def at(place):  # an index map of the grid from ``place(b, kv, iq, ik)``
        return lambda b, pair, kv, iq_ref, ik_ref: place(b, kv, iq_ref[pair], ik_ref[pair])

    rows8 = lambda x: jnp.broadcast_to(x[:, :, None, :], (*x.shape[:2], 8, seq_len))  # noqa: E731
    out_shape, out_spec = (
        (jax.ShapeDtypeStruct((b, seq_len, seq_len), q_index.dtype),
         pl.BlockSpec((1, CHUNK, CHUNK), at(lambda b, kv, iq, ik: (b, iq, ik))))
        if grad else
        (jax.ShapeDtypeStruct((b, seq_len, _LANES), jnp.float32),
         pl.BlockSpec((1, CHUNK, _LANES), at(lambda b, kv, iq, ik: (b, iq, 0))))
    )
    return pl.pallas_call(
        functools.partial(
            _kl_kernel, heads=hq, scale=d ** -0.5, width=mask_w,
            rows=b * seq_len if grad else None,
        ),
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, pairs[0].size, hkv),
            in_specs=[
                pl.BlockSpec((1, CHUNK, mask_w), at(lambda b, kv, iq, ik: (b, iq, 0))),
                pl.BlockSpec((1, group, CHUNK, d), at(lambda b, kv, iq, ik: (b, kv, iq, 0))),
                pl.BlockSpec((1, 1, CHUNK, d), at(lambda b, kv, iq, ik: (b, kv, ik, 0))),
                pl.BlockSpec((1, group, 8, CHUNK), at(lambda b, kv, iq, ik: (b, kv, 0, iq))),
                pl.BlockSpec((1, heads, CHUNK, width), at(lambda b, kv, iq, ik: (b, 0, iq, 0))),
                pl.BlockSpec((1, CHUNK, width), at(lambda b, kv, iq, ik: (b, ik, 0))),
                pl.BlockSpec((1, CHUNK, heads), at(lambda b, kv, iq, ik: (b, iq, 0))),
                pl.BlockSpec((1, 1, 8, CHUNK), at(lambda b, kv, iq, ik: (b, 0, 0, iq))),
            ],
            out_specs=out_spec,
            scratch_shapes=[pltpu.VMEM((CHUNK, CHUNK), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(
        *pairs, words, jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), rows8(lse),
        jnp.swapaxes(q_index, 1, 2), k_index, weights, rows8(lse_index[:, None, :]),
    )


def _scores_bwd_kernel(g_ref, q_ref, k_ref, w_ref, dq_ref, dk_ref, dw_ref):
    """G's tile through the score pass's transpose: dq_ref [1, J, tq, D]
    (with the q tile), dk_ref [1, S, D] (resident for the sequence) and
    dw_ref [1, tq, J], all float32 and added to."""
    iq, ik = pl.program_id(1), pl.program_id(2)
    n = pl.num_programs(1)

    @pl.when(ik == 0)
    def _():
        dq_ref[:] = jnp.zeros_like(dq_ref)
        dw_ref[:] = jnp.zeros_like(dw_ref)

    @pl.when((iq == 0) & (ik == 0))
    def _():
        @pl.loop(0, n)
        def _(i):
            at = pl.ds(pl.multiple_of(i * CHUNK, CHUNK), CHUNK)
            dk_ref[0, at, :] = jnp.zeros((CHUNK, dk_ref.shape[2]), dk_ref.dtype)

    @pl.when(ik <= iq)
    def _():
        g, keys, w = g_ref[0].astype(jnp.float32), k_ref[0], w_ref[0]
        at = pl.ds(pl.multiple_of(ik * CHUNK, CHUNK), CHUNK)
        lane = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)
        dw = jnp.zeros_like(w)
        for j in range(q_ref.shape[1]):
            qj = q_ref[0, j]
            s = _nt(qj, keys)
            live = s > 0
            hit = jnp.sum(jnp.where(live, s, 0.0) * g, axis=1, keepdims=True)
            dw = dw + jnp.where(lane == j, hit, 0.0)
            ds = jnp.where(live, g * w[:, j:j + 1], 0.0).astype(keys.dtype)
            dq_ref[0, j] = dq_ref[0, j] + jax.lax.dot_general(
                ds, keys, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )
            dk_ref[0, at, :] = dk_ref[0, at, :] + jax.lax.dot_general(
                ds, qj, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )
        dw_ref[0] = dw_ref[0] + dw


@functools.partial(jax.jit, static_argnames="interpret")
def dsa_index_scores_bwd(g, q_index, k_index, weights, interpret=False):
    """(dq_index [B, S, J, D], dk_index [B, S, D], dweights [B, S, J]),
    float32, of G [B, S, S] (read on and under the diagonal)."""
    b, seq_len, heads, width = q_index.shape
    n = seq_len // CHUNK
    dq, dk, dw = pl.pallas_call(
        _scores_bwd_kernel,
        out_shape=[
            jax.ShapeDtypeStruct((b, heads, seq_len, width), jnp.float32),
            jax.ShapeDtypeStruct((b, seq_len, width), jnp.float32),
            jax.ShapeDtypeStruct((b, seq_len, heads), jnp.float32),
        ],
        grid=(b, n, n),
        in_specs=[
            pl.BlockSpec((1, CHUNK, CHUNK), lambda b, iq, ik: (b, iq, _diag(iq, ik))),
            *_index_specs(heads, width),
        ],
        out_specs=[
            pl.BlockSpec((1, heads, CHUNK, width), lambda b, iq, ik: (b, 0, iq, 0)),
            pl.BlockSpec((1, seq_len, width), lambda b, iq, ik: (b, 0, 0)),
            pl.BlockSpec((1, CHUNK, heads), lambda b, iq, ik: (b, iq, 0)),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(g, jnp.swapaxes(q_index, 1, 2), k_index, weights)
    return jnp.swapaxes(dq, 1, 2), dk, dw
