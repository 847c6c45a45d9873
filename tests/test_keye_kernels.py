"""A learned sparse attention's passes (``torchft_tpu/ops/sparse_index.py``)
and its flash family (``flash_attention_selected``) one by one, on the CPU
in float32: the exact selection against ``lax.top_k`` row by row, ties and
all; the selected kernels through the Pallas interpreter against dense
attention under the same boolean selection (forward, lse, dq, dk, dv); the
three indexer kernels through the interpreter against the ``jax.numpy``
forms they replace on the TPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.models import llama
from torchft_tpu.ops import flash_attention as fa
from torchft_tpu.ops import sparse_index as dsa


@pytest.mark.parametrize("batch,seq,topk", [(2, 64, 12), (1, 128, 40), (1, 24, 24)])
def test_select_is_lax_top_k_row_by_row_ties_to_the_lower_index(batch, seq, topk):
    scores = jax.random.normal(jax.random.PRNGKey(seq), (batch, seq, seq))
    scores = jnp.round(scores * 2) / 2  # many ties at the topk-th place, -0.0 among them
    tile = 8
    words, runs, lse = dsa.select(scores, topk, tile, tile)
    assert words.shape == (batch, seq, dsa.mask_width(seq)) and words.dtype == jnp.int32
    kept = np.asarray(dsa.unpack(words, seq))
    assert (np.asarray(dsa.pack(jnp.asarray(kept))) == np.asarray(words)).all()
    # every row's own lax.top_k over its causal keys, in one call: a row with
    # fewer than topk of them takes them all
    causal = np.tril(np.ones((seq, seq), bool))
    vals, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), min(topk, seq))
    want = np.zeros_like(kept)
    np.put_along_axis(want, np.asarray(idx), np.asarray(vals) > -np.inf, axis=-1)
    assert (kept == want).all()
    assert (kept.sum(-1) == np.minimum(topk, np.arange(seq) + 1)).all()
    kth = np.asarray(vals)[..., -1:]
    tied_out = (np.asarray(scores) == kth) & causal & ~kept
    assert topk >= seq or tied_out.any(-1).sum() > seq // 4  # the tie rule was really asked
    assert np.allclose(
        lse, jax.nn.logsumexp(jnp.where(want, scores, -jnp.inf), axis=-1), rtol=1e-5, atol=1e-5)
    want_runs = kept.reshape(batch, seq // tile, tile, seq // tile, tile).any(axis=(2, 4))
    assert (np.asarray(runs) == want_runs).all()


@pytest.mark.parametrize("seq,tile,width", [(64, 16, 2), (256, 32, 128), (256, 128, 128)])
def test_the_selected_kernels_are_dense_attention_under_the_same_selection(seq, tile, width):
    """Forward, lse, dq, dk and dv through the interpreter, at a topk under
    the sequence so that rows really select, with ties in the scores; a kv
    tile that spans whole groups of the packed columns (width 2 under tiles
    of 16), one that a group spans (width 128 over tiles of 32) and one that
    is a group."""
    batch, hq, hkv, d, topk = 2, 4, 2, 16, seq // 4
    ks = jax.random.split(jax.random.PRNGKey(seq + tile), 4)
    q, k, v = (jax.random.normal(key, (batch, seq, h, d))
               for key, h in zip(ks, (hq, hkv, hkv)))
    scores = jnp.round(jax.random.normal(ks[3], (batch, seq, seq)) * 4) / 4
    assert dsa.mask_width(seq) == width
    assert fa.choose_tiles("selected", seq, (d,), tile, tile) == (tile, tile)
    words, runs, _ = dsa.select(scores, topk, tile, tile)
    kept = dsa.unpack(words, seq)
    assert int(kept.sum()) == batch * (topk * (topk + 1) // 2 + (seq - topk) * topk)
    weigh = jnp.cos(jnp.arange(d))

    def kernels(q, k, v):
        out, lse = fa.flash_attention_selected(q, k, v, words, runs, block_q=tile, block_k=tile)
        return (out * weigh).sum(), (out, lse)

    def dense(q, k, v):
        out, lse = llama.selected_dense_attention(q, k, v, kept)
        return (out * weigh).sum(), (out, lse)

    (_, (out, lse)), grads = jax.value_and_grad(kernels, (0, 1, 2), has_aux=True)(q, k, v)
    (_, (want, want_lse)), want_grads = jax.value_and_grad(dense, (0, 1, 2), has_aux=True)(q, k, v)
    assert jnp.allclose(out, want, atol=2e-5) and jnp.allclose(lse, want_lse, atol=2e-5)
    for got, ref in zip(grads, want_grads):
        assert jnp.allclose(got, ref, atol=5e-5)


@pytest.mark.parametrize("seq", [1024, 1536])
@pytest.mark.parametrize("hq,hkv", [(4, 2), (8, 1), (4, 4)])
def test_the_indexer_kernels_are_the_passes_they_replace(hq, hkv, seq):
    """``dsa_index_scores``, ``dsa_index_kl`` (the rows' KL sums and G) and
    ``dsa_index_scores_bwd`` through the interpreter at tiles of 512, against
    ``index_scores`` and ``index_kl`` with its gradients in their
    ``jax.numpy`` forms. The probabilities' pass takes a kv head's group of
    query heads a grid step over the causal tile pairs: two groups of two,
    one group of eight (the cell's group) and groups of one; three pairs,
    and six (a row of three pairs, which no power of two has)."""
    batch, d, heads, width, topk = 1, 16, 4, 8, 200
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    q, k, v = (jax.random.normal(key, (batch, seq, h, d)) for key, h in zip(ks, (hq, hkv, hkv)))
    q_index = jax.random.normal(ks[3], (batch, seq, heads, width))
    k_index = jax.random.normal(ks[4], (batch, seq, width))
    weights = jax.random.normal(ks[5], (batch, seq, heads))
    scores = dsa.index_scores(q_index, k_index, weights)
    got = dsa.dsa_index_scores(q_index, k_index, weights, interpret=True)
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    assert jnp.allclose(jnp.where(causal, got, 0.0), jnp.where(causal, scores, 0.0), atol=1e-4)
    words, _, lse_index = dsa.select(scores, topk, dsa.CHUNK, dsa.CHUNK)
    _, lse = llama.selected_dense_attention(q, k, v, dsa.unpack(words, seq))
    operands = (q, k, lse, words, lse_index)
    loss, grads = jax.value_and_grad(
        lambda *index: dsa.index_kl(*index, *operands), (0, 1, 2))(q_index, k_index, weights)
    sums = dsa.dsa_index_kl(q_index, k_index, weights, *operands, interpret=True)
    assert float(sums.sum() / (batch * seq)) == pytest.approx(float(loss), rel=1e-5)
    g = dsa.dsa_index_kl(q_index, k_index, weights, *operands, grad=True, interpret=True)
    got = dsa.dsa_index_scores_bwd(g, q_index, k_index, weights, interpret=True)
    for a, b in zip(got, grads):
        assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < 1e-5
