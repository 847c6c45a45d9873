"""Pallas quantization kernel tests (interpret mode on the CPU backend).

Mirrors the reference's quantization_test.py: roundtrip error bounds and
exact parity with the host-side numpy quantizer in collectives.py, so either
end of a DCN transfer can (de)quantize the other's payload.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.collectives import (
    BLOCK as HOST_BLOCK,
    dequantize_blockwise,
    quantize_blockwise,
)
from torchft_tpu.ops import (
    BLOCK,
    fused_dequantize_int8,
    fused_quantize_int8,
    fused_reduce_int8,
)


def test_block_sizes_match_host():
    assert BLOCK == HOST_BLOCK


def test_quantize_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 3.0, (5000,)).astype(np.float32))
    q, s, n = fused_quantize_int8(x)
    assert q.dtype == jnp.int8
    assert n == 5000
    out = fused_dequantize_int8(q, s, n)
    # max error is scale/2; scale = absmax/127 (global bound here)
    err = np.abs(np.asarray(out) - np.asarray(x))
    bound = np.abs(np.asarray(x)).max() / 127.0 / 2 + 1e-6
    assert err.max() <= bound * 1.01


def test_quantize_matches_host_quantizer():
    rng = np.random.default_rng(1)
    x = rng.normal(0, 1.0, (2048,)).astype(np.float32)
    q_dev, s_dev, n = fused_quantize_int8(jnp.asarray(x))
    q_host, s_host = quantize_blockwise(x)
    blocks = (n + BLOCK - 1) // BLOCK
    np.testing.assert_array_equal(
        np.asarray(q_dev).reshape(-1)[: blocks * BLOCK], q_host
    )
    np.testing.assert_allclose(np.asarray(s_dev)[:blocks], s_host, rtol=1e-6)


def test_device_quantize_host_dequantize():
    rng = np.random.default_rng(2)
    x = rng.normal(0, 2.0, (1000,)).astype(np.float32)
    q, s, n = fused_quantize_int8(jnp.asarray(x))
    blocks = (n + BLOCK - 1) // BLOCK
    host_out = dequantize_blockwise(
        np.asarray(q).reshape(-1)[: blocks * BLOCK],
        np.asarray(s)[:blocks],
        n,
    )
    dev_out = np.asarray(fused_dequantize_int8(q, s, n))
    np.testing.assert_allclose(host_out, dev_out, rtol=1e-6)


def test_zero_blocks_are_exact():
    x = jnp.zeros((1024,), jnp.float32)
    q, s, n = fused_quantize_int8(x)
    out = fused_dequantize_int8(q, s, n)
    np.testing.assert_array_equal(np.asarray(out), np.zeros(1024))


def test_fused_reduce_matches_fp32_sum():
    rng = np.random.default_rng(3)
    ranks = 4
    xs = [rng.normal(0, 1.0, (2000,)).astype(np.float32) for _ in range(ranks)]
    qs, ss = [], []
    for x in xs:
        q, s, n = fused_quantize_int8(jnp.asarray(x))
        qs.append(q)
        ss.append(s)
    q_stack = jnp.stack(qs)
    s_stack = jnp.stack(ss)
    qo, so = fused_reduce_int8(q_stack, s_stack, avg=False)
    out = np.asarray(fused_dequantize_int8(qo, so, n))
    exact = sum(xs)
    # one quantize + one requantize round trip of error
    scale_in = max(np.abs(x).max() for x in xs) / 127.0
    scale_out = np.abs(exact).max() / 127.0
    bound = ranks * scale_in / 2 + scale_out / 2 + 1e-6
    assert np.abs(out - exact).max() <= bound * 1.05


def test_fused_reduce_avg():
    ranks = 2
    xs = [np.full((512,), 4.0, np.float32), np.full((512,), 2.0, np.float32)]
    qs, ss = [], []
    for x in xs:
        q, s, n = fused_quantize_int8(jnp.asarray(x))
        qs.append(q)
        ss.append(s)
    qo, so = fused_reduce_int8(jnp.stack(qs), jnp.stack(ss), avg=True)
    out = np.asarray(fused_dequantize_int8(qo, so, n))
    np.testing.assert_allclose(out, np.full((512,), 3.0), rtol=1e-2)


def test_host_quantized_payload_device_dequantize():
    """Host-quantized payloads have exactly `blocks` rows (not a _TILE
    multiple); the device kernels must pad internally, not silently zero."""
    rng = np.random.default_rng(4)
    x = rng.normal(0, 1.0, (5 * BLOCK,)).astype(np.float32)  # 5 rows
    q_host, s_host = quantize_blockwise(x)
    out = np.asarray(
        fused_dequantize_int8(jnp.asarray(q_host), jnp.asarray(s_host), x.size)
    )
    expect = dequantize_blockwise(q_host, s_host, x.size)
    np.testing.assert_allclose(out, expect, rtol=1e-6)
    assert np.abs(out).max() > 0  # would be all-zero before the pad fix


def test_host_payload_device_reduce():
    rng = np.random.default_rng(5)
    xs = [rng.normal(0, 1.0, (3 * BLOCK,)).astype(np.float32) for _ in range(2)]
    qs, ss = zip(*(quantize_blockwise(x) for x in xs))
    qo, so = fused_reduce_int8(
        jnp.stack([jnp.asarray(q).reshape(-1, BLOCK) for q in qs]),
        jnp.stack([jnp.asarray(s) for s in ss]),
    )
    out = np.asarray(fused_dequantize_int8(qo, so, xs[0].size))
    exact = xs[0] + xs[1]
    bound = 2 * max(np.abs(x).max() for x in xs) / 127 / 2 + np.abs(exact).max() / 127 / 2
    assert np.abs(out - exact).max() <= bound * 1.05


def test_quantize_for_transfer_layout():
    from torchft_tpu.ops import quantize_for_transfer

    rng = np.random.default_rng(6)
    x = rng.normal(0, 1.0, (1000,)).astype(np.float32)
    q, s, n = quantize_for_transfer(jnp.asarray(x))
    assert n == 1000
    # decodable by the host-side decoder directly
    out = dequantize_blockwise(q, s, n)
    np.testing.assert_allclose(out, np.asarray(
        fused_dequantize_int8(jnp.asarray(q), jnp.asarray(s), n)
    ), rtol=1e-6)


# ---------------------------------------------------------------------------
# Flash attention (ops/flash_attention.py)
# ---------------------------------------------------------------------------


class TestFlashAttention:
    def _rand_qkv(self, B=2, S=256, Hq=4, Hkv=2, D=64, dtype=jnp.float32):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, S, Hq, D), dtype)
        k = jax.random.normal(ks[1], (B, S, Hkv, D), dtype)
        v = jax.random.normal(ks[2], (B, S, Hkv, D), dtype)
        return q, k, v

    def test_forward_matches_dense_fp32(self):
        from torchft_tpu.models.llama import dense_attention
        from torchft_tpu.ops.flash_attention import flash_attention

        q, k, v = self._rand_qkv()
        out_f = flash_attention(q, k, v)
        out_d = dense_attention(q, k, v)
        np.testing.assert_allclose(
            np.asarray(out_f), np.asarray(out_d), atol=2e-5
        )

    def test_forward_matches_dense_bf16(self):
        from torchft_tpu.models.llama import dense_attention
        from torchft_tpu.ops.flash_attention import flash_attention

        q, k, v = self._rand_qkv(dtype=jnp.bfloat16)
        out_f = np.asarray(flash_attention(q, k, v), np.float32)
        out_d = np.asarray(dense_attention(q, k, v), np.float32)
        np.testing.assert_allclose(out_f, out_d, atol=3e-2)

    def test_gradients_match_dense(self):
        from torchft_tpu.models.llama import dense_attention
        from torchft_tpu.ops.flash_attention import flash_attention

        q, k, v = self._rand_qkv(B=1, S=256, Hq=4, Hkv=2, D=64)

        def loss(fn, q, k, v):
            return jnp.sum(fn(q, k, v) ** 2)

        gf = jax.grad(lambda *a: loss(flash_attention, *a), (0, 1, 2))(q, k, v)
        gd = jax.grad(lambda *a: loss(dense_attention, *a), (0, 1, 2))(q, k, v)
        for a, b in zip(gf, gd):
            ref = float(jnp.max(jnp.abs(b))) + 1e-9
            rel = float(jnp.max(jnp.abs(a - b))) / ref
            assert rel < 1e-4, rel

    def test_causality(self):
        """Perturbing future tokens must not change earlier outputs."""
        from torchft_tpu.ops.flash_attention import flash_attention

        q, k, v = self._rand_qkv(B=1, S=256)
        out = flash_attention(q, k, v)
        k2 = k.at[:, 200:].set(99.0)
        v2 = v.at[:, 200:].set(-99.0)
        out2 = flash_attention(q, k2, v2)
        np.testing.assert_array_equal(
            np.asarray(out[:, :200]), np.asarray(out2[:, :200])
        )
        assert not np.allclose(np.asarray(out[:, 200:]), np.asarray(out2[:, 200:]))

    def test_unsupported_seq_len_raises(self):
        from torchft_tpu.ops.flash_attention import flash_attention, supports

        assert not supports(100)
        q, k, v = self._rand_qkv(S=100)
        with pytest.raises(ValueError):
            flash_attention(q, k, v)

    def test_model_flash_impl_matches_dense(self):
        """End-to-end through the Transformer: attn_impl='flash' ==
        attn_impl='dense' numerics (fp32, tiny model, S=128)."""
        from torchft_tpu.models import Transformer
        from torchft_tpu.models.llama import llama_debug

        cfg_d = llama_debug(
            max_seq_len=128, dtype=jnp.float32, attn_impl="dense"
        )
        cfg_f = llama_debug(
            max_seq_len=128, dtype=jnp.float32, attn_impl="flash",
            flash_min_seq=0,  # force the kernel path at this tiny S
        )
        x = jax.random.randint(jax.random.PRNGKey(3), (2, 128), 0, 256)
        model_d = Transformer(cfg_d)
        params = model_d.init(jax.random.PRNGKey(0), x)
        out_d = model_d.apply(params, x)
        out_f = Transformer(cfg_f).apply(params, x)
        np.testing.assert_allclose(
            np.asarray(out_d), np.asarray(out_f), atol=5e-4
        )


def test_chunked_transfer_layout_matches_single_shot(monkeypatch):
    """Payloads above _TRANSFER_CHUNK are quantized/pulled in slices; the
    concatenated host layout must be BIT-IDENTICAL to the single-shot path
    and the chunked dequantize must invert it exactly."""
    from torchft_tpu.ops import quantization as Q

    x = jax.random.normal(
        jax.random.PRNGKey(1), (3 * 4 * Q.BLOCK + 777,), jnp.float32
    )
    q1, s1, n1 = Q.quantize_for_transfer(x)  # single shot (payload < chunk)
    back1 = np.asarray(Q.fused_dequantize_int8(q1, s1, n1))

    monkeypatch.setattr(Q, "_TRANSFER_CHUNK", 4 * Q.BLOCK)
    qc, sc, n = Q.quantize_for_transfer(x)  # now forced through 4 chunks
    assert n == n1
    np.testing.assert_array_equal(qc, q1)
    np.testing.assert_array_equal(sc, s1)
    backc = np.asarray(Q.dequantize_from_transfer(qc, sc, n))
    np.testing.assert_array_equal(backc, back1)


def test_async_transfer_matches_sync(monkeypatch):
    """quantize_for_transfer_async (eager dispatch on the caller's thread)
    + pull_transfer_chunks must produce the bit-identical host payload the
    synchronous quantize_for_transfer produces, in both the single-shot
    and forced-chunked regimes."""
    from torchft_tpu.ops import quantization as Q

    x = jax.random.normal(
        jax.random.PRNGKey(2), (3 * 4 * Q.BLOCK + 123,), jnp.float32
    )
    q1, s1, n1 = Q.quantize_for_transfer(x)
    chunks, n = Q.quantize_for_transfer_async(x)
    qa, sa, na = Q.pull_transfer_chunks(chunks, n)
    assert na == n1
    np.testing.assert_array_equal(qa, q1)
    np.testing.assert_array_equal(sa, s1)

    monkeypatch.setattr(Q, "_TRANSFER_CHUNK", 4 * Q.BLOCK)
    chunks, n = Q.quantize_for_transfer_async(x)
    assert len(chunks) == 4
    qc, sc, nc = Q.pull_transfer_chunks(chunks, n)
    np.testing.assert_array_equal(qc, q1)
    np.testing.assert_array_equal(sc, s1)


def test_flash_gradients_bf16_tolerance():
    """bf16 backward: operands in bf16, accumulation fp32 (intentional —
    matches the forward and the MXU's native mode); pin the tolerance vs
    the bf16 dense reference so precision regressions are visible."""
    from torchft_tpu.models.llama import dense_attention
    from torchft_tpu.ops.flash_attention import flash_attention

    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (1, 256, 4, 64), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 256, 2, 64), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 256, 2, 64), jnp.bfloat16)

    def loss(fn, q, k, v):
        return jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)

    gf = jax.grad(lambda *a: loss(flash_attention, *a), (0, 1, 2))(q, k, v)
    gd = jax.grad(lambda *a: loss(dense_attention, *a), (0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        a32, b32 = a.astype(jnp.float32), b.astype(jnp.float32)
        rel = float(jnp.max(jnp.abs(a32 - b32)) / (jnp.max(jnp.abs(b32)) + 1e-9))
        assert rel < 5e-2, rel


# -- the forward's softmax state kept by the lane ---------------------------


def _rel_err(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-9))


def _assert_forward_and_gradients_match(flash, dense, q, k, v, w, tol):
    """``flash`` against ``dense``: the output and, of the loss sum(out * w),
    the gradients by q, k and v, each to ``tol`` of the largest entry."""

    def loss(fn, q, k, v):
        return jnp.sum(fn(q, k, v).astype(jnp.float32) * w)

    assert _rel_err(flash(q, k, v), dense(q, k, v)) <= tol
    gf = jax.grad(lambda *a: loss(flash, *a), (0, 1, 2))(q, k, v)
    gd = jax.grad(lambda *a: loss(dense, *a), (0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gd):
        assert _rel_err(a, b) <= tol, (name, _rel_err(a, b))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)], ids=["fp32", "bf16"])
@pytest.mark.parametrize("head_dim", [128, 64])
@pytest.mark.parametrize("S,block_k", [(512, 128), (512, 256), (512, 64), (384, 96)])
def test_flash_lanewise_softmax_state_matches_dense(S, block_k, head_dim, dtype, tol):
    """Forward and all three gradients against dense attention, GQA 4:1,
    over sweeps that hold whole, masked and skipped tiles, with the row sum
    kept as partial sums by the lane: at kv tiles of one and two whole lane
    groups (128, 256: what compiles, interpreted here), of half a group
    (64) and of three groups of 32 lanes (96)."""
    from torchft_tpu.models.llama import dense_attention
    from torchft_tpu.ops.flash_attention import flash_attention

    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(ks[0], (1, S, 4, head_dim), dtype)
    k = jax.random.normal(ks[1], (1, S, 1, head_dim), dtype)
    v = jax.random.normal(ks[2], (1, S, 1, head_dim), dtype)
    w = jax.random.normal(ks[3], (1, S, 4, head_dim), jnp.float32)
    flash = functools.partial(flash_attention, block_q=128, block_k=block_k)
    _assert_forward_and_gradients_match(flash, dense_attention, q, k, v, w, tol)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)], ids=["fp32", "bf16"])
def test_flash_block_diffusion_lanewise_tiles_match_dense(dtype, tol):
    """The block-diffusion kernels at tiles of a whole lane group, as they
    compile (the sdar tests interpret tiles of 16 to 48)."""
    from torchft_tpu.models.llama import block_diffusion_mask, dense_attention
    from torchft_tpu.ops.flash_attention import flash_attention_block_diffusion

    L, b, block, D = 256, 4, 128, 128
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    q = jax.random.normal(ks[0], (1, 2 * L, 4, D), dtype)
    k = jax.random.normal(ks[1], (1, 2 * L, 1, D), dtype)
    v = jax.random.normal(ks[2], (1, 2 * L, 1, D), dtype)
    w = jax.random.normal(ks[3], (1, 2 * L, 4, D), jnp.float32)
    mask = block_diffusion_mask(L, b)
    flash = functools.partial(flash_attention_block_diffusion, block_length=b, block=block)
    dense = functools.partial(dense_attention, mask=mask)
    _assert_forward_and_gradients_match(flash, dense, q, k, v, w, tol)


@pytest.mark.parametrize("block_k", [128, 64])
def test_flash_block_with_a_fully_masked_q_tile_merges_to_zero_weight(block_k):
    """An offset block whose first q tile sees no key (every step of its
    sweep skipped): out 0 and lse <= -1e29 there under the deferred row
    sum, and the ring's merge gives those rows no weight."""
    from torchft_tpu.models.llama import dense_attention
    from torchft_tpu.ops.flash_attention import flash_attention_block

    S, D = 256, 128
    ks = jax.random.split(jax.random.PRNGKey(13), 5)
    q = jax.random.normal(ks[0], (1, S, 4, D), jnp.float32)
    k_old, k_new = (jax.random.normal(kk, (1, S, 1, D), jnp.float32) for kk in ks[1:3])
    v_old, v_new = (jax.random.normal(kk, (1, S, 1, D), jnp.float32) for kk in ks[3:5])
    block = functools.partial(flash_attention_block, block_q=128, block_k=block_k)
    # q rows at 0..255; the "new" keys at 128..383: rows 0..127 see none.
    o_new, lse_new = block(q, k_new, v_new, 0, 128)
    np.testing.assert_array_equal(np.asarray(o_new[:, :128]), 0.0)
    assert float(jnp.max(lse_new[:, :, :128])) <= -1e29
    assert bool(jnp.all(jnp.isfinite(lse_new[:, :, 128:]) & (lse_new[:, :, 128:] > -1e29)))
    # ... and a block wholly in the future is that for every row.
    o_far, lse_far = block(q, k_new, v_new, 0, 4096)
    np.testing.assert_array_equal(np.asarray(o_far), 0.0)
    assert float(jnp.max(lse_far)) <= -1e29
    # The ring's merge (parallel/ring_attention.py, fold): the "old" keys
    # sit at -256..-1, every row sees all of them.
    o_old, lse_old = block(q, k_old, v_old, 0, -S)
    merged = jnp.logaddexp(lse_old, lse_new)
    w_old = jnp.exp(lse_old - merged).transpose(0, 2, 1)[..., None]
    w_new = jnp.exp(lse_new - merged).transpose(0, 2, 1)[..., None]
    assert float(jnp.max(w_new[:, :128])) == 0.0
    out = w_old * o_old + w_new * o_new
    np.testing.assert_allclose(  # rows 0..127: the old keys alone
        np.asarray(out[:, :128]), np.asarray(o_old[:, :128]), rtol=0, atol=0
    )
    key_at = jnp.concatenate([jnp.arange(-S, 0), jnp.arange(128, 128 + S)])
    want = dense_attention(
        q, jnp.concatenate([k_old, k_new], 1), jnp.concatenate([v_old, v_new], 1),
        mask=jnp.arange(S)[:, None] >= key_at[None, :],
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


# -- the kernels choose their tiles from the shape they are given -------------

# (family, length, widths, keywords of choose_tiles, compiled, the tiles)
CHOICES = [
    # 1,024 where it divides the length, at every cell's length and head width
    ("causal", 8192, (128,), {}, True, (1024, 1024)),
    ("causal", 4096, (128,), {}, True, (1024, 1024)),
    ("causal", 8192, (64,), {}, True, (1024, 1024)),
    # 512 where 1,024 does not divide it: still the kernel, never dense
    ("causal", 1536, (128,), {}, True, (512, 512)),
    ("causal", 2560, (128,), {}, True, (512, 512)),
    ("causal", 3584, (128,), {}, False, (512, 512)),
    # one tile a sequence: the reference check's sample, and the lengths under a tile
    ("causal", 1024, (128,), {}, True, (1024, 1024)),
    ("causal", 768, (128,), {}, True, (768, 768)),
    ("causal", 256, (64,), {}, True, (256, 256)),
    ("causal", 48, (16,), {}, False, (48, 48)),
    # no tiling, as before: the caller runs dense attention
    ("causal", 1280, (128,), {}, True, None),
    ("causal", 1008, (128,), {}, True, None),  # past 512 rows, no whole lane tiles
    ("causal", 100, (128,), {}, False, None),
    # the bounds a caller names are the LARGEST tiles, each side its own
    ("causal", 8192, (128,), {"block_q": 512, "block_k": 512}, True, (512, 512)),
    ("causal", 8192, (128,), {"block_q": 1024, "block_k": 512}, True, (1024, 512)),
    ("causal", 8192, (128,), {"block_q": 2048, "block_k": 2048}, True, (1024, 1024)),
    ("causal", 128, (16,), {"block_q": 32, "block_k": 32}, False, (32, 32)),
    ("causal", 384, (128,), {"block_q": 128, "block_k": 96}, False, (128, 96)),
    ("causal", 96, (16,), {"block_q": 64, "block_k": 64}, False, None),
    # the ring's offset block: each side by its own length
    ("block", 2048, (64,), {"kv_len": 1536}, True, (1024, 512)),
    ("block", 256, (128,), {"kv_len": 256, "block_q": 128, "block_k": 64}, False, (128, 64)),
    # block diffusion: one square tile of whole blocks, a stream's length the sweep's
    ("block_diffusion", 8192, (128,), {"block_length": 4}, True, (1024, 1024)),
    ("block_diffusion", 8192, (128,), {"block_length": 32}, True, (1024, 1024)),
    ("block_diffusion", 1024, (128,), {"block_length": 4}, True, (1024, 1024)),
    ("block_diffusion", 1024, (128,), {"block_length": 32}, True, (1024, 1024)),
    ("block_diffusion", 1536, (128,), {"block_length": 4}, True, (512, 512)),
    ("block_diffusion", 64, (16,), {"block_length": 4}, False, (64, 64)),
    ("block_diffusion", 64, (16,), {"block_length": 32}, False, (64, 64)),
    ("block_diffusion", 64, (128,), {"block_length": 4}, True, None),  # compiled: whole lane tiles
    ("block_diffusion", 8192, (128,), {"block_length": 4, "block_q": 512, "block_k": 1024}, True, (512, 512)),
    ("block_diffusion", 768, (128,), {"block_length": 12, "block_q": 384, "block_k": 384}, True, (384, 384)),
    ("block_diffusion", 1024, (128,), {"block_length": 24}, False, None),  # a tile would cut a block
    ("block_diffusion", 8192, (128,), {"block_length": 0}, False, None),
    # latent attention: the causal tilings at the widths that have been compiled
    ("mla", 8192, (128, 64, 128), {}, True, (1024, 1024)),
    ("mla", 1536, (128, 64, 128), {}, True, (512, 512)),
    ("mla", 96, (48, 16, 32), {"block_q": 48, "block_k": 32}, False, (48, 32)),
    ("mla", 8192, (96, 64, 128), {}, True, None),
    ("mla", 8192, (128, 48, 128), {}, True, None),
]


@pytest.mark.parametrize("family,length,widths,kw,compiled,want", CHOICES, ids=str)
def test_the_chooser_takes_the_largest_good_tile_the_shape_admits(
    family, length, widths, kw, compiled, want, monkeypatch
):
    from torchft_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_interpret", lambda: not compiled)
    assert fa.choose_tiles(family, length, widths, **kw) == want
    # the predicates answer for the tiles the chooser would take
    bounds = [kw[b] for b in ("block_q", "block_k") if b in kw]
    if family == "causal":
        assert fa.supports(length, *bounds) == (want is not None)
    elif family == "mla":
        assert fa.supports_mla(length, *widths, *bounds) == (want is not None)
    elif family == "block_diffusion":
        assert fa.supports_block_diffusion(
            length, kw["block_length"], *([min(bounds)] if bounds else [])
        ) == (want is not None)


def test_no_length_the_kernels_took_at_tiles_of_512_is_lost():
    """Every multiple of 16 up to 8,192: what the causal predicate admitted
    while every call ran tiles of 512 (the rule it had) it admits now, at
    tiles that cut the length whole; a ``LlamaConfig`` of the defaults
    asks under the same bounds, and one that names 32 still runs 32."""
    from torchft_tpu.models.llama import LlamaConfig
    from torchft_tpu.ops.flash_attention import choose_tiles, supports

    def admitted_at_512(s):
        b = min(512, s)
        return s % b == 0 and b % 16 == 0

    cfg = LlamaConfig()
    for s in range(16, 8192 + 1, 16):
        tiles = choose_tiles("causal", s, (cfg.head_dim,), cfg.flash_block_q, cfg.flash_block_k)
        assert supports(s) == (tiles is not None)
        if admitted_at_512(s):
            assert tiles is not None and s % tiles[0] == 0 and s % tiles[1] == 0, s
            assert tiles == ((1024, 1024) if s % 1024 == 0 else (min(512, s),) * 2), s
    small = LlamaConfig(flash_block_q=32, flash_block_k=32)
    assert choose_tiles(
        "causal", 8192, (small.head_dim,), small.flash_block_q, small.flash_block_k
    ) == (32, 32)


def _latent_dense_joined(q, k, v):
    """Latent attention's dense path on arrays joined for the one harness:
    q = [q_nope | q_rope], k = [k_nope | k_rope of the one shared head
    broadcast], split again inside."""
    from torchft_tpu.models.mla import latent_dense_attention

    dn = v.shape[-1]
    return latent_dense_attention(
        q[..., :dn], q[..., dn:], k[..., :dn], k[:, :, 0, dn:], v
    )


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)], ids=["fp32", "bf16"])
@pytest.mark.parametrize("tile", [256, 512], ids=["two_lane_groups", "one_tile"])
@pytest.mark.parametrize("family", ["causal", "block", "block_diffusion", "mla"])
def test_every_family_matches_dense_past_one_lane_group_and_at_one_tile(family, tile, dtype, tol):
    """Interpreted: forward and every gradient against the dense path over
    S = 512 (a stream of 512 under block diffusion) at tiles of 256, two
    whole lane groups a tile and a sweep of whole, masked and skipped
    tiles, and at ONE tile a sequence or stream (n = 1: every sweep is its
    first and last step at once), which is what the reference check's
    1,024-token sample runs since the kernels take tiles of 1,024."""
    from torchft_tpu.models.llama import block_diffusion_mask, dense_attention
    from torchft_tpu.ops import flash_attention as fa

    S, D = 512, 128
    ks = jax.random.split(jax.random.PRNGKey(17), 4)
    rows = 2 * S if family == "block_diffusion" else S
    q = jax.random.normal(ks[0], (1, rows, 4, D), dtype)
    k = jax.random.normal(ks[1], (1, rows, 1, D), dtype)
    v = jax.random.normal(ks[2], (1, rows, 1, D), dtype)
    w = jax.random.normal(ks[3], (1, rows, 4, D), jnp.float32)
    if family == "causal":
        assert fa.choose_tiles("causal", S, (D,), tile, tile) == (tile, tile)
        flash = functools.partial(fa.flash_attention, block_q=tile, block_k=tile)
        dense = dense_attention
    elif family == "block":  # at offsets 0, 0 the offset block is causal attention
        flash = lambda q, k, v: fa.flash_attention_block(  # noqa: E731
            q, k, v, 0, 0, block_q=tile, block_k=tile)[0]
        dense = dense_attention
    elif family == "block_diffusion":
        assert fa.choose_tiles("block_diffusion", S, (D,), tile, tile, block_length=4) == (tile, tile)
        flash = functools.partial(fa.flash_attention_block_diffusion, block_length=4, block=tile)
        dense = functools.partial(dense_attention, mask=block_diffusion_mask(S, 4))
    else:
        # heads of 128 + 64 | 128 on one shared rotary key: q is 192 wide,
        # k's one head carries [k_nope | k_rope], the values 128.
        q = jax.random.normal(ks[0], (1, S, 4, D + 64), dtype)
        k = jax.random.normal(ks[1], (1, S, 4, D + 64), dtype)
        k = k.at[..., D:].set(k[:, :, :1, D:])  # one rotary key a position
        v = jax.random.normal(ks[2], (1, S, 4, D), dtype)

        def flash(q, k, v):
            return fa.flash_attention_mla(
                q[..., :D], q[..., D:], k[..., :D], k[:, :, 0, D:], v,
                block_q=tile, block_k=tile,
            )

        dense = _latent_dense_joined
    _assert_forward_and_gradients_match(flash, dense, q, k, v, w, tol)


# ---------------------------------------------------------------------------
# int4 codec (bits=4): packing, parity, transfer layout
# ---------------------------------------------------------------------------


def test_nibble_pack_roundtrip():
    from torchft_tpu.collectives import pack_nibbles, unpack_nibbles

    rng = np.random.default_rng(7)
    q = rng.integers(-7, 8, size=4096).astype(np.int8)
    packed = pack_nibbles(q)
    assert packed.size == q.size // 2
    np.testing.assert_array_equal(unpack_nibbles(packed, q.size), q)


def test_int4_host_roundtrip_error_bound():
    rng = np.random.default_rng(8)
    x = rng.normal(0, 2.0, (3 * HOST_BLOCK + 100,)).astype(np.float32)
    q, s = quantize_blockwise(x, bits=4)
    assert q.size == ((x.size + HOST_BLOCK - 1) // HOST_BLOCK) * HOST_BLOCK // 2
    back = dequantize_blockwise(q, s, x.size, bits=4)
    # per-block bound: scale/2, scale = blockwise absmax / 7
    pad = np.zeros(s.size * HOST_BLOCK, np.float32)
    pad[: x.size] = x
    per_block_scale = np.repeat(s, HOST_BLOCK)[: x.size]
    assert (np.abs(back - x) <= per_block_scale / 2 + 1e-6).all()


def test_int4_device_matches_host_quantizer():
    """fused_quantize(bits=4) through the interpret-mode Pallas kernel +
    jnp packing must produce the bit-identical wire payload the host
    numpy codec produces."""
    from torchft_tpu.ops import fused_dequantize, fused_quantize

    rng = np.random.default_rng(9)
    x = rng.normal(0, 1.0, (2 * BLOCK + 64,)).astype(np.float32)
    q_dev, s_dev, n = fused_quantize(jnp.asarray(x), 4)
    q_host, s_host = quantize_blockwise(x, bits=4)
    blocks = (n + BLOCK - 1) // BLOCK
    np.testing.assert_array_equal(
        np.asarray(q_dev).reshape(-1)[: blocks * BLOCK // 2], q_host
    )
    np.testing.assert_allclose(np.asarray(s_dev)[:blocks], s_host, rtol=1e-6)
    # device payload decodes identically on either end
    back_dev = np.asarray(fused_dequantize(q_host, s_host, n, 4))
    back_host = dequantize_blockwise(q_host, s_host, n, bits=4)
    np.testing.assert_array_equal(back_dev, back_host)


def test_int4_transfer_layout_matches_host(monkeypatch):
    from torchft_tpu.ops import quantization as Q

    x = jax.random.normal(
        jax.random.PRNGKey(3), (3 * 4 * Q.BLOCK + 200,), jnp.float32
    )
    q1, s1, n1 = Q.quantize_for_transfer(x, bits=4)
    q_host, s_host = quantize_blockwise(np.asarray(x), bits=4)
    np.testing.assert_array_equal(q1, q_host)
    # XLA folds the /7 into a reciprocal multiply -> scales can sit 1 ulp
    # off the host's true division; q still matches bit-for-bit above.
    np.testing.assert_allclose(s1, s_host, rtol=1e-6)
    # The wire contract: the SAME payload bytes decode bit-identically on
    # either end (scales ship with the payload; nobody re-derives them).
    back = np.asarray(Q.dequantize_from_transfer(q1, s1, n1, bits=4))
    np.testing.assert_array_equal(
        back, dequantize_blockwise(q1, s1, n1, bits=4)
    )

    # chunked regime: layout must be bit-identical to single-shot
    monkeypatch.setattr(Q, "_TRANSFER_CHUNK", 4 * Q.BLOCK)
    chunks, n = Q.quantize_for_transfer_async(x, bits=4)
    assert len(chunks) == 4
    qc, sc, nc = Q.pull_transfer_chunks(chunks, n, bits=4)
    np.testing.assert_array_equal(qc, q1)
    np.testing.assert_allclose(sc, s1, rtol=1e-6)
    backc = np.asarray(Q.dequantize_from_transfer(qc, sc, n, bits=4))
    np.testing.assert_array_equal(backc, back)
