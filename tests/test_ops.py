"""Pallas quantization kernel tests (interpret mode on the CPU backend; the
flash kernels' are in tests/test_flash_attention.py).

Mirrors the reference's quantization_test.py: roundtrip error bounds and
exact parity with the host-side numpy quantizer in collectives.py, so either
end of a DCN transfer can (de)quantize the other's payload.
"""

import jax
import jax.numpy as jnp
import numpy as np

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
