"""The flash kernels of ``ops/flash_attention.py``, through the interpreter
on the CPU, every family against plain attention under its mask, values and
gradients: the causal family and the ring's offset block, the chooser of
tiles, block diffusion against the reference's mask, latent attention at
unlike widths, the band; and, for every family value, that the sweep runs
exactly the tile pairs in which the mask keeps an entry, once each, inside
the brackets of the kv heads the backward holds.
The kernels compiled for a described chip are in tests/test_tpu_compile.py;
the models that call them have their own files."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells
from benchmark.tests.test_smallthinker_reference import PUBLISHED
from torchft_tpu.models import sdar_moe_debug
from torchft_tpu.models.llama import (
    block_diffusion_attention,
    block_diffusion_mask,
    dense_attention,
    window_mask,
)
from torchft_tpu.models.mla import latent_dense_attention
from torchft_tpu.ops import flash_attention as fa
from torchft_tpu.ops.flash_attention import (
    block_diffusion_tiles,
    flash_attention_block_diffusion,
    flash_attention_mla,
    supports_block_diffusion,
    supports_mla,
)

sdar_reference = cells.arch_module("sdar_moe", "reference")
flops = cells.arch_module("smallthinker", "flops")


# -- the causal family, the ring's offset block, the chooser of tiles -----------


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


# -- block diffusion: the kernels against the reference's mask -----------------


def _dense_masked(q, k, v, see):
    g = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(see[None, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("b,length,block", [(4, 64, 32), (32, 128, 32), (4, 48, 16), (12, 96, 48)])
def test_the_kernels_are_dense_attention_under_the_reference_mask(b, length, block):
    """Interpreted, float32, four query heads on two key/value heads, two
    to four tiles a stream: outputs and all three gradients against dense
    attention over [x_t | x_0] under the REFERENCE's mask (the program's
    own dense mask is held to it too)."""
    keys = jax.random.split(jax.random.PRNGKey(b), 4)
    q = jax.random.normal(keys[0], (2, 2 * length, 4, 16))
    k, v = (jax.random.normal(key, (2, 2 * length, 2, 16)) for key in keys[1:3])
    w = jax.random.normal(keys[3], q.shape)
    see = sdar_reference.visible(length, b)
    assert jnp.array_equal(block_diffusion_mask(length, b), see)
    assert supports_block_diffusion(length, b, block) and length // block >= 2

    def flash(q, k, v):
        return flash_attention_block_diffusion(q, k, v, block_length=b, block=block)

    assert jnp.allclose(flash(q, k, v), _dense_masked(q, k, v, see), atol=2e-5)
    got = jax.grad(lambda *a: (flash(*a) * w).sum(), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (_dense_masked(*a, see) * w).sum(), argnums=(0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        assert jnp.allclose(g, r, atol=5e-5), float(jnp.abs(g - r).max())


def test_the_tile_schedule_counts_what_the_sweeps_run():
    # n^2 + 2n tiles of the 4 n^2: 80 of 256 at the 8 tiles a stream the
    # kernels choose, 288 of 1,024 at 16 where the tiles are held to 512
    assert block_diffusion_tiles(8192, 4) == (8192 * 8192 + 8192 * 4, 80 * 1024 * 1024)
    assert block_diffusion_tiles(8192, 4, 512) == (8192 * 8192 + 8192 * 4, 288 * 512 * 512)
    assert block_diffusion_tiles(64, 4, 32) == (64 * 64 + 64 * 4, 8 * 32 * 32)
    assert not supports_block_diffusion(1024, 24)  # a tile would cut a block
    assert not supports_block_diffusion(1000, 4) and not supports_block_diffusion(64, 0)
    with pytest.raises(ValueError, match="do not tile"):
        flash_attention_block_diffusion(*(jnp.zeros((1, 96, 2, 16)),) * 3, block_length=5)
    cfg = sdar_moe_debug(attn_impl="flash", flash_min_seq=64, flash_block_q=32, flash_block_k=32)
    assert block_diffusion_attention(cfg, 128) == ("flash", pytest.approx(4352 / 8192))
    assert block_diffusion_attention(cfg, 32) == ("dense", pytest.approx((256 + 64) / 1024))
    with pytest.raises(ValueError, match="whole blocks"):
        block_diffusion_attention(cfg, 36)  # 18 positions a stream: no whole blocks of 4


# -- latent attention: the kernels at unlike widths ------------------------------


def _per_head_dense(q_nope, q_rope, k_nope, k_rope_heads, v):
    """Dense causal attention over 192-wide queries and keys joined, the
    rotary key given for EVERY head ([B,S,H,Dr])."""
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, k_rope_heads], axis=-1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    keep = jnp.tril(jnp.ones((q.shape[1],) * 2, dtype=bool))
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("seq,heads,widths,blocks", [
    (128, 3, (32, 16, 32), (32, 32)),   # 4 x 4 tiles: the skipped tiles' clamped index maps
    (96, 2, (48, 16, 32), (48, 32)),    # unlike tiles, the cell's ratio of widths 192 | 128
    (64, 4, (16, 8, 24), (64, 64)),     # one tile
    (128, 1, (128, 64, 128), (64, 64)),  # the published widths, one head
])
def test_the_latent_kernels_are_the_dense_path_at_unlike_widths(seq, heads, widths, blocks):
    """Interpreted, float32: the output and the gradients of both parts of
    the queries, both parts of the keys and the values against the dense
    path, and against dense attention over joined 192-wide heads in which
    every head has a rotary key of its own: the shared key's gradient is
    the sum of those over the heads."""
    dn, dr, dv = widths
    keys = jax.random.split(jax.random.PRNGKey(seq + heads), 6)
    q_nope, k_nope = (jax.random.normal(k, (2, seq, heads, dn)) for k in keys[:2])
    q_rope = jax.random.normal(keys[2], (2, seq, heads, dr))
    k_rope = jax.random.normal(keys[3], (2, seq, dr))
    v, w = (jax.random.normal(k, (2, seq, heads, dv)) for k in keys[4:])
    assert supports_mla(seq, dn, dr, dv, *blocks)

    def flash(*a):
        return flash_attention_mla(*a, block_q=blocks[0], block_k=blocks[1])

    args = (q_nope, q_rope, k_nope, k_rope, v)
    assert flash(*args).shape == (2, seq, heads, dv)
    assert jnp.allclose(flash(*args), latent_dense_attention(*args), atol=2e-5)
    grads = lambda f, *a: jax.grad(  # noqa: E731
        lambda *b: (f(*b) * w).sum(), argnums=(0, 1, 2, 3, 4))(*a)
    got, want = grads(flash, *args), grads(latent_dense_attention, *args)
    for g, r, name in zip(got, want, ("dq_nope", "dq_rope", "dk_nope", "dk_rope", "dv")):
        assert g.shape == r.shape and jnp.allclose(g, r, atol=1e-4), (
            name, float(jnp.abs(g - r).max()))
    a_head = jnp.broadcast_to(k_rope[:, :, None], (2, seq, heads, dr))
    per_head = grads(_per_head_dense, q_nope, q_rope, k_nope, a_head, v)
    assert jnp.allclose(got[3], per_head[3].sum(axis=2), atol=1e-4)
    assert float(jnp.abs(per_head[3][:, :, 0] - got[3]).max()) > 1e-2 or heads == 1
    for g, r in zip(got[:3] + got[4:], per_head[:3] + per_head[4:]):
        assert jnp.allclose(g, r, atol=1e-4)


def test_the_latent_family_refuses_what_it_does_not_compute(monkeypatch):
    from torchft_tpu.ops import flash_attention

    assert not supports_mla(100, 128, 64, 128)  # no whole tiles
    monkeypatch.setattr(flash_attention, "_interpret", lambda: False)
    assert supports_mla(8192, 128, 64, 128) and supports_mla(8192, 128, 128, 256)
    assert not supports_mla(8192, 96, 64, 128) and not supports_mla(8192, 128, 64, 64)
    assert not supports_mla(8192, 128, 48, 128)
    with pytest.raises(ValueError, match="latent_dense_attention"):
        flash_attention_mla(
            jnp.zeros((1, 128, 2, 96)), jnp.zeros((1, 128, 2, 64)),
            jnp.zeros((1, 128, 2, 96)), jnp.zeros((1, 128, 64)),
            jnp.zeros((1, 128, 2, 128)))


# -- the band: the kernels are attention under the band mask ----------------------


def _qkv(seq, hq=7, hkv=1, d=16, batch=2, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(k[0], (batch, seq, hq, d)),
            jax.random.normal(k[1], (batch, seq, hkv, d)),
            jax.random.normal(k[2], (batch, seq, hkv, d)),
            jax.random.normal(k[3], (batch, seq, hq, d)))


# (sequence, window, largest q tile, largest kv tile): a window below the
# sequence in whole tiles, one that is no multiple of the tile, of one tile
# and a row more, of one position, equal to the sequence, above it, and
# unequal tiles either way.
BANDS = [(128, 64, 32, 32), (128, 48, 32, 32), (128, 33, 32, 32), (128, 1, 32, 32),
         (128, 128, 32, 32), (128, 500, 32, 32), (128, 40, 64, 32), (128, 40, 32, 64),
         (96, 50, 32, 32)]


@pytest.mark.parametrize("seq,window,bq,bk", BANDS)
def test_the_banded_kernels_are_attention_under_the_band_mask(seq, window, bq, bk):
    """Forward, dq, dk and dv, seven query heads a key/value head."""
    q, k, v, do = _qkv(seq)
    flash = lambda q, k, v: fa.flash_attention_window(  # noqa: E731
        q, k, v, window=window, block_q=bq, block_k=bk)
    plain = lambda q, k, v: dense_attention(q, k, v, mask=window_mask(seq, window))  # noqa: E731
    assert jnp.allclose(flash(q, k, v), plain(q, k, v), atol=1e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * do), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * do), (0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        assert jnp.allclose(a, b, atol=2e-5), name


@pytest.mark.parametrize("window", [128, 129, 4096])
def test_a_window_of_at_least_the_sequence_is_the_causal_family_bit_for_bit(window):
    q, k, v, do = _qkv(128, hq=4, hkv=2)
    banded = lambda *a: fa.flash_attention_window(*a, window=window, block_q=32, block_k=32)  # noqa: E731
    causal = lambda *a: fa.flash_attention(*a, block_q=32, block_k=32)  # noqa: E731
    assert np.array_equal(banded(q, k, v), causal(q, k, v))
    got = jax.grad(lambda *a: jnp.sum(banded(*a) * do), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(causal(*a) * do), (0, 1, 2))(q, k, v)
    assert all(np.array_equal(a, b) for a, b in zip(got, want))
    # one position short of it is not
    short = fa.flash_attention_window(q, k, v, window=127, block_q=32, block_k=32)
    assert not np.array_equal(short, causal(q, k, v))


# -- every family: the sweeps run the tile pairs the mask keeps ---------------------


def _kept_pairs(family, keeps):
    """The (q tile, kv tile) pairs in which the dense mask ``keeps(rows,
    cols)`` keeps any entry, a tile at a time."""
    bq, bk = family.block_q, family.block_k
    rows, cols = np.arange(bq)[:, None], np.arange(bk)[None, :]
    return {
        (iq, ik) for iq in range(family.nq) for ik in range(family.nk)
        if np.any(keeps(iq * bq + rows, ik * bk + cols))
    }


def _swept_pairs(family, scalars=(), heads=4):
    """The pairs the sweep runs (forward and the one backward), walked step
    by step as the grid walks a batch row: q head, q tile, step. The sweep
    runs no pair twice; a step that runs fetches its own tile; where no
    scalar decides the skip, a step that does not run names a tile its sweep
    fetches anyway; and for kv heads that one, two and all ``heads`` q heads
    add to, a head's bracket (``_bracket``: what zeroes and flushes the
    backward's resident dk and dv) opens once before and closes once after
    every step that adds to the head, brackets of one part never overlap,
    and every q head of the group adds the same pairs."""
    pairs = []
    for iq in range(family.nq):
        span = family.q_span(iq)
        steps = [(*family.q_sweep(span, j, *scalars), int(family.q_fetch(span, j)))
                 for j in range(family.q_steps)]
        ran = [(int(ikv), fetch) for ikv, run, fetch in steps if run]
        assert all(ikv == fetch for ikv, fetch in ran)
        if not scalars:
            assert {fetch for _, _, fetch in steps} <= {ikv for ikv, _ in ran}
        pairs += [(iq, ikv) for ikv, _ in ran]
    assert len(set(pairs)) == len(pairs)
    for group in (1, 2, heads):
        open_head, added, closed = None, {}, []
        for h in range(heads):
            for iq in range(family.nq):
                span = family.q_span(iq)
                for j in range(family.q_steps):
                    opens, closes = fa._bracket(family, group, h, iq, j)
                    if opens:
                        assert open_head is None
                        open_head = h // group
                        added[open_head] = []
                    ikv, run = family.q_sweep(span, j, *scalars)
                    if run:
                        assert open_head == h // group
                        added[open_head].append((h, iq, int(ikv)))
                    if closes:
                        assert open_head == h // group
                        closed.append(open_head)
                        open_head = None
        assert open_head is None and closed == list(range(heads // group))
        for head, steps in added.items():
            assert steps == [
                (h, iq, ikv) for h in range(head * group, (head + 1) * group)
                for iq, ikv in pairs
            ]
    return set(pairs)


def _assert_the_mask_is_the_dense_one(family, keeps, pairs, scalars=()):
    bq, bk = family.block_q, family.block_k
    rows, cols = np.arange(bq)[:, None], np.arange(bk)[None, :]
    for iq, ik in pairs:
        got = family.mask(jnp.int32(iq), jnp.int32(ik), *scalars)(jnp.zeros((bq, bk))) == 0.0
        assert np.array_equal(got, np.broadcast_to(keeps(iq * bq + rows, ik * bk + cols), (bq, bk)))


def _band(w):
    return lambda rows, cols: (rows >= cols) & (rows - cols < w)


def _two_streams(length, b):
    see = np.asarray(block_diffusion_mask(length, b))
    return lambda rows, cols: see[rows, cols]


# (family value, its dense mask on (rows, cols), its SMEM scalars): the causal
# family at equal and unequal tiles and without a mask, the ring's block ahead
# of, level with, behind and far behind its queries, block diffusion at two to
# four tiles a stream, the band's cases above.
FAMILY_CASES = [
    (fa._Causal(128, 128, 32, 32), lambda r, c: r >= c, ()),
    (fa._Causal(128, 128, 64, 32), lambda r, c: r >= c, ()),
    (fa._Causal(128, 128, 32, 64), lambda r, c: r >= c, ()),
    (fa._Causal(96, 96, 48, 32), lambda r, c: r >= c, ()),
    (fa._Causal(128, 128, 32, 32, False), lambda r, c: np.True_, ()),
    (fa._Offset(128, 96, 32, 32), lambda r, c: r >= c + 4096, (0, 4096)),
    (fa._Offset(128, 96, 32, 32), lambda r, c: r >= c, (0, 0)),
    (fa._Offset(128, 96, 32, 48), lambda r, c: r + 40 >= c, (40, 0)),
    (fa._Offset(128, 96, 32, 32), lambda r, c: r + 256 >= c + 128, (256, 128)),
    (fa._BlockDiffusion(128, 128, 32, 32, 4), _two_streams(64, 4), ()),
    (fa._BlockDiffusion(256, 256, 32, 32, 16), _two_streams(128, 16), ()),
    (fa._BlockDiffusion(96, 96, 16, 16, 4), _two_streams(48, 4), ()),
    (fa._BlockDiffusion(192, 192, 48, 48, 12), _two_streams(96, 12), ()),
    *[(fa._Window(seq, seq, bq, bk, min(w, seq)), _band(w), ()) for seq, w, bq, bk in BANDS],
]


@pytest.mark.parametrize(
    "family,keeps,scalars", FAMILY_CASES, ids=[f"{f}{s or ''}" for f, _, s in FAMILY_CASES]
)
def test_the_sweep_runs_the_tile_pairs_the_mask_keeps_once_each_inside_a_kv_heads_bracket(
    family, keeps, scalars
):
    """What a family value answers is consistent with itself: the pairs a
    q tile's sweep runs (forward and backward) and the pairs in which the
    dense mask keeps an entry are one set, each run exactly once and inside
    its kv head's bracket, and on each of them the mask closure is the dense
    mask's tile."""
    kept = _kept_pairs(family, keeps)
    assert _swept_pairs(family, scalars) == kept
    _assert_the_mask_is_the_dense_one(family, keeps, kept, scalars)


def test_block_diffusion_at_one_block_a_tile_runs_the_diagonal_tiles_it_empties():
    """The one place a sweep runs more than the mask keeps: where a tile is
    ONE block (no cell's: ``sdar-raw`` has 256 blocks a tile), noisy tile i
    on clean tile i holds only the own block, which a noisy row sees among
    the noisy keys and not the clean ones. The sweep still runs those n
    pairs (as ``block_diffusion_tiles`` counts them) and the mask empties
    them: nothing wrong comes out, n tiles of work do."""
    family = fa._BlockDiffusion(256, 256, 32, 32, 32)
    kept = _kept_pairs(family, _two_streams(128, 32))
    emptied = {(i, family.n + i) for i in range(family.n)}
    assert not emptied & kept
    assert _swept_pairs(family) == kept | emptied
    assert len(kept | emptied) * 32 * 32 == block_diffusion_tiles(128, 32, 32)[1]
    for iq, ik in emptied:
        masked = family.mask(jnp.int32(iq), jnp.int32(ik))(jnp.zeros((32, 32)))
        assert float(masked.max()) <= -1e29


@pytest.mark.parametrize("seq,window,bq,bk", BANDS + [(16384, 4096, 1024, 1024),
                                                       (16384, 4096, 512, 512),
                                                       (8192, 1000, 1024, 1024)])
def test_the_grids_run_the_tiles_the_band_touches_and_no_others(seq, window, bq, bk):
    """The window's case of the test above at the cell's sizes too, the
    pairs counted here from the band's edges, with the band's own counts:
    the sweeps' lengths tile by tile, the grid's innermost dimension (the
    longest sweep), the kept entries and the tiles' as the metrics count
    them."""
    w = min(window, seq)
    nq, nk = seq // bq, seq // bk

    def touched(iq, ik):  # any row i of the q tile with a kept column j of the kv tile
        lo_i, hi_i, lo_j, hi_j = iq * bq, iq * bq + bq - 1, ik * bk, ik * bk + bk - 1
        return lo_j <= hi_i and hi_j >= lo_i - (w - 1)

    pairs = {(iq, ik) for iq in range(nq) for ik in range(nk) if touched(iq, ik)}
    family = fa._Window(seq, seq, bq, bk, w)
    assert _swept_pairs(family) == pairs
    sweeps = fa._band_sweeps(seq, w, bq, bk)
    assert sweeps == [sum((iq, ik) in pairs for ik in range(nk)) for iq in range(nq)]
    assert sum(sweeps) == len(pairs) and family.q_steps == max(sweeps)
    if bq == bk:
        assert max(sweeps) == min(-(-(w - 1) // bq) + 1, nq)
    # ``window_tiles`` takes bounds: a band under four of a measured-good
    # tile wide takes the next one down (the last case: 512 under 1,024)
    kept, run = fa.window_tiles(seq, window, bq, bk)
    cq, ck = fa.choose_tiles("window", seq, (), bq, bk, window=window)
    assert (cq, ck) == ((512, 512) if window == 1000 else (bq, bk))
    assert run == sum(fa._band_sweeps(seq, w, cq, ck)) * cq * ck
    assert (cq, ck) != (bq, bk) or run == len(pairs) * bq * bk
    assert kept == sum(min(i + 1, w) for i in range(seq))
    assert kept == flops.window_kept_entries(dict(PUBLISHED, sliding_window_size=window), seq)


@pytest.mark.parametrize("seq,window,bounds,tiles", [
    (16384, 4096, (1024, 1024), (1024, 1024)),   # smallthinker-raw: four tiles wide
    (16384, 4097, (1024, 1024), (1024, 1024)),
    (16384, 4095, (1024, 1024), (512, 512)),
    (16384, 2048, (1024, 1024), (512, 512)),     # trinity-raw: four tiles of 512
    (16384, 1024, (1024, 1024), (512, 512)),     # no measured-good tile under 512
    (16384, 2048, (512, 512), (512, 512)),
    (16384, 2048, (1024, 512), (512, 512)),
    (16384, 16384, (1024, 1024), (1024, 1024)),  # the whole sequence: causal
    (1024, 2048, (1024, 1024), (1024, 1024)),
    (1024, 512, (128, 128), (128, 128)),         # the reference check's sample
    (1024, 256, (128, 128), (128, 128)),
    (64, 16, (16, 16), (16, 16)),                # the CPU tests' tiles
    (256, 64, (1024, 1024), (256, 256)),         # a sequence under a tile
])
def test_a_narrow_band_takes_the_next_tile_down(seq, window, bounds, tiles):
    """``choose_tiles("window", ..., window=)``: the causal family's tiles
    where the band is at least four of them wide, else the next
    measured-good tile down; bounds under every measured-good tile, a window
    of the whole sequence and a call that names no window are untouched."""
    assert fa.choose_tiles("window", seq, (128,), *bounds, window=window) == tiles
    assert fa.choose_tiles("window", seq, (128,), *bounds) == fa.choose_tiles(
        "causal", seq, (128,), *bounds)
    assert fa.supports_window(seq, window, *bounds)
    sweep = max(fa._band_sweeps(seq, min(window, seq), *tiles))
    assert fa.window_tiles(seq, window, *bounds)[1] == sum(
        fa._band_sweeps(seq, min(window, seq), *tiles)) * tiles[0] * tiles[1]
    assert sweep >= 1


def test_the_cells_schedule_is_seventy_tiles_of_the_causal_136():
    kept, run = fa.window_tiles(16384, 4096)
    assert (kept, run) == (58_722_304, 70 * 1024 * 1024)
    assert kept / run == pytest.approx(0.800, abs=5e-4)
    assert sum(fa._band_sweeps(16384, 16384, 1024, 1024)) == 136
    assert fa.window_tiles(16384, 4096, 512, 512)[1] == 252 * 512 * 512
    assert fa.supports_window(16384, 4096) and fa.supports_window(1024, 4096)
    assert not fa.supports_window(16384 + 8, 4096) and not fa.supports_window(16384, 0)
    with pytest.raises(ValueError, match="window"):
        fa.flash_attention_window(*_qkv(64)[:3], window=0)
    with pytest.raises(ValueError, match="not divisible"):
        fa.flash_attention_window(*_qkv(1000)[:3], window=100, block_q=48, block_k=48)


def test_the_other_families_programs_are_what_they_were():
    """The band is a mask closure and a sweep of its own: the four older
    entries trace to the jaxprs they traced to without this file's fifth
    family (their text names no window and their grids are the old)."""
    q, k, v, _ = _qkv(128, hq=4, hkv=2)
    text = str(jax.make_jaxpr(
        lambda *a: fa.flash_attention(*a, block_q=32, block_k=32))(q, k, v))
    assert "grid=(2, 4, 4, 4)" in text and "window" not in text
    banded = str(jax.make_jaxpr(
        lambda *a: fa.flash_attention_window(*a, window=40, block_q=32, block_k=32))(q, k, v))
    assert "grid=(2, 4, 4, 3)" in banded and "flash_attention_window" in banded


# -- the one backward: a call a family, the residents, the rule of what fits ---------


def _latent_args(seq=128, heads=4, widths=(32, 16, 32), batch=2, seed=3):
    dn, dr, dv = widths
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    q_nope, k_nope = (jax.random.normal(k, (batch, seq, heads, dn)) for k in keys[:2])
    q_rope = jax.random.normal(keys[2], (batch, seq, heads, dr))
    k_rope = jax.random.normal(keys[3], (batch, seq, dr))
    v, w = (jax.random.normal(k, (batch, seq, heads, dv)) for k in keys[4:])
    return (q_nope, q_rope, k_nope, k_rope, v), w


# (entry under tiles of 32, its arguments): every family, the causal one over
# tensors of one part and of two.
def _entries():
    q, k, v, _ = _qkv(128, hq=4, hkv=2)
    tiles = dict(block_q=32, block_k=32)
    return {
        "causal": (functools.partial(fa.flash_attention, **tiles), (q, k, v)),
        "offset": (lambda *a: fa.flash_attention_block(*a, 40, 8, **tiles)[0], (q, k, v)),
        "offset_lse": (lambda *a: fa.flash_attention_block(*a, 40, 8, **tiles)[1], (q, k, v)),
        "block_diffusion": (
            functools.partial(fa.flash_attention_block_diffusion, block_length=4, block=32),
            (q, k, v)),
        "window": (functools.partial(fa.flash_attention_window, window=40, **tiles), (q, k, v)),
        "mla": (functools.partial(fa.flash_attention_mla, **tiles), _latent_args()[0]),
    }


@pytest.mark.parametrize(
    "entry", ["causal", "offset", "offset_lse", "block_diffusion", "window", "mla"]
)
def test_the_backward_of_every_family_is_one_call(entry):
    """The jaxpr of a gradient holds two ``pallas_call``s, the forward that
    keeps its residuals and ONE backward that gives dq, dk and dv (the
    parent made a dq call and a dkv call a run of kv tiles: three, and four
    under block diffusion), on the forward's grid."""
    fn, args = _entries()[entry]
    grad = jax.grad(lambda *a: jnp.sum(fn(*a)), argnums=tuple(range(len(args))))
    text = str(jax.make_jaxpr(grad)(*args))
    assert text.count("pallas_call[") == 2, text.count("pallas_call[")
    forward = str(jax.make_jaxpr(fn)(*args))
    assert forward.count("pallas_call[") == 1
    grids = set(re.findall(r"grid=\([0-9, ]*\)", text))
    assert len(grids) == 1 and grids == set(re.findall(r"grid=\([0-9, ]*\)", forward))


@pytest.mark.parametrize("group", [4, 7])
@pytest.mark.parametrize("family", ["causal", "window", "block_diffusion"])
def test_a_groups_dk_and_dv_are_summed_over_its_q_heads_in_the_residents(family, group):
    """Two kv heads of ``group`` q heads each, four tiles a sequence: dk and
    dv against dense attention, where a kv head's gradient is the sum over
    its group (the resident accumulators take it head after head), and
    against the per-head gradients summed outside."""
    seq, hkv, d = 128, 2, 16
    q, k, v, w = _qkv(seq, hq=hkv * group, hkv=hkv, d=d, batch=1, seed=group)
    if family == "causal":
        flash = functools.partial(fa.flash_attention, block_q=32, block_k=32)
        mask = None
    elif family == "window":
        flash = functools.partial(fa.flash_attention_window, window=48, block_q=32, block_k=32)
        mask = window_mask(seq, 48)
    else:
        flash = functools.partial(fa.flash_attention_block_diffusion, block_length=4, block=32)
        mask = block_diffusion_mask(seq // 2, 4)
    dense = functools.partial(dense_attention, mask=mask)  # None: causal
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * w), (1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * w), (1, 2))(q, k, v)
    # every q head on a kv head of its own, then summed a group
    alone = jax.grad(lambda q, k, v: jnp.sum(dense(q, k, v) * w), (1, 2))(
        q, jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2))
    for name, a, b, c in zip(("dk", "dv"), got, want, alone):
        assert a.shape == (1, seq, hkv, d)
        assert jnp.allclose(a, b, atol=5e-5), (name, float(jnp.abs(a - b).max()))
        summed = c.reshape(1, seq, hkv, group, d).sum(axis=3)
        assert jnp.allclose(a, summed, atol=5e-5), name


@pytest.mark.parametrize("blocks", [(32, 32), (64, 32), (32, 64)])
def test_the_shared_rotary_keys_gradient_is_the_sum_over_four_heads(blocks):
    """The latent family's one rotary key a position: its bracket is a whole
    batch row (every head adds into one resident), the rope-free key's and
    the values' a head. Over four tiles a head, equal and unequal."""
    args, w = _latent_args()
    heads, seq, dr = 4, 128, 16
    flash = functools.partial(flash_attention_mla, block_q=blocks[0], block_k=blocks[1])
    grads = lambda f, *a: jax.grad(  # noqa: E731
        lambda *b: (f(*b) * w).sum(), argnums=(0, 1, 2, 3, 4))(*a)
    got = grads(flash, *args)
    a_head = jnp.broadcast_to(args[3][:, :, None], (2, seq, heads, dr))
    per_head = grads(_per_head_dense, args[0], args[1], args[2], a_head, args[4])
    assert got[3].shape == (2, seq, dr)
    assert jnp.allclose(got[3], per_head[3].sum(axis=2), atol=1e-4)
    for h in range(heads):  # no head's own share is the whole
        assert float(jnp.abs(per_head[3][:, :, h] - got[3]).max()) > 1e-2
    for g, r in zip(got[:3] + got[4:], per_head[:3] + per_head[4:]):
        assert jnp.allclose(g, r, atol=1e-4)


@pytest.mark.parametrize("bq,bk", [(64, 32), (32, 64), (128, 32), (32, 128)])
@pytest.mark.parametrize("family", ["causal", "offset", "window"])
def test_the_backward_at_unequal_tiles_matches_dense(family, bq, bk):
    """dq, dk and dv where a q tile spans several kv tiles and the other way
    round: the residents are addressed by the kv tile, dq's accumulator by
    the q tile."""
    seq = 128
    q, k, v, w = _qkv(seq, hq=4, hkv=2, batch=1, seed=bq + bk)
    tiles = dict(block_q=bq, block_k=bk)
    if family == "causal":
        flash, mask = functools.partial(fa.flash_attention, **tiles), None
    elif family == "offset":  # keys 24 positions behind the queries' start
        flash = lambda *a: fa.flash_attention_block(*a, 24, 0, **tiles)[0]  # noqa: E731
        mask = (jnp.arange(seq)[:, None] + 24) >= jnp.arange(seq)[None, :]
    else:
        flash = functools.partial(fa.flash_attention_window, window=50, **tiles)
        mask = window_mask(seq, 50)
    dense = functools.partial(dense_attention, mask=mask)  # None: causal
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * w), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * w), (0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        assert jnp.allclose(a, b, atol=5e-5), (name, float(jnp.abs(a - b).max()))


# (predicate of a key length, the widths the backward holds): head width
# 128's two residents, the latent cell's three (the rotary 64 a lane tile).
FITS = {
    "supports": (lambda n: fa.supports(n), (128, 128)),
    "supports_window": (lambda n: fa.supports_window(n, 4096), (128, 128)),
    "supports_mla": (lambda n: fa.supports_mla(n, 128, 64, 128), (128, 64, 128)),
    "supports_block_diffusion": (
        lambda n: fa.supports_block_diffusion(n // 2, 4), (128, 128)),
    "ring": (lambda n: fa.choose_tiles("block", 1024, (128,), kv_len=n) is not None,
             (128, 128)),
}


@pytest.mark.parametrize("name", list(FITS))
def test_a_key_length_one_tile_past_what_the_residents_fit_is_refused(name):
    """One rule for every predicate and entry: the backward's float32
    residents and the two buffers of their output blocks, at float32
    outputs, and a step's working set within a v5e core's 128 MiB. The
    last length of whole 2,048s that fits is taken, the next is not (block
    diffusion's keys are both streams, so its streams step by 1,024)."""
    fits, widths = FITS[name]
    lanes = sum(-(-w // 128) * 128 for w in widths)
    room = fa._VMEM_BYTES - fa._STEP_VMEM_BYTES
    last = room // (lanes * 12) // 2048 * 2048
    assert last >= 16384, last  # every cell's key length is held
    assert fa._backward_vmem_bytes(last, widths) <= fa._VMEM_BYTES
    assert fa._backward_vmem_bytes(last + 2048, widths) > fa._VMEM_BYTES
    assert fits(last) and not fits(last + 2048)
    assert fits(16384) and not fits(1 << 20)
    # what the call asks the compiler for is the same sum at its own dtype
    assert fa._backward_vmem_bytes(16384, (128, 128), 2) == 32 * 2**20 + fa._STEP_VMEM_BYTES


def test_an_entry_refuses_a_key_length_the_residents_do_not_fit():
    q = jax.ShapeDtypeStruct((1, 1 << 17, 2, 128), jnp.bfloat16)
    with pytest.raises(ValueError, match="dense_attention"):
        jax.eval_shape(fa.flash_attention, q, q, q)


def test_the_hash_tool_finds_the_device_program_in_a_serialized_executable():
    """tools/flash_program_hash.py: a varint length, then a message whose
    field 8 holds the program in its field 3, past fields of every other
    wire type."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(__file__), os.pardir, "tools", "flash_program_hash.py")
    spec = importlib.util.spec_from_file_location("flash_program_hash", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)

    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)

    def delimited(number, payload):
        return varint(number << 3 | 2) + varint(len(payload)) + payload

    program = bytes(range(256)) * 3
    inner = varint(1 << 3) + varint(300) + delimited(2, b"name") + delimited(3, program)
    outer = (varint(5 << 3 | 1) + b"\0" * 8 + varint(6 << 3 | 5) + b"\0" * 4
             + delimited(7, b"x" * 200) + delimited(8, inner))
    assert tool.device_program(varint(len(outer)) + outer + b"trailing") == program
