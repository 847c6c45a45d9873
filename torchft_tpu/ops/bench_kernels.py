"""Compiled-mode Pallas kernel validation + microbenchmark.

Role of the reference's GPU-gated kernel tests
(``torchft/quantization_test.py`` / ``collectives_test.py``, which only
assert numerics when a CUDA device is present): every CPU test in this
repo runs the kernels through the Pallas INTERPRETER, so compiled-mode
numerics and latency are asserted nowhere a CI record exists.  This
harness runs the int8 quantize/dequantize/fused-reduce kernels, flash
attention, the chunked state-space scan and the chunked gated delta rule
COMPILED on whatever backend is live, checks parity against dense/fp32/plain-XLA references, and
prints one JSON line.

Run:  python -m torchft_tpu.ops.bench_kernels          # any backend
      python -m torchft_tpu.ops.bench_kernels --chip   # fails off-TPU
      python -m torchft_tpu.ops.bench_kernels --tiles  # the flash kernels' tile sweep
      python -m torchft_tpu.ops.bench_kernels --gdn    # the gated delta rule alone
      python -m torchft_tpu.ops.bench_kernels --kda    # the rule with a decay a key channel
"""

from __future__ import annotations

import json
import sys
import time
from typing import Callable


def _time_call(fn: Callable, *args, reps: int = 20) -> float:
    """Median-of-reps wall ms for a jitted call (block_until_ready)."""
    import jax

    out = fn(*args)
    jax.block_until_ready(out)  # compile + warm
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] * 1e3


def _rel(got, want) -> float:
    """Relative L2 distance, in float32."""
    import jax.numpy as jnp

    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.linalg.norm(got - want) / (jnp.linalg.norm(want) + 1e-30))


def ssd_section(compiled: bool) -> dict:
    """The chunked state-space scan, ``ops/ssd.py``'s kernels against the
    plain ``ssd_chunked``: parity of y and of the five gradients, and
    milliseconds forward and forward plus backward. Compiled: the shapes
    of the ``nemotron3-raw`` cell (2 x 8192, 64 heads of 64 on 8 groups,
    state 128, bf16). Interpreted: the smallest the kernels take."""
    import jax
    import jax.numpy as jnp

    from torchft_tpu.models.mamba2 import ssd_chunked
    from torchft_tpu.ops import ssd as ssd_kernel

    b, s, g, r, p, n = (2, 8192, 8, 8, 64, 128) if compiled else (1, 256, 1, 2, 64, 128)
    h, chunk, dtype = g * r, 128, jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    args = (
        jax.random.normal(ks[0], (b, s, h, p)).astype(dtype),
        jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)) - 2.0),
        -jnp.exp(jax.random.uniform(ks[2], (h,), minval=0.0, maxval=2.77)),
        (0.3 * jax.random.normal(ks[3], (b, s, g, n))).astype(dtype),
        (0.3 * jax.random.normal(ks[4], (b, s, g, n))).astype(dtype),
    )
    weight = jax.random.normal(ks[5], (b, s, h, p))

    def both(form):
        fwd = jax.jit(lambda *a: form(*a, chunk, dtype))
        grad = jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(form(*a, chunk, dtype) * weight), argnums=(0, 1, 2, 3, 4)
        ))
        return fwd, grad

    k_fwd, k_grad = both(ssd_kernel.ssd)
    x_fwd, x_grad = both(ssd_chunked)
    out = {
        "shape": [b, s, h, p], "groups": g, "state": n, "chunk": chunk,
        "y_rel_vs_xla": _rel(k_fwd(*args), x_fwd(*args)),
        "grad_rel_vs_xla": {
            name: _rel(got, want) for name, got, want in
            zip(("x", "dt", "a", "b", "c"), k_grad(*args)[1], x_grad(*args)[1])
        },
    }
    reps = 20 if compiled else 1
    out["kernel_fwd_ms"] = round(_time_call(k_fwd, *args, reps=reps), 3)
    out["kernel_fwd_bwd_ms"] = round(_time_call(k_grad, *args, reps=reps), 3)
    # What the residual costs: the forward kernel alone, in its own
    # layouts, without and with each chunk's entering state written out.
    laid = ssd_kernel.kernel_layout(*args, dtype)
    for name, save in (("kernel_alone_fwd_ms", False), ("kernel_alone_fwd_saving_ms", True)):
        call = lambda *t, save=save: ssd_kernel.ssd_fwd(  # noqa: E731
            *t, jnp.dtype(dtype), save, not compiled
        )
        out[name] = round(_time_call(call, *laid, reps=reps), 3)
    out["xla_fwd_ms"] = round(_time_call(x_fwd, *args, reps=reps), 3)
    out["xla_fwd_bwd_ms"] = round(_time_call(x_grad, *args, reps=reps), 3)
    return out


_V5E_HBM_BYTES = 819e9  # benchmark/peaks.json, "TPU v5 lite"


def _rule_section(compiled, args, dv, dtype, kernel, plain, alone, token_bytes) -> dict:
    """A chunked delta rule alone, kernels against the plain form, both as
    (q, k, v, g, beta, chunk, dtype) -> (o, last state): parity of o, of the
    last state and of the five gradients, milliseconds forward and forward
    plus backward, the forward kernel ``alone(save)`` in its own layouts
    without and with its residuals written out, and on a chip the share of
    the least time a v5e needs for the same rule (memory-bound:
    ``token_bytes`` = a token's inputs and its o, as the benchmark's
    ``*_bytes_per_step`` count them)."""
    import jax
    import jax.numpy as jnp

    from torchft_tpu.models.gated_delta import CHUNK

    b, s, h, dk = args[0].shape
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    weight = jax.random.normal(ks[0], (b, s, h, dv))
    state_weight = jax.random.normal(ks[1], (b, h, dk, dv))

    def both(form):
        fwd = jax.jit(lambda *a: form(*a, CHUNK, dtype))

        def scalar(*a):
            o, last = form(*a, CHUNK, dtype)
            return jnp.sum(o * weight) + jnp.sum(last * state_weight)

        return fwd, jax.jit(jax.value_and_grad(scalar, argnums=(0, 1, 2, 3, 4)))

    k_fwd, k_grad = both(kernel)
    x_fwd, x_grad = both(plain)
    (o, last), (o_x, last_x) = k_fwd(*args), x_fwd(*args)
    out = {
        "shape": [b, s, h, dk, dv], "chunk": CHUNK,
        "o_rel_vs_xla": _rel(o, o_x), "state_rel_vs_xla": _rel(last, last_x),
        "grad_rel_vs_xla": {
            name: _rel(got, want) for name, got, want in
            zip(("q", "k", "v", "g", "beta"), k_grad(*args)[1], x_grad(*args)[1])
        },
    }
    reps = 20 if compiled else 1
    out["kernel_fwd_ms"] = round(_time_call(k_fwd, *args, reps=reps), 3)
    out["kernel_fwd_bwd_ms"] = round(_time_call(k_grad, *args, reps=reps), 3)
    for name, save in (("kernel_alone_fwd_ms", False), ("kernel_alone_fwd_saving_ms", True)):
        call, laid = alone(save)
        out[name] = round(_time_call(call, *laid, reps=reps), 3)
    out["xla_fwd_ms"] = round(_time_call(x_fwd, *args, reps=reps), 3)
    out["xla_fwd_bwd_ms"] = round(_time_call(x_grad, *args, reps=reps), 3)
    if compiled:  # a share of a roofline comes from a chip run only
        ins, o_bytes = token_bytes
        fwd_bytes = (ins + o_bytes) * b * s
        least = {"fwd": fwd_bytes, "fwd_bwd": 2 * fwd_bytes + ins * b * s}
        for name, nbytes in least.items():
            ms = 1e3 * nbytes / _V5E_HBM_BYTES
            out[f"least_{name}_ms"] = round(ms, 3)
            for form in ("kernel", "xla"):
                out[f"{form}_{name}_share_of_least_pct"] = round(
                    100 * ms / out[f"{form}_{name}_ms"], 2
                )
    return out


def _rule_inputs(b, s, h, dk, dv, dtype, channel_decay):
    """Unit keys, scaled unit queries, values in the compute type, a
    log-decay a head (or a key channel) and beta over (0, 2)."""
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa: E731
    return (
        unit(jax.random.normal(ks[0], (b, s, h, dk))) * dk ** -0.5,
        unit(jax.random.normal(ks[1], (b, s, h, dk))),
        jax.random.normal(ks[2], (b, s, h, dv)).astype(dtype),
        -0.1 * jax.nn.softplus(
            jax.random.normal(ks[3], (b, s, h, dk) if channel_decay else (b, s, h))
        ),
        2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h))),
    )


def gdn_section(compiled: bool) -> dict:
    """The chunked gated delta rule alone, ``ops/gated_delta.py``'s kernels
    against the plain ``gated_delta_chunked`` (``_rule_section``); the least
    time from the bytes of q, k, v in bf16, g and beta in float32 and o in
    bf16 once forward, those, o's gradient and the five gradients backward,
    as the benchmark's ``gdn_bytes_per_step`` counts them.
    Compiled: the shapes of the ``olmo-hybrid-raw`` cell (2 x 8192, 15 heads,
    keys of 96, values of 192, bf16). Interpreted: the smallest the
    kernels take."""
    import jax.numpy as jnp

    from torchft_tpu.models.gated_delta import gated_delta_chunked
    from torchft_tpu.ops import gated_delta as gdn_kernel

    b, s, h, dk, dv = (2, 8192, 15, 96, 192) if compiled else (1, 256, 2, 16, 32)
    dtype, interpret = jnp.bfloat16, not compiled
    args = _rule_inputs(b, s, h, dk, dv, dtype, channel_decay=False)

    def alone(save):
        return (
            lambda *t: gdn_kernel.gdn_fwd(*t, jnp.dtype(dtype), save, interpret),
            gdn_kernel.kernel_layout(*args),
        )

    return _rule_section(
        compiled, args, dv, dtype,
        lambda *a: gdn_kernel.gated_delta(*a, interpret=interpret), gated_delta_chunked,
        alone, (2 * h * (2 * dk + dv) + 2 * 4 * h, 2 * h * dv),
    )


def kda_section(compiled: bool) -> dict:
    """The chunked delta rule with a decay a key channel alone,
    ``ops/kda.py``'s kernels against the plain ``kda_chunked``
    (``_rule_section``); the least time as the benchmark's
    ``kda_bytes_per_step`` counts it (the log-decay is float32 a channel).
    Compiled: the shapes of the ``solar-open2-raw`` cell (2 x 8192, 8 heads
    of 128, bf16). Interpreted: the smallest the kernels take."""
    import jax.numpy as jnp

    from torchft_tpu.models.gated_delta import kda_chunked
    from torchft_tpu.ops import kda as kda_kernel

    b, s, h, d = (2, 8192, 8, 128) if compiled else (1, 256, 2, 16)
    dtype, interpret = jnp.bfloat16, not compiled
    args = _rule_inputs(b, s, h, d, d, dtype, channel_decay=True)

    def alone(save):
        return (
            lambda *t: kda_kernel.kda_fwd(*t, jnp.dtype(dtype), save, interpret),
            kda_kernel.kernel_layout(*args),
        )

    return _rule_section(
        compiled, args, d, dtype,
        lambda *a: kda_kernel.kda(*a, interpret=interpret), kda_chunked,
        alone, (2 * 3 * h * d + 4 * h * d + 4 * h, 2 * h * d),
    )


# The flash kernels' tile sweep (PERF.md section 6, PR 52): every family at
# every benchmark cell's shape. (family, cell, (B, S, Hq, Hkv), head widths
# (qk, ..., v), block length): causal heads are one width; the latent
# family's are (rope-free, rotary, value); block diffusion's S is both streams.
# The last number is block diffusion's block length, the banded family's
# window and the selected family's keys a query (a scattered selection from
# seeded scores: every causal tile pair runs, as in the cell).
SWEEP_SHAPES = [
    ("causal", "mistral", (4, 4096, 32, 8), (128,), 0),
    ("causal", "internlm2", (2, 8192, 16, 8), (128,), 0),
    ("causal", "olmoe", (4, 4096, 16, 16), (128,), 0),
    ("causal", "nemotron3", (2, 8192, 32, 2), (128,), 0),
    ("causal", "lfm2", (2, 8192, 32, 8), (64,), 0),
    ("block_diffusion", "sdar", (2, 16384, 32, 4), (128,), 4),
    ("mla", "joyai", (2, 8192, 32, 32), (128, 64, 128), 0),
    ("window", "smallthinker", (1, 16384, 28, 4), (128,), 4096),
    ("window", "trinity", (1, 16384, 32, 4), (128,), 2048),
    ("selected", "keye", (1, 16384, 32, 4), (128,), 2048),
]
SWEEP_TILES = [(512, 512), (1024, 512), (512, 1024), (1024, 1024), (2048, 512), (512, 2048)]
_V5E_BF16_FLOPS = 197e12  # benchmark/peaks.json, "TPU v5 lite"


def _sweep_case(family, dims, widths, block_length, tiles, interpret):
    """(inputs in the kernels' [B,H,S,D] layout, forward, the forward that
    keeps its residuals, the backward given them, forward FLOPs by the
    benchmark's flops.py convention: 2 FLOP a kept score entry and channel
    of QK^T and of PV)."""
    import jax
    import jax.numpy as jnp

    from torchft_tpu.ops import flash_attention as fa

    B, S, Hq, Hkv = dims
    bq, bk = tiles
    rand = lambda i, *shape: jax.random.normal(  # noqa: E731
        jax.random.PRNGKey(i), shape, jnp.float32
    ).astype(jnp.bfloat16)
    # One name a stage for every family: the ``custom_vjp``, the forward that
    # keeps its residuals, the backward given them; a family is its value
    # and the operands that lead its calls.
    lead = ()
    if family == "mla":
        dn, dr, dv = widths
        args = (rand(0, B, Hq, S, dn), rand(1, B, Hq, S, dr), rand(2, B, Hq, S, dn),
                rand(3, B, 1, S, dr), rand(4, B, Hq, S, dv))
        value = fa._Causal(S, S, bq, bk)
        split, entries, width = 2, S * S / 2, dn + dr + dv
    else:
        (d,) = widths
        args = (rand(0, B, Hq, S, d), rand(1, B, Hkv, S, d), rand(2, B, Hkv, S, d))
        if family == "causal":
            value, entries = fa._Causal(S, S, bq, bk), S * S / 2
        elif family == "window":
            window = block_length
            value, entries = fa._Window(S, S, bq, bk, window), fa.window_kept(S, window)
        elif family == "selected":
            from torchft_tpu.ops import sparse_index as dsa

            scores = jax.random.normal(jax.random.PRNGKey(9), (B, S, S), jnp.float32)
            words, runs, _ = dsa.select(scores, block_length, bq, bk)
            lead = (runs.reshape(-1), words)
            value = fa._Selected(S, S, bq, bk, width=words.shape[-1])
            entries = fa.window_kept(S, block_length)  # min(topk, t + 1) a row: the band's count
        else:
            L = S // 2
            value, entries = fa._BlockDiffusion(S, S, bq, bq, block_length), L * L + L * block_length
        split, width = 1, 2 * d
    parts = lambda a: (a[:split], a[split:-1], a[-1])  # noqa: E731 - q's parts, k's, v
    out_of = (lambda o: o[0]) if value.lse_out else (lambda o: o)  # noqa: E731
    fwd = lambda *a: out_of(fa._flash(value, lead, *parts(a), interpret))  # noqa: E731
    res = lambda *a: fa._forward_impl(value, lead, *parts(a), interpret)  # noqa: E731

    def bwd(a, do, out, lse):
        dq, dk, dv_ = fa._backward_impl(
            value, lead, *parts(a), do, lse, fa._row_delta(do, out), None, interpret
        )
        return (*dq, *dk, *dv_)

    flops = 2.0 * width * entries * Hq * B
    return args, fwd, res, bwd, flops


def tile_sweep(shapes=None, tiles=None, reps: int = 8) -> list:
    """Forward, forward + backward, and the one backward call alone (dq,
    dk and dv from the forward's residuals), ms a call and share of the
    v5e's bf16 peak by the architecture's work (the backward's: twice the
    forward's, nothing recomputed), every family at every
    cell's shape over ``tiles``. The public entries choose their tiles
    (``flash_attention.choose_tiles``), so the sweep drives the
    ``custom_vjp`` functions under them, at the tiles it is asked for, on
    inputs already in the kernels' layout. A tile the compiler refuses
    (scoped VMEM) is a row with its error."""
    import jax
    import jax.numpy as jnp

    compiled = jax.default_backend() == "tpu"
    rows = []
    for family, cell, dims, widths, b in shapes or SWEEP_SHAPES:
        for tile in tiles or SWEEP_TILES:
            if family == "block_diffusion" and tile[0] != tile[1]:
                continue  # its kernels take one square tile
            row = {"family": family, "cell": cell, "shape": list(dims),
                   "widths": list(widths), "tiles": list(tile)}
            try:
                args, fwd, res, bwd, flops = _sweep_case(
                    family, dims, widths, b, tile, not compiled
                )
                n = len(args)
                loss = lambda *a: jnp.sum(fwd(*a).astype(jnp.float32))  # noqa: E731
                both = jax.jit(jax.value_and_grad(loss, argnums=tuple(range(n))))
                out, lse = jax.jit(res)(*args)
                # the output stands in for its cotangent: a time needs no other
                back = jax.jit(lambda a, out, lse: bwd(a, out, out, lse))
                ms = {
                    "fwd": _time_call(jax.jit(fwd), *args, reps=reps),
                    "fwd_bwd": _time_call(both, *args, reps=reps),
                    "bwd": _time_call(back, args, out, lse, reps=reps),
                }
                row["ms"] = {k: round(v, 3) for k, v in ms.items()}
                if compiled:  # a share of the chip's peak is the chip's to give
                    work = {"fwd": flops, "fwd_bwd": 3 * flops, "bwd": 2 * flops}
                    row["roofline_pct"] = {
                        k: round(100 * work[k] / _V5E_BF16_FLOPS / (v / 1e3), 2)
                        for k, v in ms.items()
                    }
            except Exception as e:  # noqa: BLE001 - the compiler's refusal is the finding
                row["error"] = repr(e)[:240]
            print(json.dumps(row), flush=True)
            rows.append(row)
    return rows


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from torchft_tpu.collectives import (
        dequantize_blockwise,
        quantize_blockwise,
    )
    from torchft_tpu.models.llama import dense_attention
    from torchft_tpu.ops.flash_attention import flash_attention
    from torchft_tpu.ops.quantization import (
        BLOCK,
        fused_dequantize,
        fused_dequantize_int8,
        fused_quantize,
        fused_quantize_int8,
        fused_reduce_int8,
    )

    if "--tiles" in sys.argv[1:]:
        # A line a (family, shape, tile); 2,048 a side may not fit the
        # scoped VMEM (a row with its error), every tile under it has to run.
        print(json.dumps({"device_kind": jax.devices()[0].device_kind}), flush=True)
        refused = [r for r in tile_sweep() if "error" in r and max(r["tiles"]) <= 1024]
        return 1 if refused else 0
    backend = jax.default_backend()
    device_kind = jax.devices()[0].device_kind
    compiled = backend == "tpu"  # off-TPU these run interpreted
    if "--chip" in sys.argv[1:] and not compiled:
        print(
            f"bench_kernels --chip: JAX gave {backend!r}; the kernels "
            "would run interpreted",
            file=sys.stderr,
        )
        return 2
    for flag, section in (("gdn", gdn_section), ("kda", kda_section)):
        if f"--{flag}" in sys.argv[1:]:  # one delta rule's section alone
            print(json.dumps({"device_kind": device_kind, flag: section(compiled)}), flush=True)
            return 0
    result: dict = {
        "backend": backend,
        "device_kind": device_kind,
        "compiled": compiled,
    }

    rng = np.random.default_rng(0)

    # ---- int8 quantize/dequantize vs the host-numpy reference ----------
    # The invariants the wire protocol actually relies on (TPU divide is
    # not correctly-rounded IEEE, so quantize is NOT bit-exact vs host —
    # round-boundary values flip by one level, measured 7/4.2M on v5e):
    #   1. DEQUANTIZE is bit-exact host vs device (int8*fp32 multiply is
    #      exact) — this is what makes cross-replica bitwise equality
    #      hold, since each wire chunk is requantized by exactly one rank
    #      and every replica decodes the same bytes.
    #   2. Device quantize differs from host by at most 1 int8 level, on
    #      a vanishing fraction of values; scales agree to 1 ulp.
    #   3. Roundtrip error stays within the half-step quantization bound.
    n = 4 * 1024 * 1024
    x_host = rng.standard_normal(n).astype(np.float32)
    x = jnp.asarray(x_host)
    q, s, _ = fused_quantize_int8(x)
    jax.block_until_ready(q)
    q_ref, s_ref = quantize_blockwise(x_host)
    q_dev = np.asarray(q).reshape(-1)[: q_ref.size].astype(np.int32)
    level_diff = np.abs(q_dev - q_ref.astype(np.int32))
    s_dev = np.asarray(s)[: s_ref.size]
    # Per-BLOCK relative error (normalizing by the global max would let a
    # tiny block's scale diverge wildly and still pass).
    scale_rel_err = float(
        (np.abs(s_dev - s_ref) / (np.abs(s_ref) + 1e-30)).max()
    )
    # Host dequant of the device payload vs device dequant of the same
    # payload: must be bit-identical.
    dd = np.asarray(fused_dequantize_int8(q, s, n))
    dh = dequantize_blockwise(np.asarray(q).reshape(-1), s_dev, n)
    dequant_exact = bool(np.array_equal(dd, dh))
    # Roundtrip bound: |x - dq| <= ~half a quantization step (with 1-ulp
    # headroom for the scale disagreement).
    per_elem_scale = np.repeat(s_dev, BLOCK)[:n]
    rt_ok = bool(
        (np.abs(dd - x_host) <= 0.501 * per_elem_scale + 1e-7).all()
    )
    result["quantize"] = {
        "n": n,
        "dequantize_bit_exact": dequant_exact,
        "quantize_max_level_diff_vs_host": int(level_diff.max()),
        "quantize_level_diff_count": int((level_diff != 0).sum()),
        "scale_rel_err_vs_host": scale_rel_err,
        "roundtrip_within_half_step": rt_ok,
        "quantize_ms": round(_time_call(fused_quantize_int8, x), 3),
        "dequantize_ms": round(
            _time_call(lambda: fused_dequantize_int8(q, s, n)), 3
        ),
    }

    # ---- int4 codec (nibble-packed wire) -------------------------------
    q4, s4, _ = fused_quantize(x, 4)
    jax.block_until_ready(q4)
    q4_ref, s4_ref = quantize_blockwise(x_host, bits=4)
    q4_dev = np.asarray(q4).reshape(-1)[: q4_ref.size]
    # Same-payload decode must be bit-identical on either end.
    dd4 = np.asarray(fused_dequantize(q4_ref, s4_ref, n, 4))
    dh4 = dequantize_blockwise(q4_ref, s4_ref, n, bits=4)
    result["quantize_int4"] = {
        "payload_bytes_per_value": 0.5,
        # Counts PACKED BYTES where device packing differs from the host
        # packer (each byte holds two nibbles; same tolerance class as
        # the int8 1-level divide flips).
        "pack_mismatch_byte_count": int(
            (q4_dev != q4_ref.astype(np.int8)).sum()
        ),
        "dequantize_bit_exact": bool(np.array_equal(dd4, dh4)),
        "quantize_ms": round(
            _time_call(lambda: fused_quantize(x, 4)), 3
        ),
    }

    # ---- fused reduce vs fp32 sum --------------------------------------
    ranks = 4
    xs = rng.standard_normal((ranks, 512 * 256)).astype(np.float32)
    qs, ss = zip(*(quantize_blockwise(xs[r]) for r in range(ranks)))
    q3 = jnp.stack([jnp.asarray(qq).reshape(-1, 512) for qq in qs])
    s3 = jnp.stack([jnp.asarray(sq) for sq in ss])
    qo, so = fused_reduce_int8(q3, s3)
    got = dequantize_blockwise(
        np.asarray(qo).reshape(-1), np.asarray(so), xs.shape[1]
    )
    # Exact sum of the DEQUANTIZED inputs (the kernel's contract), then
    # one more quantize round of error.
    want = sum(
        dequantize_blockwise(np.asarray(qs[r]), np.asarray(ss[r]),
                             xs.shape[1])
        for r in range(ranks)
    )
    denom = np.abs(want).max() + 1e-9
    result["fused_reduce"] = {
        "ranks": ranks,
        "rel_err": float(np.abs(got - want).max() / denom),
        "reduce_ms": round(_time_call(fused_reduce_int8, q3, s3), 3),
    }

    # ---- flash attention vs dense --------------------------------------
    B, S, H, D = 2, 1024, 8, 64
    qkv = [
        jnp.asarray(
            rng.standard_normal((B, S, H, D)), jnp.bfloat16
        )
        for _ in range(3)
    ]
    flash_out = np.asarray(
        flash_attention(*qkv, causal=True), dtype=np.float32
    )
    dense_out = np.asarray(
        dense_attention(*qkv, causal=True), dtype=np.float32
    )
    scale = np.abs(dense_out).max() + 1e-9
    flash_fn = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
    dense_fn = jax.jit(lambda q, k, v: dense_attention(q, k, v, causal=True))
    result["flash_attention"] = {
        "shape": [B, S, H, D],
        "rel_err_vs_dense": float(np.abs(flash_out - dense_out).max() / scale),
        "flash_ms": round(_time_call(flash_fn, *qkv), 3),
        "dense_ms": round(_time_call(dense_fn, *qkv), 3),
    }

    # Long-sequence latency point: at S=1024 dispatch overhead is a
    # large part of both kernels; at S=8192 the O(S^2) work dominates,
    # so this is the pair that actually shows the flash-vs-dense win
    # (and the HBM saving: dense materializes the S^2 logits).
    S_long = 8192
    qkv_long = [
        jnp.asarray(
            rng.standard_normal((1, S_long, 8, 64)), jnp.bfloat16
        )
        for _ in range(3)
    ]
    try:
        dense_long_ms = round(_time_call(dense_fn, *qkv_long), 3)
    except Exception:  # dense S^2 logits can OOM a shared chip
        dense_long_ms = None
    result["flash_attention_long"] = {
        "shape": [1, S_long, 8, 64],
        "flash_ms": round(_time_call(flash_fn, *qkv_long), 3),
        "dense_ms": dense_long_ms,
    }

    # ---- offset-block kernel (ring attention's per-step fold) ----------
    # Full causal attention assembled from two streamed kv blocks via an
    # online-softmax merge must match dense — the single-chip proxy for
    # the ring step (mathematically equivalent to the fold in
    # parallel/ring_attention.py, which uses a logaddexp formulation).
    from torchft_tpu.ops.flash_attention import flash_attention_block

    half = S // 2
    q_, k_, v_ = qkv

    def merge(o1, l1, o2, l2):
        m = jnp.maximum(l1, l2)
        w1 = jnp.exp(l1 - m)[..., None]
        w2 = jnp.exp(l2 - m)[..., None]
        o1 = jnp.swapaxes(o1, 1, 2).astype(jnp.float32)
        o2 = jnp.swapaxes(o2, 1, 2).astype(jnp.float32)
        out = (o1 * w1 + o2 * w2) / (w1 + w2)
        return jnp.swapaxes(out, 1, 2)

    o1, l1 = flash_attention_block(q_, k_[:, :half], v_[:, :half], 0, 0)
    o2, l2 = flash_attention_block(q_, k_[:, half:], v_[:, half:], 0, half)
    block_out = np.asarray(merge(o1, l1, o2, l2), dtype=np.float32)
    # Two latency points: the diagonal block (causal-masked, the first
    # ring step) and a fully-in-the-past block (no masking, the common
    # case in an N-step ring) — the past block is the one to budget
    # ring-step time from.
    diag_fn = jax.jit(
        lambda q, k, v: flash_attention_block(q, k, v, 0, 0)
    )
    past_fn = jax.jit(
        lambda q, k, v: flash_attention_block(q, k, v, half, 0)
    )
    result["flash_block_merge"] = {
        "kv_blocks": 2,
        "rel_err_vs_dense": float(
            np.abs(block_out - dense_out).max() / scale
        ),
        "block_diag_ms": round(
            _time_call(diag_fn, q_[:, :half], k_[:, :half], v_[:, :half]),
            3,
        ),
        "block_past_ms": round(
            _time_call(past_fn, q_[:, half:], k_[:, :half], v_[:, :half]),
            3,
        ),
    }

    # ---- chunked state-space scan: kernels vs plain XLA -----------------
    result["ssd"] = ssd_section(compiled)

    # ---- chunked gated delta rule: kernels vs plain XLA -------------------
    result["gdn"] = gdn_section(compiled)
    result["kda"] = kda_section(compiled)

    ok = (
        result["quantize"]["dequantize_bit_exact"]
        and result["quantize"]["quantize_max_level_diff_vs_host"] <= 1
        and result["quantize"]["quantize_level_diff_count"] <= n // 10_000
        and result["quantize"]["scale_rel_err_vs_host"] < 1e-6
        and result["quantize"]["roundtrip_within_half_step"]
        and result["quantize_int4"]["dequantize_bit_exact"]
        # nibble packing may inherit the same 1-level divide flips
        and result["quantize_int4"]["pack_mismatch_byte_count"]
        <= n // 10_000
        and result["fused_reduce"]["rel_err"] < 0.02
        and result["flash_attention"]["rel_err_vs_dense"] < 0.03
        and result["flash_block_merge"]["rel_err_vs_dense"] < 0.03
        # two roundings of bf16 operands apart; dt and a sum many such terms
        and result["ssd"]["y_rel_vs_xla"] < 0.01
        and max(result["ssd"]["grad_rel_vs_xla"].values()) < 0.08
        and all(
            max(result[rule]["o_rel_vs_xla"], result[rule]["state_rel_vs_xla"]) < 0.01
            and max(result[rule]["grad_rel_vs_xla"].values()) < 0.08
            for rule in ("gdn", "kda")
        )
    )
    result["ok"] = bool(ok)
    print(json.dumps(result), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
