"""Single-chip MFU tuning sweep: times the RAW compiled train step on the
flagship model across flash tile sizes / remat / batch configs and prints
one JSON line per config (ms/step, tokens/s, est. MFU).

MFU tuning needs on-chip A/B at full step granularity — kernel
micro-benchmarks are mostly dispatch overhead, so each config runs the
complete fwd+bwd+optimizer step, all configs in ONE process (one process
owns the chip, and one compile cache serves the whole grid).

Run on the real chip:
    python tools/mfu_sweep.py                       # default grid
    python tools/mfu_sweep.py --configs 512x512x0   # BQxBKxREMAT picks
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def run_config(block_q: int, block_k: int, remat: bool, B: int, S: int,
               steps: int, warmup: int, preset: str = "small",
               loss_chunk: int = 0) -> dict:
    from torchft_tpu.parallel import train as train_mod

    # _LOSS_CHUNK is read at trace time (make_train_step re-jits per
    # config), so a direct module override A/Bs chunk sizes without env
    # mutation or module reloads; restored in the finally below.
    saved_chunk = train_mod._LOSS_CHUNK
    if loss_chunk:
        train_mod._LOSS_CHUNK = loss_chunk
    try:
        return _run_config_inner(
            train_mod, block_q, block_k, remat, B, S, steps, warmup,
            preset, loss_chunk,
        )
    finally:
        train_mod._LOSS_CHUNK = saved_chunk


def _run_config_inner(train_mod, block_q, block_k, remat, B, S, steps,
                      warmup, preset, loss_chunk):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from torchft_tpu.models import llama_debug, llama_small
    from torchft_tpu.parallel import auto_mesh

    build_model = train_mod.build_model
    init_train_state = train_mod.init_train_state
    make_train_step = train_mod.make_train_step

    base = llama_small if preset == "small" else llama_debug
    cfg = base(
        remat=remat,
        attn_impl="flash",
        flash_min_seq=1024,
        flash_block_q=block_q,
        flash_block_k=block_k,
    )
    mesh = auto_mesh(1)
    model = build_model(cfg, mesh)
    state, shardings = init_train_state(
        model, mesh, jax.random.PRNGKey(0), (B, S)
    )
    step = make_train_step(model, mesh, shardings)
    rng = np.random.default_rng(0)
    batch = {
        "inputs": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32
        ),
        "targets": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32
        ),
        "mask": jnp.ones((B, S), jnp.int32),
    }
    t_compile0 = time.perf_counter()
    for _ in range(max(warmup, 1)):  # >=1: the compile must not be timed
        state, metrics = step(state, batch)
    jax.block_until_ready(metrics["loss"])
    compile_s = time.perf_counter() - t_compile0
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step(state, batch)
    jax.block_until_ready(metrics["loss"])
    dt = (time.perf_counter() - t0) / steps

    n_params = sum(
        int(np.prod(p.shape))
        for p in jax.tree_util.tree_leaves(state.params)
    )
    kind = jax.devices()[0].device_kind
    # Same estimates as the headline bench (which also pulls these from
    # torchft_tpu.perf), or sweep-MFU and bench-MFU stop being comparable.
    from torchft_tpu.perf import flops_per_step, peak_tflops

    flops = flops_per_step(n_params, cfg, B, S)
    peak = peak_tflops(kind)
    mfu = (flops / dt / 1e12) / peak if peak else None
    del state, batch  # free HBM before the next config
    return {
        "block_q": block_q,
        "block_k": block_k,
        "remat": remat,
        "loss_chunk": loss_chunk or None,
        "batch": [B, S],
        "ms_per_step": round(dt * 1e3, 2),
        "tokens_per_sec": round(B * S / dt, 1),
        "mfu_est": round(mfu, 4) if mfu is not None else None,
        "compile_plus_warmup_s": round(compile_s, 1),
        "device_kind": kind,
    }


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument(
        "--configs",
        nargs="*",
        # Order = the chip-free ranking (tools/mfu_cost_rank.py): larger flash tiles first (fewer
        # K-passes; the analytic VMEM budget admits them at S=1024),
        # current default as the baseline draw, remat=1 last (priced
        # analytically at ~+1 fwd pass ~= +33% flops for -54% bytes
        # accessed / -87% transient — only wins if the step profiles
        # memory/bandwidth-bound; never read remat's cost from the raw
        # cost-analysis delta, which is body-once-invalid).  Scarce
        # chip minutes measure candidates top-down.
        default=["512x1024x0", "1024x512x0", "1024x1024x0", "512x512x0",
                 "256x1024x0", "512x512x1"],
        help="BQxBKxREMAT triples, best-candidate-first",
    )
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=1024)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--model", choices=["small", "debug"], default="small",
                   help="debug = tiny config for CPU smoke of the sweep "
                   "harness itself")
    p.add_argument("--loss-chunks", nargs="*", type=int, default=[256],
                   help="additionally sweep TORCHFT_LOSS_CHUNK values at "
                   "the best flash config (default: one draw at 256 — "
                   "the r05 ranked attack order's item 3; 128 is the "
                   "built-in chunk)")
    args = p.parse_args()

    sys.path.insert(0, ".")
    best = None

    def run_and_record(best, err_tag, **cfg):
        try:
            r = run_config(
                cfg.pop("bq"), cfg.pop("bk"), cfg.pop("rm"),
                args.batch, args.seq, args.steps, args.warmup,
                preset=args.model, **cfg,
            )
        except Exception as e:  # noqa: BLE001 - keep sweeping
            r = dict(err_tag, error=str(e)[:200])
        print(json.dumps(r), flush=True)
        if "ms_per_step" in r and (
            best is None or r["ms_per_step"] < best["ms_per_step"]
        ):
            best = r
        return best

    for spec in args.configs:
        bq, bk, rm = (int(x) for x in spec.split("x"))
        best = run_and_record(
            best, {"block_q": bq, "block_k": bk, "remat": bool(rm)},
            bq=bq, bk=bk, rm=bool(rm),
        )
    # Loss-chunk sweep at the best (or default) flash config.  DEMOTED
    # from r3's suspect #1: scan-corrected cost analysis (r05,
    # tools/mfu_cost_rank.py) shows total flops are chunk-INDEPENDENT —
    # only scan-iteration overhead vs transient bytes distinguish
    # chunks, a <=1-2% lever.  Worth one draw (256), not a grid.
    for lc in args.loss_chunks:
        bq = best["block_q"] if best else 512
        bk = best["block_k"] if best else 512
        rm = best["remat"] if best else False
        best = run_and_record(
            best, {"loss_chunk": lc},
            bq=bq, bk=bk, rm=bool(rm), loss_chunk=lc,
        )
    if best:
        print(json.dumps({"best": best}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
