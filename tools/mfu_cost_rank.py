"""Chip-free MFU candidate ranking: compile the flagship step at bench
shapes on the VIRTUAL backend and rank the tuning candidates by
HLO-level evidence (XLA cost analysis + memory analysis), so scarce
chip minutes are spent MEASURING the top candidate instead of
exploring.

What is and is not knowable off-chip:

- ``TORCHFT_LOSS_CHUNK`` and ``remat``: fully XLA-visible.  The chunked
  vocab-loss scan and rematerialization change REAL flops (recompute)
  and transient memory; ``Compiled.cost_analysis()`` /
  ``memory_analysis()`` expose both.  Dense attention is used for these
  candidates so the whole program is XLA HLO (the flash Pallas call is
  an opaque custom call to cost analysis, and on CPU it would lower
  through the interpreter anyway).
- Flash tile sizes (``flash_block_q/k``): NOT XLA-visible off-chip —
  tile choice changes the Pallas grid schedule and VMEM residency, not
  the HLO flop/byte totals.  They are ranked analytically: per-tile VMEM ~ (bq*d + 2*bk*d + bq*bk)*2
  bytes must sit well under ~16 MB VMEM, and fewer K-passes win until
  the accumulator tile spills.

Run (CPU, ~minutes — each candidate is a full flagship compile):

    JAX_PLATFORMS=cpu python tools/mfu_cost_rank.py > MFU_COST_RANK.jsonl

Prints one JSON line per candidate plus a final ``ranking`` line; the
ranked order feeds tools/mfu_sweep.py's default grid.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# flops/bytes/temp-memory extraction lives in the shared MFU accounting
# module so this ranker, bench.py, and the TORCHFT_PERF trainer path all
# read XLA cost analysis the same tolerant way.
from torchft_tpu.perf import compiled_cost as _cost  # noqa: E402


def run_candidate(loss_chunk: int, remat: bool, B: int, S: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from torchft_tpu.models import llama_small
    from torchft_tpu.parallel import auto_mesh
    from torchft_tpu.parallel import train as train_mod

    saved = train_mod._LOSS_CHUNK
    if loss_chunk:
        train_mod._LOSS_CHUNK = loss_chunk
    try:
        # Dense attention: keeps the whole program XLA-visible (see
        # module docstring); the flash-vs-dense choice itself is a
        # separate, on-chip-only axis.
        cfg = llama_small(remat=remat, attn_impl="dense")
        mesh = auto_mesh(1)
        model = train_mod.build_model(cfg, mesh)
        state, shardings = train_mod.init_train_state(
            model, mesh, jax.random.PRNGKey(0), (B, S)
        )
        step = train_mod.make_train_step(model, mesh, shardings)
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
        t0 = time.perf_counter()
        lowered = jax.jit(step, donate_argnums=(0,)).lower(state, batch)
        compiled = lowered.compile()
        compile_s = time.perf_counter() - t0
        C = loss_chunk or train_mod._LOSS_CHUNK
        rec = {
            "loss_chunk": C,
            "remat": remat,
            "B": B,
            "S": S,
            "compile_s": round(compile_s, 1),
        }
        rec.update(_cost(compiled))
        # SCAN CORRECTION (verified by a standalone probe of the chunked
        # loss, 2026-08-01): XLA cost analysis reports a lax.scan BODY
        # ONCE, not x trip count, so the raw "flops" carry only one loss
        # chunk's work and the uncorrected totals grow ~linearly in C —
        # an artifact that inverts the ranking.  The loss-scan body is
        # 8*B*C*H*V flops with jax.checkpoint (fwd 2 + recompute 2 +
        # bwd 4, XLA counting 2 flops/MAC); add the missing (n-1)
        # bodies.  After correction the loss flops are C-INDEPENDENT
        # (measured: 1.613T ckpt / 1.209T plain at every C in
        # {32..512}), i.e. chunk size is NOT a flop lever at all — only
        # scan-iteration overhead and transient bytes, neither
        # XLA-visible, distinguish chunks on-chip.
        #
        # SCOPE CAVEAT: the transformer TRUNK is also a scan (nn.scan
        # over num_layers, llama.py:408) and is NOT corrected here — so
        # flops_scan_corrected is valid for comparing LOSS-CHUNK
        # configs (identical trunk constant on both sides) and NOT for
        # remat flop deltas: the raw remat on/off difference (~96G) is
        # ONE layer's recompute body, ~num_layers x under the true
        # cost (remat recomputes every layer's forward, analytically
        # ~+1 fwd pass ~= +33% flops).  memory_analysis numbers are
        # whole-program (buffer assignment, not per-body) and ARE
        # sound: rank remat by temp_bytes/bytes_accessed + the analytic
        # flop cost, never by the raw flop delta.
        C_eff = min(C, S)  # _loss_fn clamps the same way (train.py:161)
        rec["loss_chunk"] = C_eff
        if "flops" in rec and S % C_eff == 0:
            # Same condition as _loss_fn: a non-divisor chunk takes the
            # plain full-logits path (no scan) — correcting it would ADD
            # bogus flops.
            H = cfg.hidden_size
            V = cfg.vocab_size
            n_chunks = max(S // C_eff, 1)
            body = 8.0 * B * C_eff * H * V
            rec["loss_scan_body_flops"] = body
            rec["flops_scan_corrected"] = rec["flops"] + body * (
                n_chunks - 1
            )
            rec["scan_caveat"] = (
                "trunk nn.scan uncorrected: compare loss-chunk configs "
                "only; remat deltas invalid in flops (use temp_bytes + "
                "analytic ~+1 fwd)"
            )
        return rec
    finally:
        train_mod._LOSS_CHUNK = saved


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=1024)
    p.add_argument(
        "--chunks", type=str, default="128,256,512",
        help="comma-separated TORCHFT_LOSS_CHUNK candidates",
    )
    args = p.parse_args()

    chunks = [int(c) for c in args.chunks.split(",") if c]
    records = []
    for remat in (False, True):
        for chunk in chunks:
            try:
                rec = run_candidate(chunk, remat, args.batch, args.seq)
            except Exception as e:  # noqa: BLE001 - rank what compiled
                rec = {
                    "loss_chunk": chunk,
                    "remat": remat,
                    "error": str(e)[:200],
                }
            records.append(rec)
            print(json.dumps(rec), flush=True)

    # Rank: remat OFF before remat ON (the raw flop delta between them
    # is body-once-invalid — see the scope caveat — and the true remat
    # cost is ~+1 fwd pass of flops, only worth paying when the chip
    # profiles memory/bandwidth-bound; r3 measured remat-off faster at
    # these shapes), then fewest scan-corrected flops, then bytes.
    # Errors sink to the bottom.
    def key(r):
        return (
            "error" in r,
            bool(r.get("remat")),
            r.get("flops_scan_corrected", r.get("flops", float("inf"))),
            r.get("bytes_accessed", float("inf")),
        )

    ranked = sorted(records, key=key)
    print(
        json.dumps(
            {
                "ranking": [
                    {
                        "loss_chunk": r.get("loss_chunk"),
                        "remat": r.get("remat"),
                        "flops_scan_corrected": r.get(
                            "flops_scan_corrected"
                        ),
                        "bytes_accessed": r.get("bytes_accessed"),
                        "temp_bytes": r.get("temp_bytes"),
                    }
                    for r in ranked
                ]
            }
        ),
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
