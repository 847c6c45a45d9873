"""Streaming DiLoCo training example (reference: train_diloco.py,
re-designed for JAX/TPU).

Each replica group runs ``sync_every`` *inner* steps entirely on its own
chips (compiled train step over the local mesh, collectives on ICI), then
exchanges fragment pseudogradients with the other groups over DCN through
the Manager — the flagship cross-pod config (BASELINE.json #5: islands of
v5e linked by DCN). Fragments sync round-robin with ``--fragment-sync-delay``
inner steps of overlap, and a failed sync rolls the fragment back to the
last global state instead of crashing the job.

Run two replica groups on one machine (CPU):

    torchft_tpu_lighthouse --min-replicas 1 --port 29510 &
    TORCHFT_LIGHTHOUSE=127.0.0.1:29510 REPLICA_GROUP_ID=0 python train_diloco.py &
    TORCHFT_LIGHTHOUSE=127.0.0.1:29510 REPLICA_GROUP_ID=1 python train_diloco.py &
"""

from __future__ import annotations

import argparse
import logging
import os
import sys
import time

import jax

from _train_common import (
    DurableRegime,
    drain_signal,
    enable_compile_cache,
    group_data_seed,
    perf_note_compiled,
    perf_step_suffix,
)

enable_compile_cache()  # before anything compiles

import jax.numpy as jnp
import numpy as np
import optax

from torchft_tpu import telemetry
from torchft_tpu.coordination import RequestAborted
from torchft_tpu.local_sgd import DiLoCo, partition_fragments
from torchft_tpu.manager import Manager
from torchft_tpu.models import Transformer, llama_debug
from torchft_tpu.process_group import make_process_group


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=200, help="inner steps")
    parser.add_argument(
        "--outer-steps", type=int, default=0,
        help="if >0, run until manager.current_step() reaches this OUTER "
        "step instead of a fixed inner count — the restart-safe loop (a "
        "relaunched incarnation's inner counter restarts, but every "
        "incarnation converges to the same outer target)",
    )
    parser.add_argument(
        "--result-dir", type=str, default=None,
        help="write group{REPLICA_GROUP_ID}.json with a sha256 over the "
        "GLOBAL state (fragment backups + outer optimizer) at exit — the "
        "cross-group bitwise-equality contract",
    )
    parser.add_argument("--batch-size", type=int, default=8)
    parser.add_argument("--seq-len", type=int, default=64)
    parser.add_argument("--inner-lr", type=float, default=3e-4)
    parser.add_argument("--outer-lr", type=float, default=0.7)
    parser.add_argument("--sync-every", type=int, default=20)
    parser.add_argument("--n-fragments", type=int, default=2)
    parser.add_argument("--fragment-sync-delay", type=int, default=2)
    parser.add_argument("--fragment-update-alpha", type=float, default=0.0,
                        help="weight of LOCAL params in the post-commit merge")
    parser.add_argument("--min-replicas", type=int, default=1)
    parser.add_argument("--quantize", action="store_true")
    parser.add_argument(
        "--quantize-bits", type=int, default=8, choices=(8, 4),
        help="wire width for --quantize (4 = nibble-packed, half the bytes)",
    )
    parser.add_argument(
        "--error-feedback", action="store_true",
        help="carry quantization residuals into the next sync "
        "(recommended with --quantize-bits 4)",
    )
    parser.add_argument(
        "--drain-on-sigterm",
        action=argparse.BooleanOptionalAction,
        default=True,
        help="on SIGTERM (TPU maintenance event / preemption), finish the "
        "inner step, gracefully leave the quorum at an outer boundary, "
        "exit 0",
    )
    parser.add_argument(
        "--durable-dir", type=str, default=None,
        help="orbax durable-checkpoint directory (per-group subdir "
        "added): snapshots of the GLOBAL state (fragment backups + outer "
        "optimizer) plus this group's inner params/optimizer on the "
        "--durable-every OUTER-step cadence, a final snapshot on drain, "
        "automatic resume at startup — survival of a FULL-job preemption "
        "(no live peer left to heal from)",
    )
    parser.add_argument("--durable-every", type=int, default=10)
    args = parser.parse_args()

    logging.basicConfig(level=logging.INFO)
    replica_group = os.environ.get("REPLICA_GROUP_ID", "0")
    # Late-bound: filled with manager.abort_pending_quorum once the
    # Manager exists, so a SIGTERM landing while this process is blocked
    # in a sync quorum wait interrupts the wait instead of riding it out.
    abort_hook = [lambda: None]
    sigterm_drain = drain_signal(
        args.drain_on_sigterm, on_signal=lambda: abort_hook[0]()
    )

    cfg = llama_debug()
    model = Transformer(cfg)
    tokens0 = jnp.zeros((args.batch_size, args.seq_len), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens0)["params"]
    inner_tx = optax.adamw(args.inner_lr)
    opt_state = inner_tx.init(params)

    @jax.jit
    def inner_step(params, opt_state, x, y):
        def loss_fn(p):
            logits = model.apply({"params": p}, x)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y
            ).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = inner_tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    # Warm the compile cache before joining the quorum.
    params, opt_state, _ = inner_step(params, opt_state, tokens0, tokens0)
    jax.block_until_ready(params)
    # TORCHFT_PERF: FLOPs/bytes from the compile we just paid for, so
    # boundary prints carry MFU/roofline (torchft_tpu/perf.py). No-op
    # when off.
    perf_note_compiled(
        "diloco_inner_step", inner_step, params, opt_state, tokens0,
        tokens0, tokens_per_step=args.batch_size * args.seq_len,
    )

    # Mutable handle bridging DiLoCo's get/set to the functional params.
    state = {"params": params}

    groups = partition_fragments(state["params"], args.n_fragments)

    def make_fragment(keys):
        def get():
            return {k: state["params"][k] for k in keys}

        def set_(frag):
            new = dict(state["params"])
            for k in keys:
                # device_put preserves the live params' sharding/dtype.
                new[k] = jax.tree_util.tree_map(
                    lambda cur, v: jax.device_put(
                        np.asarray(v).astype(cur.dtype),
                        getattr(cur, "sharding", None),
                    ),
                    state["params"][k],
                    frag[k],
                )
            state["params"] = new

        return (keys, get, set_)

    manager = Manager(
        pg=make_process_group(timeout=30.0),
        min_replica_size=args.min_replicas,
        use_async_quorum=False,  # DiLoCo requires sync quorum (local_sgd.py:616-620)
        replica_id=f"train_diloco_{replica_group}",
        group_rank=0,
        group_world_size=1,
    )
    abort_hook[0] = manager.abort_pending_quorum
    diloco = DiLoCo(
        manager,
        [make_fragment(g) for g in groups],
        sync_every=args.sync_every,
        outer_optimizer=optax.sgd(args.outer_lr, momentum=0.9, nesterov=True),
        fragment_sync_delay=args.fragment_sync_delay,
        fragment_update_alpha=args.fragment_update_alpha,
        should_quantize=args.quantize,
        quantize_bits=args.quantize_bits,
        error_feedback=args.error_feedback,
    )

    # Step-addressed data stream (fold_in of the loop position): stable
    # across incarnations, resumable mid-stream (see _train_common).
    data_base = jax.random.PRNGKey(group_data_seed(replica_group))
    metrics = telemetry.get_metrics_logger()

    # Durable regime: global state (fragment backups + outer optimizer,
    # via DiLoCo.state_dict) plus this group's inner params/optimizer.
    # Snapshots happen with no sync in flight (periodic saves at
    # committed syncs; the drain save at any drainable inner step, which
    # may be MID-window — inner params then sit a few inner steps past
    # the fragment backups, and the restored inner stream resumes from
    # there), so restore needs no in-flight-sync handling.
    ckpt = None

    def durable_state():
        return {
            "diloco": diloco.state_dict(),
            "params": jax.tree_util.tree_map(np.asarray, state["params"]),
            "opt_state": jax.tree_util.tree_map(np.asarray, opt_state),
            "manager": manager.state_dict(),
        }

    if args.durable_dir:
        ckpt = DurableRegime(
            args.durable_dir, replica_group, every=args.durable_every
        )
        snap = ckpt.restore_if_any()
        if snap is not None:
            diloco.load_state_dict(snap["diloco"])
            # Inner state restores OVER the fragment reset: the saved
            # inner params may sit ahead of the fragment backups (a
            # drain snapshot taken mid-window), and are the right resume
            # point for this group's local stream either way.
            state["params"] = jax.tree_util.tree_map(
                lambda cur, v: jnp.asarray(np.asarray(v), dtype=cur.dtype),
                state["params"],
                snap["params"],
            )
            opt_state = DurableRegime.rehang_like(
                opt_state, snap["opt_state"]
            )
            ckpt.restore_manager(manager, snap)
            ckpt.log_resumed(manager.current_step())

    def inner_iter():
        if args.outer_steps > 0:
            i = 0
            while manager.current_step() < args.outer_steps:
                yield i
                i += 1
        else:
            yield from range(args.steps)

    drained = False

    def maybe_drain() -> bool:
        # Drain whenever NO sync is in flight — the leave never abandons
        # a collective peers are counting on, but also never WAITS for a
        # future sync to reach a boundary: that sync needs a quorum, and
        # when every group is draining (full-job preemption) a peer that
        # drained one boundary earlier means the quorum never forms
        # again and the waiter wedges. A prepared sync (the delay
        # overlap window) is finished first; the post-sync check catches
        # the flag then. Checked immediately before diloco.step() (the
        # call that may block on a new quorum) so the undrainable window
        # is sub-millisecond, not a whole inner step.
        if not (sigterm_drain() or manager.drain_requested()):
            return False
        if diloco.sync_in_flight:
            return False
        print(
            f"[group {replica_group}] draining at outer step "
            f"{manager.current_step()} "
            f"({'SIGTERM' if sigterm_drain() else 'operator request'})",
            flush=True,
        )
        manager.leave()
        if ckpt is not None:
            ckpt.on_drain(manager.current_step(), durable_state)
        return True

    for inner in inner_iter():
        t_step0 = time.time()
        telemetry.trace_window(inner)
        kx = jax.random.fold_in(data_base, inner)
        x = jax.random.randint(
            kx, (args.batch_size, args.seq_len), 0, cfg.vocab_size
        )
        y = jnp.roll(x, -1, axis=1)
        params, opt_state, loss = inner_step(
            state["params"], opt_state, x, y
        )
        state["params"] = params
        if maybe_drain():
            drained = True
            break
        try:
            committed = diloco.step()
        except RequestAborted:
            # A SIGTERM mid-wait aborted the blocked quorum
            # (abort_pending_quorum): start_quorum raised BEFORE the
            # fragment prepared, so no sync is in flight and the global
            # state is the untouched last boundary — safe to snapshot
            # and drain. ONLY this exception resolves to a drain: any
            # other failure (e.g. a torn perform_sync) must crash
            # loudly, not exit 0 with a possibly-divergent snapshot.
            if maybe_drain():
                drained = True
                break
            raise
        if committed is not None:
            print(
                f"[group {replica_group}] inner={inner} outer_step="
                f"{manager.current_step()} loss={float(loss):.4f} "
                f"committed={committed} "
                f"participants={manager.num_participants()}"
                f"{perf_step_suffix('diloco_inner_step', time.time() - t_step0)}",
                flush=True,
            )
            if metrics is not None:
                metrics.log(
                    manager.current_step(),
                    loss=float(loss),
                    num_participants=manager.num_participants(),
                    committed=float(committed),
                    inner_step=inner,
                )
            if ckpt is not None and committed:
                ckpt.on_commit(manager.current_step(), durable_state)
            if maybe_drain():
                drained = True
                break

    final_outer = manager.current_step()
    if args.result_dir:
        import hashlib
        import json as _json

        os.makedirs(args.result_dir, exist_ok=True)
        h = hashlib.sha256()
        for frag in diloco.fragments:
            for key in sorted(frag.keys):
                for leaf in jax.tree_util.tree_leaves(frag._backup[key]):
                    h.update(np.ascontiguousarray(
                        np.asarray(leaf, np.float32)
                    ).tobytes())
            for leaf in jax.tree_util.tree_leaves(frag._opt_state):
                h.update(np.ascontiguousarray(
                    np.asarray(leaf, np.float32)
                ).tobytes())
        with open(
            os.path.join(args.result_dir, f"group{replica_group}.json"), "w"
        ) as f:
            _json.dump(
                {
                    "final_outer_step": final_outer,
                    "global_sha": h.hexdigest(),
                    "drained": drained,
                },
                f,
            )
    if ckpt is not None:
        ckpt.close()
    manager.shutdown()
    print(f"[group {replica_group}] done at outer step {final_outer}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
