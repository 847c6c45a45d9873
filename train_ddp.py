"""Fault-tolerant DDP training example (reference: train_ddp.py in
tushar00jain/torchft, re-designed for JAX/TPU).

Each *replica group* (one process here; one TPU pod slice in production)
trains a small CNN on synthetic CIFAR-shaped data. Gradients are averaged
across replica groups through the Manager (host-driven over DCN); a replica
that dies and restarts heals from a healthy peer's live checkpoint and the
job never stops.

Run two replica groups on one machine:

    torchft_tpu_lighthouse --min-replicas 1 --port 29510 &
    TORCHFT_LIGHTHOUSE=127.0.0.1:29510 REPLICA_GROUP_ID=0 python train_ddp.py &
    TORCHFT_LIGHTHOUSE=127.0.0.1:29510 REPLICA_GROUP_ID=1 python train_ddp.py &

Kill either trainer mid-run and restart it: it rejoins the quorum and heals.
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
from flax import linen as nn

from torchft_tpu import telemetry
from torchft_tpu.ddp import DistributedDataParallel
from torchft_tpu.manager import Manager, WorldSizeMode
from torchft_tpu.optim import OptimizerWrapper
from torchft_tpu.process_group import make_process_group


class Net(nn.Module):
    """Small CNN (reference model shape: train_ddp.py:116-146)."""

    @nn.compact
    def __call__(self, x):  # x: [B, 32, 32, 3]
        x = nn.Conv(16, (3, 3))(x)
        x = nn.relu(x)
        x = nn.avg_pool(x, (2, 2), strides=(2, 2))
        x = nn.Conv(32, (3, 3))(x)
        x = nn.relu(x)
        x = nn.avg_pool(x, (2, 2), strides=(2, 2))
        x = x.reshape((x.shape[0], -1))
        x = nn.Dense(64)(x)
        x = nn.relu(x)
        return nn.Dense(10)(x)


def synthetic_batch(key, batch_size: int, image_size: int = 32,
                    num_classes: int = 10):
    """Deterministic synthetic data stream (no dataset download in image)."""
    kx, ky = jax.random.split(key)
    x = jax.random.normal(
        kx, (batch_size, image_size, image_size, 3), dtype=jnp.float32
    )
    y = jax.random.randint(ky, (batch_size,), 0, num_classes)
    return x, y


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=50)
    parser.add_argument("--batch-size", type=int, default=64)
    parser.add_argument("--lr", type=float, default=1e-3)
    parser.add_argument("--min-replicas", type=int, default=1)
    parser.add_argument(
        "--model", choices=["cnn", "resnet-tiny", "resnet50"], default="cnn",
        help="cnn = the reference-shaped toy CNN; resnet50 = BASELINE "
             "config #3's model (pass --image-size 224 --num-classes 1000 "
             "for the ImageNet-shaped workload); resnet-tiny for CPU runs",
    )
    parser.add_argument(
        "--image-size", type=int, default=32,
        help="synthetic image side; BASELINE #3 at full scale uses 224",
    )
    parser.add_argument("--num-classes", type=int, default=10)
    parser.add_argument(
        "--result-dir", type=str, default=None,
        help="write group{N}.json with final step + param sha256 (the "
        "kill/heal bitwise-equality check, BASELINE #3)",
    )
    parser.add_argument("--quantize", action="store_true")
    parser.add_argument(
        "--quantize-bits", type=int, default=8, choices=(8, 4),
        help="wire width for --quantize (4 = nibble-packed, half the bytes)",
    )
    parser.add_argument(
        "--error-feedback", action="store_true",
        help="carry per-bucket quantization residuals into the next step "
        "(recommended with --quantize-bits 4)",
    )
    parser.add_argument(
        "--drain-on-sigterm",
        action=argparse.BooleanOptionalAction,
        default=True,
        help="on SIGTERM (TPU maintenance event / preemption notice), finish "
        "the current step, gracefully leave the quorum so peers re-form at "
        "tick speed (no heartbeat-timeout stall), and exit 0",
    )
    parser.add_argument(
        "--durable-dir", type=str, default=None,
        help="orbax durable-checkpoint directory (per-group subdir is "
        "added): periodic snapshots on the --durable-every cadence, a "
        "final snapshot on drain, and automatic resume from the latest "
        "snapshot at startup — what survives a FULL-job preemption, "
        "where every replica drains and there is no live peer left to "
        "heal from",
    )
    parser.add_argument("--durable-every", type=int, default=50)
    parser.add_argument(
        "--step-min-s", type=float, default=0.0,
        help="minimum wall seconds per step (drill pacing: a CPU toy "
        "step runs in ~ms, too fast for lease-based control-plane "
        "failure windows to land mid-run; 0 = full speed)",
    )
    parser.add_argument(
        "--world-size-mode",
        choices=("dynamic", "fixed_with_spares"),
        default="dynamic",
        help="fixed_with_spares: the effective participant count is "
        "pinned at --min-replicas; extra replica groups run as hot "
        "SPARES (contribute zeros, apply the same averaged update, stay "
        "in bitwise lockstep) and promote instantly - no heal - when an "
        "active group dies (reference: WorldSizeMode, manager.py:146)",
    )
    args = parser.parse_args()

    logging.basicConfig(level=logging.INFO)
    replica_group = os.environ.get("REPLICA_GROUP_ID", "0")

    if args.model == "cnn":
        if args.image_size != 32 or args.num_classes != 10:
            raise SystemExit("--model cnn is fixed at 32x32 / 10 classes")
        model = Net()
    else:
        from torchft_tpu.models import resnet_tiny, resnet50

        model = (
            resnet50(num_classes=args.num_classes)
            if args.model == "resnet50"
            else resnet_tiny(num_classes=args.num_classes)
        )
    S_img, n_cls = args.image_size, args.num_classes
    variables = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, S_img, S_img, 3))
    )
    params = {"params": variables["params"]}
    # BatchNorm running stats (ResNet): per-group mutable state, carried
    # outside the gradient path and registered for heal below.
    batch_stats = [variables.get("batch_stats")]

    @jax.jit
    def loss_and_grads(params, batch_stats, x, y):
        def loss_fn(p):
            if batch_stats is None:
                return (
                    optax.softmax_cross_entropy_with_integer_labels(
                        model.apply(p, x), y
                    ).mean(),
                    None,
                )
            logits, upd = model.apply(
                {**p, "batch_stats": batch_stats},
                x,
                mutable=["batch_stats"],
            )
            return (
                optax.softmax_cross_entropy_with_integer_labels(
                    logits, y
                ).mean(),
                upd["batch_stats"],
            )

        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(params)
        return loss, new_stats, grads

    # Compile before joining the quorum: a replica stalled in XLA compilation
    # would otherwise hold up the whole group's first step (and on TPU the
    # first compile can take tens of seconds).
    wx, wy = synthetic_batch(jax.random.PRNGKey(1), args.batch_size, S_img, n_cls)
    jax.block_until_ready(loss_and_grads(params, batch_stats[0], wx, wy))
    # TORCHFT_PERF: FLOPs/bytes from the compile we just paid for, so
    # step prints carry MFU/roofline (torchft_tpu/perf.py). No-op when off.
    perf_note_compiled("ddp_step", loss_and_grads, params, batch_stats[0],
                       wx, wy)


    manager = Manager(
        pg=make_process_group(timeout=30.0),
        min_replica_size=args.min_replicas,
        replica_id=f"train_ddp_{replica_group}",
        group_rank=0,
        group_world_size=1,
        world_size_mode=WorldSizeMode(args.world_size_mode),
    )
    opt = OptimizerWrapper(manager, optax.adam(args.lr), params)
    ddp = DistributedDataParallel(
        manager,
        error_feedback=args.error_feedback,
        quantize_bits=args.quantize_bits,
    )
    if batch_stats[0] is not None:
        # BatchNorm stats heal with the params so a recovered replica's
        # normalization matches its checkpoint source.
        manager.register_state_dict_fn(
            "batch_stats",
            lambda: jax.tree_util.tree_map(np.asarray, batch_stats[0]),
            lambda s: batch_stats.__setitem__(0, s),
        )

    # Different replica groups draw different data shards (reference:
    # DistributedSampler semantics, torchft/data.py:24-77).  The stream
    # is STEP-ADDRESSED (fold_in of the committed step), so a relaunched
    # group that heals to step N resumes at batch N instead of replaying
    # batches its first incarnation already committed.
    data_base = jax.random.PRNGKey(group_data_seed(replica_group))

    # Durable regime (composes with live heal; checkpointing/durable.py):
    # snapshots are host-numpy state dicts, so restore reuses the exact
    # heal-path loaders. Groups may snapshot one step apart (each drains
    # at its own boundary); the behind group live-heals forward at the
    # first post-resume quorum.
    ckpt = None

    def durable_state():
        state = {
            "optimizer": opt.state_dict(),
            "manager": manager.state_dict(),
        }
        if batch_stats[0] is not None:
            state["batch_stats"] = jax.tree_util.tree_map(
                np.asarray, batch_stats[0]
            )
        return state

    if args.durable_dir:
        ckpt = DurableRegime(
            args.durable_dir, replica_group, every=args.durable_every
        )
        snap = ckpt.restore_if_any()
        if snap is not None:
            opt.load_state_dict(snap["optimizer"])
            if snap.get("batch_stats") is not None:
                batch_stats[0] = snap["batch_stats"]
            ckpt.restore_manager(manager, snap)
            ckpt.log_resumed(manager.current_step())

    # Preemption-aware graceful drain (SIGTERM) + operator-initiated
    # drain (lighthouse dashboard drain button, surfaced via the quorum
    # response): either way the loop drains at the next step boundary so
    # the last commit stays clean.
    # No abort_pending_quorum hook here (unlike train_diloco): with an
    # ASYNC quorum every wait is bounded (dead-peer fast-fail +
    # collective-abort propagation), the loop-top check below drains at
    # step speed, and an eager abort would turn "finish the step, commit,
    # drain" into a failed final step whenever SIGTERM lands mid-step.
    sigterm_drain = drain_signal(args.drain_on_sigterm)

    drained = False
    metrics = telemetry.get_metrics_logger()
    while manager.current_step() < args.steps:
        if sigterm_drain() or manager.drain_requested():
            why = "SIGTERM" if sigterm_drain() else "operator request"
            print(
                f"[group {replica_group}] draining at step "
                f"{manager.current_step()} ({why})",
                flush=True,
            )
            manager.leave()  # unblock peers first; the save is local
            if ckpt is not None:
                ckpt.on_drain(manager.current_step(), durable_state)
            drained = True
            break
        step = manager.current_step()
        t_step0 = time.time()
        # Scheduled profiler window (TORCHFT_TRACE_DIR; reference:
        # train_ddp.py:169-174 torch.profiler schedule).
        telemetry.trace_window(step)
        batch_key = jax.random.fold_in(data_base, step)
        x, y = synthetic_batch(batch_key, args.batch_size, S_img, n_cls)

        opt.zero_grad()  # quorum (async; overlaps with forward/backward)
        loss, new_stats, grads = loss_and_grads(
            opt.params, batch_stats[0], x, y
        )
        # Outer replica axis, over DCN (optionally int8/int4 on the wire).
        grads = ddp.allreduce_grads(grads, should_quantize=args.quantize)
        # Stats advance inside the commit fence: a heal snapshot must
        # never pair step-N params with step-(N-1) BatchNorm stats.
        committed = opt.step(
            grads,
            on_commit=(
                (lambda: batch_stats.__setitem__(0, new_stats))
                if new_stats is not None
                else None
            ),
        )

        print(
            f"[group {replica_group}] step={step} loss={float(loss):.4f} "
            f"participants={manager.num_participants()} committed={committed} "
            f"t={time.time():.3f}"
            f"{perf_step_suffix('ddp_step', time.time() - t_step0)}",
            flush=True,
        )
        if metrics is not None:
            metrics.log(
                step,
                loss=float(loss),
                num_participants=manager.num_participants(),
                committed=float(committed),
            )
        if committed and ckpt is not None:
            # Pass the factory, not the state: durable_state() is a full
            # device->host materialization, built only on cadence steps.
            ckpt.on_commit(manager.current_step(), durable_state)
        if args.step_min_s > 0:
            time.sleep(max(0.0, args.step_min_s - (time.time() - t_step0)))

    if ckpt is not None:
        ckpt.close()
    if args.result_dir:
        import hashlib
        import json

        os.makedirs(args.result_dir, exist_ok=True)
        # Params only: BatchNorm stats are per-group mutable state fed by
        # each group's OWN data shard and legitimately diverge.
        flat = jax.tree_util.tree_leaves(opt.params)
        digest = hashlib.sha256(
            b"".join(
                np.ascontiguousarray(np.asarray(x)).tobytes() for x in flat
            )
        ).hexdigest()
        with open(
            os.path.join(args.result_dir, f"group{replica_group}.json"), "w"
        ) as f:
            json.dump(
                {
                    "group": replica_group,
                    "final_step": manager.current_step(),
                    "param_sha256": digest,
                    "drained": drained,
                },
                f,
            )
    manager.shutdown()
    print(f"[group {replica_group}] done at step {manager.current_step()}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
