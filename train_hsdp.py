"""Fault-tolerant HSDP training: the flagship composition (VERDICT r1
item 8; reference: fsdp_test.py:71-92 + ManagedDeviceMesh device_mesh.py:303-336).

Inner axes (dp/fsdp/sp/tp) are a compiled ``jax.sharding.Mesh`` riding ICI:
params born-sharded, gradient psum inside the jitted step. The outer
(replica) axis is the Manager's fault-tolerant quorum over DCN: per step,
the sharded gradient pytree is averaged across replica groups through
``ManagedMesh.allreduce_grads`` and the optimizer applies only on a
committed quorum. A killed group restarts, heals params+optimizer state
from a healthy peer's live checkpoint, and rejoins.

Run two replica groups (single host, virtual CPU mesh):

    torchft_tpu_lighthouse --min-replicas 2 --port 29510 &
    for i in 0 1; do
      JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      TORCHFT_LIGHTHOUSE=127.0.0.1:29510 REPLICA_GROUP_ID=$i \
      python train_hsdp.py --model debug --steps 20 &
    done
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import sys
import time


def main() -> int:
    from torchft_tpu.models import PRESETS

    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=20)
    parser.add_argument(
        "--model", choices=[*PRESETS, "pipeline"], default="debug"
    )
    parser.add_argument("--batch", type=int, default=8)
    parser.add_argument("--seq", type=int, default=64)
    parser.add_argument("--min-replicas", type=int, default=1)
    parser.add_argument("--quantize", action="store_true",
                        help="quantize the outer gradient allreduce")
    parser.add_argument(
        "--attn", choices=["default", "flash", "ring", "ulysses"],
        default="default",
        help="inner-mesh attention: 'flash' (Pallas blocked kernel), "
        "'ring' (ppermute k/v streaming) or 'ulysses' (all-to-all "
        "seq<->head) context parallelism over sp; 'default' keeps the "
        "model preset's impl",
    )
    parser.add_argument(
        "--quantize-bits", type=int, default=8, choices=(8, 4),
        help="wire width for --quantize (4 = nibble-packed)",
    )
    parser.add_argument(
        "--ckpt-transport", choices=["http", "pg-sharded"], default="http",
        help="heal transport: http = full-state fetch; pg-sharded = "
        "addressable shards over the replica PG, rebuilt straight onto "
        "this group's device shardings (no host gather — the 8B-scale "
        "path; checkpointing/sharded.py)",
    )
    parser.add_argument("--result-dir", type=str, default=None)
    parser.add_argument(
        "--drain-on-sigterm",
        action=argparse.BooleanOptionalAction,
        default=True,
        help="on SIGTERM (TPU maintenance event / preemption), finish the "
        "step, gracefully leave the quorum, exit 0",
    )
    parser.add_argument(
        "--durable-dir", type=str, default=None,
        help="orbax durable-checkpoint directory (per-group subdir "
        "added): periodic host-numpy snapshots on the --durable-every "
        "cadence, a final snapshot on drain, automatic resume at startup "
        "— survival of a FULL-job preemption (no live peer left to heal "
        "from); restore re-shards onto this group's mesh via the heal "
        "loader",
    )
    parser.add_argument("--durable-every", type=int, default=10)
    args = parser.parse_args()
    logging.basicConfig(level=logging.INFO)
    from _train_common import drain_signal, enable_compile_cache

    enable_compile_cache()

    # No abort_pending_quorum hook here (unlike train_diloco): with an
    # ASYNC quorum every wait is bounded (dead-peer fast-fail +
    # collective-abort propagation), the loop-top check drains at step
    # speed, and an eager abort would turn "finish the step, commit,
    # drain" into a failed final step whenever SIGTERM lands mid-step.
    sigterm_drain = drain_signal(args.drain_on_sigterm)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from torchft_tpu.device_mesh import ft_init_device_mesh
    from torchft_tpu.ft_step import FTStep
    from torchft_tpu.manager import Manager
    from torchft_tpu.parallel import auto_mesh
    from torchft_tpu.parallel.train import (
        build_model,
        init_train_state,
        make_apply_step,
        make_split_grad_step,
        router_bias_abs_max,
    )
    from torchft_tpu.process_group import make_process_group

    group = os.environ.get("REPLICA_GROUP_ID", "0")
    n_dev = len(jax.devices())
    device = {
        "platform": jax.devices()[0].platform,
        "kind": jax.devices()[0].device_kind,
        "count": n_dev,
    }
    logging.info(
        "[group %s] devices: platform=%s kind=%s count=%d", group,
        device["platform"], device["kind"], device["count"],
    )
    if args.model == "pipeline" and n_dev % 2 == 0:
        # GPipe trunk over 'pp' + data parallel over 'dp', composed with
        # the same FT replica axis (parallel/pipeline.py).
        from torchft_tpu.parallel import make_mesh

        mesh = make_mesh(pp=2, dp=n_dev // 2)
    elif args.model == "moe" and n_dev % 2 == 0:
        # Give the experts a real ep extent so the run actually exercises
        # expert-parallel dispatch (auto_mesh keeps ep=1 for dense runs).
        from torchft_tpu.parallel import make_mesh

        rest = n_dev // 2
        fsdp = 2 if rest % 2 == 0 else 1
        mesh = make_mesh(fsdp=fsdp, ep=2, tp=rest // fsdp)
    else:
        mesh = auto_mesh(n_dev)
    B, S = args.batch, args.seq
    if args.model == "pipeline":
        from jax.sharding import NamedSharding, PartitionSpec as P

        from torchft_tpu.parallel.pipeline import (
            init_pipeline_state,
            make_pipeline_loss,
        )

        cfg = PRESETS["debug"](num_layers=4)
        model = build_model(cfg, mesh)  # its cfg only: the trunk is the pipeline's
        state, shardings = init_pipeline_state(
            cfg, mesh, jax.random.PRNGKey(0), (B, S)
        )
        loss_fn = make_pipeline_loss(cfg, mesh, n_micro=2)
        bsh = NamedSharding(mesh, P("dp", None))

        def loss_and_grads(params, batch):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            return loss, {}, (grads, None)  # make_split_grad_step's form

        grad_step = jax.jit(
            loss_and_grads,
            in_shardings=(
                shardings.params,
                {"inputs": bsh, "targets": bsh, "mask": bsh},
            ),
            out_shardings=(None, None, (shardings.params, None)),
        )
    else:
        cfg = PRESETS[args.model]()
        if args.attn != "default":
            import dataclasses

            cfg = dataclasses.replace(cfg, attn_impl=args.attn)
        model = build_model(cfg, mesh)
        state, shardings = init_train_state(
            model, mesh, jax.random.PRNGKey(0), (B, S)
        )
        grad_step = make_split_grad_step(model, mesh, shardings)
    apply_step = make_apply_step(model, shardings)

    # TORCHFT_PERF: record the compiled step's FLOPs/bytes once (same
    # shapes the loop runs) so step logs carry MFU/roofline. The guard
    # keeps the off path free of the probe batch allocation.
    from torchft_tpu import perf as _perf
    if _perf.perf_enabled():
        from _train_common import perf_note_compiled

        k0 = jax.random.PRNGKey(0)
        probe = {
            "inputs": jax.random.randint(k0, (B, S), 0, cfg.vocab_size),
            "targets": jax.random.randint(k0, (B, S), 0, cfg.vocab_size),
            "mask": jnp.ones((B, S), jnp.int32),
        }
        perf_note_compiled("hsdp_grad_step", grad_step, state.params, probe,
                           tokens_per_step=B * S)
        del probe

    # Heal contract (FTStep): host numpy pytrees over http; pg-sharded keeps
    # the leaves jax arrays end to end, addressable shards rebuilt straight
    # onto the receiver's shardings (reference pg_transport.py:230-298).
    sharded_heal = args.ckpt_transport == "pg-sharded"

    pg = make_process_group(timeout=30.0)
    checkpoint_transport = None
    if sharded_heal:
        from torchft_tpu.checkpointing.pg_transport import PGTransport

        def ckpt_target():
            # Structure mirrors Manager._manager_state_dict(); the
            # "torchft" scalars need no device target.
            return {"user": {"default": ft.state_dict()}}

        checkpoint_transport = PGTransport(
            pg, timeout=60.0, state_dict_fn=ckpt_target, sharded=True
        )

    manager = Manager(
        pg=pg,
        checkpoint_transport=checkpoint_transport,
        min_replica_size=args.min_replicas,
        use_async_quorum=True,
        timeout=60.0,
        quorum_timeout=60.0,
        connect_timeout=30.0,
        max_retries=20,
    )
    mm = ft_init_device_mesh(manager, mesh=mesh)
    ft = FTStep(
        manager, mm, grad_step, apply_step, state.params, state.opt_state,
        quantize_bits=args.quantize_bits if args.quantize else None,
        sharded_heal=sharded_heal,
    )
    del state
    # Mesh-relative views (reference ManagedDeviceMesh surface): the
    # HSDP selection pairs the dynamic replica dim with the fsdp shard
    # axis; "world" flattens every axis for a composite rank/size.
    hsdp_view = mm[("replica", "fsdp")]
    world = mm.flatten(name="world")
    logging.info(
        "managed mesh: %r; hsdp view %s (size %d); world size %d rank %s",
        mm,
        hsdp_view.shape(),
        hsdp_view.size(),
        world.size(),
        world.rank(),
    )

    # Durable regime: host-numpy params + optimizer + manager scalars.
    # Restore goes through FTStep.load_state_dict (the heal loader), which
    # re-shards onto this group's mesh; the optimizer tree is re-hung on
    # the live structure by leaf order first (orbax round-trips optax
    # NamedTuples as plain containers).
    ckpt = None

    def durable_state_fn():
        return {
            "params": jax.tree_util.tree_map(np.asarray, ft.params),
            "opt_state": jax.tree_util.tree_map(np.asarray, ft.opt_state),
            "manager": manager.state_dict(),
        }

    if args.durable_dir:
        from _train_common import DurableRegime

        ckpt = DurableRegime(
            args.durable_dir, group, every=args.durable_every
        )
        snap = ckpt.restore_if_any()
        if snap is not None:
            ft.load_state_dict(
                {
                    "params": snap["params"],
                    "opt_state": DurableRegime.rehang_like(
                        ft.opt_state, snap["opt_state"]
                    ),
                }
            )
            ckpt.restore_manager(manager, snap)
            ckpt.log_resumed(manager.current_step())

    from torchft_tpu import telemetry

    metrics = telemetry.get_metrics_logger()
    losses = []
    drained = False
    try:
        while manager.current_step() < args.steps:
            step = manager.current_step()
            if sigterm_drain() or manager.drain_requested():
                logging.info(
                    "[group %s] draining at step %d (%s)", group, step,
                    "SIGTERM" if sigterm_drain() else "operator request",
                )
                manager.leave()
                if ckpt is not None:
                    ckpt.on_drain(manager.current_step(), durable_state_fn)
                drained = True
                break
            t_step0 = time.time()
            telemetry.trace_window(step)
            # Deterministic batch per step: every group that commits step k
            # computes identical params (bitwise) — heal-invariant.
            key = jax.random.PRNGKey(step)
            batch = {
                "inputs": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
                "targets": jnp.roll(
                    jax.random.randint(key, (B, S), 0, cfg.vocab_size), -1, 1
                ),
                "mask": jnp.ones((B, S), jnp.int32),
            }
            # inner: compiled HSDP (`router` is {} for a model with no
            # experts); outer: the FT replica axis over DCN, then the gate
            committed, loss, router = ft(batch)
            if committed:
                losses.append(float(loss))
                router = {k: float(v) for k, v in router.items()}
                if cfg.router_bias_update_rate:
                    router["router_bias_abs_max"] = float(
                        router_bias_abs_max(ft.params)
                    )
                logging.info(
                    "[group %s] step %d loss %.4f participants %d%s%s",
                    group, step, losses[-1], mm.replica_size(),
                    "".join(f" {k} {v:.4g}" for k, v in router.items()),
                    _perf.format_step_metrics(
                        _perf.step_metrics(
                            "hsdp_grad_step", time.time() - t_step0
                        )
                    ),
                )
                if metrics is not None:
                    metrics.log(
                        step,
                        loss=losses[-1],
                        num_participants=mm.replica_size(),
                        committed=1.0,
                        step_s=time.time() - t_step0,
                        **router,
                    )
                if ckpt is not None:
                    ckpt.on_commit(manager.current_step(), durable_state_fn)
        if args.result_dir:
            os.makedirs(args.result_dir, exist_ok=True)
            flat = jax.tree_util.tree_leaves(ft.params)
            with open(
                os.path.join(args.result_dir, f"group{group}.json"), "w"
            ) as f:
                json.dump(
                    {
                        "group": group,
                        "final_step": manager.current_step(),
                        "param_l1": float(
                            sum(np.abs(np.asarray(x)).sum() for x in flat)
                        ),
                        "param_sha256": __import__("hashlib").sha256(
                            b"".join(
                                np.ascontiguousarray(np.asarray(x)).tobytes()
                                for x in flat
                            )
                        ).hexdigest(),
                        "losses": losses[-5:],
                        "drained": drained,
                        # A strided 64-element sample of every leaf: lets
                        # two groups on different backends (not bitwise
                        # equal) be compared elementwise to a tolerance.
                        "param_sample": [
                            np.asarray(x)
                            .ravel()[:: max(x.size // 64, 1)][:64]
                            .astype(np.float64)
                            .tolist()
                            for x in flat
                        ],
                        "device": device,
                        # Where the state lives: per leaf the global and
                        # per-device shard shapes, per device the
                        # allocator's view (None off-accelerator).
                        "placement": [
                            {
                                "shape": list(x.shape),
                                "shard": list(
                                    x.addressable_shards[0].data.shape
                                ),
                                "n_shards": len(x.addressable_shards),
                                "replicated": bool(
                                    x.sharding.is_fully_replicated
                                ),
                            }
                            for x in jax.tree_util.tree_leaves(
                                (ft.params, ft.opt_state)
                            )
                        ],
                        "memory": [
                            d.memory_stats() for d in jax.devices()
                        ],
                    },
                    f,
                )
        return 0
    finally:
        if ckpt is not None:
            ckpt.close()
        manager.shutdown()


if __name__ == "__main__":
    sys.exit(main())
