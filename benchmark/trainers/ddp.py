"""Fault-tolerant data parallelism, one replica group per process: the
step loop of ``train_hsdp.py`` (lines 323-369 at a2d870a) composed from
the library's public API in the same order — ``start_quorum`` ->
``grad_step`` -> ``mm.allreduce_grads`` -> fenced ``should_commit`` ->
undonated ``apply_step``. Process group, bucket size, timeouts and the
Manager's arguments are the library's defaults as that trainer passes
them; the mix chooses only whether the replica allreduce is quantized.

The lasting home of this loop is a step function owned by the library
(PERF.md, "inside the program"); until then a gain that needs another
loop arrives as another file here with a mix and a cell of its own.

See ``raw.py`` for what a trainer file provides.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import numpy as np
import optax

from benchmark.worker import Ctx, StepOut
from torchft_tpu.parallel.train import (
    default_optimizer,
    init_train_state,
    make_grad_step,
)


def build_programs(model, mesh, shardings) -> Dict[str, Any]:
    optimizer = default_optimizer()

    def apply_fn(params, opt_state, grads):
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    return {
        "grad": make_grad_step(model, mesh, shardings),
        # No donation, as train_hsdp.py: at its peak the loop holds old and
        # new weights and moments plus the reduced gradient.
        "apply": jax.jit(
            apply_fn,
            in_shardings=(shardings.params, shardings.opt_state, shardings.params),
            out_shardings=(shardings.params, shardings.opt_state),
        ),
    }


class Trainer:
    def __init__(self, ctx: Ctx) -> None:
        from torchft_tpu.device_mesh import ft_init_device_mesh
        from torchft_tpu.manager import Manager
        from torchft_tpu.process_group import make_process_group

        self.ctx = ctx
        mix = ctx.mix
        with ctx.phase("init"):
            state, self.shardings = init_train_state(
                ctx.model, ctx.mesh, jax.random.PRNGKey(ctx.seed),
                (ctx.batch_size, ctx.seq),
            )
            jax.block_until_ready(state)
        self.params, self.opt_state = state.params, state.opt_state
        progs = build_programs(ctx.model, ctx.mesh, self.shardings)
        self.grad_step, self.apply_step = progs["grad"], progs["apply"]
        self.quantize = bool(mix["quantize"])
        self.bits = int(mix["quantize_bits"])
        self.n_started = 0
        self.first_allreduce_exact = None

        def state_dict():
            return {
                "params": jax.tree_util.tree_map(np.asarray, self.params),
                "opt_state": jax.tree_util.tree_map(np.asarray, self.opt_state),
            }

        def load_state(sd):
            self.params = jax.device_put(sd["params"], self.shardings.params)
            self.opt_state = jax.device_put(
                sd["opt_state"], self.shardings.opt_state
            )

        with ctx.phase("manager"):
            self.manager = Manager(
                pg=make_process_group(timeout=30.0),
                state_dict=state_dict,
                load_state_dict=load_state,
                min_replica_size=int(mix["min_replicas"]),
                use_async_quorum=True,
                timeout=60.0,
                quorum_timeout=60.0,
                connect_timeout=30.0,
                max_retries=20,
            )
            self.mm = ft_init_device_mesh(self.manager, mesh=ctx.mesh)

    def step(self) -> StepOut:
        ctx, manager = self.ctx, self.manager
        with ctx.span("quorum"):
            manager.start_quorum()
            if ctx.blocked:  # so the wait is not booked to the allreduce
                manager.wait_quorum()
        with ctx.span("data"):
            batch = ctx.block(ctx.batch(self.n_started))
        check_exact = (
            self.n_started == 0 and not self.quantize and ctx.n_groups == 1
        )
        self.n_started += 1
        with ctx.span("grad"):
            loss, grads = self.grad_step(self.params, batch)
            ctx.block(grads)
        # The device's gradient on the host (jax keeps the copy, so the
        # allreduce's own pull finds it there), for the check below.
        expect = (
            [np.asarray(x) for x in jax.tree_util.tree_leaves(grads)]
            if check_exact else None
        )
        with ctx.span("allreduce"):
            # Rebinding frees the device's gradient, as train_hsdp.py does.
            grads = self.mm.allreduce_grads(
                grads, should_quantize=self.quantize, quantize_bits=self.bits
            )
            ctx.block(grads)
        if expect is not None:
            # fp32, AVG over a quorum of one: the replica axis must hand
            # back the device's gradient bit for bit.
            self.first_allreduce_exact = all(
                np.array_equal(a, np.asarray(b))
                for a, b in zip(expect, jax.tree_util.tree_leaves(grads))
            )
            del expect
        # Fenced as in train_hsdp.py: the commit decision and the update
        # are one critical section against concurrent checkpoint sends.
        with manager.fenced_state_dict():
            with ctx.span("commit"):
                committed = manager.should_commit()
            if committed:
                with ctx.span("apply"):
                    self.params, self.opt_state = self.apply_step(
                        self.params, self.opt_state, grads
                    )
                    ctx.block(self.params)
        return StepOut(committed, float(loss), ctx.tokens_per_step)

    def sync(self) -> None:
        jax.block_until_ready((self.params, self.opt_state))

    def checks(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {"manager_step": self.manager.current_step()}
        if self.first_allreduce_exact is not None:
            out["quorum_of_one_allreduce_bit_exact"] = self.first_allreduce_exact
        return out

    def fingerprint_tree(self) -> Any:
        return self.params

    def close(self) -> None:
        self.manager.shutdown()
