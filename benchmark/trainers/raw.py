"""The plain single-worker step loop: ``make_train_step`` (gradient and
AdamW fused in one donated program), no Manager, no lighthouse.

What a trainer file provides (worker.py loads it by the mix's
``trainer``): ``build_programs(model, mesh, shardings)`` returning the
jitted programs by name (the rehearsal compiles them for a described
chip), and ``Trainer(ctx)`` with ``step() -> StepOut``, ``sync()``,
``checks()``, ``fingerprint_tree()`` and ``close()``. The worker owns
the window, the clock and the stop rule; a trainer owns what one step is.
"""

from __future__ import annotations

from typing import Any, Dict

import jax

from benchmark.worker import Ctx, StepOut
from torchft_tpu.parallel.train import init_train_state, make_train_step


def build_programs(model, mesh, shardings) -> Dict[str, Any]:
    return {"step": make_train_step(model, mesh, shardings)}


class Trainer:
    def __init__(self, ctx: Ctx) -> None:
        self.ctx = ctx
        self.n_started = 0
        with ctx.phase("init"):
            self.state, shardings = init_train_state(
                ctx.model, ctx.mesh, jax.random.PRNGKey(ctx.seed),
                (ctx.batch_size, ctx.seq),
            )
            jax.block_until_ready(self.state)
        self.train_step = build_programs(ctx.model, ctx.mesh, shardings)["step"]

    def step(self) -> StepOut:
        ctx = self.ctx
        with ctx.span("data"):
            batch = ctx.block(ctx.batch(self.n_started))
        self.n_started += 1
        with ctx.span("step"):
            self.state, metrics = self.train_step(self.state, batch)
            ctx.block(self.state)
        # The metrics come out of the fused program: reading them waits
        # for the whole step, optimizer included. One transfer for all of
        # them; what is not the loss goes to the worker's records under
        # the program's own names, for the per-layer metrics that read it.
        counters = {k: float(v) for k, v in jax.device_get(metrics).items()}
        return StepOut(True, counters.pop("loss"), ctx.tokens_per_step, counters)

    def sync(self) -> None:
        jax.block_until_ready(self.state)

    def checks(self) -> Dict[str, Any]:
        return {}

    def fingerprint_tree(self) -> Any:
        return self.state.params

    def close(self) -> None:
        pass
