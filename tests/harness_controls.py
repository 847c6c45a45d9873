"""The harness's reference check (``benchmark/worker.py`` ``reference_check``)
run ONCE on a small cell, and its controls read against that one compiled
sample.

A control hands the check another result in the system's place (the
reference in a lower precision or under a named departure, a gradient tree
with a dead leaf) and must come out not correct through the harness's own
comparison. Run plainly, every control traces and compiles the model's
init, the system's gradient step and the reference again, though only its
own side differs. ``shared_check`` runs the sound case once, keeps its
sample and both sides' results, and a control is the same check with the
kept reference result on one side and the control's on the other: nothing
of the sound case is traced or compiled again.
"""

from __future__ import annotations

import dataclasses
import zlib
from typing import Any, Callable, Dict, Optional

import jax
import pytest

from benchmark import worker
from torchft_tpu.parallel import train

SEED = 3000000001


def pin_path_hash(module: Any) -> None:
    """A benchmark test module that seeds a leaf's noise with ``hash`` of
    the leaf's path draws other numbers every process (Python salts a
    string's hash), and a case that compares two error levels then passes
    or fails by the salt. Tier-1 gives the module a ``hash`` that is the
    same every run; the file under ``benchmark/tests/`` stays as it is."""
    module.hash = lambda text: zlib.crc32(text.encode())


@dataclasses.dataclass
class SharedCheck:
    sound: Dict[str, Any]  # the harness's own result of the sound case
    kept: Dict[str, Any]  # "params", "sample", "system" and "reference" of it
    control: Callable[[Any], Dict[str, Any]]  # (loss, grads) in the system's place
    departed: Callable[..., Any]  # the reference under options on the kept sample
    system: Callable[..., Any]  # the program under another configuration on it


def shared_check(cell: Any, seq: int, seed: int = SEED) -> SharedCheck:
    """``cell`` (its mix already cut to a test's size) through
    ``worker.reference_check`` on a sample of ``seq`` tokens."""
    first = jax.devices()[:1]
    kept: Dict[str, Any] = {}
    real_step, real_reference = train.make_grad_step, cell.reference.loss_and_grads

    def recording_step(model, mesh, shardings):
        step = real_step(model, mesh, shardings)

        def run(params, sample):
            kept["params"], kept["sample"] = params, sample
            kept["model"], kept["mesh"], kept["shardings"] = model, mesh, shardings
            kept["system"] = step(params, sample)
            return kept["system"]
        return run

    def recording_reference(params, batch, config):
        out = real_reference(params, batch, config)
        jax.debug.callback(lambda *o: kept.__setitem__("reference", o), *out)
        return out

    def check(make_step, reference_side):
        """The harness's check under the two stand-ins, which are gone
        again when it returns."""
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(jax, "devices", lambda *a, **k: first)
            mp.setattr(worker, "CHECK_SEQ", seq)
            mp.setattr(train, "make_grad_step", make_step)
            mp.setattr(cell.reference, "loss_and_grads", reference_side)
            out = worker.reference_check(worker.Ctx(cell, seed, 0, False))
            jax.effects_barrier()
            return out

    sound = check(recording_step, recording_reference)

    def control(result):
        return check(
            lambda model, mesh, shardings: lambda params, sample: result,
            lambda params, batch, config: kept["reference"],
        )

    def departed(config: Optional[Dict[str, Any]] = None, **options):
        return jax.jit(
            lambda p, b: real_reference(p, b, config or cell.config, **options)
        )(kept["params"], kept["sample"])

    def system(**cfg_overrides):
        model = train.build_model(
            dataclasses.replace(kept["model"].cfg, **cfg_overrides), kept["mesh"])
        return real_step(model, kept["mesh"], kept["shardings"])(kept["params"], kept["sample"])

    return SharedCheck(sound, kept, control, departed, system)


def dead_leaf(grads: Any, *path: str) -> Any:
    """``grads`` with the leaf at ``path`` left at zero."""
    import jax.numpy as jnp

    def walk(node, keys):
        if not keys:
            return jnp.zeros_like(node)
        return {k: walk(v, keys[1:]) if k == keys[0] else v for k, v in node.items()}

    return walk(grads, path)
