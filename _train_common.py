"""Helpers shared by the train-script entry points (train_ddp.py,
train_diloco.py, train_hsdp.py).

Lives at the repo root ON PURPOSE: the trainers import it BEFORE the
``torchft_tpu`` package (whose __init__ pulls in every submodule), so the
compile cache is placed before anything can compile. Children are pinned
to a platform by environment alone (``JAX_PLATFORMS=cpu``)."""

from __future__ import annotations

import os
import zlib

# The one compile cache of a checkout: fixed and git-ignored, because the
# directory is part of the cache key — a path that moves never hits.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), ".jax_cache"
)


def enable_compile_cache() -> None:
    """Places JAX's persistent compilation cache. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
    is set in code; otherwise the cache is ``COMPILE_CACHE_DIR``. Call
    before the first compile."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)


def drain_signal(enabled: bool = True, on_signal=None):
    """Installs the preemption-drain SIGTERM handler and returns a
    zero-arg callable reading the flag.

    TPU maintenance events / preemptions deliver SIGTERM with a grace
    period: the handler only sets a flag; the training loop drains at its
    next step boundary (finish the step, ``manager.leave()``, exit 0) so
    the last commit stays clean. A second SIGTERM escalates to default
    kill semantics — a trainer wedged in a collective that never reaches
    a boundary must stay killable.

    ``on_signal``: optional zero-arg callable run inside the handler
    (must be signal-safe — flags and socket shutdowns only). The
    trainers pass ``manager.abort_pending_quorum`` through a late-bound
    holder so a trainer blocked in a quorum wait when the SIGTERM lands
    drains immediately instead of waiting out a quorum that may never
    form again (every peer is draining too)."""
    import signal

    flag = [False]
    if enabled:

        def _on_sigterm(_signum, _frame):
            flag[0] = True
            signal.signal(signal.SIGTERM, signal.SIG_DFL)
            if on_signal is not None:
                try:
                    on_signal()
                except Exception:  # noqa: BLE001 - never die in a handler
                    pass

        signal.signal(signal.SIGTERM, _on_sigterm)
    return lambda: flag[0]


class DurableRegime:
    """The durable-snapshot wiring shared by the train scripts: periodic
    orbax snapshots on a committed-step cadence, a final snapshot on
    drain, restore-at-boot. Composes with live heal — snapshots are the
    same host-numpy state dicts the heal path ships, so restore reuses
    the heal loaders; what durable adds is survival of a FULL-job
    preemption (every replica drains; no live peer left to heal from).

    ``state_factory`` must return the snapshot pytree; it is called only
    when a save actually happens (off-cadence steps pay nothing).
    """

    def __init__(self, directory, replica_group: str, every: int):
        from torchft_tpu.checkpointing import DurableCheckpointer

        self._ckpt = DurableCheckpointer(
            os.path.join(directory, f"group{replica_group}"), every=every
        )
        self._group = replica_group

    def restore_if_any(self):
        """Latest snapshot as a host pytree, or None on a fresh boot."""
        if self._ckpt.latest_step() is None:
            return None
        return self._ckpt.restore()

    @staticmethod
    def rehang_like(cur, saved):
        """See ``DurableCheckpointer.rehang_like``: re-hangs ``saved``'s
        leaves on ``cur``'s live tree structure (serialization flattens
        optax NamedTuples and may drift leaf dtypes)."""
        from torchft_tpu.checkpointing.durable import DurableCheckpointer

        return DurableCheckpointer.rehang_like(cur, saved)

    @staticmethod
    def restore_manager(manager, snap) -> None:
        """Loads the manager scalars from a snapshot (orbax returns them
        as numpy 0-d arrays; the Manager stores plain ints)."""
        manager.load_state_dict(
            {k: int(v) for k, v in snap["manager"].items()}
        )

    def log_resumed(self, step: int) -> None:
        # Exact phrase is load-bearing: tools/drills.py preempt-all greps
        # "resumed from durable step N" to prove the resume source.
        print(
            f"[group {self._group}] resumed from durable step {step}",
            flush=True,
        )

    def on_commit(self, step: int, state_factory) -> None:
        self._ckpt.maybe_save(step, state_factory)

    def on_drain(self, step: int, state_factory) -> None:
        """Final synchronous snapshot at the drain boundary (skipped when
        the cadence already captured this exact step)."""
        if self._ckpt.latest_step() == step:
            return
        self._ckpt.save(step, state_factory())
        self._ckpt.wait()
        print(
            f"[group {self._group}] durable snapshot at step {step}",
            flush=True,
        )

    def close(self) -> None:
        self._ckpt.close()


def perf_note_compiled(name: str, jitted_fn, *args, **kwargs):
    """Records the jitted train step's compile-time FLOPs/bytes (XLA cost
    analysis) for MFU/roofline accounting when ``TORCHFT_PERF`` is set.

    Call once right after warmup with the SAME example arguments the
    step runs on (a different shape would cost a second trace). A no-op
    returning None unless the knob is set; never raises — perf
    accounting must not be able to fail a training run. The recorded
    cost feeds ``perf_step_suffix`` and a ``perf_model`` journal event
    (tools/perf_report.py folds it into the MFU section)."""
    from torchft_tpu import perf

    return perf.record_jit_cost(name, jitted_fn, *args, **kwargs)


def perf_step_suffix(name: str, dt_s: float) -> str:
    """Progress-line suffix like `` perf[0.42 TF/s mfu=1.2%]`` for a
    measured step time, or "" when TORCHFT_PERF is off / no cost was
    recorded for ``name``. Safe to call every step (dict lookup)."""
    from torchft_tpu import perf

    m = perf.step_metrics(name, dt_s)
    return perf.format_step_metrics(m) if m else ""


def group_data_seed(replica_group: str) -> int:
    """Deterministic data-shard seed for a replica group id: stable
    ACROSS process incarnations (``hash()`` is per-process randomized,
    which would hand a relaunched group an unrelated stream) and across
    the trainers (DistributedSampler semantics, reference data.py)."""
    seed = (
        int(replica_group)
        if replica_group.isdigit()
        else zlib.crc32(replica_group.encode())
    )
    return seed % (2**31)
