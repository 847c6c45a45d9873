"""DDP quantized-wire numerical regression with golden fixtures
(companion to test_diloco_regression.py, same harness discipline —
reference: diloco_regression_test.py:30-127).

Two replica-group threads with real Managers (C++ manager-server
subprocesses), a real in-proc C++ lighthouse, and socket process groups
push deterministic per-replica gradients through
``DistributedDataParallel.allreduce_grads`` on the int4+error-feedback
wire every step. The full per-step parameter history is pinned against a
committed JSON fixture: silent drift in the DDP bucket path, the nibble
codec, or the ErrorFeedback residual math fails here.

The int4 wire is lossy but DETERMINISTIC (blockwise quantize -> fp32
alltoall reduce -> allgather), so comparisons are exact, and both
replicas must decode bitwise-identical averaged gradients.

Regenerate fixtures with:  WRITE_FIXTURE=true pytest tests/test_ddp_regression.py
"""

import json
import os
import threading
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path
from typing import List

import numpy as np
import pytest

from torchft_tpu.coordination import LighthouseServer
from torchft_tpu.ddp import DistributedDataParallel
from torchft_tpu.manager import Manager
from torchft_tpu.process_group import ProcessGroupSocket

FIXTURE_DIR = Path(__file__).parent / "fixtures"
WRITE_FIXTURE = os.environ.get("WRITE_FIXTURE", "").lower() in ("1", "true")

STEPS = 6
N = 16  # param/grad width; spans two quantizer blocks at block size 8


def _grad(replica: int, step: int) -> np.ndarray:
    """Deterministic, replica-distinct, non-representable values (forces
    real quantization error so error feedback has work to do)."""
    base = np.sin(np.arange(N, dtype=np.float32) * 0.7 + step)
    return ((replica + 1) * 0.1 * base).astype(np.float32)


def _run_replica(
    replica: int,
    lighthouse_addr: str,
    barrier: threading.Barrier,
    quantize_bits: int,
    error_feedback: bool,
) -> List[List[float]]:
    params = np.linspace(-2.0, 2.0, N, dtype=np.float32)
    manager = Manager(
        pg=ProcessGroupSocket(timeout=15.0),
        min_replica_size=2,
        use_async_quorum=False,
        timeout=15.0,
        quorum_timeout=30.0,
        replica_id=f"ddpregr{replica}",
        lighthouse_addr=lighthouse_addr,
        group_rank=0,
        group_world_size=1,
        init_sync=False,
    )
    ddp = DistributedDataParallel(
        manager,
        error_feedback=error_feedback,
        quantize_bits=quantize_bits,
    )
    history: List[List[float]] = []
    try:
        for step in range(STEPS):
            barrier.wait(timeout=60)
            manager.start_quorum()
            out = ddp.allreduce_grads(
                {"w": _grad(replica, step)}, should_quantize=True
            )
            if manager.should_commit():
                params = params - out["w"]
            history.append([float(v) for v in params])
        if error_feedback:
            assert ddp._residuals, "EF run must record bucket residuals"
    finally:
        manager.shutdown()
    return history


def _run_pair(quantize_bits: int, error_feedback: bool):
    lighthouse = LighthouseServer(
        bind="127.0.0.1:0",
        min_replicas=2,
        join_timeout_ms=20000,
        quorum_tick_ms=50,
    )
    barrier = threading.Barrier(2)
    try:
        with ThreadPoolExecutor(max_workers=2) as pool:
            futs = [
                pool.submit(
                    _run_replica,
                    r,
                    lighthouse.address(),
                    barrier,
                    quantize_bits,
                    error_feedback,
                )
                for r in range(2)
            ]
            return [f.result(timeout=180) for f in futs]
    finally:
        lighthouse.shutdown()


def _check_golden(name: str, history: List[List[float]]) -> None:
    path = FIXTURE_DIR / f"{name}.json"
    if WRITE_FIXTURE:
        FIXTURE_DIR.mkdir(exist_ok=True)
        with open(path, "w") as f:
            json.dump(history, f, indent=1)
        pytest.skip(f"wrote fixture {path}")
    assert path.exists(), (
        f"missing fixture {path}; regenerate with WRITE_FIXTURE=true"
    )
    with open(path) as f:
        golden = json.load(f)
    assert history == golden, (
        f"parameter history drifted from golden {name}; if the change is "
        "intentional, regenerate with WRITE_FIXTURE=true"
    )


@pytest.mark.timeout(240)
def test_ddp_golden_int4_error_feedback() -> None:
    h0, h1 = _run_pair(quantize_bits=4, error_feedback=True)
    assert h0 == h1, "replicas decoded different averaged gradients"
    _check_golden("ddp_int4ef", h0)


def test_device_and_host_bucket_layouts_identical() -> None:
    """The TPU device-quantize path sends one allreduce PER BUCKET so it
    stays collective-for-collective symmetric with host-path replicas
    (the socket PG pairs ops in issue order).  That only holds if
    bucketize groups jax device arrays exactly as it groups their numpy
    host copies — pin the dtype/nbytes-equivalence that symmetry rests
    on, across mixed dtypes and a bucket-cap split."""
    import jax.numpy as jnp

    from torchft_tpu.collectives import bucketize

    leaves = [
        jnp.ones((300_000,), jnp.float32),   # ~1.2 MB
        jnp.ones((64,), jnp.int32),
        jnp.ones((300_000,), jnp.float32),
        jnp.ones((128, 128), jnp.float32),
        jnp.ones((32,), jnp.int32),
    ]
    host = [np.asarray(x) for x in leaves]
    cap = 1 * 1024 * 1024  # 1 MB: forces the fp32 leaves apart
    assert bucketize(leaves, cap) == bucketize(host, cap)
    assert len(bucketize(leaves, cap)) >= 3  # the cap actually split


@pytest.mark.parametrize("bits", [8, 4])
def test_device_and_host_wire_payloads_identical(monkeypatch, bits) -> None:
    """Wire symmetry between a device-path (TPU) replica
    and a host-path (CPU) peer rests on the device path's per-bucket
    payload matching ``quantize_blockwise`` of the concatenated host
    flat BYTE-FOR-BYTE — layout equality alone
    (test_device_and_host_bucket_layouts_identical) can't catch a
    mismatched scale layout, pad handling, or nibble packing.  Drive
    ``allreduce_quantized_jax`` down the device path (Pallas interpreter
    via TORCHFT_FORCE_DEVICE_QUANT) for a multi-leaf bucket with odd
    sizes (tail-block padding) and capture what reaches the wire."""
    import jax.numpy as jnp

    from torchft_tpu import collectives as C

    rng = np.random.default_rng(7)
    leaves = [
        jnp.asarray(rng.standard_normal((37, 5)), jnp.float32),
        jnp.asarray(rng.standard_normal((300,)), jnp.float32),
        jnp.asarray(rng.standard_normal((641,)), jnp.float32),  # odd tail
    ]

    captured = {}

    def fake_pipeline(pg, q_host, s_host, n, b):
        captured["wire"] = (
            np.array(q_host, copy=True),
            np.array(s_host, copy=True),
            int(n),
            int(b),
        )
        # Tiny-payload contract: return the full fp32 local sum (peer
        # contributes zeros), as the real pipeline does for small n.
        return C.dequantize_blockwise(q_host, s_host, n, b)

    class _PG:
        def size(self):
            return 2

    monkeypatch.setenv("TORCHFT_FORCE_DEVICE_QUANT", "1")
    monkeypatch.setattr(C, "_quantized_wire_pipeline", fake_pipeline)
    work = C.allreduce_quantized_jax(_PG(), leaves, bits=bits)
    outs = work.wait(timeout=120)
    assert len(outs) == len(leaves)

    flat_host = np.concatenate(
        [np.asarray(x).reshape(-1).astype(np.float32) for x in leaves]
    )
    q_host, s_host = C.quantize_blockwise(flat_host, bits)
    q_dev, s_dev, n_dev, bits_dev = captured["wire"]
    assert bits_dev == bits
    assert n_dev == flat_host.size
    np.testing.assert_array_equal(
        q_dev, q_host,
        err_msg="device-path wire bytes != host quantize_blockwise "
        "(heterogeneous TPU/CPU replica pairs would desync)",
    )
    np.testing.assert_allclose(s_dev, s_host, rtol=1e-6, atol=0.0)


def test_error_feedback_width_pinned_at_construction() -> None:
    """A per-call quantize_bits that diverges from the ctor width would
    make the EF hook mis-decode its own wire payload — rejected loudly."""

    class _NoopManager:
        pass

    ddp = DistributedDataParallel(
        _NoopManager(), error_feedback=True, quantize_bits=4
    )
    with pytest.raises(ValueError, match="error-feedback width"):
        ddp.allreduce_grads(
            {"w": np.ones(8, np.float32)},
            should_quantize=True,
            quantize_bits=8,
        )


@pytest.mark.timeout(240)
def test_ddp_int4_error_feedback_changes_the_stream() -> None:
    """EF compensates each step's payload with the previous step's
    residual, so the int4 histories with and without feedback must
    diverge — pinning that the hook actually fires on the DDP path (a
    silently-dropped hook would make the EF fixture vacuous)."""
    h_ef, _ = _run_pair(quantize_bits=4, error_feedback=True)
    h_plain, _ = _run_pair(quantize_bits=4, error_feedback=False)
    assert h_ef != h_plain
