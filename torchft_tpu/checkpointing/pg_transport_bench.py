"""Heal-bandwidth benchmark for the PG checkpoint transport.

Role of the reference's ``torchft/checkpointing/pg_transport_bench.py``
(12 GB default workload, send/fetch wall-time): measures how fast a
restarted replica can pull a multi-GB train state from a live peer over
the socket process group — the critical input to 8B-scale heal time.

Two modes:

- ``--dense`` (default): host numpy pytree, the classic full-state
  transfer.
- ``--sharded``: the state is a pytree of ``jax.Array``s sharded over an
  ``--devices``-way mesh (fsdp-style rows); the transfer moves only
  addressable shards and the receiver rebuilds each leaf directly onto
  its devices via the sharded PGTransport path
  (checkpointing/sharded.py), deleting stale leaves as it goes.

Run (CPU box / CI):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python -m torchft_tpu.checkpointing.pg_transport_bench \
        --size-gb 1.0 --sharded --devices 8

Prints one JSON line: send/recv wall seconds, payload GB, GB/s, and a
correctness checksum verdict.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from typing import Any, List


def _require_devices(n_devices: int) -> None:
    """Asking for more devices than JAX sees is an error, never a silent
    move onto other ones."""
    import jax

    have = len(jax.devices())
    if have < n_devices:
        raise SystemExit(
            f"--devices {n_devices} but JAX sees {have} "
            f"({jax.default_backend()}); for a virtual CPU mesh run with "
            "JAX_PLATFORMS=cpu XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n_devices}"
        )


from torchft_tpu.checkpointing._bench_common import (
    build_state as _build_state_common,
    checksum as _checksum,
    checksum_ok as _checksum_ok,
    payload_bytes as _payload_bytes,
)


def _build_state(
    size_gb: float, n_leaves: int, sharded: bool, n_devices: int, fill: float
) -> Any:
    return _build_state_common(
        size_gb, n_leaves, fill, sharded=sharded, n_devices=n_devices
    )


def _calibrate(n_bytes: int) -> dict:
    """Environment floor for the same byte count: raw single-stream TCP
    loopback between two OS processes (what ANY transport pays on this
    box before doing anything useful) and single-thread memcpy.  The
    transport's recv wall divided by raw_tcp_s isolates FRAMEWORK
    overhead from environment bandwidth — on a contended 1-core host the
    GB/s number alone conflates the two."""
    import socket

    code = (
        "import socket,time\n"
        "s=socket.socket(); s.bind(('127.0.0.1',0)); s.listen(1)\n"
        "print(s.getsockname()[1],flush=True)\n"
        "c,_=s.accept(); t0=time.perf_counter(); n=0\n"
        "while True:\n"
        "    b=c.recv(1<<22)\n"
        "    if not b: break\n"
        "    n+=len(b)\n"
        "print('RECV',n,time.perf_counter()-t0,flush=True)\n"
    )
    child = subprocess.Popen(
        [sys.executable, "-c", code], stdout=subprocess.PIPE, text=True
    )
    try:
        import select

        ready, _, _ = select.select([child.stdout], [], [], 60.0)
        if not ready:
            raise TimeoutError("calibration receiver never printed its port")
        port = int(child.stdout.readline())
        buf = memoryview(bytearray(1 << 22))
        conn = socket.create_connection(("127.0.0.1", port))
        sent = 0
        while sent < n_bytes:
            m = min(len(buf), n_bytes - sent)
            conn.sendall(buf[:m])
            sent += m
        conn.close()
        tail, _ = child.communicate(timeout=600)
        rec = [ln for ln in tail.splitlines() if ln.startswith("RECV")][-1]
        _, got, wall = rec.split()
        assert int(got) == n_bytes, (got, n_bytes)
        tcp_s = float(wall)
    finally:
        if child.poll() is None:
            child.kill()

    import numpy as np

    m_bytes = min(n_bytes, 1 << 30)
    src = np.ones(m_bytes, np.uint8)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        dst = src.copy()
        best = min(best, time.perf_counter() - t0)
    del dst
    gb = 1 << 30
    return {
        "raw_tcp_s": round(tcp_s, 3),
        "raw_tcp_gb_per_s": round(n_bytes / gb / tcp_s, 3),
        "memcpy_gb_per_s": round(m_bytes / gb / best, 3),
    }


def _run_receiver(args: argparse.Namespace) -> int:
    if args.sharded:
        _require_devices(args.devices)
    from torchft_tpu.checkpointing.pg_transport import PGTransport
    from torchft_tpu.process_group import ProcessGroupSocket

    pg = ProcessGroupSocket(timeout=args.timeout)
    pg.configure(args.store, rank=1, world_size=2)
    # Target with the destination shardings (zero-filled).
    target = _build_state(
        args.size_gb, args.leaves, args.sharded, args.devices, fill=0.0
    )
    transport = PGTransport(
        pg,
        timeout=args.timeout,
        state_dict_fn=lambda: target,
        sharded=args.sharded,
        delete_stale_leaves=True,  # dedicated buffer: bounded-HBM path
    )
    t0 = time.perf_counter()
    got = transport.recv_checkpoint(
        src_rank=0, metadata="<n/a>", step=7, timeout=args.timeout
    )
    recv_s = time.perf_counter() - t0
    print(
        json.dumps(
            {"recv_s": recv_s, "checksum": _checksum(got)}
        ),
        flush=True,
    )
    pg.shutdown()
    return 0


def main(argv: List[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--size-gb", type=float, default=1.0,
                   help="payload size (reference bench default: 12)")
    p.add_argument("--leaves", type=int, default=32)
    p.add_argument("--sharded", action="store_true")
    p.add_argument("--dense", action="store_true",
                   help="host numpy pytree, full-state transfer (default)")
    p.add_argument("--devices", type=int, default=8)
    p.add_argument("--timeout", type=float, default=600.0)
    p.add_argument(
        "--calibrate", action="store_true",
        help="also measure the environment floor for the same bytes "
        "(raw 2-process TCP loopback + memcpy) and report the "
        "transport's recv wall relative to it",
    )
    p.add_argument("--store", default=None, help=argparse.SUPPRESS)
    p.add_argument("--role", default=None, help=argparse.SUPPRESS)
    args = p.parse_args(argv)
    if args.dense and args.sharded:
        p.error("--dense and --sharded are mutually exclusive")

    if args.role == "recv":
        return _run_receiver(args)

    if args.sharded:
        _require_devices(args.devices)

    from torchft_tpu.checkpointing.pg_transport import PGTransport
    from torchft_tpu.process_group import ProcessGroupSocket
    from torchft_tpu.store import TCPStoreServer

    store = TCPStoreServer()
    store_addr = f"{store.address()}/pgbench"
    child = subprocess.Popen(
        [sys.executable, "-m", __spec__.name, "--role", "recv",
         "--store", store_addr, "--size-gb", str(args.size_gb),
         "--leaves", str(args.leaves), "--devices", str(args.devices),
         "--timeout", str(args.timeout)]
        + (["--sharded"] if args.sharded else []),
        stdout=subprocess.PIPE,
        text=True,
        env=dict(os.environ),
    )
    try:
        pg = ProcessGroupSocket(timeout=args.timeout)
        pg.configure(store_addr, rank=0, world_size=2)
        state = _build_state(
            args.size_gb, args.leaves, args.sharded, args.devices, fill=1.0
        )
        payload = _payload_bytes(state)
        transport = PGTransport(pg, timeout=args.timeout,
                                sharded=args.sharded)
        t0 = time.perf_counter()
        transport.send_checkpoint(
            dst_ranks=[1], step=7, state_dict=state, timeout=args.timeout
        )
        send_s = time.perf_counter() - t0
        out, _ = child.communicate(timeout=args.timeout)
        peer = json.loads(out.strip().splitlines()[-1])
        ok = _checksum_ok(peer["checksum"], _checksum(state))
        result = {
            "bench": "pg_transport",
            "mode": "sharded" if args.sharded else "dense",
            "payload_gb": round(payload / (1 << 30), 3),
            "send_s": round(send_s, 3),
            "recv_s": round(peer["recv_s"], 3),
            "gb_per_s": round(payload / (1 << 30) / peer["recv_s"], 3),
            "checksum_ok": ok,
        }
        if args.calibrate:
            cal = _calibrate(payload)
            result["calibration"] = cal
            # recv wall over the raw byte-move floor: ~1.0 means the
            # transport is environment-bandwidth-bound (framework adds
            # nothing); production heal time then scales as
            # vs_raw_tcp * payload / NIC rate.
            result["vs_raw_tcp"] = round(
                peer["recv_s"] / cal["raw_tcp_s"], 3
            )
        print(json.dumps(result), flush=True)
        pg.shutdown()
        return 0 if ok else 1
    finally:
        if child.poll() is None:
            child.kill()
        store.shutdown()


if __name__ == "__main__":
    sys.exit(main())
