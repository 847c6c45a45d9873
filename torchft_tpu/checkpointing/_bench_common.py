"""Shared scaffolding for the checkpoint-transport bench harnesses
(pg_transport_bench / http_transport_bench): synthetic train-state
builder, payload accounting, and the content checksum both harnesses
compare — kept in ONE place so the heal-bench numbers stay comparable
across transports."""

from __future__ import annotations

from typing import Any

import numpy as np

# Relative tolerance for the sender/receiver checksum comparison; both
# harnesses must use the same value for their ok verdicts to mean the
# same thing.
CHECKSUM_RTOL = 1e-3


def build_state(
    size_gb: float,
    n_leaves: int,
    fill: float,
    sharded: bool = False,
    n_devices: int = 0,
) -> Any:
    """A train-state-shaped pytree: n_leaves 2D fp32 arrays of equal size
    (half under "params", half under "opt" as an optimizer-moment
    mirror), plus scalar step metadata.  With ``sharded=True`` the leaves
    are jax arrays row-sharded (fsdp-style) over an ``n_devices`` mesh."""
    total_elems = int(size_gb * (1 << 30) / 4)
    per_leaf = max(total_elems // n_leaves, 1 << 10)
    cols = 1024
    rows = max(per_leaf // cols, 1)
    if sharded:
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        devs = jax.devices()[:n_devices]
        mesh = Mesh(np.array(devs), ("fsdp",))
        rows = ((rows + n_devices - 1) // n_devices) * n_devices
        sharding = NamedSharding(mesh, P("fsdp", None))

        def leaf(i: int):
            # From host numpy: each device receives only its rows (a
            # jnp.full would first build the whole leaf on device 0).
            return jax.device_put(
                np.full((rows, cols), fill + i, np.float32), sharding
            )

        leaves = [leaf(i) for i in range(n_leaves)]
    else:
        leaves = [
            np.full((rows, cols), fill + i, np.float32)
            for i in range(n_leaves)
        ]
    half = n_leaves // 2
    return {
        "params": {f"layer{i}": leaves[i] for i in range(half)},
        "opt": {f"mu{i}": leaves[i] for i in range(half, n_leaves)},
        "step": 7,
    }


def payload_bytes(state: Any) -> int:
    total = 0
    for tree in (state["params"], state["opt"]):
        for v in tree.values():
            total += int(np.prod(v.shape)) * v.dtype.itemsize
    return total


def checksum(state: Any) -> float:
    """Cheap content fingerprint: sum of each leaf's first-row mean."""
    acc = 0.0
    for tree in (state["params"], state["opt"]):
        for v in tree.values():
            acc += float(np.asarray(v[0]).mean())
    return acc


def checksum_ok(got: float, expect: float) -> bool:
    return abs(got - expect) < CHECKSUM_RTOL * max(abs(expect), 1.0)
