"""Device time per step of what the trace can name of SDAR's expert layer
(three-matrix SiLU-gated experts, a share of them held): what
``gated_held_ms`` reads, by its patterns, with one difference of shape.
Block diffusion sends BOTH streams' rows through the layer, so the held
dispatch's buffer, the vector of assignments and the router's sort are
those of 2 x seq rows a sequence: the patterns are ``gated_held_ms``'s at a
sequence twice the mix's. None where the configuration is no ``sdar_moe``
one, on a program without such a layer, or where the trace has none of
these operations."""

import dataclasses

from benchmark.metrics import gated_held_ms


def read(run):
    cell = run["cell"]
    if cell.config.get("model_type") != "sdar_moe":
        return None
    rows = dataclasses.replace(cell, mix={**cell.mix, "seq": 2 * int(cell.mix["seq"])})
    return gated_held_ms.read({**run, "cell": rows})
