"""Device time per step of everything in the expert layer that the trace
can name: the grouped matmuls (moe_gmm_ms) and, by the shapes only this
layer has, the operations around them. trace_reduce keys an operation by
its HLO instruction name and the start of its result type, so a
``named_scope`` does not reach it; what does is an operation whose
(first) result is

- an array of T*K rows (T tokens, K experts a token): the two argsorts,
  the dispatch and combine gathers and their transposes, SwiGLU between
  the matmuls, forward and backward;
- bf16 and shaped like the stacked expert weights, [E, H, I] or
  [E, I, H], with or without the layer axis in front: the weights' casts
  to the compute type and their re-tilings (the optimizer's own fusions
  are float32 and are not counted);
- the router's top-k, which XLA runs as a sort over [batch, seq, E].

Not nameable, and so not in it (1.9 + 0.8 + 0.8 ms of a 226 ms step on
the first profile, PERF.md section 6, PR 27): the weighted sum over a
token's K rows and its transpose, the sum of a token's K row gradients
(results of T rows, like the rest of the block), the router's matmul and
softmax. None where the cell's architecture has no experts or the trace
none of these operations."""

import re

from benchmark import readers
from benchmark.metrics import moe_gmm_ms


def patterns(run):
    config, mix = run["cell"].config, run["cell"].mix
    experts, k = config.get("num_experts"), config.get("num_experts_per_tok")
    if not experts or not k:
        return None
    b, s = int(mix["batch"]), int(mix["seq"])
    h, i = config["hidden_size"], config["intermediate_size"]
    layers = config["num_hidden_layers"]
    first = r"^\S+ \(?"  # the instruction's name, then its (first) result
    return [
        moe_gmm_ms.PATTERN,
        rf"{first}\w+\[{b * s * k}[,\]]",
        rf"{first}bf16\[(?:{layers},)?{experts},(?:{h},{i}|{i},{h})\]",
        rf"^sort\S* \(f32\[{b},{s},{experts}\]",
    ]


def read(run):
    found = patterns(run)
    if found is None:
        return None
    return readers.kernel_ms_per_step(run, "|".join(f"(?:{p})" for p in found))
