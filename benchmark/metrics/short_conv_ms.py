"""Device time per step of what the trace can name of the gated short
convolutions between their two projections (``ShortConvMixer``: B*u, the
taps, C*w), forward, remat's second forward and backward. trace_reduce
keys an operation by its HLO instruction name and the start of its (first)
result type, so a ``named_scope`` does not reach it; what does, in the
plain XLA form the program has today (B batch, S sequence, H hidden):

- the first gate's product B*u, which XLA writes out as float32 [B, S, H]
  before the taps read it at three offsets: a fusion whose first result is
  that (no other fusion of the step leads with a float32 tensor of the
  residual stream's shape), forward and remat's forward;
- the backward pass's elementwise work as far as it stands alone: a
  reduce fusion that leads with two per-channel float32 [H] sums (the
  depthwise kernel's gradient, tap by tap) and carries the gradients of
  the taps and of the second gate beside them;
- a kernel named ``short_conv...``, for the PR that writes one.

Not nameable, and so not in it: the taps and the second gate forward,
which XLA fuses into ``out_proj``'s matmul as its producer; the gradient
of in_proj's 3H-wide output, fused likewise into both of ``in_proj``'s
backward matmuls (and computed twice there); a multi-output fusion that
leads with a bf16 [B, S, H] tensor like the rest of the block. So the
time is a lower bound of the stage's (PERF.md section 5 has the step by
scope from the compiler's own metadata). None where the cell's
architecture has no such mixer or the trace none of these operations."""

from benchmark import readers


def dims(run):
    c, mix = run["cell"].config, run["cell"].mix
    if "conv_L_cache" not in c:
        return None
    return {"b": int(mix["batch"]), "s": int(mix["seq"]), "h": c["hidden_size"]}


def patterns(d):
    return [
        r"^short_conv",
        rf"^\S*fusion\S* \(?f32\[{d['b']},{d['s']},{d['h']}\]",
        rf"^\S*reduce\S* \(f32\[{d['h']}\]\S*, f32\[{d['h']}\]",
    ]


def read(run):
    d = dims(run)
    if d is None:
        return None
    return readers.kernel_ms_per_step(run, "|".join(f"(?:{p})" for p in patterns(d)))
