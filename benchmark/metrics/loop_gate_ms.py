"""Device time per step of what the trace can name of a looped model's
exit gate and exit distribution (``models/llama.py`` ``Transformer._looped``
under the scope ``loop/gate``; ``parallel/train.py`` ``exit_distribution``
and the loss's weighted sums under ``loop/exit``), forward, remat's second
forward and backward. It is expected well under 1% of the step, and that
is what it guards: a gate or an exit loss that stops fusing, or grows a
pass over [T, B, S, H], shows here. trace_reduce keys an operation by its
HLO instruction name and the start of its (first) result type, so a
``named_scope`` does not reach it; what does, from the cell's B (batch),
S (sequence) and T (``total_ut_steps``):

- an operation whose first result is float32 [T, B, S] or [T-1, B, S]: the
  stacked gate logits, the logs and their running sum, p, the entropy's
  terms and their gradients (no other tensor of the step has a leading
  axis of the steps without the hidden axis);
- a reduce fusion that leads with a tuple of float32 [B, S] (the gate's
  dot with the normed rows, written as a multiply and a row sum beside the
  final norm's own output; the entropy's and the expected loss's sums over
  the steps) or with a scalar and then float32 [B, S] (the bias's
  gradient).

Not nameable, and so not in it: the gate kernel's gradient, which XLA
fuses into the final norm's backward pass (a result of the stream's
shape); the rows' weights' product inside the head's chunks
(``ouro_head_loss_ms``'s). The compiled step's own ``op_name`` scopes
(PERF.md section 5) say which instructions these are. None where the
configuration has no loop or the trace none of these operations."""

from benchmark import readers


def dims(run):
    c, mix = run["cell"].config, run["cell"].mix
    if not c.get("total_ut_steps"):
        return None
    return {"b": int(mix["batch"]), "s": int(mix["seq"]), "t": int(c["total_ut_steps"])}


def patterns(d):
    b, s, t = d["b"], d["s"], d["t"]
    return [
        rf"^\S+ \(?f32\[(?:{t}|{t - 1}),{b},{s}\]",
        rf"^\S*reduce\S* \(f32\[{b},{s}\]",
        rf"^\S*reduce\S* \(f32\[\]\S*, f32\[{b},{s}\]",
    ]


def read(run):
    d = dims(run)
    if d is None:
        return None
    return readers.kernel_ms_per_step(run, "|".join(f"(?:{p})" for p in patterns(d)))
