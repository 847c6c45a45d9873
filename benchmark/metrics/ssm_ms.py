"""Device time per step of the Mamba-2 mixers between their two
projections: the causal convolution, the chunked state-space scan and the
grouped gated norm, forward, remat's second forward and backward.
trace_reduce keys an operation by its HLO instruction name and the start
of its (first) result type, so a ``named_scope`` does not reach it; what
does is an operation whose first result has a shape only these stages
have (B batch, S sequence, nc = S/Q chunks of Q, H heads of P on G groups
of R = H/G, state N):

- the scan (``scan_patterns``, also what ``ssm_roofline`` times): results
  laid out by chunk, [B, nc, Q, G, ...] or [B, nc, G, ...], or, inside and
  around the ``lax.scan`` over the chunk states, [nc, B, G, R, ...],
  [B*nc*G, R, P, N] and the carried state [B, G, R, P, N] and its decay
  [B, G, R];
- the convolution: [B, S, conv_dim] (conv_dim = H P + 2 G N, no other
  tensor of the step is that wide), its padded input and the per-channel
  reductions [conv_dim] of its kernel's and bias's gradients;
- the gated norm and the scan's float32 output: float32 [B, S, H P],
  [B, S, G, H P / G], [B, S, H, P], [B, S, G] and the per-channel [H P].

Not nameable, and so not in it: fusions whose first result is another
tensor's (XLA fuses parts of these stages into the projections' matmuls
and into multi-output fusions that lead with a norm's statistics), the
bf16 [B, S, H P] input of out_proj (the attention's output has that shape
too), dt's [B, S, H]. PERF.md section 5 says how much of the step that
leaves unattributed. None where the cell's architecture has no such mixer
or the trace none of these operations."""

from benchmark import readers


def dims(run):
    c, mix = run["cell"].config, run["cell"].mix
    if "mamba_num_heads" not in c:
        return None
    h, p, g = c["mamba_num_heads"], c["mamba_head_dim"], c["n_groups"]
    b, s, q = int(mix["batch"]), int(mix["seq"]), c["chunk_size"]
    return {"b": b, "s": s, "nc": -(-s // q), "h": h, "p": p, "g": g,
            "r": h // g, "n": c["ssm_state_size"], "inner": h * p,
            "conv": h * p + 2 * g * c["ssm_state_size"], "k": c["conv_kernel"], "q": q}


FIRST = r"^\S+ \(?\w+"  # the instruction's name, then its (first) result's type


def scan_patterns(d):
    # A chunk axis is followed by the chunk's positions or by the groups:
    # the chunked loss also lays its hidden states out as [B, n, C, hidden]
    # and [n, B, C, hidden], and its n is nc where its C is Q.
    return [
        rf"{FIRST}\[{d['b']},{d['nc']},(?:{d['q']},)?{d['g']},",
        rf"{FIRST}\[{d['nc']},{d['b']},{d['g']},{d['r']},",
        rf"{FIRST}\[{d['b'] * d['nc'] * d['g']},{d['r']},{d['p']},{d['n']}\]",
        rf"{FIRST}\[{d['b']},{d['g']},{d['r']}(?:,{d['p']},{d['n']})?\]",
    ]


def patterns(d):
    b, s = d["b"], d["s"]
    return scan_patterns(d) + [
        rf"{FIRST}\[{b},(?:{s}|{s + d['k'] - 1}),{d['conv']}\]",
        rf"^\S+ \(?f32\[{d['conv']}\]",
        rf"^\S+ \(?f32\[{b},{s},{d['inner']}\]",
        rf"{FIRST}\[{b},{s},{d['g']},{d['inner'] // d['g']}\]",
        rf"{FIRST}\[{b},{s},{d['h']},{d['p']}\]",
        rf"{FIRST}\[{b},{s},{d['g']}\]",
        rf"^\S+ \(?f32\[{d['inner']}\]",
    ]


def any_of(found):
    return "|".join(f"(?:{p})" for p in found)


def read(run):
    d = dims(run)
    return None if d is None else readers.kernel_ms_per_step(run, any_of(patterns(d)))
