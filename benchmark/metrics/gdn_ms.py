"""Device time per step of the gated-delta mixers between their
projections: the causal convolution over [q | k | v], the per-head
normalisation, the chunk algebra (decay matrices, the triangular inverse,
W and U), the scan over the chunk states, the outputs and the gated
per-head norm, forward, remat's second forward and backward. A lower
bound, as ``ssm_ms`` says of itself: trace_reduce keys an operation by its
HLO instruction name and the start of its (first) result type, so what is
counted is an operation whose first result has a shape only these stages
have (B batch, S sequence, nc = S/C chunks of C = 64, H heads held, keys
of dk and values of dv):

- the chunk algebra and the scan (``scan_patterns``, also what
  ``gdn_roofline`` times): results laid out by chunk, [B, nc, C, H, ...]
  or [B, nc, H, ...] (the C x C matrices), or, in and around the
  ``lax.scan`` over the chunk states, [nc, B, C, H, ...], [nc, B, H, ...],
  one chunk's [B, C, H, dk | dv], the carried state [B, H, dk, dv] and its
  decay [B, H]; XLA slices the batch for its asynchronous copies, so a
  leading B may read 1;
- the convolution: [B, S, conv] and its padded input (conv = 2 H dk +
  H dv; no other tensor of the step is that wide) and the taps' gradient's
  [taps, conv] and [conv];
- the normalisation of q and k and the gated norm: float32 [B, S, H dk],
  [B, S, H dv] and [B, S, H], any [B, S, H, dk | dv], and the per-head
  vectors' gradients float32 [dv] and [H].

Not nameable, and so not in it: fusions whose first result is another
tensor's (XLA fuses parts of these stages into the projections' matmuls),
and the bf16 [B, S, H dk] and [B, S, H dv] tensors, which the projections'
own matmuls lead with too. None where the cell's architecture has no such
mixer or the trace none of these operations."""

from benchmark import readers

CHUNK = 64  # models/gated_delta.py CHUNK
FIRST = r"^\S+ \(?\w+"  # the instruction's name, then its (first) result's type


def dims(run):
    c, mix = run["cell"].config, run["cell"].mix
    if "linear_num_key_heads" not in c:
        return None
    h, dk, dv = c["linear_num_key_heads"], c["linear_key_head_dim"], c["linear_value_head_dim"]
    b, s = int(mix["batch"]), int(mix["seq"])
    return {"b": b, "s": s, "nc": -(-s // CHUNK), "c": CHUNK, "h": h, "dk": dk,
            "dv": dv, "conv": 2 * h * dk + h * dv, "k": c["linear_conv_kernel_dim"]}


def scan_patterns(d):
    b, width = rf"(?:1|{d['b']})", rf"(?:{d['dk']}|{d['dv']}|{d['c']})"
    return [
        rf"{FIRST}\[{b},{d['nc']},(?:{d['c']},|1,)?{d['h']}[,\]]",
        rf"{FIRST}\[{d['nc']},{b},(?:{d['c']},)?{d['h']}[,\]]",
        rf"{FIRST}\[{b},(?:{d['c']},)?{d['h']},{width}[,\]]",
        rf"{FIRST}\[{b},{d['h']}\]",
    ]


def patterns(d):
    b, s, h = d["b"], d["s"], d["h"]
    return scan_patterns(d) + [
        rf"{FIRST}\[{b},(?:{s}|{s + d['k'] - 1}),{d['conv']}\]",
        rf"^\S+ \(?f32\[(?:{d['k']},)?{d['conv']}\]",
        rf"^\S+ \(?f32\[{b},{s},(?:{h * d['dk']}|{h * d['dv']}|{h})\]",
        rf"{FIRST}\[{b},{s},{h},(?:{d['dk']}|{d['dv']})\]",
        rf"^\S+ \(?f32\[(?:{d['dv']}|{h})\]",
    ]


def any_of(found):
    return "|".join(f"(?:{p})" for p in found)


def read(run):
    d = dims(run)
    return None if d is None else readers.kernel_ms_per_step(run, any_of(patterns(d)))
