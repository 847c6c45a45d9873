"""Device time per step of the Kimi delta mixers between their
projections: the causal convolution over [q | k | v], the decay's and
beta's activations, the chunk algebra (the decayed products in sub-blocks,
the triangular inverse, W and U), the scan over the chunk states, the
outputs and the float32 side of the gated per-head norm, forward, remat's
second forward and backward. A lower bound, as ``gdn_ms`` says of itself:
trace_reduce keys an operation by its HLO instruction name and the start of
its (first) result type, so what is counted is an operation whose first
result has a shape only these stages have (B batch, S sequence, nc = S/C
chunks of C = 64 in ns = 4 sub-blocks of 16, H heads held, keys and values
of d):

- the chunk algebra and the scan (``scan_patterns``, also what
  ``kda_roofline`` times): results laid out by chunk, [B, nc, ..., H, ...]
  whatever stands between (a chunk's C rows, a sub-block's 16, the rows
  before a sub-block, the C x C and 16 x 16 matrices after H) or, in and
  around the ``lax.scan`` over the chunk states, [nc, B, ...], one
  chunk's [B, C, H, d], the carried state [B, H, d, d] and its decay
  [B, H, d]; XLA slices the batch for its asynchronous copies, so a leading
  B may read 1;
- the convolution: [B, S, 3 H d] and its padded input (no other tensor of
  the step is that wide) and the taps' gradient's [taps, 3 H d], [1, 3 H d]
  and [3 H d];
- the float32 [B, S, H d]: the decay's softplus, the gate's sigmoid and
  their gradients.

Not nameable, and so not in it: fusions whose first result is another
tensor's (XLA fuses parts of these stages into the projections' matmuls);
the bf16 [B, S, H d] tensors, which the projections' own matmuls lead with
too; every [B, S, H, d] (the normalisation of q and k, the gated norm),
which the attention layer's projections and gate lead with too where it
holds as many heads of the same width, as this cell's does; beta's
[B, S, H], which is the router's gates' [B, S, K] where a token takes as
many experts as the mixer holds heads, as this cell's does; what XLA lays
out with B and nc merged. None where the cell's architecture has no such
mixer or the trace none of these operations."""

from benchmark import readers

CHUNK = 64  # models/gated_delta.py CHUNK
FIRST = r"^\S+ \(?\w+"  # the instruction's name, then its (first) result's type


def dims(run):
    c, mix = run["cell"].config, run["cell"].mix
    linear = c.get("linear_attn_config")
    if not isinstance(linear, dict) or "kda_allow_neg_eigval" not in c:
        return None
    h, d = linear["num_heads"], linear["head_dim"]
    b, s = int(mix["batch"]), int(mix["seq"])
    return {"b": b, "s": s, "nc": -(-s // CHUNK), "c": CHUNK, "h": h, "d": d,
            "conv": 3 * h * d, "k": linear["short_conv_kernel_size"]}


def scan_patterns(d):
    b = rf"(?:1|{d['b']})"
    return [
        rf"{FIRST}\[{b},{d['nc']},(?:\d+,)*{d['h']}[,\]]",
        rf"{FIRST}\[{d['nc']},{b},(?:\d+,)*{d['h']}[,\]]",
        rf"{FIRST}\[{b},(?:{d['c']},)?{d['h']},{d['d']}[,\]]",
    ]


def patterns(d):
    b, s, h = rf"(?:1|{d['b']})", d["s"], d["h"]
    return scan_patterns(d) + [
        rf"{FIRST}\[{b},(?:{s}|{s + d['k'] - 1}),{d['conv']}\]",
        rf"^\S+ \(?f32\[(?:(?:{d['k']}|1),)?{d['conv']}\]",
        rf"^\S+ \(?f32\[{b},{s},{h * d['d']}\]",
    ]


def any_of(found):
    return "|".join(f"(?:{p})" for p in found)


def read(run):
    d = dims(run)
    return None if d is None else readers.kernel_ms_per_step(run, any_of(patterns(d)))
