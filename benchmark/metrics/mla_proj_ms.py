"""Device time per step of what the trace can name of latent attention
around its flash kernels (``models/mla.py``: the low-rank projections,
their norms, the rotary step and the splits and layouts that feed the
kernels), forward, remat's second forward and backward. trace_reduce keys
an operation by its HLO instruction name and the start of its (first)
result type, so a ``named_scope`` does not reach it; what does, from the
cell's B (batch), S (sequence) and the configuration's heads, ranks and
head widths, is an operation other than the kernels themselves
(``flash_attention...``, which ``flash_ms`` reads) whose first result is

- [B,S,r_kv + Dr], [B,S,r_kv] or [B,S,Dr]: W_kva's matmul, the split of
  its result, the latent's norm and its gradient, the shared rotary key's
  rotation and the sum that brings its gradient back;
- [B,S,H,Dn + Dr] or [B,S,H,Dn + Dv]: W_qb's and W_kvb's matmuls with
  the norm's scaling fused in;
- [B,S,H,d] or [B,H,S,d] for a d of Dn, Dr or Dv: the rotation of the
  queries' rotary part, the slices, copies and transposes between the
  projections' layout and the kernels', the broadcasts of the backward
  pass;
- a tuple that leads with float32 [r_q] or [r_kv] and then [B,S]: the
  backward pass of a bottleneck's norm (the scale's gradient, the rows'
  statistics), with the gradient of the bottleneck fused in.

Not nameable, and so not in it: W_qa's matmul forward, which XLA fuses
with the norm's row statistics and leads with a float32 [B,S] like every
other norm of the block; the four weight gradients, which XLA fuses into
the optimizer's update of each weight (tuples of float32 weight shapes, as
every other weight's); the output projection W_o (a result of the
residual stream's shape). So the time is a lower bound of the stage's.
None where the configuration has no latent ranks or the trace none of
these operations."""

from benchmark import readers


def dims(run):
    c, mix = run["cell"].config, run["cell"].mix
    if "kv_lora_rank" not in c or "q_lora_rank" not in c:
        return None
    return {
        "b": int(mix["batch"]), "s": int(mix["seq"]),
        "h": c["num_attention_heads"], "rq": c["q_lora_rank"],
        "rkv": c["kv_lora_rank"], "dn": c["qk_nope_head_dim"],
        "dr": c["qk_rope_head_dim"], "dv": c["v_head_dim"],
    }


def patterns(d):
    b, s, h, rq, rkv = d["b"], d["s"], d["h"], d["rq"], d["rkv"]
    dn, dr, dv = d["dn"], d["dr"], d["dv"]
    first = r"^(?!flash_attention)\S+ \(?"  # an instruction's name, then its (first) result
    widths = "|".join(str(w) for w in sorted({dn + dr, dn + dv, dn, dr, dv}))
    return [
        rf"{first}\w+\[{b},{s},(?:{rkv + dr}|{rkv}|{dr})\]",
        rf"{first}\w+\[{b},(?:{s},{h}|{h},{s}),(?:{widths})\]",
        rf"^\S*fusion\S* \(f32\[(?:{rq}|{rkv})\]\S*, f32\[{b},{s}\]",
    ]


def read(run):
    d = dims(run)
    if d is None:
        return None
    return readers.kernel_ms_per_step(run, "|".join(f"(?:{p})" for p in patterns(d)))
