"""Device time per step of what the trace can name of the head and loss
(``parallel.train._loss_and_metrics`` after the trunk: the vocabulary
projection, the softmax cross-entropy and their gradients, a scan over
chunks of C tokens a row of the batch). trace_reduce keys an operation by
its HLO instruction name and the start of its (first) result type, so a
``named_scope`` does not reach it; what does, from the cell's B (batch),
S (sequence), H (hidden) and V (vocabulary), whatever divisor of S the
chunk C is (128 in the checkpointed scan this layer was up to PR 41, from
the shapes since PR 42, or what ``TORCHFT_LOSS_CHUNK`` says), with a
chunk's rows as [B,C] (PR 41) or as one axis of R = B*C (PR 42):

- a fusion that leads with a float32 [B,C] or [B,C,V] result, or with a
  float32 [R] result followed by one that starts [R, (the logits) or, in
  a fusion XLA gave no name but ``fusion.N``, by a second [R]: the logits
  matmul with the row maxima it carries out beside the logits (in the
  checkpointed scan twice, forward and recomputed), the sum of
  exponentials and the target's logit;
- a single [R,V] result: the logits' gradient where it is written out;
- a single result shaped like the head, [H,V], in the compute type or in
  float32: the weight gradient's matmul and its accumulation across the
  chunks (bfloat16 in the checkpointed scan's backward carry, float32 in
  the single pass), the head's cast to the compute type, the gradient's
  product with the scalar cotangent;
- what leads with [S/C,B,C,H] or [S/C,R,H]: the chunk's hidden-state
  gradient (the matmul of the logits gradient with the head, in PR 41's
  program fused into a ``dynamic-update-slice``) and the copies that lay
  the hidden states out by chunk and back.

Not nameable, and so not in it: with a tied table the sum of the head's
gradient into the table's, which leads with [V,H] like the optimizer's own
fusions; the optimizer's update of the head, a tuple of [H,V] results and
not this layer's; what XLA fuses of the hidden-state gradient's way back
into the final norm's backward pass. A model whose shapes make [H,V] or
[S/C,R,H] the shape of another tensor would count that tensor's
operations too (at C = S/B the latter is the residual stream's [B,S,H]
and is left out); none of the benchmark's does. Where R = H a plain
``fusion.N`` that leads with two float32 [H] results reads like the row
sums: ``internlm2-raw`` (R = H = 2,048) has one, the optimizer's update of
a norm weight, 0.4 us a step; ``lfm2-raw``'s four
``multiply_reduce_fusion.N`` of that shape (the short convolutions'
gradients, 12.6 ms a step, ``short_conv_ms``'s) are told apart by their
name alone (``tests/test_tpu_compile.py`` holds the patterns against two
cells' compiled programs). In a cell that runs other programs beside the
gradient program (the fault-tolerant cells: the replica allreduce, the
update), an operation of theirs that leads with a bare [H,V] result is
counted as well. None where the trace has none of these operations."""

from benchmark import readers


def dims(run):
    c, mix = run["cell"].config, run["cell"].mix
    return {
        "b": int(mix["batch"]), "s": int(mix["seq"]),
        "h": c["hidden_size"], "v": c["vocab_size"],
    }


def patterns(d):
    """One pattern a kind of operation, over every chunk a sequence of
    ``s`` tokens can be cut into (multiples of 128 below ``s``)."""
    b, s, h, v = d["b"], d["s"], d["h"], d["v"]
    chunks = [c for c in range(128, s, 128) if s % c == 0]
    if not chunks:
        return []

    def any_of(fmt, among=chunks):
        return "|".join(fmt.format(c=c, r=b * c, n=s // c) for c in among)

    # [S/C,R,H] at C = S/B is the residual stream's own [B,S,H].
    apart = [c for c in chunks if (s // c, b * c) != (b, s)]

    return [
        rf"^\S*fusion\S* \(?f32\[{b},(?:{any_of('{c}')})(?:,{v})?\]",
        rf"^\S*fusion\S* \(f32\[(?P<r>{any_of('{r}')})\]\S*, f32\[(?P=r),",
        rf"^fusion\.\d+ \(f32\[(?P<rows>{any_of('{r}')})\]\S*, f32\[(?P=rows)\]",
        rf"^\S+ \w+\[(?:{any_of('{r}')}),{v}\]",
        rf"^\S+ (?:bf16|f32)\[{h},{v}\]",
        rf"^\S+ \(?\w+\[(?:{any_of('{n},' + str(b) + ',{c}')}|{any_of('{n},{r}', apart)}),{h}\]",
    ]


def read(run):
    found = patterns(dims(run))
    if not found:
        return None
    return readers.kernel_ms_per_step(run, "|".join(f"(?:{p})" for p in found))
