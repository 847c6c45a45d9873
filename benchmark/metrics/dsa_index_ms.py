"""Device time per step of a learned sparse attention's indexer around its
selection (``torchft_tpu/ops/sparse_index.py``): the score pass
I = sum_j w_j relu(qI_j kI^T) over every causal entry, the head-summed
probabilities of the main attention and the indexer's loss against them
(``index_kl``), and the indexer's backward, which forms G = softmax(I) - p
a chunk of queries at a time and hands it to the score pass's transpose.
Remat runs none of it twice: the selection is kept. trace_reduce keys an
operation by its HLO instruction name and the start of its (first) result
type, so a ``named_scope`` does not reach it; what does:

- the Pallas kernels of those passes, named for the jit around them,
  ``dsa_index_scores``, ``dsa_index_kl`` and ``dsa_index_scores_bwd``
  (what a TPU runs for sequences of whole tiles of 512);
- of the ``jax.numpy`` forms (other sequences), an operation whose first
  result is laid out by chunk of C = 512 query rows against all S keys with
  a heads' axis beside them: [C, J, S] (the index heads' scores) or
  [G, C, S] (a key/value head's group of query heads' scores), under unit
  leading axes.

The selection's own operations work on a chunk's rows against the keys with
no heads' axis, [C, S], and are ``dsa_select_ms``'s. None where the
configuration has no ``sa_config`` or the trace none of these operations."""

from benchmark import readers

CHUNK = 512  # ops/sparse_index.py CHUNK
FIRST = r"^\S+ \(?\w+"  # the instruction's name, then its (first) result's type
KERNELS = r"^dsa_index"


def dims(run):
    c, mix = run["cell"].config, run["cell"].mix
    if "sa_config" not in c:
        return None
    return {
        "b": int(mix["batch"]), "s": int(mix["seq"]), "c": CHUNK,
        "j": c["sa_config"]["indexer_num_heads"],
        "g": c["num_attention_heads"] // c["num_key_value_heads"],
    }


def patterns(d):
    s, c = d["s"], d["c"]
    return [
        KERNELS,
        rf"{FIRST}\[(?:1,)*{c},{d['j']},{s}\]",
        rf"{FIRST}\[(?:1,)*{d['g']},{c},{s}\]",
    ]


def read(run):
    d = dims(run)
    if d is None:
        return None
    return readers.kernel_ms_per_step(run, "|".join(f"(?:{p})" for p in patterns(d)))
