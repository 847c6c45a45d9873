"""Device time per step of the probabilities' pass of a learned sparse
attention's indexer, a part of ``dsa_index_ms``: the Pallas kernel
``dsa_index_kl`` (``torchft_tpu/ops/sparse_index.py``), which sums the main
attention's probabilities over its heads a tile pair at a time, recomputes
the pair's I and gives the pair's share of the indexer's loss (forward) or
of G = (softmax(I) - p) / rows (backward): two calls a layer and step. What
``dsa_index_ms`` holds beside it is the score pass (``dsa_index_scores``)
and its transpose (``dsa_index_scores_bwd``). ``dsa_index_ms`` says how
the trace keys an operation. None where the trace has no such kernel: a
configuration with no ``sa_config``, or the ``jax.numpy`` form of other
sequences, whose fusions no name tells from the score pass's."""

from benchmark import readers

KERNEL = r"^dsa_index_kl"


def read(run):
    return readers.kernel_ms_per_step(run, KERNEL)
