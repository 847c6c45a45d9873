"""The indexer kernels' share of their roofline: the least time the chip
needs for the indexer's passes a step requires (this architecture's
flops.py: ``index_flops_per_step``, the score pass forward and its two
backward products over ALL causal entries at 2 FLOP an entry, index head and
width, and one QK^T of the main heads for the head-summed probabilities,
nothing recomputed; ``index_bytes_per_step``) over the kernels' measured
device time (``dsa_index_ms``). The kernels compute more than that: I is
recomputed in both probabilities' passes and in the transpose, the
probabilities' pass runs forward (the loss) and backward (G), the score
pass's contractions are 64 deep on a 128-deep MXU, and every tile pair on or
under the diagonal runs whole: the distance to 100. None where the
architecture has no indexer or the trace none of its kernels."""

from benchmark import readers
from benchmark.metrics import dsa_index_ms


def read(run):
    ms = dsa_index_ms.read(run)
    mix = run["cell"].mix
    b, s = int(mix["batch"]), int(mix["seq"])
    ops = readers.kernel_work(run, "index_flops_per_step", b, s)
    nbytes = readers.kernel_work(run, "index_bytes_per_step", b, s)
    if ms is None or ops is None or nbytes is None:
        return None
    least = max(
        ops / readers.peak(run, "bf16_flops_per_s"),
        nbytes / readers.peak(run, "hbm_bytes_per_s"),
    )
    return 100.0 * least * 1e3 / ms
