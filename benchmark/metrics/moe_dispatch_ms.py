"""What droplessness costs beside the matmuls: moe_ms less moe_gmm_ms,
the sorts, the gathers that dispatch and combine rows, SwiGLU and the
weights' casts (moe_ms says what the trace can name)."""

from benchmark.metrics import moe_gmm_ms, moe_ms


def read(run):
    whole, gmm = moe_ms.read(run), moe_gmm_ms.read(run)
    return None if whole is None or gmm is None else whole - gmm
