"""Device time per step of the banded (sliding-window) flash kernels alone,
forward, dq and dkv (remat runs the forward twice): a part of ``flash_ms``,
whose pattern matches these names too. PATTERN is the name the trace shows
for them: a kernel is named for the jit around it,
``ops/flash_attention.py`` ``flash_attention_window``, which is what tells
them from the causal family's ``flash_attention.N`` of the same step's
global layers. None where the trace has no such kernel (a program without
the family, a cell without a windowed layer)."""

from benchmark import readers

PATTERN = r"^flash_attention_window"


def read(run):
    return readers.kernel_ms_per_step(run, PATTERN)
