"""Device time per step of the expert layer's grouped matmuls (gate, up
and down of every assignment; forward and the two backward products; a
forward that remat runs again counts again). PATTERN is the name the
trace shows for XLA's own lowering of ``lax.ragged_dot`` on a TPU: the
Mosaic kernels ``ragged-dot-none`` and their ``ragged-dot-metadata``
(looked at on a real profile, PERF.md section 6, PR 27)."""

from benchmark import readers

PATTERN = r"^ragged-dot"


def read(run):
    return readers.kernel_ms_per_step(run, PATTERN)
