"""The banded flash kernels' share of their roofline in Trinity-Mini's
sliding layers, as ``swa_roofline`` reads it: the least time the chip needs
for the attention those layers require (this architecture's flops.py:
``swa_flops_per_step`` over the entries the band KEEPS at w = 2,048,
31,458,304 a head and sequence at 16,384, never the tiles the kernels run,
nothing recomputed; compute-bound) over the kernels' measured device time
(``trinity_swa_ms``). The tiles' masked entries (a fifth of what the
kernels compute at tiles of 512: ``trinity_swa_kept_share``) and remat's
second forward are the distance to 100."""

from benchmark.metrics.swa_roofline import read  # noqa: F401
