"""Of the score entries Trinity-Mini's sliding layers compute, the share
the band keeps: the step program's own ``swa_kept_share``, as
``swa_kept_share`` reads it, a constant of the compiled tile schedule. At
S = 16,384, w = 2,048: 0.800 at the tiles of 512 the band's rule takes (150
tiles a head); 0.667 at 1,024 (45), 0.889 at 256 (540), so it also says
which tiles a step compiled."""

from benchmark.metrics.swa_kept_share import read  # noqa: F401
