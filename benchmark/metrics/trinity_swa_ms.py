"""Device time per step of the banded (sliding-window) flash kernels in
Trinity-Mini's four sliding layers, forward, dq and dkv (remat runs the
forward twice), as ``swa_ms`` reads it: the trace's
``flash_attention_window`` kernels, which is what tells them from the
causal family's ``flash_attention.N`` of the same step's global layer. At
S = 16,384 under a window of 2,048 the band's rule takes tiles of 512
(``choose_tiles``): a query tile's sweep is five key tiles, two of them
crossed by a mask edge."""

from benchmark.metrics.swa_ms import read  # noqa: F401
