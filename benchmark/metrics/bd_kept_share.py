"""Of the score entries the attention computes under the block-diffusion
mask, the share the mask keeps: the step program's own ``bd_kept_share``,
a constant of its compiled tile schedule (kept entries L^2 + L*b over the
entries of the tiles the flash kernels run, (n^2 + 2n) tiles of 512 x 512
with n = L/512; over the whole 2L x 2L square where the program fell back
to dense attention). One minus it is the part of ``flash_ms`` spent on
entries that are masked away inside kept tiles: 0.889 at L = 8192, b = 4.
None on a program whose step counts no such thing."""

from benchmark import readers


def read(run):
    return readers.counter_median(run, "bd_kept_share")
