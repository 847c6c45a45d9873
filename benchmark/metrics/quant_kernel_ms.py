"""Device time per step of the Pallas quantize and dequantize kernels of
the int8 replica allreduce, by the names the trace shows: the quantize
kernel takes its enclosing jit's name, `_quantize_rows.N`; the dequantize
kernel is called outside any jit and is a bare `tpu_custom_call.N`
(PERF.md §7: name the kernels). The slices, copies and
`dynamic-update-slice`s XLA runs around them are not kernel time; the
breakdown lists them."""

from benchmark import readers

PATTERN = r"^(_quantize_rows|tpu_custom_call)\."


def read(run):
    return readers.kernel_ms_per_step(run, PATTERN)
