"""p50 per bucket of the program's `torchft::collectives::dequant_push`
stage over the traced steps. Stages of different buckets run on threads
and overlap, so the three do not add up to `allreduce_ms`."""

from benchmark import readers


def read(run):
    return readers.host_annotation_p50_ms(run, "torchft::collectives::dequant_push")
