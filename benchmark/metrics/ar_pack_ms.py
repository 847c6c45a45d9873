"""Median per step of the summed `torchft::ddp::pack` (concatenate each
bucket's leaves into one flat buffer, plus the error-feedback
compensation when on) and `torchft::manager::host_copy` (`to_mutable`)
spans: the host's copies before the process group sees a bucket."""

from benchmark import span_readers


def read(run):
    return span_readers.sum_ms(
        run, "torchft::ddp::pack", "torchft::manager::host_copy"
    )
