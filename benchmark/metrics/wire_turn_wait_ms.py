"""p50 per bucket of `torchft::collectives::wire_turn_wait`: how long a
bucket whose payload was ready waited for the serialised wire
(`_WireOrder`) behind the buckets issued before it."""

from benchmark import span_readers


def read(run):
    return span_readers.p50_ms(run, "torchft::collectives::wire_turn_wait")
