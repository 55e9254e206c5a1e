"""Share of the file encoder's time (span_seconds{name=ec.write_ec_files},
ec/encoder.py) the producer spent blocked on the shard writers'
backpressure (span_seconds{name=ec.encode.write_wait}): how far the
shard writes hold the encode back. The wait is timed only when it
happens, so a program that times the encoder but never waited reads 0."""
from benchmark.program_spans import span_total


def read(run):
    whole = span_total(run["counters"], "ec.write_ec_files")
    part = span_total(run["counters"], "ec.encode.write_wait")
    if not whole:
        return None
    return (part or 0.0) / whole * 100
