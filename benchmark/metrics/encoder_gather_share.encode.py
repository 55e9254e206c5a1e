"""Share of the file encoder's time (span_seconds{name=ec.write_ec_files},
ec/encoder.py) spent in the row-group transpose that turns .dat bytes
into codec columns (span_seconds{name=ec.encode.gather})."""
from benchmark.program_spans import span_total


def read(run):
    whole = span_total(run["counters"], "ec.write_ec_files")
    part = span_total(run["counters"], "ec.encode.gather")
    if not whole or part is None:
        return None
    return part / whole * 100
