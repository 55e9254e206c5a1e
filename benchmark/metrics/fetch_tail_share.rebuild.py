"""Share of the rebuild's fetch time spent after a chunk's first range
had arrived, waiting for the rest: Σ`span_seconds{name=ec.fetch.tail}`
over Σ`span_seconds{name=ec.rebuild.fetch}` (server/volume_server.py).
Where the fan-out needs every range it asks, this is the wait on the
slowest holder. None on a program without the interval."""
from benchmark.program_spans import span_total


def read(run):
    tail = span_total(run["counters"], "ec.fetch.tail")
    fetch = span_total(run["counters"], "ec.rebuild.fetch")
    if tail is None or not fetch:
        return None
    return tail / fetch * 100
