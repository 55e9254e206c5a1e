"""The partial rebuild's fetch rate: bytes it read from other servers
(ec_repair_read_bytes_by_code_total{mode=partial}) over the seconds
spent fetching them (span_seconds{name=ec.rebuild.fetch}). MB = 1e6."""
from benchmark.deploy import total
from benchmark.program_spans import span_total


def read(run):
    s = span_total(run["counters"], "ec.rebuild.fetch")
    if not s:
        return None
    moved = total(run["counters"], "ec_repair_read_bytes_by_code_total",
                  mode="partial")
    return moved / s / 1e6
