"""Share of the seal window the shell spent spreading the new shards:
the copies to the other servers, their mounts and the source's delete
of what it gave away (span_seconds{name=ec.encode.spread},
shell/commands_ec.py)."""
from benchmark.program_spans import span_total


def read(run):
    if not any(j.get("op") == "encode" for j in run["jobs"]):
        return None
    s = span_total(run["counters"], "ec.encode.spread")
    return None if s is None else s / run["window_s"] * 100
