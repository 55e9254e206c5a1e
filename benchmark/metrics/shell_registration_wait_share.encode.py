"""Share of the seal window the shell spent waiting for the master to
list every new shard (span_seconds{name=ec.encode.wait_registration},
shell/commands_ec.py): the heartbeat's part of a seal job."""
from benchmark.program_spans import span_total


def read(run):
    if not any(j.get("op") == "encode" for j in run["jobs"]):
        return None
    s = span_total(run["counters"], "ec.encode.wait_registration")
    return None if s is None else s / run["window_s"] * 100
