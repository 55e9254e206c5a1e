"""Share of the seal window spent in the volume server's generate
handler (request_trace_seconds{handler=handle_ec_generate}); the rest
is the shell's spread of shards, the mounts and the delete."""
from benchmark.deploy import total


def read(run):
    if not any(j.get("op") == "encode" for j in run["jobs"]):
        return None
    s = total(run["counters"], "request_trace_seconds_sum",
              handler="handle_ec_generate")
    return s / run["window_s"] * 100
