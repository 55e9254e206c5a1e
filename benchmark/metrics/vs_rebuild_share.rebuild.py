"""Share of the summed ec.rebuild wall time spent in the rebuilder's
partial-rebuild handler (request_trace_seconds
{handler=handle_ec_rebuild_partial}); the rest is the shell's planning
and the wait for the master's registration."""
from benchmark.deploy import total


def read(run):
    jobs = [j for j in run["jobs"] if j.get("op") == "rebuild" and "end" in j]
    if not jobs:
        return None
    s = total(run["counters"], "request_trace_seconds_sum",
              handler="handle_ec_rebuild_partial")
    return s / sum(j["end"] - j["start"] for j in jobs) * 100
