"""Share of the summed ec.rebuild wall time the rebuilder spent
fetching shard ranges from other servers, throttle included
(span_seconds{name=ec.rebuild.fetch}, server/volume_server.py partial
rebuild)."""
from benchmark.program_spans import job_seconds, span_total


def read(run):
    jobs = job_seconds(run, "rebuild")
    s = span_total(run["counters"], "ec.rebuild.fetch")
    if not jobs or s is None:
        return None
    return s / jobs * 100
