"""Share of the summed ec.rebuild wall time spent writing the rebuilt
shard files (span_seconds{name=ec.rebuild.write})."""
from benchmark.program_spans import job_seconds, span_total


def read(run):
    jobs = job_seconds(run, "rebuild")
    s = span_total(run["counters"], "ec.rebuild.write")
    if not jobs or s is None:
        return None
    return s / jobs * 100
