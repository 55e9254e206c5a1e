"""Share of the summed ec.rebuild wall time spent in the partial
rebuild's codec calls, one per chunk: pad, H2D, kernel and D2H
(span_seconds{name=ec.rebuild.reconstruct})."""
from benchmark.program_spans import job_seconds, span_total


def read(run):
    jobs = job_seconds(run, "rebuild")
    s = span_total(run["counters"], "ec.rebuild.reconstruct")
    if not jobs or s is None:
        return None
    return s / jobs * 100
