"""Median of the same reads as degraded_read_p99_ms (not a decider):
the service as the client sees it, with the tail left out."""


def read(run):
    reads = [r for r in run["reads"] if r["status"] == 200]
    if not reads:
        return None
    ms = sorted((r["end"] - r["due"]) * 1e3 for r in reads)
    return ms[(len(ms) - 1) // 2]
