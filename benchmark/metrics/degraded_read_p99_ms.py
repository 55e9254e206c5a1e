"""99th percentile (nearest rank) of every read of the window, each
timed from its due time; a read that got no answer counts as the
generator's 60 s timeout. Host clock."""
import math

TIMEOUT_MS = 60000.0


def read(run):
    reads = run["reads"]
    if not reads:
        return None
    ms = sorted((r["end"] - r["due"]) * 1e3 if r["status"] == 200
                else TIMEOUT_MS for r in reads)
    return ms[math.ceil(0.99 * len(ms)) - 1]
