"""Share of the shard ranges the window's fan-outs submitted that found
every fetch-pool worker busy and waited for one
(Δec_fetch_fanout_queued_total / Δec_fetch_fanout_ranges_total,
server/volume_server.py `_remote_shards_fetch_sync`). 0 where each
fan-out fits the pool; None on a program without the counters."""
from benchmark.deploy import total


def read(run):
    c = run["counters"]
    names = {n for n, _ in c}
    if not {"ec_fetch_fanout_queued_total",
            "ec_fetch_fanout_ranges_total"} <= names:
        return None
    ranges = total(c, "ec_fetch_fanout_ranges_total")
    if not ranges:
        return None
    return total(c, "ec_fetch_fanout_queued_total") / ranges * 100
