"""Bytes the repair fetched from other servers
(ec_repair_read_bytes_by_code_total) per shard byte rebuilt: the
repair planner's fan-in, less the shards the rebuilder holds itself,
which it reads locally and the counter leaves out. A count."""
from benchmark.deploy import total


def read(run):
    jobs = [j for j in run["jobs"] if j.get("op") == "rebuild" and "end" in j]
    rebuilt = sum(j["rebuilt_bytes"] for j in jobs)
    if not rebuilt:
        return None
    return total(run["counters"], "ec_repair_read_bytes_by_code_total") \
        / rebuilt
