"""Bytes of the shard ranges the partial rebuild handed the codec, local
and remote (ec_repair_input_bytes_total), per shard byte rebuilt: the
repair plan's input set, with no fan-out extras. 10 for an RS(10,4)
single loss, 6 for LRC(12,2,2)'s local group, 6.5 or 7 for
Hitchhiker's half ranges. None on a program without the counter. A
count."""
from benchmark.deploy import total

COUNTER = "ec_repair_input_bytes_total"


def read(run):
    if not any(name == COUNTER for name, _ in run["counters"]):
        return None
    jobs = [j for j in run["jobs"] if j.get("op") == "rebuild" and "end" in j]
    rebuilt = sum(j["rebuilt_bytes"] for j in jobs)
    if not rebuilt:
        return None
    return total(run["counters"], COUNTER) / rebuilt
