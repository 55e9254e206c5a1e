"""How fast a volume gets back to full redundancy: the volume .dat
bytes restored, summed, over the summed wall time of the ec.rebuild
commands, each from its start to the master's registration of every
shard. The loss and its detection lie outside the timed intervals.
Host clock; MB = 1e6 bytes."""


def read(run):
    jobs = [j for j in run["jobs"] if j.get("op") == "rebuild" and "end" in j]
    if not jobs:
        return None
    return sum(j["dat_bytes"] for j in jobs) / \
        sum(j["end"] - j["start"] for j in jobs) / 1e6
