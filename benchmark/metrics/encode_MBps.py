"""A seal job's rate: .dat bytes of every volume whose ec.encode ran in
the window, over the window's wall time. Jobs run back to back and the
one in flight at the close runs to its end, so the window holds whole
jobs. Host clock; MB = 1e6 bytes."""


def read(run):
    jobs = [j for j in run["jobs"] if j.get("op") == "encode" and "end" in j]
    if not jobs:
        return None
    return sum(j["dat_bytes"] for j in jobs) / run["window_s"] / 1e6
