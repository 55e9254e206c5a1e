"""Open-loop reader, run as a child process that never imports JAX:
the chip belongs to the parent, and a generator in the parent would
share its interpreter lock with the servers it measures.

    python3 benchmark/loadgen.py <plan.json> <out.json>

The plan gives the absolute start (time.monotonic(), one clock for
every process of the host), the spacing of arrivals, the targets
[url, fid] and the target of each request in order. Request i is due
at start + i * interval and is timed from its due time, so a stall
delays every request behind it. Each answer is kept as its BLAKE2b
digest for the parent to compare.
"""
from __future__ import annotations

import asyncio
import hashlib
import json
import sys
import time


async def _run(plan: dict) -> list:
    import aiohttp

    start, interval = plan["start"], plan["interval_s"]
    targets, order = plan["targets"], plan["requests"]
    timeout = aiohttp.ClientTimeout(total=plan["timeout_s"])
    out: list = [None] * len(order)
    conn = aiohttp.TCPConnector(limit=plan.get("connections", 64))
    async with aiohttp.ClientSession(connector=conn,
                                     timeout=timeout) as http:
        async def one(i: int) -> None:
            due = start + i * interval
            await asyncio.sleep(max(0.0, due - time.monotonic()))
            sent = time.monotonic()
            url, fid = targets[order[i]]
            status, digest = 0, ""
            try:
                async with http.get(f"http://{url}/{fid}") as resp:
                    body = await resp.read()
                    status = resp.status
                    digest = hashlib.blake2b(body, digest_size=16
                                             ).hexdigest()
            except (aiohttp.ClientError, asyncio.TimeoutError) as e:
                digest = f"error: {type(e).__name__}"
            out[i] = [due, sent, time.monotonic(), status, digest]

        await asyncio.gather(*(one(i) for i in range(len(order))))
    return out


def main(argv: list[str]) -> int:
    with open(argv[0], encoding="utf-8") as f:
        plan = json.load(f)
    rows = asyncio.run(_run(plan))
    with open(argv[1], "w", encoding="utf-8") as f:
        json.dump(rows, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
