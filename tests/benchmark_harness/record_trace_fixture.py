"""Record the trace fixture of test_trace_reduce.py on the chip:

    python3 tests/benchmark_harness/record_trace_fixture.py

One traced `bench.window` holding two pallas encodes (bench.encode_job),
a 50 ms host wait (bench.idle) and a reconstruct (bench.rebuild_job),
written to fixtures/tpu_window.xplane.pb with its summary beside it.
"""
from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def main() -> int:
    import jax
    import numpy as np

    from benchmark import trace_reduce
    from benchmark.run import start_trace
    from seaweedfs_tpu.ec.backend import ReedSolomon

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record the fixture on the chip")
    rs = ReedSolomon(10, 4, backend="pallas")
    data = np.random.default_rng(0).integers(0, 256, (10, 8 << 20),
                                             dtype=np.uint8)
    parity = rs.encode(data)
    shards = {i: data[i] for i in range(1, 10)}
    shards.update({10 + j: parity[j] for j in range(4)})
    rs.reconstruct(shards, missing=[0])
    tmp = tempfile.mkdtemp()
    try:
        start_trace(tmp)
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
            with jax.profiler.TraceAnnotation("bench.encode_job"):
                rs.encode(data)
                rs.encode(data)
            with jax.profiler.TraceAnnotation("bench.idle"):
                time.sleep(0.05)
            with jax.profiler.TraceAnnotation("bench.rebuild_job"):
                rs.reconstruct(shards, missing=[0])
        jax.profiler.stop_trace()
        src = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                     "*.xplane.pb"))[0]
        dst = os.path.join(HERE, "fixtures", "tpu_window.xplane.pb")
        shutil.copyfile(src, dst)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    summary = trace_reduce.reduce(trace_reduce.read_events(dst))
    summary["device_kind"] = jax.devices()[0].device_kind
    with open(dst.replace(".xplane.pb", ".json"), "w",
              encoding="utf-8") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
