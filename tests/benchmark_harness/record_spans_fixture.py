"""Record the span fixture of test_program_spans.py on the chip:

    python3 tests/benchmark_harness/record_spans_fixture.py

One traced `bench.window` holding a `bench.encode_job` in which the
file encoder (ec/encoder.py write_ec_files) erasure-codes a 200 MB .dat
with the pallas codec in 8 MiB-per-shard blocks, so the trace carries
the program's `swfs.` annotations and the kernel under its own name.
Written to fixtures/tpu_spans.xplane.pb, with its summary beside it.
"""
from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

DAT_BYTES = 200 << 20
CHUNK = 8 << 20


def main() -> int:
    import jax
    import numpy as np

    from benchmark import program_spans, trace_reduce
    from benchmark.run import start_trace
    from seaweedfs_tpu.ec.encoder import write_ec_files

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record the fixture on the chip")
    tmp = tempfile.mkdtemp()
    try:
        base = os.path.join(tmp, "1")
        data = np.random.default_rng(0).integers(0, 256, DAT_BYTES,
                                                 dtype=np.uint8)
        data.tofile(base + ".dat")
        # the first encode compiles; the traced one runs warm
        write_ec_files(base, backend="pallas", chunk=CHUNK)
        trace = os.path.join(tmp, "trace")
        start_trace(trace)
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
            with jax.profiler.TraceAnnotation("bench.encode_job"):
                write_ec_files(base, backend="pallas", chunk=CHUNK)
        jax.profiler.stop_trace()
        src = glob.glob(os.path.join(trace, "plugins", "profile", "*",
                                     "*.xplane.pb"))[0]
        dst = os.path.join(HERE, "fixtures", "tpu_spans.xplane.pb")
        shutil.copyfile(src, dst)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    summary = trace_reduce.reduce(program_spans.read_events(dst))
    summary["device_kind"] = jax.devices()[0].device_kind
    with open(dst.replace(".xplane.pb", ".json"), "w",
              encoding="utf-8") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
