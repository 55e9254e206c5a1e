"""The chip benchmark of seaweedfs-tpu: one data-driven harness.

`python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>` runs one cell of BENCHMARK.json once. Everything that
belongs to one configuration, traffic mix or metric is a file of its
own, found by name (see spec.py).
"""
