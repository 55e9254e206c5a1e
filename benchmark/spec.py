"""Finds a cell's parts by name, so a later PR adds a configuration,
a traffic mix or a metric by adding files and entries only:

- the cell: an entry of `workloads` in <root>/BENCHMARK.json;
- its configuration: <root>/benchmark/configs/<config>.json;
- its traffic mix: <root>/benchmark/traffic/<traffic>.json, parameters
  that the one general generator (traffic.py) reads; or, where a mix
  needs code of its own, <root>/benchmark/traffic/<traffic>.py with a
  class `Traffic` of the generator's interface (it may subclass
  benchmark.traffic.Traffic) and its parameters as `PARAMS`;
- each metric: a reader <root>/benchmark/metrics/<metric name>.py with
  `def read(run) -> float | None`, returning None where it finds
  nothing to read;
- a configuration's own plain reference, where reference.py does not
  describe its code (a code whose shard bytes are not a per-column map
  of the data bytes, such as a sub-striped one):
  <root>/benchmark/references/<config name>.py with `def shards(dat,
  code, large, small, which) -> dict[int, np.ndarray]`, of the same
  meaning as reference.shards. A configuration without one is checked
  against reference.py.
"""
from __future__ import annotations

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class SpecError(Exception):
    pass


def _load_json(path: str) -> dict:
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing {path}") from None


def _load_module(path: str, prefix: str):
    name = os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(
        prefix + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, root: str = ROOT) -> dict:
    """{cell, config, traffic, generator, end_to_end, per_layer}
    for one cell of <root>/BENCHMARK.json: the metrics are those that
    apply to it. A traffic module is found before a data file."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    here = os.path.join(root, "benchmark")
    config = _load_json(os.path.join(here, "configs",
                                     cell["config"] + ".json"))
    module = os.path.join(here, "traffic", cell["traffic"] + ".py")
    generator = None
    if os.path.exists(module):
        mod = _load_module(module, "benchmark_traffic_")
        generator = mod.Traffic
        traffic = dict(getattr(mod, "PARAMS", {}))
    else:
        traffic = _load_json(os.path.join(here, "traffic",
                                          cell["traffic"] + ".json"))

    def applies(metric: dict, moved: set | None = None) -> bool:
        if "workloads" in metric:
            return name in metric["workloads"]
        # a per-layer metric without `workloads` is read in every cell
        # that reports the end-to-end metric it moves
        return moved is None or metric["moves"] in moved

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    moved = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if applies(m, moved)]
    return {"cell": cell, "config": config, "traffic": traffic,
            "generator": generator, "end_to_end": e2e, "per_layer": layer,
            "run_seconds": bench["run_seconds"]}


def traffic_class(cell: dict):
    """The generator class of a cell that load_cell returned: its traffic
    module's `Traffic`, else the general one."""
    if cell["generator"] is not None:
        return cell["generator"]
    from benchmark.traffic import Traffic

    return Traffic


def reader(metric: str, root: str = ROOT):
    """The `read` function of <root>/benchmark/metrics/<metric>.py."""
    path = os.path.join(root, "benchmark", "metrics", metric + ".py")
    if not os.path.exists(path):
        raise SpecError(f"metric {metric!r} has no reader at {path}")
    return _load_module(path, "benchmark_metric_").read


def reference(config: str, root: str = ROOT):
    """The `shards` function of <root>/benchmark/references/<config>.py,
    or None where the configuration brings no reference of its own."""
    path = os.path.join(root, "benchmark", "references", config + ".py")
    if not config or not os.path.exists(path):
        return None
    return _load_module(path, "benchmark_reference_").shards
