"""A configuration, a traffic mix and a metric are found by name: new
files and entries in a root, and no edit to the harness."""
from __future__ import annotations

import json
import os

from benchmark import spec


def _write(path: str, body) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        f.write(body if isinstance(body, str) else json.dumps(body))


def test_new_files_are_found_by_name(tmp_path):
    root = str(tmp_path)
    _write(os.path.join(root, "benchmark", "configs", "new_code.json"),
           {"code": {"spec": "6.3", "k": 6, "global": 3}})
    _write(os.path.join(root, "benchmark", "traffic", "new_mix.json"),
           {"volumes": 2, "jobs": "encode"})
    _write(os.path.join(root, "benchmark", "metrics", "new_metric.x.py"),
           "def read(run):\n    return run['window_s'] * 2\n")
    _write(os.path.join(root, "benchmark", "metrics", "other.py"),
           "def read(run):\n    return None\n")
    _write(os.path.join(root, "BENCHMARK.json"), {
        "run_seconds": 10,
        "workloads": [{"name": "new_code.new_mix", "config": "new_code",
                       "traffic": "new_mix", "chips": 1, "why": "x"}],
        "end_to_end": [
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"},
            {"name": "elsewhere", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock",
             "workloads": ["another.cell"]}],
        "per_layer": [
            {"name": "new_metric.x", "unit": "s", "better": "lower",
             "source": "host_clock", "layer": "l", "moves": "setup_s"},
            {"name": "other", "unit": "s", "better": "lower",
             "source": "host_clock", "layer": "l", "moves": "elsewhere"}]})
    got = spec.load_cell("new_code.new_mix", root)
    assert got["config"]["code"]["k"] == 6
    assert got["traffic"] == {"volumes": 2, "jobs": "encode"}
    assert [m["name"] for m in got["end_to_end"]] == ["setup_s"]
    # no `workloads`: read wherever the metric it moves is reported
    assert [m["name"] for m in got["per_layer"]] == ["new_metric.x"]
    assert spec.reader("new_metric.x", root)({"window_s": 1.5}) == 3.0
    assert spec.reader("other", root)({}) is None


def test_new_traffic_module_is_found_by_name(tmp_path):
    """A mix that needs code of its own: benchmark/traffic/<kind>.py,
    found before a data file of the same name."""
    root = str(tmp_path)
    _write(os.path.join(root, "benchmark", "configs", "rs.json"),
           {"code": {"spec": "10.4", "k": 10, "global": 4}})
    _write(os.path.join(root, "benchmark", "traffic", "new_kind.py"),
           "from benchmark.traffic import Traffic as General\n"
           "PARAMS = {'volumes': 3, 'jobs': 'encode'}\n"
           "class Traffic(General):\n"
           "    def loss(self, dep):\n"
           "        return [0, 1]\n")
    _write(os.path.join(root, "benchmark", "traffic", "new_kind.json"),
           {"volumes": 1})
    _write(os.path.join(root, "benchmark", "traffic", "plain.json"),
           {"volumes": 1})
    _write(os.path.join(root, "BENCHMARK.json"), {
        "run_seconds": 10,
        "workloads": [{"name": n, "config": "rs", "traffic": n,
                       "chips": 1, "why": "x"}
                      for n in ("new_kind", "plain")],
        "end_to_end": [{"name": "setup_s", "unit": "s", "better": "lower",
                        "bound": 0.25, "source": "host_clock"}],
        "per_layer": []})
    from benchmark.traffic import Traffic as General

    got = spec.load_cell("new_kind", root)
    assert got["traffic"] == {"volumes": 3, "jobs": "encode"}
    cls = spec.traffic_class(got)
    assert cls is not General and issubclass(cls, General)
    gen = cls(got["traffic"], got["config"], 7, str(tmp_path / "w"))
    assert gen.loss(None) == [0, 1] and gen.k == 10
    plain = spec.load_cell("plain", root)
    assert plain["traffic"] == {"volumes": 1}
    assert spec.traffic_class(plain) is General


def test_every_listed_part_of_the_benchmark_exists():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json"),
              encoding="utf-8") as f:
        bench = json.load(f)
    for cell in bench["workloads"]:
        got = spec.load_cell(cell["name"])
        for m in got["end_to_end"] + got["per_layer"]:
            assert callable(spec.reader(m["name"]))
        assert got["per_layer"] and len(got["end_to_end"]) >= 2
    for cfg in bench["configs"]:
        assert os.path.exists(os.path.join(spec.ROOT, cfg["file"]))


def test_unknown_cell_and_missing_reader_are_errors(tmp_path):
    import pytest

    with pytest.raises(spec.SpecError):
        spec.load_cell("no.such_cell")
    with pytest.raises(spec.SpecError):
        spec.reader("no_such_metric", str(tmp_path))


_CODE = {"code": {"spec": "10.4", "k": 10, "local": 0, "global": 4},
         "large_block_bytes": 1 << 20, "small_block_bytes": 1 << 10}
# a configuration's own reference: reference.py's bytes, one flipped
_FLIP_ONE = ("from benchmark import reference\n"
             "def shards(dat, code, large, small, which=None):\n"
             "    out = reference.shards(dat, code, large, small, which)\n"
             "    out[min(out)] = out[min(out)].copy()\n"
             "    out[min(out)][0] ^= 1\n"
             "    return out\n")


def _dat(tmp_path) -> str:
    import numpy as np

    path = str(tmp_path / "ref.dat")
    np.random.default_rng(5).integers(0, 256, 30_000, dtype=np.uint8) \
        .tofile(path)
    return path


def test_configuration_reference_is_found_before_the_plain_one(tmp_path):
    import numpy as np

    from benchmark import check, reference

    root = str(tmp_path)
    _write(os.path.join(root, "benchmark", "references", "own_code.py"),
           _FLIP_ONE)
    dat = _dat(tmp_path)
    plain = reference.shards(np.fromfile(dat, dtype=np.uint8),
                             _CODE["code"], _CODE["large_block_bytes"],
                             _CODE["small_block_bytes"])
    assert spec.reference("own_code", root) is not None
    own = check.reference_shards(dat, dict(_CODE, name="own_code"),
                                 root=root)
    assert sorted(own) == sorted(plain)
    assert np.count_nonzero(own[0] != plain[0]) == 1
    # a configuration without a file of its own falls back to reference.py
    assert spec.reference("other_code", root) is None
    other = check.reference_shards(dat, dict(_CODE, name="other_code"),
                                   root=root)
    assert all(np.array_equal(other[s], plain[s]) for s in plain)


def test_configuration_reference_decides_shard_bytes_wrong(tmp_path):
    """Shards that match reference.py read wrong where the
    configuration's own reference says otherwise."""
    import types

    from benchmark import check

    root = str(tmp_path / "root")
    _write(os.path.join(root, "benchmark", "references", "own_code.py"),
           _FLIP_ONE)
    dat = _dat(tmp_path)
    plain = check.reference_shards(dat, _CODE)
    paths = {}
    for sid, want in plain.items():
        paths[sid] = str(tmp_path / f"7.ec{sid:02d}")
        want.tofile(paths[sid])
    t = types.SimpleNamespace(ref_dat=dat, jobs=[], sealed_paths={7: paths})
    got, _ = check.shard_checks(t, dict(_CODE, name="plain_code"), root)
    assert got["shard_bytes_wrong"] == 0
    got, _ = check.shard_checks(t, dict(_CODE, name="own_code"), root)
    assert got["shard_bytes_wrong"] == 1
