"""A new configuration, traffic mix, per-layer metric or kind of deployment
is a new file, found by the name ``BENCHMARK.json`` gives it."""
import json
import time

import jax
import pytest

from bench import harness

DRIVER = '''
from bench.harness import Check

class Cell:
    def __init__(self, config, traffic, seed, devices, seconds):
        self.n = config["n"] * traffic["factor"]
    def setup(self):
        pass
    def run(self, seconds, tracer):
        import time
        self.t_open = time.monotonic()
        self.t_close = self.t_open + 1.0
    def end_to_end(self):
        return {"things_per_s": float(self.n)}
    def counts(self):
        return self.n, 0
    def release(self):
        pass
    def checks(self):
        return [Check("exact", 0.0, 0.0)]
'''


def test_harness_finds_each_part_by_name(tmp_path):
    b = tmp_path / "bench"
    for d in ("configs", "traffic", "metrics", "drivers"):
        (b / d).mkdir(parents=True)
    (b / "configs" / "toy.json").write_text(json.dumps({"kind": "toy",
                                                        "n": 3}))
    (b / "traffic" / "steady.json").write_text(json.dumps({"factor": 2}))
    (b / "drivers" / "toy.py").write_text(DRIVER)
    (b / "metrics" / "toy.twice.py").write_text(
        "def read(run):\n    return 2 * run.cell.n\n")
    (b / "metrics" / "toy.silent.py").write_text(
        "def read(run):\n    return None\n")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "toy", "file": "bench/configs/toy.json"}],
        "workloads": [{"name": "toy.steady", "config": "toy",
                       "traffic": "steady", "chips": 1}],
        "end_to_end": [{"name": "things_per_s", "unit": "1/s"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": "toy.twice", "unit": "1", "moves":
                       "things_per_s"},
                      {"name": "toy.silent", "unit": "1", "moves":
                       "things_per_s", "workloads": ["toy.steady"]},
                      {"name": "elsewhere", "unit": "1", "moves":
                       "things_per_s", "workloads": ["other.cell"]}]}))
    spec = harness.load_spec("toy.steady", root=tmp_path)
    assert spec.config["n"] == 3 and spec.traffic["factor"] == 2
    assert [m["name"] for m in spec.per_layer] == ["toy.twice", "toy.silent"]
    cpu = jax.devices()[:1]
    out = harness.run_cell(spec, 1, 1.0, False, t_process=time.monotonic(),
                           devices=cpu)
    assert out["correct"] and out["metrics"]["things_per_s"]["value"] == 6
    assert list(out)[-1] == "checks"
    out = harness.run_cell(spec, 1, 1.0, True, t_process=time.monotonic(),
                           devices=cpu)
    assert out["metrics"] == {"toy.twice": {"value": 12.0, "unit": "1"}}
    with pytest.raises(KeyError):
        harness.load_spec("no.such.cell", root=tmp_path)
    with pytest.raises(FileNotFoundError):
        harness.metric_reader("no.such.metric", b)
