"""A cell, a mix and a per-layer metric added as new files and new manifest
entries are found and run without an edit to any file already there."""
import json
import os

import small

import run as bench_run

DUMMY_GENERATOR = '''
class Generator:
    spans = ("dummy.step",)

    def __init__(self, cell, seed, tracer):
        self.cell, self.seed, self.tracer = cell, seed, tracer

    def setup(self, seconds):
        self.units = int(self.cell.config["units"])

    def window(self, seconds, opened):
        opened()
        return {"attempted": self.units, "seconds": 1.0,
                "metrics": {"sims_per_s": float(self.units)},
                "info": {}, "counters": {"dummy_count": 7.0}}

    def release(self):
        pass

    def check(self, control=False):
        return {"values": {"answer_gap": 0.0}, "failed": 0, "missing": 0,
                "units": self.units}

    def work(self):
        return {}
'''

DUMMY_METRIC = '''
from harness.readers import counter


def read(run):
    return counter(run, "dummy_count")
'''


def _write(path, text):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(text)


def test_new_cell_mix_and_metric_are_found(tmp_path):
    root = small.small_root(str(tmp_path))
    bench = os.path.join(root, "bench")
    before = {p: open(os.path.join(dp, p)).read()
              for dp, _, files in os.walk(bench) for p in files}
    _write(os.path.join(bench, "configs", "dummy.json"), json.dumps({"units": 3}))
    _write(os.path.join(bench, "traffic", "dummy-mix.json"), json.dumps({"generator": "dummy"}))
    _write(os.path.join(bench, "generators", "dummy.py"), DUMMY_GENERATOR)
    _write(os.path.join(bench, "metrics", "dummy.count.py"), DUMMY_METRIC)
    _write(os.path.join(bench, "limits", "dummy.dummy-mix.json"), json.dumps({"limits": {
        "answer_gap": {"limit": 0}, "missing": {"limit": 0}, "window_traces": {"limit": 0}}}))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    manifest["configs"].append({"name": "dummy", "source": "test", "file": "bench/configs/dummy.json",
                                "reduced": [], "why": "test"})
    manifest["workloads"].append({"name": "dummy.dummy-mix", "config": "dummy",
                                  "traffic": "dummy-mix", "chips": 1, "why": "test"})
    for m in manifest["end_to_end"]:
        if "workloads" in m and m["name"] == "sims_per_s":
            m["workloads"].append("dummy.dummy-mix")
    manifest["per_layer"].append({"name": "dummy.count", "unit": "1", "better": "lower",
                                  "source": "program_counter", "layer": "test",
                                  "moves": "sims_per_s", "workloads": ["dummy.dummy-mix"]})
    with open(path, "w") as f:
        json.dump(manifest, f)

    plain = bench_run.run("dummy.dummy-mix", 5, 1.0, False, require_chip=False, root=root)
    assert plain["correct"] and plain["attempted"] == 3
    assert set(plain["metrics"]) == {"sims_per_s", "setup_s"}
    traced = bench_run.run("dummy.dummy-mix", 5, 1.0, True, require_chip=False, root=root)
    assert traced["metrics"] == {"dummy.count": {"value": 7.0, "unit": "1"}}
    after = {p: open(os.path.join(dp, p)).read()
             for dp, _, files in os.walk(bench) for p in files if p in before}
    assert after == {p: before[p] for p in after}
