"""Everything a cell is made of is found by name; a new configuration,
mix, driver kind or metric is new files and new entries only."""

import json
import os
import shutil

import pytest

from benchmark import run as bench

ROOT = bench.ROOT


def _spec():
    return bench.load_json(os.path.join(ROOT, "BENCHMARK.json"))


@pytest.mark.parametrize("workload", [w["name"] for w in _spec()["workloads"]])
def test_every_cell_resolves_to_its_files(workload):
    spec = _spec()
    cell, config, traffic = bench.resolve(spec, workload)
    assert config["chips"] == cell["chips"]
    assert os.path.isfile(os.path.join(ROOT, "benchmark", "drivers", traffic["driver"] + ".py"))
    e2e, layer = bench.cell_metrics(spec, workload)
    names = [m["name"] for m in e2e]
    assert "setup_s" in names and len(names) >= 2
    assert layer
    for m in layer:
        assert m["moves"] in names
        reader = bench.load_module(os.path.join(ROOT, "benchmark", "metrics", m["name"] + ".py"), "r")
        assert callable(reader.read)


def test_unknown_workload_is_refused():
    with pytest.raises(bench.BenchError):
        bench.resolve(_spec(), "no-such-cell")


_DRIVER = '''
from benchmark.lib.check import Analysis
from benchmark.lib.redeploy import Deployment
from benchmark.lib.result import Result


def run(run):
    r = Result()
    r.setup_s = 1.5
    r.e2e["widgets_per_s"] = run.config["rate"] * run.traffic["scale"]
    r.window_s = run.seconds
    r.phase_s = {"host_exec": 0.25 * run.seconds}
    dep = Deployment("killable", "killable-0", "", "")
    r.analyses = [Analysis(dep, 0, [])]
    r.attempted = 1
    return r
'''

_READER = '''
from benchmark.lib.readers import phase_share


def read(result):
    return phase_share(result, "host_exec")
'''


def test_a_cell_added_as_files_in_a_fresh_tree_is_found_and_run(tmp_path):
    """A configuration, a mix, a driver kind and a per-layer metric added
    only as files (and entries) under another root are found and run."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench_dir = os.path.join(root, "benchmark")
    with open(os.path.join(bench_dir, "configs", "widget-1chip.json"), "w") as fh:
        json.dump({"chips": 1, "rate": 4.0}, fh)
    with open(os.path.join(bench_dir, "traffic", "widgets.json"), "w") as fh:
        json.dump({"driver": "widget", "scale": 2.0}, fh)
    with open(os.path.join(bench_dir, "drivers", "widget.py"), "w") as fh:
        fh.write(_DRIVER)
    with open(os.path.join(bench_dir, "metrics", "widget_share.deep.py"), "w") as fh:
        fh.write(_READER)
    spec = _spec()
    spec["configs"].append({"name": "widget-1chip", "source": "test", "file": "benchmark/configs/widget-1chip.json", "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "widgets", "config": "widget-1chip", "traffic": "widgets", "chips": 1, "why": "test"})
    spec["end_to_end"].append({"name": "widgets_per_s", "unit": "1/s", "better": "higher", "bound": 0.1, "source": "host_clock", "workloads": ["widgets"]})
    spec["per_layer"].append({"name": "widget_share.deep", "unit": "fraction", "better": "lower", "source": "program_span", "layer": "widgets", "moves": "widgets_per_s"})

    cell, config, traffic = bench.resolve(spec, "widgets", root=root)
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    for trace in (False, True):
        run = bench.Run(cell, config, traffic, 7, 2.0, False, None, root=root)
        run.trace = trace
        line = bench.execute(run, spec, dict(device))
        assert line["correct"] is True
        assert list(line)[-1] == "checks"
        if trace:
            assert line["metrics"] == {"widget_share.deep": {"value": 0.25, "unit": "fraction"}}
        else:
            assert line["metrics"]["widgets_per_s"] == {"value": 8.0, "unit": "1/s"}
            assert line["metrics"]["setup_s"] == {"value": 1.5, "unit": "s"}


def test_a_run_that_finds_no_chip_fails_unless_the_cpu_is_asked_for(monkeypatch):
    import jax

    assert jax.devices()[0].platform == "cpu"
    monkeypatch.setenv("JAX_PLATFORMS", "")
    with pytest.raises(bench.BenchError):
        bench.devices_for(1)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert [d.platform for d in bench.devices_for(1)] == ["cpu"]


def test_a_tree_without_the_program_prints_no_result(tmp_path, capsys):
    shutil.copytree(os.path.join(ROOT, "benchmark"), str(tmp_path / "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), str(tmp_path))
    import subprocess
    import sys

    out = subprocess.run([sys.executable, "benchmark/run.py", "--workload", "bectoken-t3",
                          "--seed", "1", "--seconds", "1", "--trace", "0"],
                         cwd=str(tmp_path), capture_output=True, text=True, timeout=120,
                         env={"PATH": os.environ.get("PATH", ""), "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
    assert "system under test" in out.stderr
