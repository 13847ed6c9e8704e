"""BENCHMARK.json against the benchmark contract, and the command's refusals."""
import os
import re
import shutil
import subprocess
import sys

import pytest

from chipbench import spec

ROOT = spec.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
RUN = [sys.executable, os.path.join("benchmarks", "chip", "run.py")]


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "benchmarks/chip/run.py"]
    assert 1 <= bench["run_seconds"] <= 51
    for p in bench["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_names_and_units_use_allowed_characters(bench):
    names = [e["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for e in bench[key]]
    names += [w[k] for w in bench["workloads"] for k in ("config",
                                                         "traffic")]
    names += [k for c in bench["configs"] for k in c["reduced"]]
    for n in names:
        assert NAME.match(n), n
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        ns = [e["name"] for e in bench[key]]
        assert len(ns) == len(set(ns)), key


def test_every_workload_names_existing_files(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    used = set()
    for w in bench["workloads"]:
        assert w["config"] in configs
        used.add(w["config"])
        assert w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        cfg = configs[w["config"]]
        path = os.path.join(ROOT, cfg["file"])
        assert os.path.isfile(path)
        assert any(cfg["file"].startswith(p + "/") for p in bench["paths"])
        assert os.path.isfile(os.path.splitext(path)[0] + ".py")
        assert spec.load_traffic(w["traffic"])
        assert spec.load_limits(w["name"])
    assert used == set(configs)
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))


def test_every_metric_has_a_reader_and_its_cells(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
        assert callable(spec.metric_reader(m["name"]))
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= cells
        for c in m["workloads"]:
            assert c in e2e[m["moves"]].get("workloads", [c])
        assert callable(spec.metric_reader(m["name"]))
    for c in cells:
        e = [m["name"] for m in spec.cell_metrics(bench, c, False)]
        assert "setup_s" in e and len(e) >= 2
        assert spec.cell_metrics(bench, c, True)


def test_unknown_workload_is_refused_with_the_valid_list(bench):
    with pytest.raises(spec.UnknownWorkload) as e:
        spec.find_cell(bench, "no-such-cell")
    for w in bench["workloads"]:
        assert w["name"] in str(e.value)
    p = subprocess.run(RUN + ["--workload", "no-such-cell", "--seed", "1",
                              "--seconds", "1"], cwd=ROOT,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout == ""
    assert "valid:" in p.stderr


def test_run_on_the_cpu_fails_and_names_the_platform(bench):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = bench["workloads"][0]["name"]
    p = subprocess.run(RUN + ["--workload", cell, "--seed", "1",
                              "--seconds", "1"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout == ""
    assert "'cpu'" in p.stderr and "not a TPU" in p.stderr


def test_run_fails_with_only_the_benchmark_files(bench, tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in bench["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    cell = bench["workloads"][0]["name"]
    p = subprocess.run(RUN + ["--workload", cell, "--seed", "1",
                              "--seconds", "1"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not p.stdout.strip() or not p.stdout.strip().splitlines()[-1] \
        .startswith("{")
