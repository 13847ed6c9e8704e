"""``BENCHMARK.json`` and the per-name files it points to."""
from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))


class UnknownWorkload(ValueError):
    """A ``--workload`` that ``BENCHMARK.json`` does not name."""


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    """The ``workloads`` entry called ``name``; unknown names list the valid."""
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    valid = ", ".join(sorted(c["name"] for c in bench["workloads"]))
    raise UnknownWorkload(f"unknown workload {name!r}; valid: {valid}")


def config_entry(bench: dict, name: str) -> dict:
    for cfg in bench["configs"]:
        if cfg["name"] == name:
            return cfg
    raise KeyError(f"BENCHMARK.json names no configuration {name!r}")


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_config(bench: dict, name: str, root: str = ROOT) -> dict:
    return _read_json(os.path.join(root, config_entry(bench, name)["file"]))


def load_traffic(name: str) -> dict:
    return _read_json(os.path.join(BENCH_DIR, "traffic", f"{name}.json"))


def load_limits(workload: str) -> dict:
    return _read_json(os.path.join(BENCH_DIR, "limits", f"{workload}.json"))


def load_module(path: str):
    """Import one file by path (metric readers, configuration modules)."""
    tag = os.path.relpath(path, BENCH_DIR).replace(os.sep, "_")
    name = "chipbench_file_" + "".join(c if c.isalnum() else "_" for c in tag)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def config_module(bench: dict, name: str, root: str = ROOT):
    """``configs/<name>.py``: how the configuration builds its code, and its reference."""
    path = os.path.splitext(
        os.path.join(root, config_entry(bench, name)["file"]))[0] + ".py"
    return load_module(path)


def metric_reader(name: str):
    return load_module(os.path.join(BENCH_DIR, "metrics", f"{name}.py")).read


def cell_metrics(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The metric entries a run of ``cell`` reports (per-layer when traced)."""
    entries = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in entries if cell in m.get("workloads", [cell])]
