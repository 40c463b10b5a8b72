"""Find everything a cell needs by name: the benchmark's entries and its data files.

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a configuration
and a traffic mix.  Each lives in a file of its own, so that a new cell, mix,
configuration or per-layer metric is added by adding files and entries:

  configuration   the ``file`` its ``configs`` entry gives (JSON)
  traffic mix     ``bench/traffic/<traffic>.json``
  family          ``bench/families/<family>.py`` (named by the configuration)
  per-layer metric ``bench/metrics/<name>.py``, whose ``read(run)`` returns a
                  number or None
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _by_name(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}; known: {[e['name'] for e in entries]}")


def workload(bench: dict, name: str) -> dict:
    return _by_name(bench["workloads"], name, "workload")


def config(bench: dict, name: str, root: str = ROOT) -> dict:
    entry = _by_name(bench["configs"], name, "configuration")
    with open(os.path.join(root, entry["file"])) as f:
        cfg = json.load(f)
    cfg["name"] = name
    return cfg


def traffic(name: str, bench_dir: str = BENCH_DIR) -> dict:
    with open(os.path.join(bench_dir, "traffic", f"{name}.json")) as f:
        return json.load(f)


def _load_module(path: str, modname: str):
    spec = importlib.util.spec_from_file_location(modname, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family(name: str, bench_dir: str = BENCH_DIR):
    return _load_module(
        os.path.join(bench_dir, "families", f"{name}.py"), f"bench_family_{name}"
    )


def metric_reader(name: str, bench_dir: str = BENCH_DIR):
    mod = _load_module(
        os.path.join(bench_dir, "metrics", f"{name}.py"),
        "bench_metric_" + name.replace(".", "_").replace("-", "_"),
    )
    return mod.read


def cell_metrics(bench: dict, cell: str, section: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    e2e = {
        m["name"]
        for m in bench["end_to_end"]
        if "workloads" not in m or cell in m["workloads"]
    }
    out = []
    for m in bench[section]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif section == "end_to_end" or m["moves"] in e2e:
            out.append(m)
    return out
