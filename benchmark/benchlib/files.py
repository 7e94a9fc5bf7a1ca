"""Where the benchmark's data lives, found by the names BENCHMARK.json gives.

A cell names a configuration and a traffic mix; the configuration entry names
its file; a mix is ``<traffic dir>/<traffic>.json``; a traffic kind is
``benchmark/traffic_kinds/<kind>.py``; a layer metric is
``benchmark/layer_metrics/<metric>.py``. Nothing here lists names: a later PR
adds files and BENCHMARK.json entries and edits no file that exists.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with its configuration and traffic files."""

    def __init__(self, bench_path: str, workload: str):
        self.bench_path = os.path.abspath(bench_path)
        self.bench = load_json(self.bench_path)
        # file names in BENCHMARK.json are relative to the checkout root; a
        # rehearsal file (benchmark/tests/...) gives its own paths likewise
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in cells:
            raise SystemExit(f"no workload {workload!r} in {bench_path}; "
                             f"have {sorted(cells)}")
        self.entry = cells[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        confs = {c["name"]: c for c in self.bench["configs"]}
        self.config_entry = confs[self.entry["config"]]
        cfg_path = os.path.join(ROOT, self.config_entry["file"])
        self.config = load_json(cfg_path)
        # the mixes live in the traffic/ beside the configuration's directory
        self.traffic = load_json(os.path.join(
            os.path.dirname(os.path.dirname(cfg_path)), "traffic",
            self.entry["traffic"] + ".json"))

    def metric_names(self, group: str):
        """Names of the ``end_to_end`` / ``per_layer`` metrics this cell
        reports (a metric with a ``workloads`` key only where listed)."""
        return [m["name"] for m in self.bench[group]
                if "workloads" not in m or self.name in m["workloads"]]

    def metric(self, name: str) -> dict:
        for group in ("end_to_end", "per_layer"):
            for m in self.bench[group]:
                if m["name"] == name:
                    return m
        raise KeyError(name)


def load_module(kind_dir: str, name: str):
    """benchmark/<kind_dir>/<name>.py as a module (names may hold '-' and
    '.', so this is by path and not by import)."""
    path = os.path.join(BENCH_DIR, kind_dir, name + ".py")
    if not os.path.isfile(path):
        return None
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind_dir}_{name}".replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks_for(device_kind: str) -> dict:
    table = load_json(os.path.join(BENCH_DIR, "peaks.json"))["devices"]
    if device_kind not in table:
        raise SystemExit(f"device kind {device_kind!r} is not in "
                         f"benchmark/peaks.json ({sorted(table)}): add its "
                         f"published peaks with their source")
    return table[device_kind]
