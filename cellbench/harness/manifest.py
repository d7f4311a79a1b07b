"""Find a cell's files by the names `BENCHMARK.json` gives.

Everything that belongs to one configuration, one traffic mix, one cell or
one per-layer metric sits in a file of its own, so a later PR adds a cell by
adding files and one manifest entry and edits nothing that is there:

    BENCHMARK.json workloads[i]   -> {"name", "config", "traffic", "chips"}
    configs[j].file               -> the configuration as it is run
    cellbench/traffic/<traffic>.json      the mix's parameters
    cellbench/cells/<cell>.json           the cell's own numbers (rate, clients)
    cellbench/generators/<name>.py        named by the mix's "generator"
    cellbench/layer_metrics/<metric>.py   one reader per per-layer metric
    cellbench/reference/<name>.py         named by the configuration's "reference"
    cellbench/roofline/<name>.py          imported by the readers that need it
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, bench_dir: str = BENCH_DIR):
    """cellbench/<kind>/<name>.py as a module (no package import needed, so
    a file added by a later PR is found with nothing registered anywhere)."""
    path = os.path.join(bench_dir, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} named {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(f"cellbench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of `workloads`, with every file it names resolved."""

    def __init__(self, manifest: dict, name: str, root: str = ROOT):
        cells = {w["name"]: w for w in manifest["workloads"]}
        if name not in cells:
            raise SystemExit(
                f"unknown workload {name!r}; BENCHMARK.json has {sorted(cells)}"
            )
        self.name = name
        self.entry = cells[name]
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in manifest["configs"]}
        self.config_entry = configs[self.entry["config"]]
        self.config_path = os.path.join(root, self.config_entry["file"])
        self.config = load_json(self.config_path)
        bench = os.path.join(root, manifest["paths"][0])
        self.traffic = load_json(
            os.path.join(bench, "traffic", f"{self.entry['traffic']}.json")
        )
        cell_path = os.path.join(bench, "cells", f"{name}.json")
        # a cell's own numbers override the mix's "load" section (the rate
        # is the cell's, not the mix's)
        self.load = dict(self.traffic.get("load", {}))
        if os.path.isfile(cell_path):
            self.load.update(load_json(cell_path).get("load", {}))

        def reports(metric):
            return name in metric.get("workloads", cells)

        self.end_to_end = [m for m in manifest["end_to_end"] if reports(m)]
        e2e = {m["name"] for m in self.end_to_end}
        self.per_layer = [
            m for m in manifest["per_layer"] if reports(m) and m["moves"] in e2e
        ]
