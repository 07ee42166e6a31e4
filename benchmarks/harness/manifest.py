"""BENCHMARK.json, and the files its names lead to.

Everything that belongs to one configuration, one cell, one traffic
kind or one per-layer metric is a file of its own, found here by name:

    <file of the configs entry>           the configuration as it is run
    configs/<config>.py                   builds the program's model
    reference/<config>.py                 the plain reference
    workloads/<cell>.json                 the cell's traffic parameters
    traffic/<kind>.py                     the generator the cell names
    layer_metrics/<metric>.py             one reader per per-layer metric
"""
from __future__ import annotations

import importlib.util
import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


class Manifest:
    def __init__(self, root):
        self.root = os.path.abspath(root)
        with open(os.path.join(self.root, "BENCHMARK.json")) as f:
            self.data = json.load(f)
        self.bench_dir = os.path.join(self.root, self.data["paths"][0])

    def _entry(self, group, name):
        for entry in self.data[group]:
            if entry["name"] == name:
                return entry
        known = ", ".join(e["name"] for e in self.data[group])
        raise KeyError(f"{name!r} is not among BENCHMARK.json's {group} "
                       f"({known})")

    def cell(self, name):
        return self._entry("workloads", name)

    def cell_params(self, name):
        path = os.path.join(self.bench_dir, "workloads", name + ".json")
        with open(path) as f:
            return json.load(f)

    def config(self, name):
        with open(os.path.join(self.root,
                               self._entry("configs", name)["file"])) as f:
            return json.load(f)

    def module(self, directory, name):
        """The module at <bench_dir>/<directory>/<name>.py, loaded by
        path: a name with a dot in it is still one file."""
        path = os.path.join(self.bench_dir, directory, name + ".py")
        if not os.path.isfile(path):
            raise FileNotFoundError(
                f"{os.path.relpath(path, self.root)} is missing: "
                f"{directory}/ holds one file per name")
        spec = importlib.util.spec_from_file_location(
            f"benchmarks_{directory}_{re.sub(r'[^A-Za-z0-9_]', '_', name)}",
            path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module

    def metrics(self, group, cell_name):
        """The entries of `end_to_end` or `per_layer` that this cell
        reports: those without a `workloads` key, and those listing it."""
        return [m for m in self.data[group]
                if cell_name in m.get("workloads", [cell_name])]
