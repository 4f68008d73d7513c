"""BENCHMARK.json and the files it names. The harness finds a cell's
configuration, traffic mix, driver and metric readers by name; nothing
here knows any of them."""

from __future__ import annotations

import importlib
import json
import os
from typing import List


class Manifest:
    def __init__(self, root: str):
        self.root = root
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.doc = json.load(f)

    def cell(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                       f"{[w['name'] for w in self.doc['workloads']]}")

    def config(self, name: str) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                with open(os.path.join(self.root, c["file"])) as f:
                    return json.load(f)
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, cell: dict) -> dict:
        path = os.path.join(self.package_dir(), "traffic",
                            f"{cell['config']}.{cell['traffic']}.json")
        with open(path) as f:
            return json.load(f)

    def package_dir(self) -> str:
        return os.path.join(self.root, self.doc["paths"][0])

    def package(self) -> str:
        return self.doc["paths"][0].replace("/", ".")

    def metrics(self, cell_name: str, kind: str) -> List[dict]:
        """The `kind` ("end_to_end" or "per_layer") metrics the cell
        reports: those that list it, and those that list no cells."""
        return [m for m in self.doc[kind]
                if "workloads" not in m or cell_name in m["workloads"]]

    def reader(self, metric: str):
        return importlib.import_module(
            f"{self.package()}.metrics.{metric}")

    def driver(self, name: str):
        return importlib.import_module(f"{self.package()}.drivers.{name}")
