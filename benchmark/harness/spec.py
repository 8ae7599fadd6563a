"""Find a cell's files by the names ``BENCHMARK.json`` gives."""

from __future__ import annotations

import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


class Cell:
    """One entry of ``workloads`` with its configuration, its traffic mix
    and the metrics that name it."""

    def __init__(self, bench: dict, name: str, root: str = ROOT):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(
                f"unknown workload {name!r}; BENCHMARK.json has "
                f"{sorted(cells)}")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])

        def find(kind: str, name: str) -> str:
            """``<path>/<kind>/<name>.json`` in the first of the
            benchmark's directories that has it."""
            tried = [os.path.join(root, p, kind, name + ".json")
                     for p in bench["paths"]]
            for path in tried:
                if os.path.exists(path):
                    return path
            raise SystemExit(f"no file for {kind} {name!r}: tried {tried}")

        self.config = load_json(find("configs", self.entry["config"]))
        self.traffic = load_json(find("traffic", self.entry["traffic"]))
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = []
        for m in bench["per_layer"]:
            if name not in m.get("workloads", [name]):
                continue
            self.per_layer.append(load_json(find("layer_metrics",
                                                 m["name"])))
