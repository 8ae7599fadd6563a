"""Find a cell's files by the names ``BENCHMARK.json`` and the files give.

``BENCHMARK.json`` names a cell's configuration and traffic mix; the
traffic file names its ``generator`` and the configuration file its
``reference``.  Each is ``<path>/<kind>/<name><ext>`` in the first of the
benchmark's ``paths`` that has it, the two modules as every data file.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def load_module(path: str, kind: str, name: str):
    """The module in the file ``path``, loaded once a process."""
    tag = f"benchmark_{kind}_{name}"
    mod = sys.modules.get(tag)
    if mod is not None and os.path.samefile(mod.__file__, path):
        return mod
    spec = importlib.util.spec_from_file_location(tag, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[tag] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[tag]
        raise
    return mod


class Cell:
    """One entry of ``workloads`` with its configuration, its traffic mix,
    the generator and the reference they name, and the metrics that name
    the cell."""

    def __init__(self, bench: dict, name: str, root: str = ROOT):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(
                f"unknown workload {name!r}; BENCHMARK.json has "
                f"{sorted(cells)}")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])

        def find(kind: str, name: str, ext: str = ".json",
                 named_by: str = "BENCHMARK.json") -> str:
            """``<path>/<kind>/<name><ext>`` in the first of the
            benchmark's directories that has it."""
            tried = [os.path.join(root, p, kind, name + ext)
                     for p in bench["paths"]]
            for path in tried:
                if os.path.exists(path):
                    return path
            raise SystemExit(f"{named_by} names the {kind} {name!r} and "
                             f"no file has it: tried {tried}")

        self.config_path = find("configs", self.entry["config"])
        self.config = load_json(self.config_path)
        self.traffic_path = find("traffic", self.entry["traffic"])
        self.traffic = load_json(self.traffic_path)
        for doc, path, key in ((self.traffic, self.traffic_path,
                                "generator"),
                               (self.config, self.config_path,
                                "reference")):
            if not isinstance(doc.get(key), str):
                raise SystemExit(f"{path} names no {key}")
        kind = self.traffic["generator"]
        self.generator = load_module(
            find("generators", kind, ".py", self.traffic_path),
            "generator", kind)
        ref = self.config["reference"]
        self.reference = load_module(
            find("reference", ref, ".py", self.config_path),
            "reference", ref)
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = []
        for m in bench["per_layer"]:
            if name not in m.get("workloads", [name]):
                continue
            self.per_layer.append(load_json(find("layer_metrics",
                                                 m["name"])))
