"""Finds every part of the benchmark by the names in ``BENCHMARK.json``.

Nothing here knows a cell, a configuration, a traffic mix, a plane or a
metric by name: a cell ``<config>.<traffic>`` resolves to
``configs/<config>.json`` (the file the entry names), to
``traffic/<traffic>.json``, to ``planes/<plane>.py`` (the plane the
configuration states) and, for each metric listed for it, to
``metrics/<name>.py``. A later PR adds files and entries and edits none.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TRAFFIC_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")


class SpecError(ValueError):
    pass


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Spec:
    """``BENCHMARK.json`` and the files it names, under ``root``."""

    def __init__(self, root: str = ROOT):
        self.root = root
        self.bench_dir = os.path.join(root, "benchmarks")
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.doc = json.load(f)
        self.check_names()

    # ------------------------------------------------------------ listing

    @property
    def run_seconds(self) -> int:
        return self.doc["run_seconds"]

    def cells(self) -> list:
        return [w["name"] for w in self.doc["workloads"]]

    def cell(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise SpecError(f"no workload {name!r} in BENCHMARK.json; it lists "
                        f"{', '.join(self.cells())}")

    def metrics(self, kind: str, cell: str) -> list:
        """The ``end_to_end`` or ``per_layer`` entries a cell reports: all
        without a ``workloads`` key, else those that list the cell."""
        return [m for m in self.doc[kind]
                if "workloads" not in m or cell in m["workloads"]]

    # ---------------------------------------------------------- resolving

    def config(self, cell: str) -> dict:
        want = self.cell(cell)["config"]
        for c in self.doc["configs"]:
            if c["name"] == want:
                with open(os.path.join(self.root, c["file"])) as f:
                    return json.load(f)
        raise SpecError(f"workload {cell!r} names configuration {want!r}, "
                        f"which BENCHMARK.json does not list")

    def traffic(self, cell: str) -> dict:
        mix = self.cell(cell)["traffic"]
        path = os.path.join(self.bench_dir, "traffic", mix + ".json")
        if not os.path.exists(path):
            raise SpecError(f"traffic mix {mix!r}: no file {path}")
        with open(path) as f:
            return json.load(f)

    def plane(self, cell: str):
        """The module that runs a world of the configuration's plane."""
        plane = self.config(cell)["plane"]
        if not NAME.match(plane):
            raise SpecError(f"plane name {plane!r}")
        path = os.path.join(self.bench_dir, "planes", plane + ".py")
        if not os.path.exists(path):
            raise SpecError(f"plane {plane!r}: no file {path}")
        return _load_module(path, f"benchmarks.planes.{plane}")

    def reader(self, metric: str):
        """``read(records) -> number or None`` of one metric."""
        path = os.path.join(self.bench_dir, "metrics", metric + ".py")
        if not os.path.exists(path):
            raise SpecError(f"metric {metric!r}: no reader {path}")
        return _load_module(
            path, "benchmarks.metrics." + metric.replace(".", "_")).read

    # ----------------------------------------------------------- checking

    def check_names(self) -> None:
        """Names and units against the characters the contract allows."""
        doc = self.doc
        names = [c["name"] for c in doc["configs"]]
        for w in doc["workloads"]:
            names += [w["name"], w["config"], w["traffic"]]
        for c in doc["configs"]:
            names += list(c.get("reduced", []))
        for kind in ("end_to_end", "per_layer"):
            for m in doc[kind]:
                names.append(m["name"])
                if not UNIT.match(m["unit"]):
                    raise SpecError(f"unit {m['unit']!r} of {m['name']!r}")
                if m["better"] not in ("lower", "higher"):
                    raise SpecError(f"better {m['better']!r} of {m['name']!r}")
        for name in names:
            if not NAME.match(name):
                raise SpecError(f"name {name!r}")
        for kind in ("configs", "workloads"):
            seen = [e["name"] for e in doc[kind]]
            if len(seen) != len(set(seen)):
                raise SpecError(f"a name appears twice in {kind}")
        metric_names = [m["name"] for k in ("end_to_end", "per_layer")
                        for m in doc[k]]
        if len(metric_names) != len(set(metric_names)):
            raise SpecError("a metric name appears twice")

    def check_files(self) -> None:
        """Every cell resolves: configuration, traffic, plane, readers."""
        for cell in self.cells():
            self.config(cell)
            self.traffic(cell)
            self.plane(cell)
            for kind in ("end_to_end", "per_layer"):
                for m in self.metrics(kind, cell):
                    self.reader(m["name"])
