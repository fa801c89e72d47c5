"""Finds a cell's files by the names in ``BENCHMARK.json``.

A cell is ``<config>.<traffic>``: the configuration file named by the
``configs`` entry, the traffic mix ``bench/traffic/<traffic>.json``,
the weights ``bench/weights/<config>.json`` and one reader
``bench/metrics/<metric>.py`` (or ``<quantity>.py`` for a metric named
``<quantity>.<kind>``) per metric the cell reports.  Nothing
here names a cell, so a new cell is new files and new entries.
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


class Cell:
    """Everything one ``--workload`` needs, read from files."""

    def __init__(self, root: Path, name: str):
        self.root = Path(root)
        bench = load_json(self.root / "BENCHMARK.json")
        by_name = {w["name"]: w for w in bench["workloads"]}
        if name not in by_name:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        self.workload = by_name[name]
        self.name = name
        self.chips = int(self.workload["chips"])
        cfg = {c["name"]: c for c in bench["configs"]}[self.workload["config"]]
        self.config_name = cfg["name"]
        self.config = load_json(self.root / cfg["file"])
        self.traffic = load_json(
            self.root / "bench" / "traffic" / f"{self.workload['traffic']}.json")
        self.weights = load_json(
            self.root / "bench" / "weights" / f"{self.config_name}.json")
        self.run_seconds = bench["run_seconds"]
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", (name,))]
        moved = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", ())
                          or ("workloads" not in m and m["moves"] in moved)]

    def reader(self, metric: str):
        """The ``read(run)`` function of ``bench/metrics/<metric>.py``,
        or else of ``<quantity>.py`` for a metric ``<quantity>.<kind>``:
        a quantity split by the kind of cell that reports it is read
        the same way in each."""
        metrics = self.root / "bench" / "metrics"
        path = metrics / f"{metric}.py"
        if not path.exists():
            path = metrics / f"{metric.split('.', 1)[0]}.py"
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + metric.replace(".", "_").replace("-", "_"),
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


def check_names(bench: dict) -> list:
    """Names and units of ``BENCHMARK.json`` that break its rules."""
    bad = []
    names = [c["name"] for c in bench["configs"]] + \
        [w["name"] for w in bench["workloads"]] + \
        [w["traffic"] for w in bench["workloads"]] + \
        [w["config"] for w in bench["workloads"]] + \
        [k for c in bench["configs"] for k in c["reduced"]]
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            names.append(m["name"])
            if not UNIT.match(m["unit"]):
                bad.append(("unit", m["unit"]))
    bad += [("name", n) for n in names if not NAME.match(n)]
    return bad
