"""``BENCHMARK.json`` and the files it names, found by name.

A cell names a configuration and a traffic mix; a per-layer metric names
its reader.  Each lives in a file of its own, so a later change adds a
configuration, a mix or a metric by adding files and entries only:

    <root>/BENCHMARK.json
    <file of the configs entry>            one configuration (JSON)
    <root>/bench/traffic/<traffic>.json    one traffic mix
    <root>/bench/metrics/<metric>.py       one reader: read(run) -> float | None

and below them, each named by a configuration or traffic file:

    <root>/bench/corpora/<generator>.py    one corpus generator (``corpus.generator``)
    <root>/bench/filters/<schema>.py       one filter schema (``filter``)
    <root>/bench/loops/<kind>.py           one arrival loop (``loop.kind``)
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def plugin(root: str, folder: str, name: str, what: str):
    """The module ``<root>/bench/<folder>/<name>.py``; a name with no
    such file is refused as an unknown ``what``."""
    path = os.path.join(root, "bench", folder, f"{name}.py")
    if os.path.basename(name) != name or not os.path.isfile(path):
        raise ValueError(f"unknown {what} {name!r}: no file bench/{folder}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{folder}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload entry resolved to its files' contents."""

    name: str
    chips: int
    config: dict  # the configuration file
    traffic: dict  # the traffic file
    end_to_end: tuple  # BENCHMARK.json entries this cell reports
    per_layer: tuple


class Bench:
    def __init__(self, root: str = ROOT):
        self.root = root
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.spec = json.load(f)

    def _entry(self, key: str, name: str) -> dict:
        for e in self.spec[key]:
            if e["name"] == name:
                return e
        raise KeyError(f"no {key} entry named {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        with open(os.path.join(self.root, self._entry("configs", name)["file"])) as f:
            return json.load(f)

    def traffic(self, name: str) -> dict:
        with open(os.path.join(self.root, "bench", "traffic", f"{name}.json")) as f:
            return json.load(f)

    def end_to_end_for(self, cell: str) -> list[dict]:
        return [m for m in self.spec["end_to_end"]
                if "workloads" not in m or cell in m["workloads"]]

    def per_layer_for(self, cell: str) -> list[dict]:
        """Metrics listing this cell, or listing none and moving an
        end-to-end metric the cell reports."""
        reported = {m["name"] for m in self.end_to_end_for(cell)}
        return [m for m in self.spec["per_layer"]
                if cell in m.get("workloads", ())
                or ("workloads" not in m and m["moves"] in reported)]

    def cell(self, name: str) -> Cell:
        w = self._entry("workloads", name)
        return Cell(
            name=name,
            chips=int(w["chips"]),
            config=self.config(w["config"]),
            traffic=self.traffic(w["traffic"]),
            end_to_end=tuple(self.end_to_end_for(name)),
            per_layer=tuple(self.per_layer_for(name)),
        )

    def reader(self, metric: str) -> Callable:
        """``read`` of ``bench/metrics/<metric>.py``."""
        return plugin(self.root, "metrics", metric, "metric").read

    def schema(self, config: dict):
        """The filter schema of a configuration (``bench/filters/``)."""
        return plugin(self.root, "filters", config["filter"], "filter schema")
