"""Finds what a cell is made of by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own under the benchmark's directory, so
a later PR adds a cell by adding files and an entry.  An unknown name is
an error that lists what exists.
"""

from __future__ import annotations

import importlib
import json
import os

BENCH_DIR = "benchmarks"


class Bench:
    """``BENCHMARK.json`` of the checkout at ``root`` and the files it
    names."""

    def __init__(self, root: str):
        self.root = root
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.doc = json.load(f)

    def _entry(self, section: str, name: str) -> dict:
        by_name = {e["name"]: e for e in self.doc[section]}
        if name not in by_name:
            raise KeyError(
                f"unknown {section} entry {name!r}; BENCHMARK.json has "
                f"{sorted(by_name)}")
        return by_name[name]

    def _json(self, relpath: str) -> dict:
        with open(os.path.join(self.root, relpath)) as f:
            return json.load(f)

    def workload(self, name: str) -> dict:
        return self._entry("workloads", name)

    def config(self, name: str) -> dict:
        return self._json(self._entry("configs", name)["file"])

    def _find(self, folder: str, name: str, ext: str) -> str:
        """``benchmarks/<folder>/<name><ext>`` relative to the root."""
        relpath = os.path.join(BENCH_DIR, folder, name + ext)
        if not os.path.exists(os.path.join(self.root, relpath)):
            have = sorted(
                f[:-len(ext)] for f in os.listdir(
                    os.path.join(self.root, BENCH_DIR, folder))
                if f.endswith(ext) and not f.startswith("_"))
            raise KeyError(
                f"no {relpath}; {BENCH_DIR}/{folder} has {have}")
        return relpath

    def traffic(self, name: str) -> dict:
        return self._json(self._find("traffic", name, ".json"))

    def metrics(self, section: str, workload: str) -> list[dict]:
        """The ``end_to_end`` or ``per_layer`` metrics this cell reports:
        all without a ``workloads`` key, and those that list the cell."""
        return [m for m in self.doc[section]
                if workload in m.get("workloads", [workload])]

    def module(self, folder: str, name: str):
        """``benchmarks/<folder>/<name>.py``: a per-layer metric's reader,
        a family's plain reference or its operation counts."""
        self._find(folder, name, ".py")
        return importlib.import_module(f"{BENCH_DIR}.{folder}.{name}")
