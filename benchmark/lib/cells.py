"""Finding a cell's files by name.  ``BENCHMARK.json`` is the index; this
module knows no cell, configuration, traffic mix or metric by name."""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Dict, List

LIB = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(LIB)
ROOT = os.path.dirname(BENCH_DIR)


class Cell:
    def __init__(self, name: str, root: str = ROOT):
        self.root = root
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.bench = json.load(f)
        hits = [w for w in self.bench["workloads"] if w["name"] == name]
        if not hits:
            known = ", ".join(w["name"] for w in self.bench["workloads"])
            raise KeyError(f"no workload {name!r} in BENCHMARK.json ({known})")
        self.entry = hits[0]
        self.name = name
        self.chips = int(self.entry["chips"])
        cfg_entry = next(c for c in self.bench["configs"]
                         if c["name"] == self.entry["config"])
        with open(os.path.join(root, cfg_entry["file"])) as f:
            self.config = json.load(f)
        # the published keys plus the derived ones, flat: what flops.py reads
        self.model = {**self.config, **self.config.get("derived", {})}
        bench_dir = os.path.join(root, self.bench["paths"][0])
        with open(os.path.join(bench_dir, "traffic",
                               self.entry["traffic"] + ".json")) as f:
            self.traffic = json.load(f)
        self.bench_dir = bench_dir

    def _reported(self, group: str) -> List[Dict]:
        return [m for m in self.bench[group]
                if "workloads" not in m or self.name in m["workloads"]]

    @property
    def end_to_end(self) -> List[Dict]:
        return self._reported("end_to_end")

    @property
    def per_layer(self) -> List[Dict]:
        return self._reported("per_layer")

    @staticmethod
    def reader_at(path: str):
        """A metric's own module, loaded from its file (names may hold
        dots, so not by import)."""
        stem = os.path.basename(path)[:-3]
        spec = importlib.util.spec_from_file_location(
            "metric_" + "".join(c if c.isalnum() else "_" for c in stem), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def flags(self, extra: Dict = None) -> List[str]:
        """The configuration's program flags as a CLI list."""
        out: List[str] = []
        for k, v in {**self.config["flags"], **(extra or {})}.items():
            out += ["--" + k, str(v).lower() if isinstance(v, bool) else str(v)]
        return out


def load_kind(kind: str):
    """A traffic kind's runner: ``benchmark/lib/kind_<kind>.py`` with
    ``run(cell, args, clock) -> Result``.  New kinds are new files."""
    path = os.path.join(LIB, f"kind_{kind}.py")
    if not os.path.isfile(path):
        raise KeyError(f"traffic kind {kind!r} has no runner: add {path}")
    return importlib.import_module(f"benchmark.lib.kind_{kind}")
