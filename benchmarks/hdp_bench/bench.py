"""The benchmark's data, found by name.

``BENCHMARK.json`` at the root of the checkout names every cell, metric
and configuration. Each of them lives in a file of its own under the
harness directory, so a cell or a metric is added by adding files and
entries, never by editing one:

  configs/<config>.json    a deployment: corpus shape, sampler and
                           serving settings, its source and its cuts
  traffic/<traffic>.json   a traffic mix: what one run does with the
                           configuration (``kind`` "train" or "serve")
  metrics/<metric>.py      a per-layer metric: ``read(run)`` returns its
                           value from the run's trace, spans and counts,
                           or None where it finds nothing to read

A metric split by the end-to-end metric it moves, ``<base>.<part>``,
reads with ``metrics/<base>.py`` where it has no file of its own.
"""

from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
KINDS = ("train", "serve")


class BenchError(ValueError):
    """BENCHMARK.json or a file it names is missing or malformed."""


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list   # BENCHMARK.json entries this cell reports
    per_layer: list    # (entry, read function) this cell reports


class Bench:
    def __init__(self, spec: dict, dirs=(HERE,)):
        self.spec = spec
        self.dirs = tuple(Path(d) for d in dirs)

    @classmethod
    def load(cls, path=ROOT / "BENCHMARK.json", dirs=(HERE,)):
        try:
            with open(path) as f:
                spec = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            raise BenchError(f"cannot read {path}: {e}") from None
        return cls(spec, dirs)

    def find(self, kind: str, name: str, ext: str) -> Path:
        if not NAME.match(name):
            raise BenchError(f"bad {kind} name {name!r}")
        for d in self.dirs:
            p = d / kind / f"{name}{ext}"
            if p.is_file():
                return p
        raise BenchError(f"no {kind}/{name}{ext} under "
                         f"{[str(d) for d in self.dirs]}")

    def data(self, kind: str, name: str) -> dict:
        with open(self.find(kind, name, ".json")) as f:
            return json.load(f)

    def reader(self, name: str):
        base = name.rsplit(".", 1)[0]
        try:
            path = self.find("metrics", name, ".py")
        except BenchError:
            if base == name:
                raise
            path = self.find("metrics", base, ".py")
        spec = importlib.util.spec_from_file_location(
            "hdp_bench_metric_" + re.sub(r"\W", "_", name), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        if not callable(getattr(mod, "read", None)):
            raise BenchError(f"{path} defines no read(run)")
        return mod.read

    def cell(self, name: str) -> Cell:
        ws = [w for w in self.spec.get("workloads", []) if w["name"] == name]
        if not ws:
            raise BenchError(f"no workload {name!r} in BENCHMARK.json")
        w = ws[0]
        config = self.data("configs", w["config"])
        traffic = self.data("traffic", w["traffic"])
        if traffic.get("kind") not in KINDS:
            raise BenchError(f"traffic {w['traffic']!r}: kind must be one "
                             f"of {KINDS}")
        e2e = [m for m in self.spec.get("end_to_end", [])
               if name in m.get("workloads", [name])]
        moved = {m["name"] for m in e2e}
        layer = [(m, self.reader(m["name"]))
                 for m in self.spec.get("per_layer", [])
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
        return Cell(name=name, chips=int(w["chips"]), config=config,
                    traffic=traffic, end_to_end=e2e, per_layer=layer)

    def validate(self) -> list:
        """Every cell resolves: its files exist and parse, every metric
        has a reader, every name is well formed. Returns the cells."""
        names = [m["name"] for k in ("end_to_end", "per_layer")
                 for m in self.spec.get(k, [])]
        for n in names + [w["name"] for w in self.spec.get("workloads", [])]:
            if not NAME.match(n):
                raise BenchError(f"bad name {n!r}")
        if len(set(names)) != len(names):
            raise BenchError("two metrics share a name")
        e2e = {m["name"] for m in self.spec.get("end_to_end", [])}
        for m in self.spec.get("per_layer", []):
            if m.get("moves") not in e2e:
                raise BenchError(f"{m['name']} moves {m.get('moves')!r}, "
                                 "which is no end-to-end metric")
        return [self.cell(w["name"]) for w in self.spec.get("workloads", [])]
