"""Finding a cell's files by the names in ``BENCHMARK.json``.

- configuration: the ``file`` of its entry in ``configs``;
- traffic mix: ``bench/traffic/<traffic>.json``, whose ``kind`` names the
  driver ``bench/kinds/<kind>.py``;
- per-layer metric: ``bench/metrics/<name>.py``, which defines
  ``read(rd)``: the number from the run's readings ``rd``, or None where
  there is nothing to read;
- a cell not yet proven on the chip: ``bench/candidates.json``.

Adding a cell, a mix or a metric adds files; none of these is edited.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import sys
from pathlib import Path
from typing import Any, Callable

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"


def load_spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    spec: dict
    entry: dict

    def end_to_end(self) -> list[dict]:
        return [m for m in self.spec["end_to_end"] if self._has(m)]

    def per_layer(self) -> list[dict]:
        """The cell's per-layer metrics: those that list it, and those
        without a list whose end-to-end metric the cell reports."""
        reported = {m["name"] for m in self.end_to_end()}
        out = []
        for m in self.spec["per_layer"]:
            if "workloads" in m:
                if self.name in m["workloads"]:
                    out.append(m)
            elif m["moves"] in reported:
                out.append(m)
        return out

    def _has(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]


def candidates(root: Path = ROOT) -> dict:
    """Cells built and rehearsed but not yet proven on the chip, in
    ``BENCHMARK.json``'s form; a benchmark PR moves each one over once it
    is proven."""
    path = root / "bench" / "candidates.json"
    return json.loads(path.read_text()) if path.exists() else {"workloads": []}


def cell(name: str, root: Path = ROOT) -> Cell:
    specs = (load_spec(root), candidates(root))
    for spec in specs:
        entries = {w["name"]: w for w in spec["workloads"]}
        if name in entries:
            break
    else:
        known = sorted(w["name"] for s in specs for w in s["workloads"])
        raise KeyError(f"no workload {name!r}; known: {known}")
    entry = entries[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[entry["config"]]["file"]).read_text())
    mix = json.loads((root / "bench" / "traffic" / f"{entry['traffic']}.json").read_text())
    return Cell(name, int(entry["chips"]), config, mix, spec, entry)


def driver(kind: str):
    """The module that runs cells of this traffic kind."""
    return importlib.import_module(f"bench.kinds.{kind}")


def reader(metric: str) -> Callable[[dict], Any]:
    path = BENCH / "metrics" / f"{metric}.py"
    mod_name = "bench.metrics._" + metric.replace(".", "_").replace("-", "_")
    if mod_name not in sys.modules:
        loaded = importlib.util.spec_from_file_location(mod_name, path)
        module = importlib.util.module_from_spec(loaded)
        loaded.loader.exec_module(module)
        sys.modules[mod_name] = module
    return sys.modules[mod_name].read
