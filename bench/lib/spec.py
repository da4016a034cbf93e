"""Finding a cell's files by the names in ``BENCHMARK.json``.

- configuration: the ``file`` of its entry in ``configs``;
- its architecture: the two modules the configuration names, by path
  from the root, under ``program`` (builds the program's model from the
  file's published keys and ``precision``, names its parameter leaves as
  the reference does, states the family's small size) and ``reference``
  (the plain float32 reference, importing nothing of the program, and
  the operations and bytes its equations count);
- traffic mix: ``bench/traffic/<traffic>.json``, whose ``kind`` names the
  driver ``bench/kinds/<kind>.py``;
- per-layer metric: ``bench/metrics/<name>.py``, which defines
  ``read(rd)``: the number from the run's readings ``rd``, or None where
  there is nothing to read;
- a cell not yet proven on the chip: ``bench/candidates.json``.

Adding a cell, a mix, a metric or an architecture adds files; none of
these is edited.
"""

from __future__ import annotations

import dataclasses
import hashlib
import importlib
import importlib.util
import json
import re
import sys
from pathlib import Path
from types import ModuleType
from typing import Any, Callable

ROOT = Path(__file__).resolve().parents[2]


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
    program: ModuleType
    reference: ModuleType
    root: Path = ROOT

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
    program, reference = family(config, root)
    mix = json.loads((root / "bench" / "traffic" / f"{entry['traffic']}.json").read_text())
    return Cell(name, int(entry["chips"]), config, mix, spec, entry, program, reference, root)


FAMILY = ("program", "reference")


def family(config: dict, root: Path = ROOT) -> tuple[ModuleType, ModuleType]:
    """The ``program`` and ``reference`` modules that ``config`` names; a
    configuration without either key is an error, never a default."""
    for key in FAMILY:
        if key not in config:
            raise KeyError(f"configuration {config.get('name')!r} names no {key!r} module")
    return module(root / config["program"]), module(root / config["reference"])


def module(path: Path) -> ModuleType:
    """The Python file at ``path``, loaded once a process."""
    path = path.resolve()
    tag = re.sub(r"\W", "_", path.stem) + "_" + hashlib.sha1(str(path).encode()).hexdigest()[:10]
    name = "bench._by_path." + tag
    if name not in sys.modules:
        if not path.is_file():
            raise FileNotFoundError(f"no module file {path}")
        loaded = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(loaded)
        sys.modules[name] = mod  # a dataclass looks its module up while it is made
        try:
            loaded.loader.exec_module(mod)
        except BaseException:
            del sys.modules[name]
            raise
    return sys.modules[name]


def driver(kind: str):
    """The module that runs cells of this traffic kind."""
    return importlib.import_module(f"bench.kinds.{kind}")


def reader(metric: str, root: Path = ROOT) -> Callable[[dict], Any]:
    return module(root / "bench" / "metrics" / f"{metric}.py").read
