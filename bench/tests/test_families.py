"""An architecture enters the benchmark as files: a configuration that
names its ``program`` and ``reference`` modules, those two modules (the
counts beside the reference), and readers. No other module of the
harness is wired to one family, and a second family runs through the
same driver with files and entries added and nothing edited."""

import ast
import dataclasses
import json
import shutil

import jax
import pytest

from bench.lib import spec
from small import ROOT, small_cell
from test_rehearsal import load_run

FIXTURE = ROOT / "bench" / "tests" / "fixtures" / "window"
WORKLOAD = "window-l2.train.stream"
TRAIN = "yi6b-l2.train.stream"


def config_files() -> list:
    """Every configuration file: those of BENCHMARK.json and
    candidates.json, and the fixture family's."""
    files = [ROOT / c["file"] for s in (spec.load_spec(), spec.candidates()) for c in s["configs"]]
    return files + sorted(FIXTURE.glob("*.json"))


@pytest.mark.parametrize("path", config_files(), ids=lambda p: p.name)
def test_every_configuration_names_modules_that_load(path):
    config = json.loads(path.read_text())
    program, reference = spec.family(config)
    assert callable(program.model) and callable(program.leaf_name) and program.SMALL
    assert callable(reference.dims) and callable(reference.train_flops_per_sequence)
    assert reference.dims(config).vocab == config["vocab_size"]


@pytest.mark.parametrize("key", spec.FAMILY)
def test_a_configuration_without_a_module_is_an_error(key):
    config = json.loads((ROOT / "bench" / "configs" / "yi-6b-l2.json").read_text())
    del config[key]
    with pytest.raises(KeyError, match=f"names no '{key}' module"):
        spec.family(config)


def hard_wired(source: str) -> list[str]:
    """Imports of the dense reference and ``ArchConfig(...)`` calls."""
    found = []
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            names = [f"{node.module}.{a.name}" for a in node.names] + [node.module or ""]
        elif isinstance(node, ast.Call):
            f = node.func
            names = [getattr(f, "id", None) or getattr(f, "attr", "")]
        else:
            continue
        if any(n in ("bench.lib.reference", "ArchConfig") or n.startswith("bench.lib.reference.")
               for n in names):
            found.append(ast.unparse(node)[:80])
    return found


@pytest.mark.parametrize("source,found", [
    ("from bench.lib import reference", 1),
    ("from bench.lib.reference import Dims", 1),
    ("import bench.lib.reference as r", 1),
    ("from repro.models import model\nmodel.ArchConfig(name='x')", 1),
    ("from bench.lib import flops, spec", 0),
])
def test_the_scan_finds_what_it_looks_for(source, found):
    assert len(hard_wired(source)) == found


def test_no_module_outside_a_family_is_wired_to_one():
    named = {(ROOT / json.loads(f.read_text())[k]).resolve()
             for f in config_files() for k in spec.FAMILY}
    wired = {}
    for path in sorted((ROOT / "bench").rglob("*.py")):
        if path.resolve() in named or "out" in path.relative_to(ROOT).parts:
            continue
        got = hard_wired(path.read_text())
        if got:
            wired[str(path.relative_to(ROOT))] = got
    assert wired == {}


def fixture_root(tmp_path):
    """A copy of the benchmark with the fixture family's training cell
    added: a configuration file and entries, nothing edited. The cell
    reports what the accepted training cell reports."""
    root = tmp_path / "root"
    root.mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", root)
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(FIXTURE / "window-l2.json", root / "bench" / "configs")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "window-l2", "source": "https://arxiv.org/abs/2408.00118",
        "file": "bench/configs/window-l2.json", "reduced": [],
        "why": "test fixture: the dense family with a sliding window on alternate layers"})
    bench["workloads"].append({
        "name": WORKLOAD, "config": "window-l2", "traffic": "stream-train", "chips": 1,
        "why": "the training driver on a second architecture"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if TRAIN in m.get("workloads", ()):
            m["workloads"].append(WORKLOAD)
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root


@pytest.mark.parametrize("window_in_reference", [True, False])
def test_a_second_family_enters_as_files(window_in_reference, tmp_path, monkeypatch):
    """Rehearsed through ``run.execute`` like the accepted cell. With the
    window left out of the reference alone (the control), the loss no
    longer agrees: the check sees the architecture."""
    root = fixture_root(tmp_path)
    cell = small_cell(WORKLOAD, root=root)
    assert cell.program.__file__ == str(root / "bench/tests/fixtures/window/program.py")
    if not window_in_reference:
        windowed = cell.reference.dims
        monkeypatch.setattr(cell.reference, "dims",
                            lambda c: dataclasses.replace(windowed(c), window=None))
    res = load_run().execute(cell, 2**33 + 3, 3.0, window_in_reference, jax.devices()[:1],
                             device_kind="TPU v5 lite", out_dir=tmp_path / "out")
    if window_in_reference:
        assert res["correct"] is True, res["checks"]
        assert set(res["metrics"]) == {m["name"] for m in cell.per_layer()}
        assert len(res["metrics"]) == 5
        assert all(m["value"] > 0 for m in res["metrics"].values())
    else:
        assert res["correct"] is False
        assert not res["checks"]["loss_gap"]["ok"]


def test_windowed_counts_see_fewer_keys():
    """The fixture's count differs from the dense one in the windowed
    layers' attention alone."""
    config = json.loads((FIXTURE / "window-l2.json").read_text())
    _, ref = spec.family(config)
    _, dense = spec.family(json.loads((ROOT / "bench/configs/yi-6b-l2.json").read_text()))
    dm = ref.dims(config)
    seq, w = 32, dm.window
    saved = sum(i - min(i, w) for i in range(1, seq + 1))  # keys the window hides
    per_key = 3 * 4 * dm.n_heads * dm.head_dim  # QK^T and PV, forward and backward
    assert dense.train_flops_per_sequence(dm, seq) - ref.train_flops_per_sequence(dm, seq) \
        == per_key * saved
