"""The whole command, rehearsed on the CPU at a small size through the
harness's internals (the command itself refuses the CPU), and the same
with four virtual devices."""

import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from bench.lib import spec
from small import ROOT, small_cell

RUN = ROOT / "bench" / "run.py"


def load_run():
    s = importlib.util.spec_from_file_location("bench_run_cli", RUN)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


def rehearse(workload: str, trace: bool, tmp_path, seconds: float = 3.0, **mix) -> dict:
    run = load_run()
    cell = small_cell(workload, **mix)
    res = run.execute(cell, 2**33 + 3, seconds, trace, jax.devices()[: cell.chips],
                      device_kind="TPU v5 lite", out_dir=tmp_path)
    assert list(res)[-1] == "checks"
    json.dumps(res)  # the result line is plain JSON
    return res


@pytest.mark.parametrize("workload", ["yi6b.chat.steady", "yi6b-l2.train.stream"])
def test_untraced_run_reports_the_end_to_end_metrics(workload, tmp_path):
    res = rehearse(workload, False, tmp_path)
    cell = spec.cell(workload)
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end()}
    for m in cell.end_to_end():
        got = res["metrics"][m["name"]]
        assert got["unit"] == m["unit"] and got["value"] > 0
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] >= 1
    assert "breakdown" not in res


@pytest.mark.parametrize("workload", ["yi6b.chat.steady", "yi6b-l2.train.stream"])
def test_traced_run_reports_the_per_layer_metrics(workload, tmp_path):
    res = rehearse(workload, True, tmp_path)
    cell = spec.cell(workload)
    assert res["correct"] is True
    assert set(res["metrics"]) == {m["name"] for m in cell.per_layer()}
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
    for key in ("device_ops", "idle_gaps"):
        assert 0 < len(res["breakdown"][key]) <= 10


def test_the_command_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(RUN), "--workload", "yi6b.chat.steady",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_the_command_needs_the_program(tmp_path):
    """A checkout that holds only BENCHMARK.json and the benchmark fails."""
    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "yi6b.chat.steady",
                        "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=tmp_path,
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


FOUR = """
import sys, json
sys.path[:0] = {paths!r}
import jax
assert len(jax.devices()) == 4
from test_rehearsal import rehearse
from pathlib import Path
res = rehearse("yi6b.chat.steady", True, Path({tmp!r}), chips=4)
print(json.dumps({{"correct": res["correct"], "count": res["device"]["count"],
                  "metrics": sorted(res["metrics"])}}))
"""


def test_four_virtual_devices(tmp_path):
    """Four replicas, one per device, behind one serving group, traced."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    here = str(Path(__file__).resolve().parent)
    code = FOUR.format(paths=[str(ROOT), str(ROOT / "src"), here], tmp=str(tmp_path))
    p = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                       env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["correct"] is True and got["count"] == 4
    assert got["metrics"] == sorted(m["name"] for m in spec.cell("yi6b.chat.steady").per_layer())
