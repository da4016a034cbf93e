"""Run one benchmark cell on the chips of this machine.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and metrics are found by name
from ``BENCHMARK.json`` (see ``bench/lib/spec.py``). The run sets up,
warms every shape the window uses, measures for ``--seconds``, checks
what the timed path produced against the plain reference, and prints one
JSON object as the last line of standard output: the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics (from a profiler trace
of part of the window) with ``--trace 1``. The numbers compared, each
beside its limit, are the last lines of standard error and the last key
of that object.

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for.
"""

from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

OUT_DIR = ROOT / "bench" / "out"


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def devices_or_exit(chips: int) -> list:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU (JAX found {devs[0].platform}); nothing run")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found {len(devs)}")
    return devs


def enable_cache() -> str:
    """The program's persistent compilation cache, with every program
    kept, so that a second run in a checkout compiles nothing."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def execute(cell, seed: int, seconds: float, trace: bool, devices, *,
            device_kind: str | None = None, variant: str = "program",
            out_dir: Path = OUT_DIR) -> dict:
    """Set up, measure and judge one run of ``cell``; returns the result
    object. ``device_kind`` picks the peaks where it is not the device's
    own (the CPU rehearsals)."""
    from bench.kinds.common import Ctx
    from bench.lib import flops, spec

    peak = flops.peaks(device_kind or devices[0].device_kind)
    out_dir.mkdir(parents=True, exist_ok=True)
    ctx = Ctx(cell=cell, seed=seed, seconds=seconds, trace=trace, devices=devices,
              out_dir=out_dir, started=STARTED, variant=variant)
    out = spec.driver(cell.mix["kind"]).run(ctx, peak)
    return assemble(cell, trace, devices, peak, out)


def assemble(cell, trace: bool, devices, peak, out) -> dict:
    from bench.lib import spec

    e2e = dict(out["e2e"])
    e2e["setup_s"] = out["window_open"] - STARTED
    rd = dict(out["readings"], peak=peak)
    traced = out.get("traced")
    metrics = {}
    extra_device = {}
    breakdown = None
    if trace:
        summary = traced.summary()
        rd["trace"] = summary
        for m in cell.per_layer():
            value = spec.reader(m["name"], cell.root)(rd)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        extra_device = {"busy_s": summary.busy_s, "window_s": summary.window_s}
        breakdown = {"device_ops": [[k, v] for k, v in summary.top_ops],
                     "idle_gaps": [[k, v] for k, v in summary.idle_by_host]}
    else:
        for m in cell.end_to_end():
            if m["name"] not in e2e:
                raise KeyError(f"the {cell.mix['kind']} driver reports no {m['name']!r}")
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    checks = out["checks"]
    for c in checks.values():
        c["ok"] = c["value"] >= c["limit"] if c.get("at_least") else c["value"] <= c["limit"]
    dev = devices[0]
    result = {
        "correct": all(c["ok"] for c in checks.values()) and out["failed"] == 0,
        "attempted": out["attempted"],
        "failed": out["failed"],
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices), "memory_peak_bytes": out["memory_peak_bytes"],
                   **extra_device},
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def report(result: dict) -> None:
    for name, c in result["checks"].items():
        rel = ">=" if c.get("at_least") else "<="
        print(f"check {name}: {c['value']} (limit {rel} {c['limit']}) "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def main(argv=None) -> None:
    args = parse(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench.lib import spec

    cell = spec.cell(args.workload)
    devices = devices_or_exit(cell.chips)
    enable_cache()
    report(execute(cell, args.seed, args.seconds, bool(args.trace), devices))


if __name__ == "__main__":
    main()
