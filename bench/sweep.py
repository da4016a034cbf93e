"""Find a serving cell's knee: the highest offered rate it sustains
without a growing backlog. Run once, when a cell's rate is chosen; the
cell then offers a fixed rate and never searches.

    python bench/sweep.py --workload <name> --rates 2,3,4 --seconds 20 --seed <n>

One process sets up and warms once, then offers each rate open loop for
``--seconds``. Per rate it prints one JSON line: latencies, completed
tokens per second, requests still unanswered when the window closed,
the seconds it took to answer them, and the ratio of the median latency
of the last third of requests to that of the first third (a backlog that
grows shows as a ratio well above 1).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--stop-ratio", type=float, default=3.0)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import numpy as np

    from bench import run as bench_run
    from bench.kinds import open_loop_lm as olm
    from bench.kinds.common import Ctx
    from bench.lib import spec, traffic

    cell = spec.cell(args.workload)
    devices = bench_run.devices_or_exit(cell.chips)
    bench_run.enable_cache()
    ctx = Ctx(cell=cell, seed=args.seed, seconds=args.seconds, trace=False,
              devices=devices, out_dir=bench_run.OUT_DIR, started=bench_run.STARTED)
    dm = cell.reference.dims(cell.config)
    m, engines, log, group = olm._build(ctx)
    olm._warm(ctx, log, group, dm.vocab)
    base = 0
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(cell.mix, rate_per_s=rate)
        plan = [dataclasses.replace(p, rid=base + p.rid)
                for p in traffic.schedule(mix, args.seconds, args.seed, dm.vocab)]
        base += len(plan)
        w = olm.window(ctx, engines, log, group, plan, None)
        lat = olm.latencies_ms(w)
        by_due = [(w["answered"][p.rid][0] - w["due"][p.rid]) * 1e3
                  for p in sorted(plan, key=lambda p: p.due_s) if p.rid in w["answered"]]
        third = max(1, len(by_due) // 3)
        late_at_close = sum(1 for p in plan if p.rid not in w["answered"]
                            or w["answered"][p.rid][0] > w["t_end"])
        last_done = max((v[0] for v in w["answered"].values()), default=w["t_end"])
        row = {
            "rate_per_s": rate, "requests": len(plan), "answered": len(w["answered"]),
            "p50_ms": float(np.percentile(lat, 50)) if lat else None,
            "p95_ms": float(np.percentile(lat, 95)) if lat else None,
            "out_tokens_per_s": sum(len(g) for s, _, g in w["answered"].values()
                                    if s <= w["t_end"]) / args.seconds,
            "unanswered_at_close": late_at_close,
            "drain_after_close_s": max(0.0, last_done - w["t_end"]),
            "late_third_over_early_third": float(np.median(by_due[-third:])
                                                 / max(np.median(by_due[:third]), 1e-9)),
            "window_compiles": w["compiles"], "lateness": w["lateness"],
        }
        print(json.dumps(row), flush=True)
        if row["late_third_over_early_third"] > args.stop_ratio:
            break  # well past the knee: higher rates only take longer


if __name__ == "__main__":
    main()
