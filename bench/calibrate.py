"""Readings that set a cell's correctness limits: the program's on many
seeds, its low-precision control's, and planted faults'.

    python bench/calibrate.py --workload <name> --seeds 1,2,3 --what program
    python bench/calibrate.py --workload <name> --seeds 1,2,3 --what control

One process, one JSON line per seed and reading. Not part of a
benchmark run.

- Serving: ``program`` runs the cell (short window, the cell's own load)
  and reads every served-token number (``open_loop_lm.gap_numbers``),
  held or not; ``control`` runs the program's own int8-weight path
  (``Policy(weights_int8=True)``, ``quantize_params``) in the program's
  place.
- Training: ``program`` reads the three numbers of the first three
  steps against the float32 reference; ``control`` puts the reference
  computed from fp8 operands in the program's place; ``half_batch`` feeds
  the program's step only the first half of each batch.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def serving(cell, seed: int, what: str, seconds: float, devices, peak, out_dir) -> dict:
    import numpy as np

    from bench.kinds import open_loop_lm as olm
    from bench.kinds.common import Ctx

    variant = "program_int8" if what == "control" else "program"
    ctx = Ctx(cell=cell, seed=seed, seconds=seconds, trace=False, devices=devices,
              out_dir=out_dir, started=0.0, variant=variant)
    out = olm.run(ctx, peak)
    row = {"seed": seed, "what": what, "failed": out["failed"],
           **{k: v["value"] for k, v in out["checks"].items()}}
    if out["gaps"]:
        row.update(olm.gap_numbers(np.concatenate(out["gaps"])))
    return row


def training(cell, seed: int, what: str, devices) -> dict:
    import jax
    import jax.numpy as jnp

    from bench.kinds import stream_train as st
    from bench.kinds.common import Ctx
    from repro.train import adamw
    from repro.train.trainer import build_train_step

    ctx = Ctx(cell=cell, seed=seed, seconds=0.0, trace=False, devices=devices,
              out_dir=Path("."), started=0.0)
    dm = cell.reference.dims(cell.config)
    ref = st.reference_readings(ctx, dm)
    limits = cell.config["limits"]
    if what == "control":
        low = st.reference_readings(ctx, dm, fp8=True)
        checks = st.compare(low["losses"], low["grad_norms"], low["delta_norms"], ref, limits)
    else:
        m = cell.program.model(cell.config)
        opt = adamw(cell.config["train"]["lr"])
        step_fn, _ = build_train_step(m, opt)
        key = jax.random.PRNGKey(st.traffic.key_seed(seed))
        state = jax.jit(lambda k: (lambda p: {"params": p, "opt": opt.init(p)})(m.init(k)))(key)
        batches = iter(st.checked_rows(ctx))
        half = what == "half_batch"

        def next_batch():
            b = next(batches)
            return {"tokens": jnp.asarray(b[: len(b) // 2] if half else b)}

        state, losses, grads, deltas = st.checked_steps(ctx, step_fn, state, next_batch, m, key)
        del state
        gc.collect()
        checks = st.compare(losses, grads, deltas, ref, limits)
    return {"seed": seed, "what": what, **{k: v["value"] for k, v in checks.items()},
            "ref_losses": ref["losses"]}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--what", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    from bench import run as bench_run
    from bench.lib import flops, spec

    cell = spec.cell(args.workload)
    devices = bench_run.devices_or_exit(cell.chips)
    bench_run.enable_cache()
    peak = flops.peaks(devices[0].device_kind)
    for what in args.what.split(","):
        for seed in (int(s) for s in args.seeds.split(",")):
            if cell.mix["kind"] == "open_loop_lm":
                row = serving(cell, seed, what, args.seconds, devices, peak, bench_run.OUT_DIR)
            else:
                row = training(cell, seed, what, devices)
            print(json.dumps(row), flush=True)
            gc.collect()


if __name__ == "__main__":
    main()
