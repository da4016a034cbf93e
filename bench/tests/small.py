"""Small cells for the CPU tests: the real configuration files and mixes
cut to a size the CPU runs in seconds. The small size and its limits are
the family's own: ``SMALL``, ``SMALL_TRAIN_LIMITS`` and
``SMALL_SERVE_LIMITS`` of the module the configuration names as its
``program``."""

from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [p for p in (str(ROOT), str(ROOT / "src")) if p not in sys.path]

from bench.lib import flops, spec  # noqa: E402


def small_cell(workload: str, chips: int | None = None, root: Path = ROOT,
               **mix_over) -> spec.Cell:
    """``workload`` at the small size; ``chips`` replicas of a serving
    cell (each on its own device) over twice as many partitions."""
    real = spec.cell(workload, root)
    cfg = dict(real.config, **real.program.SMALL)
    mix = dict(real.mix)
    if mix["kind"] == "open_loop_lm":
        cfg["serve"] = {"n_slots": 4, "n_blocks": 48, "block_size": 16, "max_blocks": 6}
        cfg["limits"] = real.program.SMALL_SERVE_LIMITS
        mix.update(rate_per_s=6.0, trace_seconds=1.0, check_tokens=24,
                   prompt_len={"dist": "lognormal", "median": 24, "sigma": 0.8, "min": 8,
                               "max": 64, "points": 4},
                   max_new={"dist": "lognormal", "median": 6, "sigma": 0.8, "min": 2, "max": 24})
    else:
        cfg["train"] = dict(cfg["train"], seq=32, batch=2)
        cfg["limits"] = real.program.SMALL_TRAIN_LIMITS
        ref = real.reference
        per_seq = ref.train_flops_per_sequence(ref.dims(cfg), 32)
        peak = flops.peaks("TPU v5 lite")["bf16_flops_per_s"]
        mix.update(backlog_seconds_at_peak=32 * per_seq / peak, warm_steps=1,
                   trace_seconds=0.5)
    if chips and mix["kind"] == "open_loop_lm":
        mix["request_partitions"] = 2 * chips
    mix.update(mix_over)
    return dataclasses.replace(real, chips=chips or real.chips, config=cfg, mix=mix)
