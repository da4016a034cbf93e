"""Small cells for the CPU tests: the real configuration files and mixes
cut to a size the CPU runs in seconds."""

from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [p for p in (str(ROOT), str(ROOT / "src")) if p not in sys.path]

from bench.lib import flops, spec  # noqa: E402
from bench.lib.reference import Dims  # noqa: E402

SMALL = {"hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
         "num_hidden_layers": 2, "num_key_value_heads": 2, "head_dim": 16,
         "vocab_size": 256}


# bf16 rounding weighs more at width 64 than at 4096, so the small size
# has limits of its own, set like the cell's (CPU, seeds 1-4 and 2**33+3):
# the program read at most 0.00125 / 0.0030 / 0.0026, the fp8 control at
# least 0.0090 / 0.0094 / 0.0041
SMALL_TRAIN_LIMITS = {"loss_gap": 0.004, "first_grad_norm_gap": 0.006, "change_norm_gap": 0.02}
# served tokens: on the CPU the program reads 0 at widths 64 and 512; the
# int8-weight control at width 512 read 0.011 to 0.027 at the widest and
# above 0 on the mean over seeds 1-3
SMALL_SERVE_LIMITS = {"served_logit_gap": 0.005, "served_logit_gap_mean": 1e-4}


def small_config(name: str, **over) -> dict:
    cfg = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
    cfg.update(SMALL, **over)
    if "serve" in cfg:
        cfg["serve"] = {"n_slots": 4, "n_blocks": 48, "block_size": 16, "max_blocks": 6}
        cfg["limits"] = SMALL_SERVE_LIMITS
    return cfg


def small_cell(workload: str, chips: int | None = None, **mix_over) -> spec.Cell:
    """``workload`` at the small size; ``chips`` replicas of a serving
    cell (each on its own device) over twice as many partitions."""
    real = spec.cell(workload)
    cfg = small_config(real.entry["config"])
    mix = dict(real.mix)
    if mix["kind"] == "open_loop_lm":
        mix.update(rate_per_s=6.0, trace_seconds=1.0, check_tokens=24,
                   prompt_len={"dist": "lognormal", "median": 24, "sigma": 0.8, "min": 8,
                               "max": 64, "points": 4},
                   max_new={"dist": "lognormal", "median": 6, "sigma": 0.8, "min": 2, "max": 24})
    else:
        cfg["train"] = dict(cfg["train"], seq=32, batch=2)
        cfg["limits"] = SMALL_TRAIN_LIMITS
        per_seq = flops.train_flops_per_sequence(Dims.from_config(cfg), 32)
        peak = flops.peaks("TPU v5 lite")["bf16_flops_per_s"]
        mix.update(backlog_seconds_at_peak=32 * per_seq / peak, warm_steps=1,
                   trace_seconds=0.5)
    if chips and mix["kind"] == "open_loop_lm":
        mix["request_partitions"] = 2 * chips
    mix.update(mix_over)
    return spec.Cell(real.name, chips or real.chips, cfg, mix, real.spec, real.entry)
