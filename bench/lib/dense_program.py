"""The program under test for the dense GQA decoder family (the Yi /
LLaMA family), whose plain reference is ``bench/lib/reference.py``.

A configuration names this module as its ``program``. The harness takes
from it the program's model, built from the configuration's published
(hf) keys and its ``precision``; the reference's name of each of the
model's parameter leaves; and the family's small size for the CPU
rehearsals (``bench/tests/small.py``).
"""

from __future__ import annotations

from repro.models.model import ArchConfig, StreamModel
from repro.models.policy import Policy

# the small size: the configuration's published keys that a CPU
# rehearsal replaces
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


def arch(config: dict) -> ArchConfig:
    """The program's architecture from a configuration file's published
    (hf) keys."""
    heads = int(config["num_attention_heads"])
    return ArchConfig(
        name=config["name"], d_model=int(config["hidden_size"]),
        n_layers=int(config["num_hidden_layers"]), n_heads=heads,
        n_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config.get("head_dim", config["hidden_size"] // heads)),
        d_ff=int(config["intermediate_size"]), vocab=int(config["vocab_size"]),
        rope_theta=float(config["rope_theta"]), norm_eps=float(config["rms_norm_eps"]),
    )


def model(config: dict, **policy) -> StreamModel:
    p = config["precision"]
    return StreamModel(arch(config), Policy(
        param_dtype=p["param_dtype"], compute_dtype=p["compute_dtype"],
        kv_cache_dtype=p["kv_cache_dtype"], **policy,
    ))


def leaf_name(path) -> str:
    """The reference's name of one of the program's parameter leaves:
    its innermost key, or the key above a norm's ``w``."""
    keys = [str(getattr(k, "key", k)) for k in path]
    return keys[-2] if keys[-1] == "w" else keys[-1]
