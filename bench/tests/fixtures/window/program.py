"""The program of the test family: the dense family's model with a
sliding window of ``sliding_window`` keys on the first layer of each
pair (``ArchConfig(pattern=("local", "attn"))``), the second attending
causally over all keys. Its reference is ``reference.py`` beside it."""

from __future__ import annotations

import dataclasses

from bench.lib import dense_program as dense
from repro.models.model import StreamModel
from repro.models.policy import Policy

# the configuration is at the small size already
SMALL = {"hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
         "num_hidden_layers": 2, "num_key_value_heads": 2, "head_dim": 16,
         "vocab_size": 256, "sliding_window": 8}
# set like the dense family's (CPU, seeds 1-6 and 2**33+3): the program
# read at most 0.0034 / 0.0032 / 0.0040; the reference without the window
# at least 0.040 / 0.071 / 0.021, the fp8 control at least 0.0048 / 0.016
# / 0.0083
SMALL_TRAIN_LIMITS = {"loss_gap": 0.01, "first_grad_norm_gap": 0.008, "change_norm_gap": 0.01}


def model(config: dict, **policy) -> StreamModel:
    p = config["precision"]
    arch = dataclasses.replace(dense.arch(config), pattern=("local", "attn"),
                               window=int(config["sliding_window"]))
    return StreamModel(arch, Policy(
        param_dtype=p["param_dtype"], compute_dtype=p["compute_dtype"],
        kv_cache_dtype=p["kv_cache_dtype"], **policy,
    ))


def leaf_name(path) -> str:
    """A layer's leaf under its slot (``s0.wq``), the others as the dense
    family names them."""
    keys = [str(getattr(k, "key", k)) for k in path]
    name = dense.leaf_name(path)
    return f"{keys[1]}.{name}" if keys[0] == "slots" else name
