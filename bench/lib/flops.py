"""The chip peaks that counted operations and bytes are held against,
and what of the counting depends on no architecture.

The counts themselves depend on the architecture, so each lives beside
the equations it counts, in the module that a configuration names as its
``reference`` (for the dense family ``bench/lib/reference.py``); a
driver hands that module to the metric readers as ``rd["counts"]``.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parents[1] / "peaks.json"

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1,
               "float8_e4m3fn": 1, "float8_e5m2": 1}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind`` from the table; a device that is not
    in it is an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


def decode_contexts(prompt_len: int, max_new: int) -> range:
    """Keys each decode step of one request sees: the first token comes
    from the admission, then step j attends over ``prompt_len + j``."""
    return range(prompt_len + 1, prompt_len + max_new)


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline: the larger of compute time and memory time."""
    return max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
