"""Operations and bytes that the algorithm needs, from a configuration's
shapes alone, and the chip peaks they are held against.

Counts are the least work: matrix products at 2 operations per
multiply-add, causal attention over the positions a query may see, and
the bytes a step cannot avoid moving (each weight once, each live cached
key and value once, in the configuration's dtypes). What an
implementation adds on top (padding, masked work, copies) is not counted.
"""

from __future__ import annotations

import json
from pathlib import Path

from bench.lib.reference import Dims

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


def layer_matmul_params(dm: Dims) -> int:
    """Weights one layer multiplies per token: q, k, v, o and the gated MLP."""
    d, hd = dm.d_model, dm.head_dim
    attn = d * dm.n_heads * hd * 2 + d * dm.n_kv_heads * hd * 2
    return attn + 3 * d * dm.d_ff


def matmul_params(dm: Dims) -> int:
    """Weights multiplied per token through the whole model, unembedding
    included (the embedding is a lookup)."""
    return dm.n_layers * layer_matmul_params(dm) + dm.d_model * dm.vocab_padded


def param_count(dm: Dims) -> int:
    return read_params(dm, 0) + dm.vocab_padded * dm.d_model


def read_params(dm: Dims, n_tokens: int) -> int:
    """Weights a pass over ``n_tokens`` tokens reads: every matrix and
    norm, and only the embedding rows it looks up."""
    norms = (2 * dm.n_layers + 1) * dm.d_model
    return matmul_params(dm) + norms + n_tokens * dm.d_model


def attn_flops(dm: Dims, n_keys: int) -> int:
    """One query against ``n_keys`` keys in every layer: QK^T and PV."""
    return 4 * dm.n_layers * dm.n_heads * dm.head_dim * n_keys


def kv_bytes_per_token(dm: Dims, kv_dtype: str) -> int:
    return 2 * dm.n_layers * dm.n_kv_heads * dm.head_dim * DTYPE_BYTES[kv_dtype]


def prefill_flops(dm: Dims, n: int) -> int:
    """Admission of an ``n``-token prompt: every layer over every
    position, causal attention, the unembedding for the last position."""
    layers = 2 * dm.n_layers * layer_matmul_params(dm) * n
    attn = 4 * dm.n_layers * dm.n_heads * dm.head_dim * n * (n + 1) // 2
    return layers + attn + 2 * dm.d_model * dm.vocab_padded


def prefill_bytes(dm: Dims, n: int, param_dtype: str, kv_dtype: str) -> int:
    """Every weight read once, the prompt's keys and values written once."""
    return (read_params(dm, n) * DTYPE_BYTES[param_dtype]
            + n * kv_bytes_per_token(dm, kv_dtype))


def decode_flops(dm: Dims, contexts) -> int:
    """One decode step over rows whose queries see ``contexts`` keys each."""
    per_row = 2 * matmul_params(dm)
    return sum(per_row + attn_flops(dm, c) for c in contexts)


def decode_bytes(dm: Dims, contexts, param_dtype: str, kv_dtype: str) -> int:
    """Every weight once per step, each row's live keys and values once."""
    contexts = list(contexts)
    return (read_params(dm, len(contexts)) * DTYPE_BYTES[param_dtype]
            + sum(contexts) * kv_bytes_per_token(dm, kv_dtype))


def decode_totals(dm: Dims, calls: int, contexts, param_dtype: str, kv_dtype: str):
    """Operations and bytes of ``calls`` decode steps whose rows, all
    together, saw ``contexts`` keys each: weights once per step."""
    contexts = list(contexts)
    pb = DTYPE_BYTES[param_dtype]
    nbytes = (calls * read_params(dm, 0) * pb + len(contexts) * dm.d_model * pb
              + sum(contexts) * kv_bytes_per_token(dm, kv_dtype))
    return decode_flops(dm, contexts), nbytes


def decode_contexts(prompt_len: int, max_new: int) -> range:
    """Keys each decode step of one request sees: the first token comes
    from the admission, then step j attends over ``prompt_len + j``."""
    return range(prompt_len + 1, prompt_len + max_new)


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline: the larger of compute time and memory time."""
    return max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])


def train_flops_per_sequence(dm: Dims, seq: int) -> int:
    """Forward and backward (3x the forward) of one ``seq``-token
    sequence: the layers on every position, causal attention, the
    unembedding on the ``seq - 1`` positions that have a label.
    Recomputation is not counted."""
    layers = 2 * dm.n_layers * layer_matmul_params(dm) * seq
    unembed = 2 * dm.d_model * dm.vocab_padded * (seq - 1)
    attn = 4 * dm.n_layers * dm.n_heads * dm.head_dim * seq * (seq + 1) // 2
    return 3 * (layers + unembed + attn)
