"""Plain float32 reference of the test family: the dense reference's
pieces (``bench/lib/reference.py``), with a band mask on the first layer
of each pair, whose queries see the last ``sliding_window`` keys. It
imports nothing of the program. The operations it counts differ from the
dense family's in the windowed layers' attention alone."""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from bench.lib import reference as dense

AdamW = dense.AdamW
SLOTS = ("s0", "s1")  # windowed, then causal over all keys


@dataclasses.dataclass(frozen=True)
class Dims(dense.Dims):
    window: int | None = None  # keys a windowed layer's query sees; None: all


def dims(config: dict) -> Dims:
    return Dims(**dataclasses.asdict(dense.Dims.from_config(config)),
                window=int(config["sliding_window"]))


def init_params(dims: Dims, key, dtype=jnp.bfloat16) -> dict:
    """The program's draws: each slot's stack from its own key."""
    d, vp = dims.d_model, dims.vocab_padded
    ks = jax.random.split(key, 8)
    n = dims.n_layers // len(SLOTS)
    return {
        "embed": dense._normal(ks[0], (vp, d), 1.0 / math.sqrt(d), dtype),
        "final_norm": jnp.ones((d,), dtype),
        "unembed": dense._normal(ks[3], (d, vp), 1.0 / math.sqrt(d), dtype),
        "slots": {s: dense.layer_params(dims, jax.random.fold_in(ks[1], i), n, dtype)
                  for i, s in enumerate(SLOTS)},
    }


LEAVES = ("embed", "final_norm", "unembed") + tuple(
    f"{s}.{n}" for s in SLOTS for n in dense.LAYER_LEAVES)


def leaf(params: dict, name: str):
    slot, _, n = name.rpartition(".")
    return params["slots"][slot][n] if slot else params[name]


def merge(params: dict, part: dict) -> dict:
    out = dict(params, slots={s: dict(v) for s, v in params["slots"].items()})
    for name, v in part.items():
        slot, _, n = name.rpartition(".")
        if slot:
            out["slots"][slot][n] = v
        else:
            out[name] = v
    return out


TREE = dense.Tree(LEAVES, (LEAVES[3:], ("embed",), ("unembed", "final_norm")), leaf, merge)


def hidden(params: dict, tokens, dims: Dims, fp8: bool = False):
    positions = jnp.arange(tokens.shape[1])
    band = positions[None, :] <= positions[:, None]
    if dims.window is not None:
        band &= positions[:, None] - positions[None, :] < dims.window
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)

    def body(x, lp):
        x = dense._layer(dims, x, lp["s0"], positions, fp8, band)
        return dense._layer(dims, x, lp["s1"], positions, fp8), None

    x, _ = jax.lax.scan(body, x, params["slots"])
    return dense._rms(x, params["final_norm"], dims.norm_eps)


def loss(params: dict, tokens, dims: Dims, fp8: bool = False):
    return dense.next_token_loss(hidden(params, tokens, dims, fp8), params["unembed"],
                                 tokens, fp8)


def train_readings(dims: Dims, key, batches, opt: AdamW, *,
                   param_dtype=jnp.bfloat16, fp8: bool = False) -> dict:
    return dense.adamw_readings(TREE, lambda k: init_params(dims, k, param_dtype),
                                lambda p, tokens: loss(p, tokens, dims, fp8), key, batches, opt)


def train_flops_per_sequence(dm: Dims, seq: int) -> int:
    """The dense count, with a windowed layer's query at position i
    seeing min(i + 1, window) keys."""
    layers = 2 * dm.n_layers * dense.layer_matmul_params(dm) * seq
    unembed = 2 * dm.d_model * dm.vocab_padded * (seq - 1)
    windowed = dm.n_layers // len(SLOTS)
    seen = (dm.n_layers - windowed) * seq * (seq + 1) // 2 + windowed * sum(
        min(i, dm.window or seq) for i in range(1, seq + 1))
    attn = 4 * dm.n_heads * dm.head_dim * seen
    return 3 * (layers + unembed + attn)
