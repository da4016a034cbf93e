"""jit'd wrappers bridging model-layer calling conventions to the kernels.

The model layers use (B, S, H, D) activation layout and grouped (GQA /
SSD-group) KV; the kernels want (B, H, S, D). These wrappers do the
(XLA-fused) transposes, and the repeats of grouped tensors for the scans
(flash attention reads grouped K/V itself), and are the only entry
points the rest of the codebase calls. :func:`default_interpret` is the
one place that chooses the Pallas interpreter: on the CPU backend only.
The kernels themselves take ``interpret`` with no default, so no caller
on an accelerator lands in the interpreter by omission.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention
from repro.kernels.rglru_scan import rglru_scan_kernel
from repro.kernels.ssd_scan import ssd_scan

__all__ = ["attention_op", "rglru_op", "ssd_op", "default_interpret"]


def default_interpret() -> bool:
    return jax.default_backend() == "cpu"


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "softcap", "interpret")
)
def attention_op(
    q: jax.Array,  # (B, S, H, D)
    k: jax.Array,  # (B, S, Kv, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Flash self-attention in model layout. GQA K/V go to the kernel
    unrepeated. ``interpret`` None is :func:`default_interpret`; a caller
    that knows it is lowered for a TPU passes False."""
    out = flash_attention(
        jnp.moveaxis(q, 1, 2),  # (B, H, S, D)
        jnp.moveaxis(k, 1, 2),
        jnp.moveaxis(v, 1, 2),
        causal=causal,
        window=window,
        softcap=softcap,
        interpret=default_interpret() if interpret is None else interpret,
    )
    return jnp.moveaxis(out, 1, 2)


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_op(
    x: jax.Array,  # (B, S, H, P) — model layout
    dt: jax.Array,  # (B, S, H)
    A: jax.Array,  # (H,)
    Bm: jax.Array,  # (B, S, G, N)
    Cm: jax.Array,  # (B, S, G, N)
    init_state: jax.Array | None = None,  # (B, H, N, P)
    *,
    chunk: int = 256,
):
    b, s, h, p = x.shape
    g = Bm.shape[2]
    rep = h // g
    xt = jnp.moveaxis(x, 1, 2)  # (B, H, S, P)
    dtt = jnp.moveaxis(dt, 1, 2)  # (B, H, S)
    Bt = jnp.moveaxis(Bm, 1, 2)  # (B, G, S, N)
    Ct = jnp.moveaxis(Cm, 1, 2)
    if rep != 1:
        Bt = jnp.repeat(Bt, rep, axis=1)
        Ct = jnp.repeat(Ct, rep, axis=1)
    y, st = ssd_scan(
        xt, dtt.astype(jnp.float32), A, Bt, Ct, init_state,
        chunk=chunk, interpret=default_interpret(),
    )
    return jnp.moveaxis(y, 1, 2), st


@functools.partial(jax.jit, static_argnames=("t_block",))
def rglru_op(
    x: jax.Array,  # (B, S, C) gated input (fp32)
    log_a: jax.Array,  # (B, S, C) fp32
    h0: jax.Array | None = None,
    *,
    t_block: int = 256,
):
    return rglru_scan_kernel(
        x.astype(jnp.float32),
        log_a.astype(jnp.float32),
        h0,
        t_block=min(t_block, x.shape[1]),
        interpret=default_interpret(),
    )
