"""Flash attention on the TPU, forward and backward: splash attention.

The kernels are the splash attention kernels that ship with JAX
(``jax.experimental.pallas.ops.tpu.splash_attention``): a forward that
keeps the online-softmax statistics (row max, denominator, accumulator)
in VMEM and emits the output plus one logsumexp per query row, and dq /
dkv backward kernels that recompute each block's probabilities from
that logsumexp. No score tensor reaches HBM in either direction; the
residuals of the backward are q, k, v, the output and the logsumexp.

* Masks are per head (one mask object for every head); blocks that the
  mask covers entirely are skipped, so causal attention issues about half
  the MXU work of a masked full product.
* Grouped-query attention is native: query head ``h`` reads KV head
  ``h // (H / Kv)`` through the kernel's index map, so K/V are never
  repeated in HBM.
* Splash does not scale the logits: q is multiplied by ``1/sqrt(D)``
  first, and a soft cap, if any, applies to the scaled logits as in
  ``ref.mha``.
* Block sizes follow the sequence: the largest of 512, 256 and 128 that
  divides it, for the forward and both backward kernels.

Validated against ``ref.mha`` (forward and the gradients of q, k and v)
in interpret mode (tests/test_kernels.py), and compiled for a described
v5e (tests/test_tpu_compile.py).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as splash

BLOCKS = (512, 256, 128)


def block_size(seq: int) -> int:
    """The largest of ``BLOCKS`` dividing ``seq``."""
    for b in BLOCKS:
        if seq % b == 0:
            return b
    raise ValueError(f"sequence length {seq} is not a multiple of {BLOCKS[-1]}")


def _mask(seq: int, causal: bool, window: int | None):
    shape = (seq, seq)
    if window is not None:
        # key j is seen by query i when i - window < j (and j <= i if causal)
        return splash.LocalMask(shape, (window - 1, 0 if causal else None), offset=0)
    return splash.CausalMask(shape) if causal else splash.FullMask(shape)


def flash_attention(
    q: jax.Array,  # (B, H, S, D)
    k: jax.Array,  # (B, Kv, S, D), H a multiple of Kv
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    interpret: bool,
) -> jax.Array:
    """Self-attention of ``q`` over ``k``/``v``; returns (B, H, S, D) in
    ``q``'s dtype. Differentiable in q, k and v."""
    _, h, s, d = q.shape
    b = block_size(s)
    sizes = splash.BlockSizes(
        block_q=b, block_kv=b, block_kv_compute=b,
        block_q_dkv=b, block_kv_dkv=b, block_kv_dkv_compute=b,
        block_q_dq=b, block_kv_dq=b,
    )
    kernel = splash.make_splash_mha(
        splash.MultiHeadMask([_mask(s, causal, window)] * h),
        block_sizes=sizes,
        attn_logits_soft_cap=softcap,
        head_shards=1,
        q_seq_shards=1,
        interpret=interpret,
    )
    scaled = (q.astype(jnp.float32) / math.sqrt(d)).astype(q.dtype)
    return jax.vmap(kernel)(scaled, k, v)
