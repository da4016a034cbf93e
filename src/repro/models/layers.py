"""Shared transformer layers — JAX-functional, policy-sharded.

Conventions:
* params are nested dicts of jnp arrays; every init function has a matching
  ``*_pspecs`` returning the same treedef of ``PartitionSpec``s (tested).
* layer stacks are **scanned**: per-layer params carry a leading ``layers``
  dim (spec ``None``) — this keeps HLO size and compile time flat in depth.
* attention tensor-parallel strategy is divisibility-driven (``attn_strategy``):
  - ``heads``: KV repeated to H heads then Q/K/V sharded on heads over the
    model axis (repeat-then-shard is a local slice, not a broadcast copy);
  - ``seq``:   context parallelism for head counts that don't divide the
    model axis (qwen2 28H, arctic 56H, gemma2 8H, whisper 6H): the query
    *block* is sharded on its sequence dim, K/V stay unrepeated+replicated
    and the GQA einsum runs grouped — per-device score block is
    (B_loc, K, G, Qb/tp, S);
  - ``none``:  replicated attention compute (no model axis / tiny models).
* unsharded causal or full self-attention at 128-multiple lengths, lowered
  for a TPU, runs the flash kernel (``kernels/flash_attention.py``,
  forward and backward; :func:`flash_applies`). Elsewhere queries are
  processed in chunks (``lax.map`` over blocks) so fp32 score
  blocks never exceed (B, H, q_block, S) — the "XLA-flash" pattern. Sliding
  window layers slice K/V to a static (window + q_block) span per block:
  O(S·window) work, not O(S²).
* RoPE uses the *interleaved* (GPT-J) pairing so rotation partners are
  adjacent and never straddle a shard boundary.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.metrics import default_registry
from repro.kernels.ops import attention_op
from repro.models.policy import Policy

__all__ = [
    "AttnParams",
    "attention",
    "attention_init",
    "attention_pspecs",
    "attn_strategy",
    "decode_attention",
    "embed_init",
    "flash_applies",
    "paged_decode_attention",
    "layer_norm",
    "mlp",
    "mlp_init",
    "mlp_pspecs",
    "rms_norm",
    "rope",
    "softcap",
    "wsc",
]


def wsc(x: jax.Array, spec: P | None) -> jax.Array:
    """with_sharding_constraint when there is a spec and a mesh set by
    ``jax.set_mesh``; ``x`` unchanged otherwise. Errors from the
    constraint itself propagate: a spec that does not fit the mesh is a
    bug to see."""
    if spec is None or jax.sharding.get_abstract_mesh().empty:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


# ---------------------------------------------------------------------- norms
def rms_norm(x: jax.Array, w: jax.Array, eps: float, *, plus_one: bool = False) -> jax.Array:
    """RMSNorm in fp32 (gemma-style ``(1 + w)`` scaling when plus_one)."""
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    scale = (1.0 + w.astype(jnp.float32)) if plus_one else w.astype(jnp.float32)
    return (y * scale).astype(dt)


def layer_norm(x: jax.Array, w: jax.Array, b: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(dt)


def softcap(x: jax.Array, cap: float | None) -> jax.Array:
    """Gemma-2 logit soft-capping: cap * tanh(x / cap)."""
    if cap is None:
        return x
    return (cap * jnp.tanh(x.astype(jnp.float32) / cap)).astype(x.dtype)


# ----------------------------------------------------------------------- init
def _normal(rng, shape, scale, dtype):
    return (scale * jax.random.normal(rng, shape, dtype=jnp.float32)).astype(dtype)


def embed_init(rng, vocab: int, d: int, dtype) -> jax.Array:
    return _normal(rng, (vocab, d), 1.0 / math.sqrt(d), dtype)


# ----------------------------------------------------------------------- RoPE
def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Interleaved rotary embedding.

    x: (B, S, H, D) with D even; positions: (S,) or (B, S).
    """
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)  # (half,)
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B?, S, half)
    cos = jnp.cos(ang)[:, :, None, :]  # (B?, S, 1, half)
    sin = jnp.sin(ang)[:, :, None, :]
    xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (half, 2))
    x0, x1 = xf[..., 0], xf[..., 1]
    y0 = x0 * cos - x1 * sin
    y1 = x0 * sin + x1 * cos
    y = jnp.stack([y0, y1], axis=-1).reshape(x.shape)
    return y.astype(x.dtype)


# ------------------------------------------------------------------------ MLP
def mlp_init(rng, L: int, d: int, d_ff: int, kind: str, dtype) -> dict:
    ks = jax.random.split(rng, 3)
    s_in, s_out = 1.0 / math.sqrt(d), 1.0 / math.sqrt(d_ff)
    p = {
        "w_in": _normal(ks[0], (L, d, d_ff), s_in, dtype),
        "w_out": _normal(ks[1], (L, d_ff, d), s_out, dtype),
    }
    if kind == "gated":
        p["w_gate"] = _normal(ks[2], (L, d, d_ff), s_in, dtype)
    return p


def mlp_pspecs(policy: Policy, d: int, d_ff: int, kind: str) -> dict:
    tp = policy.tp(d_ff)
    io = P(None, policy.fsdp(d, has_tp=tp is not None), tp)
    oi = P(None, tp, policy.fsdp(d, has_tp=tp is not None))
    p = {"w_in": io, "w_out": oi}
    if kind == "gated":
        p["w_gate"] = io
    return p


def mlp(p: dict, x: jax.Array, kind: str, act: str = "silu") -> jax.Array:
    h = jnp.einsum("bsd,df->bsf", x, p["w_in"])
    actf = {"silu": jax.nn.silu, "gelu": partial(jax.nn.gelu, approximate=True)}[act]
    if kind == "gated":
        g = jnp.einsum("bsd,df->bsf", x, p["w_gate"])
        h = actf(g) * h
    else:
        h = actf(h)
    return jnp.einsum("bsf,fd->bsd", h, p["w_out"])


# ------------------------------------------------------------------ attention
@dataclasses.dataclass(frozen=True)
class AttnParams:
    """Static attention hyper-params for one block kind."""

    n_heads: int
    n_kv: int
    head_dim: int
    rope_theta: float = 10000.0
    use_rope: bool = True
    causal: bool = True
    window: int | None = None  # sliding-window size (local attention)
    softcap: float | None = None  # gemma2 attn-logit capping
    bias: bool = False  # qwen2 QKV bias
    q_block: int = 512  # query chunk for the XLA-flash path
    cross: bool = False  # enc-dec cross attention (K/V from encoder)


def attn_strategy(ap: AttnParams, policy: Policy, seq_len: int) -> str:
    """heads | seq | none — see module docstring."""
    tp = policy.size(policy.tp_axis)
    if tp == 1:
        return "none"
    if ap.n_heads % tp == 0:
        return "heads"
    if seq_len % tp == 0 and seq_len >= tp:
        return "seq"
    return "none"


def attention_init(rng, L: int, d: int, ap: AttnParams, dtype) -> dict:
    ks = jax.random.split(rng, 5)
    s = 1.0 / math.sqrt(d)
    so = 1.0 / math.sqrt(ap.n_heads * ap.head_dim)
    p = {
        "wq": _normal(ks[0], (L, d, ap.n_heads, ap.head_dim), s, dtype),
        "wk": _normal(ks[1], (L, d, ap.n_kv, ap.head_dim), s, dtype),
        "wv": _normal(ks[2], (L, d, ap.n_kv, ap.head_dim), s, dtype),
        "wo": _normal(ks[3], (L, ap.n_heads, ap.head_dim, d), so, dtype),
    }
    if ap.bias:
        p["bq"] = jnp.zeros((L, ap.n_heads, ap.head_dim), dtype)
        p["bk"] = jnp.zeros((L, ap.n_kv, ap.head_dim), dtype)
        p["bv"] = jnp.zeros((L, ap.n_kv, ap.head_dim), dtype)
    return p


def attention_pspecs(policy: Policy, d: int, ap: AttnParams) -> dict:
    h = policy.tp(ap.n_heads)
    kv = policy.tp(ap.n_kv)
    eq = policy.fsdp(d, has_tp=h is not None)
    ekv = policy.fsdp(d, has_tp=kv is not None)
    p = {
        "wq": P(None, eq, h, None),
        "wk": P(None, ekv, kv, None),
        "wv": P(None, ekv, kv, None),
        "wo": P(None, h, None, eq),
    }
    if ap.bias:
        p["bq"] = P(None, h, None)
        p["bk"] = P(None, kv, None)
        p["bv"] = P(None, kv, None)
    return p


def _project_qkv(p: dict, x: jax.Array, ap: AttnParams, positions: jax.Array):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if ap.bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    if ap.use_rope:
        q = rope(q, positions, ap.rope_theta)
        k = rope(k, positions, ap.rope_theta)
    return q, k, v


def _repeat_kv(k: jax.Array, n_heads: int) -> jax.Array:
    """(B,S,Kv,D) -> (B,S,H,D), kv head h serves q heads [h*rep, (h+1)*rep)."""
    b, s, kv, d = k.shape
    rep = n_heads // kv
    if rep == 1:
        return k
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, kv, rep, d)).reshape(
        b, s, n_heads, d
    )


def _mask_bias(q_pos, k_pos, causal: bool, window: int | None) -> jax.Array:
    """(Q, K) additive fp32 mask."""
    ok = jnp.ones((q_pos.shape[0], k_pos.shape[0]), dtype=bool)
    if causal:
        ok &= k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        ok &= k_pos[None, :] > q_pos[:, None] - window
    return jnp.where(ok, 0.0, -1e30).astype(jnp.float32)


def attention(
    p: dict,
    x: jax.Array,  # (B, S, d)
    ap: AttnParams,
    policy: Policy,
    positions: jax.Array | None = None,  # (S,)
    kv_source: jax.Array | None = None,  # encoder states for cross attention
    return_kv: bool = False,  # prefill: also return unrepeated K/V
):
    """Full-sequence attention (training / prefill): the flash kernel
    where :func:`flash_applies` and the program is lowered for a TPU,
    else chunked over queries."""
    b, s, d = x.shape
    if positions is None:
        positions = jnp.arange(s)
    strat = attn_strategy(ap, policy, s)
    batch = policy.batch_spec(b)
    tp = policy.tp_axis

    if ap.cross:
        src = kv_source
        src_pos = jnp.arange(src.shape[1])
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
        k = jnp.einsum("bsd,dhk->bshk", src, p["wk"])
        v = jnp.einsum("bsd,dhk->bshk", src, p["wv"])
    else:
        q, k, v = _project_qkv(p, x, ap, positions)
        src_pos = positions
    kv_out = (k, v) if return_kv else None
    flash = flash_applies(ap, policy, s)
    default_registry().counter(
        "attention_calls_total", path="flash" if flash else "chunked"
    ).inc()

    if strat == "seq":
        out = _context_parallel_attention(q, k, v, positions, src_pos, ap, policy)
    else:
        if strat == "heads":
            k = _repeat_kv(k, ap.n_heads)
            v = _repeat_kv(v, ap.n_heads)
            spec = P(batch, None, tp, None)
        else:
            spec = P(batch, None, None, None)
        q, k, v = wsc(q, spec), wsc(k, spec), wsc(v, spec)

        def chunked(q, k, v):
            return _chunked_attention(
                q, k, v, positions, src_pos, ap,
                block_spec=spec, out_spec=spec, grouped=False,
                unroll=policy.unroll,
            )

        if flash:
            # the kernel where the program is lowered for a TPU; the
            # chunked path on every other platform
            out = jax.lax.platform_dependent(
                q, k, v,
                tpu=partial(
                    attention_op, causal=ap.causal, softcap=ap.softcap,
                    interpret=False,
                ),
                default=chunked,
            )
        else:
            out = chunked(q, k, v)
        out = wsc(out, spec)

    y = jnp.einsum("bshd,hdm->bsm", out, p["wo"])
    y = wsc(y, P(batch, None, None))
    if return_kv:
        return y, kv_out[0], kv_out[1]
    return y


def flash_applies(ap: AttnParams, policy: Policy, seq_len: int) -> bool:
    """Whether full-sequence attention of this shape takes the flash
    kernel when lowered for a TPU: causal or full self-attention with no
    window, sequence and head dim multiples of 128, on operands no mesh
    shards. Positions are taken to ascend one by one, as every caller
    passes them."""
    mesh = jax.sharding.get_abstract_mesh()
    return (
        not ap.cross
        and ap.window is None
        and seq_len % 128 == 0
        and ap.head_dim % 128 == 0
        and policy.size(tuple(policy.mesh_axes)) == 1
        and (mesh.empty or mesh.size == 1)
    )


def _chunked_attention(q, k, v, q_pos, k_pos, ap: AttnParams, *,
                       block_spec=None, out_spec=None, grouped: bool,
                       pos_offset=None, unroll: bool = False):
    """lax.map over query chunks; scores never exceed (B, H, qb, Sk).

    ``grouped`` keeps KV unrepeated and runs the GQA einsum with a group
    dim (used inside the context-parallel shard_map where per-shard KV
    replication would waste memory).
    """
    b, s, h, d = q.shape
    scale = 1.0 / math.sqrt(ap.head_dim)
    sk = k.shape[1]
    qb = min(ap.q_block, s)
    if s % qb != 0:
        qb = s
    nb = s // qb
    causal = ap.causal and not ap.cross
    sliced_window = ap.window is not None and not ap.cross and ap.window + qb < sk
    span = min(ap.window + qb, sk) if ap.window is not None else sk
    gq = ap.n_heads // ap.n_kv

    def block(i):
        qs = i * qb
        qi = jax.lax.dynamic_slice_in_dim(q, qs, qb, axis=1)
        if block_spec is not None:
            qi = wsc(qi, block_spec)
        qp = jax.lax.dynamic_slice_in_dim(q_pos, qs, qb, axis=0)
        if pos_offset is not None:
            qp = qp + pos_offset
        if sliced_window:
            # static-size K/V span ending at this block's last query
            last_q = (qp[-1] if pos_offset is None else qp[-1])
            ks = jnp.clip(last_q + 1 - span, 0, sk - span)
            ki = jax.lax.dynamic_slice_in_dim(k, ks, span, axis=1)
            vi = jax.lax.dynamic_slice_in_dim(v, ks, span, axis=1)
            kp = jax.lax.dynamic_slice_in_dim(k_pos, ks, span, axis=0)
        else:
            ki, vi, kp = k, v, k_pos
        bias = _mask_bias(qp, kp, causal, ap.window)
        if grouped and gq > 1:
            qg = qi.reshape(qi.shape[:2] + (ap.n_kv, gq, ap.head_dim))
            sc = jnp.einsum("bqkgd,bskd->bkgqs", qg, ki).astype(jnp.float32) * scale
            sc = softcap(sc, ap.softcap) if ap.softcap else sc
            sc = sc + bias[None, None, None]
            w = jax.nn.softmax(sc, axis=-1).astype(qi.dtype)
            ob = jnp.einsum("bkgqs,bskd->bqkgd", w, vi).reshape(qi.shape)
        else:
            ki2 = _repeat_kv(ki, ap.n_heads) if ki.shape[2] != ap.n_heads else ki
            vi2 = _repeat_kv(vi, ap.n_heads) if vi.shape[2] != ap.n_heads else vi
            sc = jnp.einsum("bqhd,bkhd->bhqk", qi, ki2).astype(jnp.float32) * scale
            sc = softcap(sc, ap.softcap) if ap.softcap else sc
            sc = sc + bias[None, None]
            w = jax.nn.softmax(sc, axis=-1).astype(qi.dtype)
            ob = jnp.einsum("bhqk,bkhd->bqhd", w, vi2)
        if block_spec is not None:
            ob = wsc(ob, block_spec)
        return ob

    if nb == 1:
        return block(jnp.int32(0))
    _, outs = jax.lax.scan(
        lambda c, i: (c, block(i)), 0, jnp.arange(nb),
        unroll=True if unroll else 1,
    )  # (nb, B, qb, H, D)
    return jnp.moveaxis(outs, 0, 1).reshape(b, s, h, d)


def _context_parallel_attention(q, k, v, positions, src_pos, ap: AttnParams, policy: Policy):
    """Context parallelism via shard_map: the query sequence is sharded
    over the model axis; K/V are (explicitly) all-gathered once per layer.

    Used when head counts don't divide the model axis (qwen2 28H, arctic
    56H, gemma2 8H, whisper 6H). Expressing this through the SPMD
    partitioner instead breaks at the query-chunking reshape (the
    partitioner falls back to fully-replicated fp32 Q/K/V — an 8.6 GB/dev
    regression measured in EXPERIMENTS.md §Perf it-1).
    """
    from jax.experimental.shard_map import shard_map

    mesh = getattr(policy, "_mesh_obj", None)
    b, s, h, d = q.shape
    batch = policy.batch_spec(b)
    tp = policy.tp_axis
    if mesh is None:  # no mesh: plain chunked attention (test path)
        return _chunked_attention(
            q, k, v, positions, src_pos, ap, grouped=ap.n_kv != ap.n_heads,
            unroll=policy.unroll,
        )

    cross = ap.cross

    def body(q_l, k_l, v_l, qpos_l, kpos):
        # q_l: (B_l, S/tp, H, D); k_l/v_l: cross ? (B_l, S_src, Kv, D)
        #                                        : (B_l, S/tp, Kv, D)
        if not cross:
            k_g = jax.lax.all_gather(k_l, tp, axis=1, tiled=True)
            v_g = jax.lax.all_gather(v_l, tp, axis=1, tiled=True)
        else:
            k_g, v_g = k_l, v_l
        return _chunked_attention(
            q_l, k_g, v_g, qpos_l[0], kpos[0], ap,
            grouped=ap.n_kv != ap.n_heads, unroll=policy.unroll,
        )

    qpos = positions[None].astype(jnp.int32)  # (1, S) -> shard over tp
    kpos = src_pos[None].astype(jnp.int32)
    kv_in = P(batch, None, None, None) if cross else P(batch, tp, None, None)
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(batch, tp, None, None),
            kv_in,
            kv_in,
            P(None, tp),
            P(None, None),
        ),
        out_specs=P(batch, tp, None, None),
        check_rep=False,
    )(q, k, v, qpos, kpos)


# ------------------------------------------------------------- decode (1-tok)
def decode_attention(
    p: dict,
    x: jax.Array,  # (B, 1, d)
    cache_k: jax.Array,  # (B, S_cache, Kv, D)
    cache_v: jax.Array,
    cache_pos: jax.Array,  # int32 count of tokens already in cache:
    #                        scalar (whole batch in lockstep) or (B,)
    #                        per-row (continuous batching: each slot at
    #                        its own position)
    ap: AttnParams,
    policy: Policy,
    *,
    ring: bool = False,  # cache is a window-sized ring buffer (local layers)
    cache_seq_spec=None,  # mesh axes sharding the cache seq dim, if any
):
    """One-token decode against a KV cache; returns (out, new_k, new_v).

    With a seq-sharded cache the softmax over the sharded key axis lowers to
    a local masked reduce + a tiny cross-shard reduction — flash-decode's
    schedule, derived by the SPMD partitioner.

    A vector ``cache_pos`` switches every position-dependent step to
    per-row form: RoPE rotates each row by its own position, the new K/V
    lands at each row's own slot (one scatter instead of a slice update),
    and the validity/window masks become (B, S). The flash-decode
    shard_map path stays scalar-only (its predicated slot write assumes
    one slot per step); per-row decode falls through to the plain path.
    """
    b, one, d = x.shape
    s_cache = cache_k.shape[1]
    pos = jnp.asarray(cache_pos, jnp.int32)
    per_row = pos.ndim == 1
    positions = pos[:, None] if per_row else jnp.reshape(pos, (1,))
    batch = policy.batch_spec(b)
    cache_spec = P(batch, cache_seq_spec, None, None)

    if ap.cross:
        # K/V are the (precomputed) encoder projections: no update, no mask.
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
        kf = _repeat_kv(cache_k, ap.n_heads).astype(q.dtype)
        vf = _repeat_kv(cache_v, ap.n_heads).astype(q.dtype)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, kf).astype(jnp.float32)
        sc = sc / math.sqrt(ap.head_dim)
        w = jax.nn.softmax(sc, axis=-1).astype(q.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", w, vf)
        y = jnp.einsum("bshd,hdm->bsm", out, p["wo"])
        return y, cache_k, cache_v

    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    kn = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    vn = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if ap.bias:
        q, kn, vn = q + p["bq"], kn + p["bk"], vn + p["bv"]
    if ap.use_rope:
        q = rope(q, positions, ap.rope_theta)
        kn = rope(kn, positions, ap.rope_theta)

    mesh = getattr(policy, "_mesh_obj", None)
    if cache_seq_spec is not None and mesh is not None and not per_row:
        out, cache_k, cache_v = _flash_decode(
            q, kn, vn, cache_k, cache_v, pos, ap, policy, mesh,
            ring=ring, seq_axes=cache_seq_spec,
        )
    else:
        slot = jnp.mod(pos, s_cache) if ring else pos
        if per_row:
            rows = jnp.arange(b)
            cache_k = cache_k.at[rows, slot].set(kn[:, 0].astype(cache_k.dtype))
            cache_v = cache_v.at[rows, slot].set(vn[:, 0].astype(cache_v.dtype))
        else:
            cache_k = jax.lax.dynamic_update_slice_in_dim(
                cache_k, kn.astype(cache_k.dtype), slot, axis=1
            )
            cache_v = jax.lax.dynamic_update_slice_in_dim(
                cache_v, vn.astype(cache_v.dtype), slot, axis=1
            )
        cache_k = wsc(cache_k, cache_spec)
        cache_v = wsc(cache_v, cache_spec)
        kf = _repeat_kv(cache_k, ap.n_heads).astype(q.dtype)
        vf = _repeat_kv(cache_v, ap.n_heads).astype(q.dtype)
        rep_spec = P(batch, None, policy.tp(ap.n_heads), None)
        kf = wsc(kf, rep_spec)
        vf = wsc(vf, rep_spec)
        scale = 1.0 / math.sqrt(ap.head_dim)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, kf).astype(jnp.float32) * scale
        sc = softcap(sc, ap.softcap) if ap.softcap else sc
        valid = _decode_valid(pos, s_cache, ring=ring, window=ap.window)
        sc = jnp.where(
            valid[:, None, None, :] if per_row else valid[None, None, None, :],
            sc, -1e30,
        )
        w = jax.nn.softmax(sc, axis=-1).astype(q.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", w, vf)
    y = jnp.einsum("bshd,hdm->bsm", out, p["wo"])
    return y, cache_k, cache_v


def _decode_valid(pos, s_cache: int, *, ring: bool, window: int | None):
    """Cache-slot validity for one-token decode: slots holding positions
    0..pos (inclusive of the token just written), intersected with the
    sliding window for non-ring window layers. Scalar ``pos`` → (S,);
    vector ``pos`` (B,) → per-row (B, S) windows."""
    idx = jnp.arange(s_cache)
    if pos.ndim == 1:
        valid = idx[None, :] <= pos[:, None]
        if not ring and window is not None:
            valid &= idx[None, :] > pos[:, None] - window
        return valid
    valid = idx <= pos
    if not ring and window is not None:
        valid &= idx > pos - window
    return valid


def paged_decode_attention(
    p: dict,
    x: jax.Array,  # (B, 1, d)
    cache_k: jax.Array,  # (N_blocks, block, Kv, D) — physical block pool,
    #                       shared by every slot (no batch dim)
    cache_v: jax.Array,
    cache_pos: jax.Array,  # (B,) int32 per-row token counts
    block_table: jax.Array,  # (B, max_blocks) int32 physical block ids;
    #                          virtual position p of row b lives at
    #                          (block_table[b, p // block], p % block)
    ap: AttnParams,
    policy: Policy,
):
    """One-token decode against a paged (block-table) KV cache.

    Rows with different prompt lengths share one physical pool without
    fragmentation: each row owns ceil(len / block) blocks, mapped through
    its block-table row. The new token's K/V is scattered to the owning
    (block, offset) pair; reads gather each row's table into a contiguous
    (B, max_blocks * block) view and run the same per-row masked softmax
    as the plain decode path — data beyond a row's ``cache_pos`` (stale
    freed-block contents included) is masked to -1e30, so block recycling
    needs no zeroing. Idle rows must point their table at the reserved
    scratch block 0 so their (discarded) writes never land in a live
    row's blocks. Returns (out, new_k, new_v).
    """
    b = x.shape[0]
    n_phys, blk_sz, n_kv, hd = cache_k.shape
    max_blocks = block_table.shape[1]
    pos = jnp.asarray(cache_pos, jnp.int32)
    positions = pos[:, None]  # (B, 1)

    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    kn = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    vn = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if ap.bias:
        q, kn, vn = q + p["bq"], kn + p["bk"], vn + p["bv"]
    if ap.use_rope:
        q = rope(q, positions, ap.rope_theta)
        kn = rope(kn, positions, ap.rope_theta)

    # scatter the new token: row r -> (table[r, pos_r // blk], pos_r % blk).
    # Rows whose pos drifted past their table (recycled slots) clamp to
    # the last table entry — an all-zeros table routes them to scratch.
    rows = jnp.arange(b)
    tbl_idx = jnp.minimum(pos // blk_sz, max_blocks - 1)
    blk = block_table[rows, tbl_idx]
    off = jnp.mod(pos, blk_sz)
    cache_k = cache_k.at[blk, off].set(kn[:, 0].astype(cache_k.dtype))
    cache_v = cache_v.at[blk, off].set(vn[:, 0].astype(cache_v.dtype))

    # gather each row's blocks into a contiguous virtual sequence
    s_virt = max_blocks * blk_sz
    kf = cache_k[block_table].reshape(b, s_virt, n_kv, hd)
    vf = cache_v[block_table].reshape(b, s_virt, n_kv, hd)
    kf = _repeat_kv(kf, ap.n_heads).astype(q.dtype)
    vf = _repeat_kv(vf, ap.n_heads).astype(q.dtype)
    scale = 1.0 / math.sqrt(ap.head_dim)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, kf).astype(jnp.float32) * scale
    sc = softcap(sc, ap.softcap) if ap.softcap else sc
    valid = _decode_valid(pos, s_virt, ring=False, window=ap.window)
    sc = jnp.where(valid[:, None, None, :], sc, -1e30)
    w = jax.nn.softmax(sc, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", w, vf)
    y = jnp.einsum("bshd,hdm->bsm", out, p["wo"])
    return y, cache_k, cache_v


def _flash_decode(
    q, kn, vn, cache_k, cache_v, pos, ap: AttnParams, policy: Policy, mesh,
    *, ring: bool, seq_axes,
):
    """Flash-decode: the KV cache's sequence dim is sharded over ``seq_axes``
    (typically the model axis, plus data when batch < DP degree); each shard
    streams only its cache slice and partial softmax statistics are merged
    with a log-sum-exp psum — the collective is O(B·H·D), not O(S).

    The new token's K/V is written by exactly the shard owning its slot
    (predicated dynamic_update_slice). Queries/heads stay replicated across
    ``seq_axes`` — decode attention is cache-bandwidth-bound, and this keeps
    head counts free of divisibility constraints.
    """
    from jax.experimental.shard_map import shard_map

    b, one, h_, d_ = q.shape
    s_cache = cache_k.shape[1]
    batch = policy.batch_spec(b)
    axes = seq_axes if isinstance(seq_axes, tuple) else (seq_axes,)
    nshard = policy.size(axes)
    s_loc = s_cache // nshard
    scale = 1.0 / math.sqrt(ap.head_dim)
    gq = ap.n_heads // ap.n_kv

    def body(q_l, kn_l, vn_l, ck, cv, pos_l):
        bl = q_l.shape[0]  # local batch (sharded when batch covers data axes)
        # shard coordinate along the (possibly composite) seq axes
        idx = jnp.int32(0)
        for a in axes:
            idx = idx * policy.mesh_axes[a] + jax.lax.axis_index(a)
        offset = idx * s_loc
        pos_s = pos_l[0]
        slot = jnp.mod(pos_s, s_cache) if ring else pos_s
        lslot = jnp.clip(slot - offset, 0, s_loc - 1)
        in_range = (slot >= offset) & (slot < offset + s_loc)
        # predicated in-place write: out-of-range shards rewrite the
        # current value (a full-cache select would double the cache temps)
        cur_k = jax.lax.dynamic_slice_in_dim(ck, lslot, 1, axis=1)
        cur_v = jax.lax.dynamic_slice_in_dim(cv, lslot, 1, axis=1)
        up_k = jnp.where(in_range, kn_l.astype(ck.dtype), cur_k)
        up_v = jnp.where(in_range, vn_l.astype(cv.dtype), cur_v)
        ck = jax.lax.dynamic_update_slice_in_dim(ck, up_k, lslot, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cv, up_v, lslot, axis=1)

        qg = q_l.reshape(bl, 1, ap.n_kv, gq, ap.head_dim)
        sc = jnp.einsum(
            "bqkgd,bskd->bkgqs", qg, ck.astype(q_l.dtype)
        ).astype(jnp.float32) * scale  # (B, K, G, 1, S_loc)
        sc = softcap(sc, ap.softcap) if ap.softcap else sc
        gidx = offset + jnp.arange(s_loc)
        valid = gidx <= pos_s
        if not ring and ap.window is not None:
            valid &= gidx > pos_s - ap.window
        sc = jnp.where(valid[None, None, None, None, :], sc, -jnp.inf)
        m_loc = jnp.max(sc, axis=-1, keepdims=True)  # (B,K,G,1,1)
        m_safe = jnp.where(jnp.isfinite(m_loc), m_loc, 0.0)
        p_ = jnp.where(jnp.isfinite(sc), jnp.exp(sc - m_safe), 0.0)
        l_loc = jnp.sum(p_, axis=-1, keepdims=True)
        o_loc = jnp.einsum(
            "bkgqs,bskd->bkgqd", p_.astype(q_l.dtype), cv.astype(q_l.dtype)
        )
        # merge across shards
        m_g = jax.lax.pmax(m_safe, axes)
        corr = jnp.exp(m_safe - m_g)
        l_g = jax.lax.psum(l_loc * corr, axes)
        o_g = jax.lax.psum(o_loc * corr.astype(o_loc.dtype), axes)
        out = (o_g / jnp.maximum(l_g, 1e-30).astype(o_loc.dtype)).astype(q_l.dtype)
        return out.reshape(bl, 1, ap.n_heads, ap.head_dim), ck, cv

    cache_in = P(batch, seq_axes, None, None)
    rep = P(batch, None, None, None)
    out, ck, cv = shard_map(
        body,
        mesh=mesh,
        in_specs=(rep, rep, rep, cache_in, cache_in, P(None)),
        out_specs=(rep, cache_in, cache_in),
        check_rep=False,
    )(q, kn, vn, cache_k, cache_v, jnp.reshape(pos, (1,)))
    return out, ck, cv
