"""Plain float32 reference of a dense GQA decoder (the Yi / LLaMA family).

Straight ``jax.numpy``: no kernels, no cache, no batching tricks, every
matrix product at ``Precision.HIGHEST``. It imports nothing of the
program and takes nothing the program made: the weights are drawn again
from the run's seed, in the order and at the scales the published
initialisation of this repository's model family uses, so that both
sides hold the same numbers.

Departures from the published Yi description (arXiv:2403.04652, hf
``01-ai/Yi-6B``), each shared with the program under test:

- RoPE pairs adjacent dimensions (GPT-J layout) where the published
  checkpoints rotate the two halves of a head (NeoX layout). The two are
  the same model up to a fixed permutation of the query and key columns.
- Weights are random draws, not the trained checkpoint.
- Parameters are stored in the configuration's ``param_dtype``
  (bfloat16) between optimizer steps, as the configuration states; every
  operation on them is float32.

``fp8=True`` computes every matrix product from float8 (e4m3) operands,
weights scaled per output column and activations per row: the control
one precision step below the configuration's bfloat16.

A configuration names this module as its ``reference``; its program is
``bench/lib/dense_program.py``. The harness takes from it: ``dims`` of a
configuration; the weights (``init_params``); the served-token gaps
(``served_gaps``) and the training readings (``train_readings`` with
``AdamW``); and the operations and bytes that these equations need
(section "counts"), which the metric readers get as ``rd["counts"]``.
Another family's reference may build on the pieces here, ``adamw_readings``
among them.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib.flops import DTYPE_BYTES

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0

# leaves of the reference tree, by the name each has in the comparison
LAYER_LEAVES = ("norm1", "wq", "wk", "wv", "wo", "norm2", "w_in", "w_gate", "w_out")
LEAVES = ("embed", "final_norm", "unembed") + LAYER_LEAVES


@dataclasses.dataclass(frozen=True)
class Dims:
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float

    @property
    def vocab_padded(self) -> int:
        # the program's unembedding is drawn over the vocabulary rounded up
        # to 128 rows; the draw has to have the same shape to give the
        # same numbers
        return -(-self.vocab // 128) * 128

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        """From a configuration file's published keys (hf names)."""
        heads = int(cfg["num_attention_heads"])
        return cls(
            d_model=int(cfg["hidden_size"]),
            n_layers=int(cfg["num_hidden_layers"]),
            n_heads=heads,
            n_kv_heads=int(cfg["num_key_value_heads"]),
            head_dim=int(cfg.get("head_dim", cfg["hidden_size"] // heads)),
            d_ff=int(cfg["intermediate_size"]),
            vocab=int(cfg["vocab_size"]),
            rope_theta=float(cfg["rope_theta"]),
            norm_eps=float(cfg["rms_norm_eps"]),
        )


def dims(config: dict) -> Dims:
    return Dims.from_config(config)


# ------------------------------------------------------------------ weights
def _normal(key, shape, scale, dtype):
    return (scale * jax.random.normal(key, shape, dtype=jnp.float32)).astype(dtype)


def init_params(dims: Dims, key, dtype=jnp.bfloat16) -> dict:
    """The model's weights from ``key``: the same draws, keys and scales
    as the family's initialisation (embedding, blocks stacked over depth,
    unembedding), stored in ``dtype``."""
    d, vp = dims.d_model, dims.vocab_padded
    ks = jax.random.split(key, 8)
    s_in = 1.0 / math.sqrt(d)
    return {
        "embed": _normal(ks[0], (vp, d), s_in, dtype),
        "final_norm": jnp.ones((d,), dtype),
        "unembed": _normal(ks[3], (d, vp), s_in, dtype),
        "layers": layer_params(dims, jax.random.fold_in(ks[1], 0), dims.n_layers, dtype),
    }


def layer_params(dims: Dims, key, n: int, dtype) -> dict:
    """A stack of ``n`` layers' weights from the key of its slot."""
    d, H, K, hd, f = dims.d_model, dims.n_heads, dims.n_kv_heads, dims.head_dim, dims.d_ff
    kb = jax.random.split(key, 8)
    ka = jax.random.split(kb[0], 5)
    km = jax.random.split(kb[2], 3)
    s_in, s_o, s_ff = 1.0 / math.sqrt(d), 1.0 / math.sqrt(H * hd), 1.0 / math.sqrt(f)
    return {
        "norm1": jnp.ones((n, d), dtype),
        "wq": _normal(ka[0], (n, d, H, hd), s_in, dtype),
        "wk": _normal(ka[1], (n, d, K, hd), s_in, dtype),
        "wv": _normal(ka[2], (n, d, K, hd), s_in, dtype),
        "wo": _normal(ka[3], (n, H, hd, d), s_o, dtype),
        "norm2": jnp.ones((n, d), dtype),
        "w_in": _normal(km[0], (n, d, f), s_in, dtype),
        "w_gate": _normal(km[2], (n, d, f), s_in, dtype),
        "w_out": _normal(km[1], (n, f, d), s_ff, dtype),
    }


def leaf(params: dict, name: str):
    return params["layers"][name] if name in LAYER_LEAVES else params[name]


# ---------------------------------------------------------------- numerics
def _fake_f8(x, axis):
    """x rounded to e4m3 with one scale per slice along ``axis``. The
    gradient passes straight through in float32: a cast's own gradient
    would round the cotangents to e4m3 unscaled, and most would vanish."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    q = (x / scale).astype(F8).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def _mm(spec: str, x, w, fp8: bool, w_in_axes: tuple[int, ...]):
    """einsum of activations ``x`` with weights ``w``; with ``fp8`` both
    operands go through e4m3 first (``x`` per row over its last axis,
    ``w`` per output column over its input axes)."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if fp8:
        x = _fake_f8(x, -1)
        w = _fake_f8(w, w_in_axes)
    return jnp.einsum(spec, x, w, precision=HIGHEST)


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _rope(x, positions, theta):
    """Adjacent-pair rotary embedding; x (B, T, heads, hd), positions (T,)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs  # (T, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    xp = x.reshape(x.shape[:-1] + (half, 2))
    x0, x1 = xp[..., 0], xp[..., 1]
    return jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos], -1).reshape(x.shape)


def _layer(dims: Dims, x, lp, positions, fp8: bool, mask=None):
    """One block; ``mask`` (T, S) says which keys each query sees, causal
    where it is None."""
    b, t, _ = x.shape
    K, G, hd = dims.n_kv_heads, dims.n_heads // dims.n_kv_heads, dims.head_dim
    h = _rms(x, lp["norm1"], dims.norm_eps)
    q = _mm("btd,dhk->bthk", h, lp["wq"], fp8, (0,))
    k = _mm("btd,dhk->bthk", h, lp["wk"], fp8, (0,))
    v = _mm("btd,dhk->bthk", h, lp["wv"], fp8, (0,))
    q = _rope(q, positions, dims.rope_theta).reshape(b, t, K, G, hd)
    k = _rope(k, positions, dims.rope_theta)
    s = jnp.einsum("btkgd,bskd->bkgts", q, k, precision=HIGHEST) / math.sqrt(hd)
    if mask is None:
        mask = positions[None, :] <= positions[:, None]  # (T, S)
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgts,bskd->btkgd", p, v, precision=HIGHEST)
    o = o.reshape(b, t, dims.n_heads, hd)
    x = x + _mm("bthk,hkd->btd", o, lp["wo"], fp8, (0, 1))
    h = _rms(x, lp["norm2"], dims.norm_eps)
    gate = _mm("btd,df->btf", h, lp["w_gate"], fp8, (0,))
    up = _mm("btd,df->btf", h, lp["w_in"], fp8, (0,))
    return x + _mm("btf,fd->btd", jax.nn.silu(gate) * up, lp["w_out"], fp8, (0,))


def hidden(params: dict, tokens, dims: Dims, fp8: bool = False):
    """Final-normed hidden states (B, T, d) of ``tokens`` (B, T), layer by
    layer under a scan, so one layer's float32 weights live at a time."""
    positions = jnp.arange(tokens.shape[1])
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)

    def body(x, lp):
        return _layer(dims, x, lp, positions, fp8), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    return _rms(x, params["final_norm"], dims.norm_eps)


def logits_at(params: dict, tokens, positions, dims: Dims, fp8: bool = False):
    """Logits (B, P, V) at ``positions`` (B, P) of ``tokens`` (B, T)."""
    h = hidden(params, tokens, dims, fp8)
    h = jnp.take_along_axis(h, positions[:, :, None], axis=1)
    return _mm("bpd,dv->bpv", h, params["unembed"], fp8, (0,))


def loss(params: dict, tokens, dims: Dims, fp8: bool = False):
    """Mean next-token cross-entropy over every position of ``tokens``."""
    return next_token_loss(hidden(params, tokens, dims, fp8), params["unembed"], tokens, fp8)


def next_token_loss(h, unembed, tokens, fp8: bool):
    """Mean cross-entropy of the logits from final-normed hidden states
    ``h`` (B, T, d) of ``tokens`` (B, T) against each next token."""
    logits = _mm("btd,dv->btv", h[:, :-1], unembed, fp8, (0,))
    labels = tokens[:, 1:]
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)


# ---------------------------------------------------------------- serving
def served_gaps(params, dims: Dims, requests, t_max: int, p_max: int) -> list[np.ndarray]:
    """For each ``(prompt, served)`` pair, the gap by which each served
    token's reference logit lies below the reference's best at its
    position (teacher-forced on the served tokens). Rows run one at a time
    at one padded shape, so one program serves every request."""
    f32 = jax.jit(lambda p, t, q: logits_at(p, t, q, dims))
    out = []
    for prompt, served in requests:
        n_p, n_s = len(prompt), len(served)
        tokens = np.zeros((1, t_max), np.int32)
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        tokens[0, : len(seq)] = seq
        pos = np.full((1, p_max), n_p - 1, np.int32)
        pos[0, :n_s] = np.arange(n_p - 1, n_p - 1 + n_s)
        ref = np.asarray(f32(params, tokens, pos))[0, :n_s]
        best = ref.max(-1)
        out.append(best - ref[np.arange(n_s), np.asarray(served)])
    return out


# ---------------------------------------------------------------- training
@dataclasses.dataclass(frozen=True)
class AdamW:
    lr: float
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.01
    max_grad_norm: float = 1.0


# gradients are taken a group of leaves at a time, so that float32
# weights, gradients and both moments of the whole model never have to
# live on the chip together; the largest group first, while no updated
# leaf is held beside the old ones yet
GROUPS = (LAYER_LEAVES, ("embed",), ("unembed", "final_norm"))


def _merge(params, part):
    out = {k: v for k, v in params.items() if k != "layers"}
    out["layers"] = dict(params["layers"])
    for n, v in part.items():
        if n in LAYER_LEAVES:
            out["layers"][n] = v
        else:
            out[n] = v
    return out


@dataclasses.dataclass(frozen=True)
class Tree:
    """How the training reference reaches a family's weights: each leaf
    by the name the comparison gives it, and the groups of leaves whose
    gradients are taken together."""
    leaves: tuple[str, ...]
    groups: tuple[tuple[str, ...], ...]
    leaf: Callable  # (params, name) -> that leaf
    merge: Callable  # (params, {name: leaf}) -> params with those leaves replaced


DENSE = Tree(LEAVES, GROUPS, leaf, _merge)


def train_readings(dims: Dims, key, batches, opt: AdamW, *,
                   param_dtype=jnp.bfloat16, fp8: bool = False) -> dict:
    """Follow ``len(batches)`` AdamW steps from the seeded weights
    (``adamw_readings``)."""
    return adamw_readings(DENSE, lambda k: init_params(dims, k, param_dtype),
                          lambda p, tokens: loss(p, tokens, dims, fp8), key, batches, opt)


def adamw_readings(tree: Tree, init: Callable, loss_fn: Callable, key, batches,
                   opt: AdamW) -> dict:
    """Follow ``len(batches)`` AdamW steps from the weights ``init(key)``
    under ``loss_fn(params, tokens)``.

    Returns each step's loss, the per-leaf norm of the first (clipped)
    gradient as the optimizer receives it, and the per-leaf norm of the
    parameters' change over all steps, as the last step leaves them
    stored."""
    params = jax.jit(init)(key)
    # both moments wait on the host between updates: float32 moments of
    # the whole model beside its weights, gradients and activations would
    # not fit one chip
    m = {n: np.zeros(tree.leaf(params, n).shape, np.float32) for n in tree.leaves}
    v = {n: np.zeros(tree.leaf(params, n).shape, np.float32) for n in tree.leaves}

    def split(params, group):
        return {n: tree.leaf(params, n) for n in group}

    def grads_of(group):
        def f(part32, params, tokens):
            return loss_fn(tree.merge(params, part32), tokens)

        vg = jax.value_and_grad(f)

        def run(params, tokens):
            part32 = {n: x.astype(jnp.float32) for n, x in split(params, group).items()}
            return vg(part32, params, tokens)

        return run

    @jax.jit
    def sq_norms(g):
        return {n: jnp.sum(jnp.square(x)) for n, x in g.items()}

    @partial(jax.jit, donate_argnums=(2, 3))
    def adam(p, g, m, v, scale, t):
        bc1, bc2 = 1 - opt.b1 ** t, 1 - opt.b2 ** t
        new = {}
        for n in g:
            gn = g[n] * scale
            mn = opt.b1 * m[n] + (1 - opt.b1) * gn
            vn = opt.b2 * v[n] + (1 - opt.b2) * gn * gn
            pf = p[n].astype(jnp.float32)
            u = (mn / bc1) / (jnp.sqrt(vn / bc2) + opt.eps) + opt.weight_decay * pf
            new[n] = ((pf - opt.lr * u).astype(p[n].dtype), mn, vn)
        return new

    group_fns = [(g, jax.jit(grads_of(g))) for g in tree.groups]
    losses, first_grad = [], {}
    for t, tokens in enumerate(batches, start=1):
        tokens = jnp.asarray(tokens, jnp.int32)
        total, loss_t = 0.0, None
        for group, fn in group_fns:  # pass 1: the global norm, for clipping
            lv, g = fn(params, tokens)
            loss_t = float(lv)
            total += sum(float(s) for s in sq_norms(g).values())
            del g
        scale = min(1.0, opt.max_grad_norm / (math.sqrt(total) + 1e-9))
        losses.append(loss_t)
        updated = {}
        for group, fn in group_fns:  # pass 2: every group's update from the old weights
            _, g = fn(params, tokens)
            if t == 1:
                first_grad.update(
                    {n: math.sqrt(float(s)) * scale for n, s in sq_norms(g).items()}
                )
            new = adam(split(params, group), g, {n: jnp.asarray(m[n]) for n in group},
                       {n: jnp.asarray(v[n]) for n in group}, jnp.float32(scale), jnp.float32(t))
            del g
            for n in group:
                updated[n] = new[n][0]
                m[n], v[n] = np.asarray(new[n][1]), np.asarray(new[n][2])
            del new
        params = tree.merge(params, updated)
    del m, v
    start = jax.jit(init)(key)
    delta = jax.jit(lambda a, b: {
        n: jnp.sqrt(jnp.sum(jnp.square(tree.leaf(a, n).astype(jnp.float32)
                                       - tree.leaf(b, n).astype(jnp.float32))))
        for n in tree.leaves
    })(params, start)
    return {
        "losses": losses,
        "grad_norms": first_grad,
        "delta_norms": {n: float(x) for n, x in delta.items()},
    }


# ------------------------------------------------------------------ counts
# Operations and bytes that the algorithm needs, from the shapes alone:
# matrix products at 2 operations per multiply-add, causal attention over
# the positions a query may see, and the bytes a step cannot avoid moving
# (each weight once, each live cached key and value once, in the
# configuration's dtypes). What an implementation adds on top (padding,
# masked work, copies) is not counted.
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


def train_flops_per_sequence(dm: Dims, seq: int) -> int:
    """Forward and backward (3x the forward) of one ``seq``-token
    sequence: the layers on every position, causal attention, the
    unembedding on the ``seq - 1`` positions that have a label.
    Recomputation is not counted."""
    layers = 2 * dm.n_layers * layer_matmul_params(dm) * seq
    unembed = 2 * dm.d_model * dm.vocab_padded * (seq - 1)
    attn = 4 * dm.n_layers * dm.n_heads * dm.head_dim * seq * (seq + 1) // 2
    return 3 * (layers + unembed + attn)
