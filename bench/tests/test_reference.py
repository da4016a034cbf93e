"""The float32 reference against the program at the reduced yi-6b
configuration on the CPU: the same weights from the same key, and the
same logits and loss when the program runs in float32. The reference is
the module the yi-6b configurations name.

Departures from the published description are in the reference's
docstring: adjacent-pair RoPE, random weights, parameters stored in the
configuration's dtype between optimizer steps."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from bench.lib import spec
from repro.models.model import StreamModel
from repro.models.policy import Policy
from repro.train import adamw
from repro.train.trainer import build_train_step

reference = spec.cell("yi6b-l2.train.stream").reference
CFG = dataclasses.replace(configs.get_reduced("yi-6b"), rope_theta=5e6, norm_eps=1e-5)
DIMS = reference.Dims(CFG.d_model, CFG.n_layers, CFG.n_heads, CFG.n_kv_heads, CFG.hd,
                      CFG.d_ff, CFG.vocab, CFG.rope_theta, CFG.norm_eps)
F32 = Policy(param_dtype="float32", compute_dtype="float32", kv_cache_dtype="float32")


def program_leaves(params) -> dict:
    out = {"embed": params["embed"], "unembed": params["unembed"],
           "final_norm": params["final_norm"]["w"][0]}
    blk = params["slots"]["s0"]
    out.update(norm1=blk["norm1"]["w"], norm2=blk["norm2"]["w"], **blk["mixer"], **blk["mlp"])
    return out


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_same_weights_from_the_same_key(dtype):
    key = jax.random.PRNGKey(1234)
    policy = Policy(param_dtype=jnp.dtype(dtype).name)
    mine = jax.jit(StreamModel(CFG, policy).init)(key)
    ref = jax.jit(lambda k: reference.init_params(DIMS, k, dtype))(key)
    for name, x in program_leaves(mine).items():
        y = reference.leaf(ref, name)
        assert x.dtype == y.dtype and x.shape == y.shape, name
        assert np.array_equal(np.asarray(x, np.float32), np.asarray(y, np.float32)), name


def test_logits_match_the_program_in_float32():
    key = jax.random.PRNGKey(7)
    model = StreamModel(CFG, F32)
    tokens = jax.random.randint(jax.random.PRNGKey(8), (2, 24), 0, CFG.vocab)
    with jax.default_matmul_precision("highest"):
        want, _ = model.forward(model.init(key), {"tokens": tokens})
    params = reference.init_params(DIMS, key, jnp.float32)
    pos = jnp.tile(jnp.arange(24)[None], (2, 1))
    got = reference.logits_at(params, tokens, pos, DIMS)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4, rtol=1e-4)


def test_loss_matches_the_program_in_float32():
    key = jax.random.PRNGKey(9)
    model = StreamModel(CFG, F32)
    tokens = jax.random.randint(jax.random.PRNGKey(10), (2, 32), 0, CFG.vocab)
    with jax.default_matmul_precision("highest"):
        want, _ = model.loss(model.init(key), {"tokens": tokens})
    got = reference.loss(reference.init_params(DIMS, key, jnp.float32), tokens, DIMS)
    assert float(got) == pytest.approx(float(want), abs=1e-5)


def test_three_steps_follow_the_program_in_float32():
    key = jax.random.PRNGKey(11)
    model = StreamModel(CFG, F32)
    opt = adamw(3e-4)
    step, _ = build_train_step(model, opt)
    batches = [np.asarray(jax.random.randint(jax.random.PRNGKey(20 + i), (2, 16), 0, CFG.vocab))
               for i in range(3)]
    params = model.init(key)
    state = {"params": params, "opt": opt.init(params)}
    losses = []
    with jax.default_matmul_precision("highest"):
        for i, b in enumerate(batches):
            state, m = step(state, {"tokens": jnp.asarray(b)})
            losses.append(float(m["loss"]))
            if i == 0:
                first = {k: float(jnp.linalg.norm(v / 0.1))
                         for k, v in program_leaves(state["opt"]["m"]).items()}
        start = program_leaves(model.init(key))
        change = {k: float(jnp.linalg.norm(a - start[k]))
                  for k, a in program_leaves(state["params"]).items()}
    ref = reference.train_readings(DIMS, key, batches, reference.AdamW(lr=3e-4),
                                   param_dtype=jnp.float32)
    # Adam divides each gradient by its own root mean square, so an entry
    # near zero turns a rounding difference into a whole step of lr: the
    # later steps agree to a few parts in ten thousand, not to rounding
    assert ref["losses"][0] == pytest.approx(losses[0], abs=1e-5)
    assert ref["losses"] == pytest.approx(losses, abs=1e-3)
    for name in reference.LEAVES:
        assert ref["grad_norms"][name] == pytest.approx(first[name], rel=1e-4), name
        assert ref["delta_norms"][name] == pytest.approx(change[name], rel=1e-2), name


def test_fp8_control_departs_from_float32():
    key = jax.random.PRNGKey(12)
    params = reference.init_params(DIMS, key, jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(13), (1, 24), 0, CFG.vocab)
    pos = jnp.arange(24)[None]
    hi = reference.logits_at(params, tokens, pos, DIMS)
    lo = reference.logits_at(params, tokens, pos, DIMS, fp8=True)
    err = float(jnp.max(jnp.abs(hi - lo)))
    assert 1e-3 < err < 1.0
