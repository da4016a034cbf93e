"""Model zoo: per-arch smoke tests (reduced configs), decode consistency,
mixer oracles, causality, pspec/param tree congruence."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import repro.configs as C
from repro.models.model import StreamModel
from repro.models.policy import Policy

# full model-zoo sweep: minutes of jit on CPU — excluded from the fast tier
pytestmark = pytest.mark.slow

RNG = np.random.default_rng(0)
FP32 = dict(param_dtype="float32", compute_dtype="float32")


def _model(aid, **pol_kw):
    cfg = C.get_reduced(aid)
    m = StreamModel(cfg, Policy(**pol_kw))
    params = m.init(jax.random.PRNGKey(0))
    return cfg, m, params


def _batch(cfg, s=32, b=2):
    return {
        k: jnp.asarray(v)
        for k, v in C.make_batch(cfg, C.ShapeCell("s", s, b, "train"), RNG).items()
    }


@pytest.mark.parametrize("aid", C.names())
def test_smoke_forward_one_train_step(aid):
    """The assigned-architecture smoke test: reduced config, one forward +
    one train step on CPU; asserts output shapes and no NaNs."""
    from repro.train.optimizer import adamw

    cfg, m, params = _model(aid)
    batch = _batch(cfg)
    logits, aux = m.forward(params, batch)
    s_total = batch["tokens"].shape[1] + (cfg.frontend_len if cfg.frontend == "patches" else 0)
    assert logits.shape == (2, s_total, cfg.vocab_padded)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    opt = adamw(1e-3)
    state = {"params": params, "opt": opt.init(params)}
    (loss, metrics), grads = jax.value_and_grad(lambda p: m.loss(p, batch), has_aux=True)(
        state["params"]
    )
    assert np.isfinite(float(loss))
    new_params, _ = opt.update(grads, state["opt"], state["params"])
    l2, _ = m.loss(new_params, batch)
    assert np.isfinite(float(l2))


@pytest.mark.parametrize("aid", C.names())
def test_param_pspecs_tree_matches_params(aid):
    cfg = C.get_reduced(aid)
    pol = Policy(mesh_axes={"data": 2, "model": 4})
    m = StreamModel(cfg, pol)
    params = jax.eval_shape(lambda: m.init(jax.random.PRNGKey(0)))
    specs = m.param_pspecs()
    assert jax.tree.structure(params) == jax.tree.structure(
        specs, is_leaf=lambda x: isinstance(x, P)
    )
    # every spec entry must be rank-compatible with its param
    flat_p = jax.tree.leaves(params)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    for arr, sp in zip(flat_p, flat_s):
        assert len(sp) <= len(arr.shape), (arr.shape, sp)


@pytest.mark.parametrize("aid", C.names())
def test_prefill_decode_matches_forward(aid):
    B, S = 2, 32
    cfg = C.get_reduced(aid)
    if cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0)
        )
    m = StreamModel(cfg, Policy(**FP32))
    params = m.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, S, B)
    logits_full, _ = m.forward(params, batch)
    toks = batch["tokens"]
    last, cache = m.prefill(params, dict(batch, tokens=toks[:, :-1]), S + 8, cache_dtype=jnp.float32)
    np.testing.assert_allclose(
        np.asarray(last), np.asarray(logits_full[:, -2]), rtol=3e-4, atol=3e-4
    )
    front = cfg.frontend_len if cfg.frontend == "patches" else 0
    step_logits, cache = m.decode_step(params, cache, toks[:, -1:], jnp.int32(S - 1 + front))
    np.testing.assert_allclose(
        np.asarray(step_logits[:, 0]), np.asarray(logits_full[:, -1]), rtol=5e-3, atol=5e-3
    )


@pytest.mark.parametrize("aid", ["mamba2-2.7b", "gemma2-2b", "recurrentgemma-9b", "qwen2-7b"])
def test_causality(aid):
    """logits[:, :k] must not depend on tokens after k."""
    cfg, m, params = _model(aid, **FP32)
    batch = _batch(cfg, 32, 2)
    l_full, _ = m.forward(params, batch)
    short = dict(batch, tokens=batch["tokens"][:, :20])
    l_short, _ = m.forward(params, short)
    front = cfg.frontend_len if cfg.frontend == "patches" else 0
    np.testing.assert_allclose(
        np.asarray(l_full[:, : 20 + front]), np.asarray(l_short), atol=2e-4, rtol=2e-4
    )


def test_chunked_loss_invariant_to_chunk_size():
    cfg, m, params = _model("gemma2-2b", **FP32)
    batch = _batch(cfg, 33, 2)  # odd length: ragged tail
    losses = [float(m.loss(params, batch, loss_chunk=c)[0]) for c in (4, 8, 16, 64)]
    assert max(losses) - min(losses) < 1e-4, losses


def test_ssd_chunk_invariance():
    from repro.models.ssm import ssd_chunked

    k = jax.random.PRNGKey(1)
    ks = jax.random.split(k, 5)
    b, s, h, p, n = 1, 64, 2, 8, 16
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)))
    Bm = jax.random.normal(ks[3], (b, s, 1, n))
    Cm = jax.random.normal(ks[4], (b, s, 1, n))
    outs = [np.asarray(ssd_chunked(x, dt, A, Bm, Cm, c)[0]) for c in (8, 16, 32, 64)]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], atol=1e-4, rtol=1e-4)


def test_rglru_assoc_scan_matches_sequential():
    from repro.kernels import ref
    from repro.models.rglru import rglru_scan

    k = jax.random.PRNGKey(2)
    x = jax.random.normal(k, (2, 50, 16))
    log_a = -jnp.abs(jax.random.normal(jax.random.fold_in(k, 1), (2, 50, 16))) * 0.3
    h, hl = rglru_scan(x, log_a)
    hr, hlr = ref.rglru(x, log_a)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), atol=1e-5, rtol=1e-5)


def test_local_attention_respects_window():
    """Tokens beyond the sliding window must not affect outputs."""
    cfg = C.get_reduced("gemma2-2b")
    # make every layer local to isolate the window effect
    cfg = dataclasses.replace(cfg, pattern=("local",), n_layers=2, window=8)
    m = StreamModel(cfg, Policy(**FP32))
    params = m.init(jax.random.PRNGKey(0))
    t1 = jnp.asarray(RNG.integers(0, cfg.vocab, (1, 32)).astype(np.int32))
    t2 = t1.at[:, 0].set((t1[:, 0] + 1) % cfg.vocab)  # perturb token 0
    l1, _ = m.forward(params, {"tokens": t1})
    l2, _ = m.forward(params, {"tokens": t2})
    # last position (31) attends to keys > 31-8=23 only: unaffected by token 0
    np.testing.assert_allclose(
        np.asarray(l1[:, -1]), np.asarray(l2[:, -1]), atol=2e-4, rtol=2e-4
    )
    assert not np.allclose(np.asarray(l1[:, 4]), np.asarray(l2[:, 4]), atol=1e-4)


def test_moe_capacity_drops_are_bounded():
    """With capacity_factor=1.0 some tokens drop but loss stays finite."""
    cfg = C.get_reduced("qwen3-moe-30b-a3b")
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=1.0))
    m = StreamModel(cfg, Policy())
    params = m.init(jax.random.PRNGKey(0))
    loss, metrics = m.loss(params, _batch(cfg))
    assert np.isfinite(float(loss))
    assert float(metrics["aux"]) > 0  # router aux loss active


def test_param_counts_match_assigned_scale():
    """Full configs instantiate (eval_shape only) at the published scale."""
    expect = {
        "mamba2-2.7b": (2.4e9, 3.1e9),
        "qwen3-moe-30b-a3b": (28e9, 33e9),
        "arctic-480b": (430e9, 520e9),
        "qwen2-7b": (7e9, 8.2e9),
        "gemma2-2b": (2.2e9, 3.2e9),
        "yi-6b": (5.5e9, 6.5e9),
        "mistral-large-123b": (115e9, 130e9),
        "pixtral-12b": (11e9, 13.5e9),
        "recurrentgemma-9b": (8e9, 10.5e9),
        "whisper-tiny": (25e6, 60e6),
    }
    for aid, (lo, hi) in expect.items():
        n = C.get(aid).param_count()
        assert lo <= n <= hi, f"{aid}: {n:,} params outside [{lo:,.0f}, {hi:,.0f}]"


# ------------------------------------------------ flash attention selection
YI_HEADS = dict(n_heads=8, n_kv=2, head_dim=128)  # yi's head dim, GQA 4


@pytest.mark.parametrize("case,ap_kw,s,mesh_axes,want", [
    ("causal", {}, 256, {}, True),
    ("full", dict(causal=False), 256, {}, True),
    ("one_device_mesh", {}, 256, {"data": 1, "model": 1}, True),
    ("cross", dict(cross=True), 256, {}, False),
    ("window", dict(window=128), 256, {}, False),
    ("ragged", {}, 200, {}, False),
    ("head_dim_64", dict(head_dim=64), 256, {}, False),
    ("tp_mesh", {}, 256, {"model": 2}, False),
    ("dp_mesh", {}, 256, {"data": 2, "model": 1}, False),
])
def test_flash_attention_selection(case, ap_kw, s, mesh_axes, want):
    """Causal or full self-attention at 128-multiple lengths on operands
    no mesh shards takes the kernel; every other shape stays chunked."""
    from repro.models import layers as L

    ap = L.AttnParams(**{**YI_HEADS, **ap_kw})
    assert L.flash_applies(ap, Policy(mesh_axes=mesh_axes), s) is want


def _attention_calls(path: str) -> int:
    from repro.core.metrics import default_registry

    return default_registry().counter_value("attention_calls_total", path=path)


@pytest.mark.parametrize("s,path", [(256, "flash"), (200, "chunked")])
def test_attention_calls_counted_and_cpu_lowers_chunked(s, path):
    """Each traced attention call counts once under the path its shape
    selects; lowered for the CPU, the train step holds no Pallas kernel."""
    cfg = dataclasses.replace(C.get_reduced("yi-6b"), head_dim=128)
    m = StreamModel(cfg, Policy())
    params = m.init(jax.random.PRNGKey(0))
    batch = {"tokens": jnp.zeros((2, s), jnp.int32)}
    other = "chunked" if path == "flash" else "flash"
    before = _attention_calls(path), _attention_calls(other)
    step = jax.jit(jax.value_and_grad(lambda p: m.loss(p, batch)[0]))
    lowered = step.lower(params)
    assert _attention_calls(path) > before[0]
    assert _attention_calls(other) == before[1]
    assert "tpu_custom_call" not in lowered.as_text()
    assert "tpu_custom_call" not in lowered.compile().as_text()
    loss, _ = step(params)
    assert np.isfinite(float(loss))
