"""Compiles for a described TPU v5e: what the chip's compiler accepts.

Nothing runs: each case lowers a program at real widths against a
``v5e:2x2`` topology description and compiles it with the TPU compiler
that ships with JAX, which refuses misaligned kernel blocks, kernels over
the fast-memory budget and programs over the chip's memory. The
topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.configs as configs
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rglru_scan import rglru_scan_kernel
from repro.kernels.ssd_scan import ssd_scan
from repro.models.model import StreamModel
from repro.models.policy import Policy
from repro.train.optimizer import adamw
from repro.train.trainer import build_train_step

# the continuous engine's paged cache at the chip run's size
N_SLOTS, N_BLOCKS, BLOCK, MAX_BLOCKS = 8, 256, 16, 64


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's compiles can be written to the persistent cache
    # but never read back: keep the cache off while these run
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _shapes(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree
    )


def _kernel_compiled(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_flash_attention_yi6b_heads(one_chip):
    """Forward and backward at yi-6b's 32 query / 4 KV heads of 128."""
    q = jax.ShapeDtypeStruct((1, 32, 2048, 128), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 4, 2048, 128), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        out = flash_attention(q, k, v, interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    c = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    assert _kernel_compiled(c)


# the yi-6b-l2 train step's temporaries with the chunked XLA attention,
# whose backward read float32 scores stacked per layer and query chunk
CHUNKED_STEP_TEMP_BYTES = 5_695_114_752


def test_train_step_yi6b_l2_takes_flash(one_chip):
    """The train step at yi-6b widths, 2 layers, 2 x 1024 lowers its
    attention to the kernel, and holds no score stacks."""
    cfg = dataclasses.replace(configs.get("yi-6b"), n_layers=2)
    model = StreamModel(cfg, Policy())
    opt = adamw(3e-4)
    step, _ = build_train_step(model, opt)
    state = _shapes(one_chip, jax.eval_shape(
        lambda k: (lambda p: {"params": p, "opt": opt.init(p)})(model.init(k)),
        jax.random.PRNGKey(0),
    ))
    compiled = step.lower(state, {"tokens": _ints(one_chip, 2, 1024)}).compile()
    assert _kernel_compiled(compiled)
    assert compiled.memory_analysis().temp_size_in_bytes < CHUNKED_STEP_TEMP_BYTES


@pytest.mark.parametrize("b,c", [(2, 4096), (1, 2560)])  # C=2560: recurrentgemma-9b
def test_rglru_scan_widths(one_chip, b, c):
    x = jax.ShapeDtypeStruct((b, 2048, c), jnp.float32, sharding=one_chip)
    h0 = jax.ShapeDtypeStruct((b, c), jnp.float32, sharding=one_chip)
    compiled = _compile(
        lambda x, a, h: rglru_scan_kernel(x, a, h, interpret=False), x, x, h0
    )
    assert _kernel_compiled(compiled)


def test_ssd_scan_mamba2_widths(one_chip):
    b, h, s, p, n = 1, 80, 2048, 64, 128  # mamba2-2.7b heads, head dim, state

    def sd(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = _compile(
        lambda x, dt, A, B, C: ssd_scan(x, dt, A, B, C, chunk=256, interpret=False),
        sd((b, h, s, p), jnp.bfloat16), sd((b, h, s)), sd((h,)),
        sd((b, h, s, n), jnp.bfloat16), sd((b, h, s, n), jnp.bfloat16),
    )
    assert _kernel_compiled(compiled)


@pytest.fixture(scope="module")
def yi6b_engine(one_chip):
    """yi-6b at its published widths, depth cut to 2 layers (the layers
    are scanned, so the compile barely depends on depth), as shapes."""
    cfg = dataclasses.replace(configs.get("yi-6b"), n_layers=2)
    model = StreamModel(cfg, Policy())
    params = _shapes(one_chip, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    caches = _shapes(one_chip, jax.eval_shape(
        lambda: model.init_paged_cache(
            N_SLOTS, N_BLOCKS, BLOCK, MAX_BLOCKS, dtype=jnp.float32
        )
    ))
    return model, params, caches


def _ints(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def test_continuous_decode_step_yi6b(one_chip, yi6b_engine):
    model, params, caches = yi6b_engine
    compiled = _compile(
        model.decode_step, params, caches,
        _ints(one_chip, N_SLOTS, 1), _ints(one_chip, N_SLOTS),
    )
    logits = compiled.out_info[0]
    assert logits.shape == (N_SLOTS, 1, model.cfg.vocab_padded)


def test_continuous_admission_prefill_yi6b(one_chip, yi6b_engine):
    model, params, caches = yi6b_engine
    plen = 500
    compiled = _compile(
        model.paged_admit, params, caches, _ints(one_chip, 1, plen),
        _ints(one_chip), _ints(one_chip, -(-plen // BLOCK)),
        _ints(one_chip, MAX_BLOCKS), _ints(one_chip),
    )
    assert compiled.out_info[0].shape == (model.cfg.vocab_padded,)
