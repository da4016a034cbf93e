"""Operation and byte counts against hand arithmetic at the reduced
yi-6b configuration, and the peaks table. The counts are the dense
family's, beside its reference: the module the yi-6b configurations name."""

import pytest

import repro.configs as configs
from bench.lib import flops, spec

COUNTS = spec.cell("yi6b-l2.train.stream").reference


def reduced():
    c = configs.get_reduced("yi-6b")  # d 64, 3 layers, 4 / 2 heads of 16, ff 128, vocab 256
    return COUNTS.Dims(c.d_model, c.n_layers, c.n_heads, c.n_kv_heads, c.hd, c.d_ff, c.vocab,
                       c.rope_theta, c.norm_eps)


def test_parameter_counts_by_hand():
    dm = reduced()
    # q and o: 64 x 4 x 16 each; k and v: 64 x 2 x 16 each; gated MLP 3 x 64 x 128
    assert COUNTS.layer_matmul_params(dm) == 2 * 4096 + 2 * 2048 + 3 * 8192 == 36864
    assert COUNTS.matmul_params(dm) == 3 * 36864 + 64 * 256
    # and the program's own count of its parameters
    assert COUNTS.param_count(dm) == configs.get_reduced("yi-6b").param_count()


def test_prefill_and_decode_by_hand():
    dm = reduced()
    n = 10
    layers = 2 * 3 * 36864 * n
    attn = 4 * 3 * 4 * 16 * (n * (n + 1) // 2)  # causal: query i sees i keys
    assert COUNTS.prefill_flops(dm, n) == layers + attn + 2 * 64 * 256
    per_row = 2 * (3 * 36864 + 64 * 256)
    assert COUNTS.decode_flops(dm, [5, 7]) == 2 * per_row + 4 * 3 * 4 * 16 * (5 + 7)
    # bf16 weights once (embedding rows only as looked up), bf16 KV of 12 keys
    kv_token = 2 * 3 * 2 * 16 * 2
    weights = (3 * 36864 + 64 * 256 + 7 * 64) * 2
    assert COUNTS.decode_bytes(dm, [5, 7], "bfloat16", "bfloat16") == \
        weights + 2 * 64 * 2 + 12 * kv_token
    ops, nbytes = COUNTS.decode_totals(dm, 2, [5, 7, 6], "bfloat16", "bfloat16")
    assert nbytes == 2 * weights + 3 * 64 * 2 + 18 * kv_token
    assert list(flops.decode_contexts(100, 4)) == [101, 102, 103]


def test_train_flops_by_hand():
    dm = reduced()
    s = 32
    fwd = 2 * 3 * 36864 * s + 2 * 64 * 256 * (s - 1) + 4 * 3 * 4 * 16 * s * (s + 1) // 2
    assert COUNTS.train_flops_per_sequence(dm, s) == 3 * fwd


def test_roofline_takes_the_larger_bound():
    peak = flops.peaks("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12 and peak["hbm_bytes_per_s"] == 819e9
    assert flops.least_seconds(197e12, 1.0, peak) == pytest.approx(1.0)
    assert flops.least_seconds(1.0, 819e9, peak) == pytest.approx(1.0)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        flops.peaks("cpu")
