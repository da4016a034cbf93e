"""The low-precision controls at a size a test run holds. On the chip
they run at the cells' own sizes through ``bench/calibrate.py``, and the
limits in the configuration files are set from those readings."""

import jax
import pytest

from bench import calibrate
from bench.lib import flops
from small import small_cell

# the CPU's bf16 rounding matches the float32 reference at width 64, and
# the int8-weight path does too; at width 512 the int8 path departs
WIDER = {"hidden_size": 512, "intermediate_size": 1024, "num_attention_heads": 8,
         "num_key_value_heads": 2, "head_dim": 64, "vocab_size": 2048}


@pytest.mark.parametrize("seed", [1, 2])
def test_serving_control_comes_out_not_correct(seed, tmp_path):
    """The program's own int8-weight path, in the program's place, fails
    the served-token comparison that the program passes."""
    cell = small_cell("yi6b.chat.steady")
    cell.config.update(WIDER)
    peak = flops.peaks("TPU v5 lite")
    prog = calibrate.serving(cell, seed, "program", 3.0, jax.devices(), peak, tmp_path)
    ctl = calibrate.serving(cell, seed, "control", 3.0, jax.devices(), peak, tmp_path)
    limits = cell.config["limits"]
    assert all(prog[k] <= v for k, v in limits.items())
    assert any(ctl[k] > v for k, v in limits.items())


@pytest.mark.parametrize("seed", [5, 6, 7])
def test_training_control_departs_further_than_the_program(seed):
    """The reference computed from fp8 operands, in the program's place,
    reads at least three times what the program reads."""
    cell = small_cell("yi6b-l2.train.stream")
    prog = calibrate.training(cell, seed, "program", jax.devices())
    ctl = calibrate.training(cell, seed, "control", jax.devices())
    assert ctl["loss_gap"] > 3 * prog["loss_gap"]
    assert ctl["first_grad_norm_gap"] > 3 * prog["first_grad_norm_gap"]
    limits = cell.config["limits"]
    assert all(prog[k] <= v for k, v in limits.items())
    assert any(ctl[k] > v for k, v in limits.items())
