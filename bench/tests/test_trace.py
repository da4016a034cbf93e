"""The trace reduction, on a synthetic trace with known numbers and on a
small trace recorded on the CPU from named jitted functions."""

import time

import jax
import jax.numpy as jnp
import pytest

from bench.lib import trace


def test_synthetic_busy_programs_and_gaps():
    chip = "/device:TPU:0"
    ops = [(0, 40, "a", "jit_decode_step"), (20, 60, "b", "jit_decode_step"),  # overlap
           (100, 150, "c", "jit_paged_admit"), (200, 230, "d", "jit_decode_step")]
    runs = [(0, 60, "jit_decode_step"), (100, 150, "jit_paged_admit"),
            (200, 230, "jit_decode_step")]
    host = [(0, 300, "bench.window"), (55, 120, "bench.poll_all"), (150, 210, "bench.wait"),
            (160, 190, "bench.produce"), (230, 300, "bench.poll_all")]
    s = trace.summarize(trace.Trace({chip: ops}, {chip: runs}, host))
    assert s.window_s == pytest.approx(300e-9)
    assert s.busy_s == pytest.approx((60 + 50 + 30) * 1e-9)  # union, not sum
    assert s.programs(r"^jit_decode_step$") == (pytest.approx(90e-9), 2)
    assert s.programs(r"^jit_paged_admit$") == (pytest.approx(50e-9), 1)
    idle = dict(s.idle_by_host)
    # gaps: 60-100 (poll_all open), 150-200 (produce, begun inside wait,
    # is innermost at the midpoint 175), 230-300 (poll_all)
    assert idle == {"bench.poll_all": pytest.approx(110e-9),
                    "bench.produce": pytest.approx(50e-9)}
    assert dict(s.top_ops)["jit_decode_step:a"] == pytest.approx(40e-9)


def test_window_clips_and_defaults_to_device_span():
    chip = "/device:TPU:0"
    ops = [(0, 100, "a", "m"), (300, 400, "b", "m")]
    s = trace.summarize(trace.Trace({chip: ops}, {chip: []}, [(50, 350, "bench.window")]))
    assert s.window_s == pytest.approx(300e-9) and s.busy_s == pytest.approx(100e-9)
    s = trace.summarize(trace.Trace({chip: ops}, {chip: []}, []))
    assert s.window_s == pytest.approx(400e-9) and s.busy_s == pytest.approx(200e-9)


def test_program_name_strips_the_program_id():
    assert trace.program_name("jit_decode_step(123)") == "jit_decode_step"
    assert trace.program_name("jit_step") == "jit_step"


def test_op_name_keeps_the_instruction_name():
    text = "%fusion.12 = bf16[28,1,4096]{2,0,1:T(8,128)(2,1)} fusion(bf16[28,1,4096] %p0)"
    assert trace.op_name(text) == "fusion.12"
    assert trace.op_name("dot.3") == "dot.3"


def test_control_flow_is_not_a_top_op():
    chip = "/device:TPU:0"
    ops = [(0, 100, "while.5", "jit_decode_step"), (10, 60, "fusion.1", "jit_decode_step"),
           (60, 90, "fusion.2", "jit_decode_step")]
    s = trace.summarize(trace.Trace({chip: ops}, {chip: []}, []))
    assert [k for k, _ in s.top_ops] == ["jit_decode_step:fusion.1", "jit_decode_step:fusion.2"]
    assert s.busy_s == pytest.approx(100e-9)


def test_recorded_cpu_trace(tmp_path):
    @jax.jit
    def decode_step(x):
        return jnp.tanh(x @ x).sum()

    x = jnp.ones((256, 256))
    decode_step(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.poll_all"):
                decode_step(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    s = trace.summarize(trace.load(trace.find_xplane(tmp_path)))
    secs, calls = s.programs(r"^jit_decode_step$")
    assert calls == 3 and secs > 0
    assert s.chips == 1
    assert 0 < s.busy_s < s.window_s
    idle = dict(s.idle_by_host)
    assert idle["bench.wait"] >= 3 * 0.02 * 0.9  # the sleeps are idle, and named
    assert s.window_s >= 0.06
