"""Broker→device data-path microbenchmarks (DESIGN.md §10).

The paper's central claim is training/serving *directly from the
stream*; this benchmark measures the path that makes it real — a fetched
``RecordBatch`` becoming device-resident ``jnp`` arrays — and gates the
two optimizations PR-7 added:

* **decode** — µs/batch and bytes/s for decoding one fetched batch of
  fixed-layout records, four ways: the per-record Python baseline
  (``codec.decode(bytes(v))`` per record — what a naive consumer
  writes), the copying matrix path (``to_matrix`` + column slicing, the
  pre-PR-7 vectorized path), the **zero-copy framed view path**
  (``decode_frames``: per-field strided ndarray views over the segment
  buffer, no bytes move), and the **measured fallback copy** (the same
  entry point on a deliberately unaligned layout — one vectorized column
  copy per field). ``DEC_REPS`` slice-interleaved (per_record, framed)
  pairs; ``check_bench.py`` gates the median within-pair speedup at
  ≥ ``10x`` (measures ~1000x+ — the gate floor is deliberately far below
  the quiet-host reading so only a real regression to per-record work
  trips it).
* **overlap** — end-to-end poll→device records/s over a
  :class:`~repro.data.pipeline.StreamingBatchIterator` consumed through
  :func:`~repro.data.pipeline.device_feed`, double-buffered
  (``depth=2``) vs fully serial (``depth=0``), with a jitted
  matmul-stack device step per batch. ``OVR_REPS`` slice-interleaved
  (serial, overlap) pairs so shared-host drift cancels out of the
  within-pair ratio. The file records ``host_cores``
  (``sched_getaffinity``): on a multi-core host the background
  poll+decode+``device_put`` genuinely runs during the device step and
  ``check_bench.py`` gates the median ratio at ≥ 1.05x; on a
  **single-core** host (this reference container) the two legs timeshare
  one CPU — overlap physically cannot beat serial, the theoretical
  ceiling is 1.0 — so the gate instead holds overlap at parity (≥ 0.90x:
  the pipeline must cost nothing to leave on, which is what lets the
  same code path win on real multi-core metal).
* **step** — poll→step records/s feeding a *real* kernel from
  ``kernels/``: each streamed batch reshapes into (B, S, H, D) and runs
  :func:`~repro.kernels.ops.attention_op` (Pallas flash attention,
  interpret mode on CPU), overlap on. Schema-gated (must be present and
  positive); the absolute number is the honest record of what this host
  sustains stream→kernel.

Prints ``name,us_per_call,derived`` CSV rows like the other benchmarks
and writes the full result set to ``BENCH_datapath.json``::

    PYTHONPATH=src python -m benchmarks.datapath

Nightly CI sources ``scripts/profile_env.sh`` first (tcmalloc, XLA
flags) so the recorded numbers reflect the tuned-host configuration.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.log import LogConfig, StreamLog
from repro.data.formats import RawCodec
from repro.data.pipeline import StreamingBatchIterator, device_feed, ingest
from repro.kernels.ops import attention_op

# decode section: 4096 × 260 B records (float32[64] data + int32 label)
DEC_N = 4096
DEC_REPS = 5
DEC_FRAMED_ITERS = 200  # framed decode is ~µs; amortize timer granularity
DEC_COPY_ITERS = 50

# overlap section: 1024-record batches, 2048-record fetches
OVR_N = 24_576
OVR_BATCH = 1024
OVR_FETCH = 2048
OVR_REPS = 9
# tanh(x @ W) repetitions per device step: deep enough that the fixed
# per-batch pipeline cost (queue handoff, thread wakeup) amortizes into
# a realistic device leg — at 8 the handoff tax alone reads ~10% on the
# single-core reference host
OVR_STEP_DEPTH = 24

# step section: records reshape to (B, S, 1, D) for flash attention,
# whose kernel takes lengths in multiples of 128
STEP_SEQ = 128
STEP_DIM = 64
STEP_EPOCHS = 2

OUT_JSON = "BENCH_datapath.json"


def _row(name: str, seconds: float, derived: str = "") -> None:
    print(f"{name},{seconds * 1e6:.1f},{derived}")


def _median(xs: list[float]) -> float:
    ys = sorted(xs)
    return ys[len(ys) // 2]


# ------------------------------------------------------------------- decode
def _decode_fixture(codec: RawCodec, n: int, seed: int = 0):
    """One contiguous fetched batch of n encoded records."""
    rng = np.random.default_rng(seed)
    log = StreamLog()
    log.create_topic("bench", LogConfig(num_partitions=1))
    arrays = {}
    for f in codec.fields:
        if np.issubdtype(np.dtype(f.dtype), np.floating):
            arrays[f.name] = rng.normal(size=(n,) + f.shape).astype(f.dtype)
        else:
            arrays[f.name] = (
                rng.integers(0, 100, size=(n,) + f.shape).astype(f.dtype)
            )
    log.produce_batch("bench", codec.encode_batch(arrays), partition=0)
    return log.read_range("bench", 0, 0, n)


def bench_decode() -> dict:
    codec = RawCodec("float32", (64,), "int32", ())  # 260 B, aligned
    batch = _decode_fixture(codec, DEC_N)
    assert batch.framed(codec.record_bytes) is not None
    nbytes = DEC_N * codec.record_bytes

    def time_per_record() -> float:
        t0 = time.perf_counter()
        out = [codec.decode(bytes(v)) for v in batch.values]
        dt = time.perf_counter() - t0
        assert len(out) == DEC_N
        return dt

    def time_framed() -> float:
        t0 = time.perf_counter()
        for _ in range(DEC_FRAMED_ITERS):
            out = codec.decode_frames(batch)
        dt = (time.perf_counter() - t0) / DEC_FRAMED_ITERS
        assert out["data"].shape == (DEC_N, 64)
        return dt

    # slice-interleaved pairs: each (per_record, framed) pair runs back
    # to back, so the within-pair ratio is immune to absolute-speed drift
    pairs = []
    for _ in range(DEC_REPS):
        pairs.append(
            {"per_record_us": time_per_record() * 1e6,
             "framed_us": time_framed() * 1e6}
        )
    per_rec_s = _median([p["per_record_us"] for p in pairs]) / 1e6
    framed_s = _median([p["framed_us"] for p in pairs]) / 1e6
    speedup = _median([p["per_record_us"] / p["framed_us"] for p in pairs])

    def timed(fn, iters: int) -> float:
        t0 = time.perf_counter()
        for _ in range(iters):
            fn()
        return (time.perf_counter() - t0) / iters

    # pre-PR-7 vectorized path: one (n, record_bytes) copy + column copies
    matrix_s = timed(lambda: codec.decode_matrix(batch.to_matrix()),
                     DEC_COPY_ITERS)

    # measured fallback: a 3-byte uint8 field forces every later offset
    # off-alignment, so decode_frames takes the vectorized column copy
    codec_u = RawCodec("uint8", (3,), "float32", (64,))
    batch_u = _decode_fixture(codec_u, DEC_N, seed=1)
    arrays_u, zero_copy_u = codec_u.decode_span(
        *batch_u.framed(codec_u.record_bytes)[0]
    )
    assert not zero_copy_u  # the fixture really is unaligned
    fallback_s = timed(lambda: codec_u.decode_frames(batch_u),
                       DEC_COPY_ITERS)
    nbytes_u = DEC_N * codec_u.record_bytes

    return {
        "per_record": {
            "us_per_batch": per_rec_s * 1e6,
            "MB_per_s": nbytes / per_rec_s / 1e6,
        },
        "matrix_copy": {
            "us_per_batch": matrix_s * 1e6,
            "MB_per_s": nbytes / matrix_s / 1e6,
        },
        "framed_view": {
            "us_per_batch": framed_s * 1e6,
            "MB_per_s": nbytes / framed_s / 1e6,
            "zero_copy": True,
        },
        "fallback_copy": {
            "us_per_batch": fallback_s * 1e6,
            "MB_per_s": nbytes_u / fallback_s / 1e6,
            "zero_copy": False,
        },
        "pairs": pairs,
        "speedup": speedup,
    }


# ------------------------------------------------------------------ overlap
def _overlap_fixture() -> tuple[StreamLog, object]:
    rng = np.random.default_rng(2)
    log = StreamLog()
    msg = ingest(
        log, "stream", RawCodec("float32", (STEP_DIM,), "int32", ()),
        {
            "data": rng.normal(size=(OVR_N, STEP_DIM)).astype(np.float32),
            "label": np.arange(OVR_N, dtype=np.int32),
        },
        "bench-datapath",
        message_set_size=OVR_FETCH,
    )
    return log, msg


def _make_step():
    @jax.jit
    def step(x, w):
        y = x
        for _ in range(OVR_STEP_DEPTH):
            y = jnp.tanh(y @ w)
        return y.sum()

    w = jnp.eye(STEP_DIM, dtype=jnp.float32) * 0.5
    # warm the compile cache outside the measured region
    step(jnp.zeros((OVR_BATCH, STEP_DIM), jnp.float32), w).block_until_ready()
    return step, w


def _run_pipeline(log, msg, step, w, depth: int) -> float:
    """records/s through poll → zero-copy decode → device_put → step."""
    it = StreamingBatchIterator(
        log, msg, OVR_BATCH, split="all", epochs=1, fetch_records=OVR_FETCH
    )
    n_records = it.steps_per_epoch() * OVR_BATCH
    t0 = time.perf_counter()
    last = None
    for b in device_feed(iter(it), depth=depth):
        last = step(b["data"], w)
    last.block_until_ready()
    return n_records / (time.perf_counter() - t0)


def bench_overlap() -> dict:
    log, msg = _overlap_fixture()
    step, w = _make_step()
    _run_pipeline(log, msg, step, w, 0)  # warm page cache / allocator
    pairs = []
    for _ in range(OVR_REPS):
        pairs.append(
            {
                "serial_records_per_s": _run_pipeline(log, msg, step, w, 0),
                "overlap_records_per_s": _run_pipeline(log, msg, step, w, 2),
            }
        )
    return {
        "serial": {
            "records_per_s": _median(
                [p["serial_records_per_s"] for p in pairs]
            )
        },
        "overlap": {
            "records_per_s": _median(
                [p["overlap_records_per_s"] for p in pairs]
            )
        },
        "pairs": pairs,
        "speedup": _median(
            [
                p["overlap_records_per_s"] / p["serial_records_per_s"]
                for p in pairs
            ]
        ),
        "host_cores": len(os.sched_getaffinity(0)),
    }


# --------------------------------------------------------------------- step
def bench_step(log, msg) -> dict:
    """poll→step through a real Pallas kernel (flash attention)."""
    att_b = OVR_BATCH // STEP_SEQ

    @jax.jit
    def step(x):
        qkv = x.reshape(att_b, STEP_SEQ, 1, STEP_DIM)
        return attention_op(qkv, qkv, qkv, causal=True).sum()

    step(jnp.zeros((OVR_BATCH, STEP_DIM), jnp.float32)).block_until_ready()
    it = StreamingBatchIterator(
        log, msg, OVR_BATCH, split="all", epochs=STEP_EPOCHS,
        fetch_records=OVR_FETCH,
    )
    steps = it.steps_per_epoch() * STEP_EPOCHS
    t0 = time.perf_counter()
    last = None
    for b in device_feed(iter(it), depth=2):
        last = step(b["data"])
    last.block_until_ready()
    dt = time.perf_counter() - t0
    return {
        "kernel": "attention_op",
        "records_per_s": steps * OVR_BATCH / dt,
        "us_per_step": dt / steps * 1e6,
        "steps": steps,
    }


def main() -> None:
    results: dict = {
        "config": {
            "decode": {"records": DEC_N, "reps": DEC_REPS},
            "overlap": {
                "records": OVR_N,
                "batch": OVR_BATCH,
                "fetch_records": OVR_FETCH,
                "reps": OVR_REPS,
            },
            "step": {"seq": STEP_SEQ, "dim": STEP_DIM,
                     "epochs": STEP_EPOCHS},
            "host_cores": len(os.sched_getaffinity(0)),
        },
    }
    print("name,us_per_call,derived")

    dec = bench_decode()
    results["decode"] = dec
    _row("datapath_decode_per_record", dec["per_record"]["us_per_batch"] / 1e6,
         f"{dec['per_record']['MB_per_s']:.0f}MB/s")
    _row("datapath_decode_matrix_copy",
         dec["matrix_copy"]["us_per_batch"] / 1e6,
         f"{dec['matrix_copy']['MB_per_s']:.0f}MB/s")
    _row("datapath_decode_framed_view",
         dec["framed_view"]["us_per_batch"] / 1e6,
         f"{dec['framed_view']['MB_per_s']:.0f}MB/s_"
         f"{dec['speedup']:.0f}x_vs_per_record")
    _row("datapath_decode_fallback_copy",
         dec["fallback_copy"]["us_per_batch"] / 1e6,
         f"{dec['fallback_copy']['MB_per_s']:.0f}MB/s_unaligned")

    ovr = bench_overlap()
    results["overlap"] = ovr
    _row("datapath_poll_to_device_serial",
         1.0 / ovr["serial"]["records_per_s"],
         f"{ovr['serial']['records_per_s'] / 1e3:.0f}krec/s")
    _row("datapath_poll_to_device_overlap",
         1.0 / ovr["overlap"]["records_per_s"],
         f"{ovr['overlap']['records_per_s'] / 1e3:.0f}krec/s_"
         f"{ovr['speedup']:.2f}x_cores{ovr['host_cores']}")

    log, msg = _overlap_fixture()
    st = bench_step(log, msg)
    results["step"] = st
    _row("datapath_poll_to_kernel_step", st["us_per_step"] / 1e6,
         f"{st['records_per_s'] / 1e3:.0f}krec/s_{st['kernel']}")

    with open(OUT_JSON, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)
    print(f"# wrote {OUT_JSON}")


if __name__ == "__main__":
    main()
