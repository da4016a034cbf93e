"""Training cells: a train step fed from a token topic on the broker.

Set-up ingests a backlog of token records into a one-partition topic of
a three-broker ``BrokerCluster`` and announces it (``data.ingest``). The
step is the program's own (``build_train_step``: value and gradient of
``StreamModel.loss``, the optimizer's update, the state donated), fed by
``StreamingBatchIterator`` -> ``device_feed``, and the loop reads each
step's loss back as ``TrainingJob.run`` does. One state and one step are
built; set-up drives them from the seed through their first steps, which
are checked, and the window continues with the same objects.

The model, the reference, the leaves' names and the counts come from
the modules the configuration names (``Cell.program``,
``Cell.reference``). The run is judged on the first three steps against
the float32 reference started from the same seed on the same rows:

- each step's loss;
- per leaf, the norm of the first gradient as the optimizer received
  it, read from its first moment after one step (m / (1 - b1));
- per leaf, the norm of the parameters' change over the three steps.

Both norms are compared as the gap between the program's norm and the
reference's, over the larger of the reference's norm of that leaf and of
the median leaf; the worst leaf counts. Leaves whose reference gradient
is under a thousandth of the median leaf's move by rounding alone and
are left out of the change.
"""

from __future__ import annotations

import gc
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.core as core
import repro.data as data
from bench.kinds.common import Ctx, TracedWindow
from bench.lib import traffic
from bench.lib.probes import peak_bytes, tree_bytes
from repro.data.pipeline import StreamingBatchIterator, device_feed
from repro.train import adamw
from repro.train.trainer import build_train_step

TOPIC = "lm-tokens"
CHECKED_STEPS = 3


def backlog_records(ctx: Ctx, dm, seq: int, peak: dict) -> int:
    """Records the chip could train on in the mix's stated seconds at its
    peak rate: the feed never runs dry."""
    per_seq = ctx.cell.reference.train_flops_per_sequence(dm, seq)
    return math.ceil(ctx.cell.mix["backlog_seconds_at_peak"] * peak["bf16_flops_per_s"] / per_seq)


def rows(ctx: Ctx, n: int, seq: int, vocab: int) -> np.ndarray:
    """The token rows of the backlog, from the seed."""
    return traffic.rng(ctx.seed, traffic.STREAM_PROMPTS).integers(
        0, vocab, (n, seq)).astype(np.int32)


def _norms_fn():
    @jax.jit
    def first_grad(m, b1):  # the clipped gradient is m1 / (1 - b1)
        return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x / (1 - b1)))), m)

    @jax.jit
    def change(a, b):
        return jax.tree.map(
            lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32) - y.astype(jnp.float32)))),
            a, b)

    return first_grad, change


def _by_name(tree, leaf_name) -> dict[str, float]:
    return {leaf_name(p): float(v) for p, v in jax.tree_util.tree_leaves_with_path(tree)}


def run(ctx: Ctx, peak: dict) -> dict:
    cfg, mix = ctx.cell.config, ctx.cell.mix
    tcfg = cfg["train"]
    dm = ctx.cell.reference.dims(cfg)
    batch, seq = tcfg["batch"], tcfg["seq"]
    m = ctx.cell.program.model(cfg)
    opt = adamw(tcfg["lr"])
    log = core.BrokerCluster(3)
    log.create_topic(TOPIC, core.LogConfig(num_partitions=1))
    n_rec = backlog_records(ctx, dm, seq, peak)
    msg = data.ingest(
        log, TOPIC, data.RawCodec("int32", (seq,), "int32", ()),
        {"data": rows(ctx, n_rec, seq, dm.vocab), "label": np.zeros(n_rec, np.int32)},
        "bench-train",
    )
    step_fn, _ = build_train_step(m, opt)
    key = jax.random.PRNGKey(traffic.key_seed(ctx.seed))
    state = jax.jit(lambda k: (lambda p: {"params": p, "opt": opt.init(p)})(m.init(k)))(key)
    ctx.say(f"layers={dm.n_layers} batch={batch}x{seq} backlog_records={n_rec} "
            f"state_bytes={tree_bytes(state)}")
    it = StreamingBatchIterator(log, msg, batch, split="train", epochs=None, fetch_records=4096)
    stream = device_feed(iter(it), depth=2)

    def next_batch():
        with jax.profiler.TraceAnnotation("bench.feed_next"):
            return {"tokens": next(stream)["data"]}

    def one_step(state):
        b = next_batch()
        with jax.profiler.TraceAnnotation("bench.train_step"):
            state, metrics = step_fn(state, b)
            loss = {k: float(v) for k, v in metrics.items()}["loss"]
        return state, loss

    try:
        state, losses, grads, deltas = checked_steps(ctx, step_fn, state, next_batch, m, key)
        for _ in range(mix["warm_steps"]):
            state, _ = one_step(state)

        traced = TracedWindow(ctx.out_dir) if ctx.trace else None
        trace_s = min(float(mix["trace_seconds"]), ctx.seconds)
        t0 = time.perf_counter()
        t_end = t0 + ctx.seconds
        steps, bad, traced_steps = 0, 0, 0
        while True:
            now = time.perf_counter()
            if traced and traced.t0 is None and now >= t_end - trace_s:
                traced.start()
            if now >= t_end:
                break
            state, loss = one_step(state)
            steps += 1
            bad += not math.isfinite(loss)
            if traced and traced.running:
                traced_steps += 1
        t1 = time.perf_counter()
        if traced and traced.running:
            traced.stop()
    finally:
        close = getattr(stream, "close", None)
        if close is not None:
            close()
        it.close()
    peak_mem = peak_bytes(ctx.devices[:1])
    del state, stream, it
    gc.collect()

    tokens = steps * batch * seq
    e2e = {"train_tokens_per_s": tokens / (t1 - t0)}
    ctx.say(f"window_steps={steps} window_s={t1 - t0:.4f} losses={losses} peak_bytes={peak_mem}")
    rd = {"dims": dm, "counts": ctx.cell.reference, "precision": cfg["precision"], "chips": 1,
          "train": {"steps": steps, "seconds": t1 - t0, "batch": batch, "seq": seq,
                    "traced_steps": traced_steps}}

    ref = reference_readings(ctx, dm)
    checks = compare(losses, grads, deltas, ref, cfg["limits"])
    checks["nonfinite_window_losses"] = {"value": bad, "limit": 0}
    return {"e2e": e2e, "readings": rd, "checks": checks, "attempted": steps,
            "failed": bad, "memory_peak_bytes": peak_mem, "traced": traced,
            "window_open": t0}


def checked_steps(ctx: Ctx, step_fn, state, next_batch, m, key):
    """The first ``CHECKED_STEPS`` steps of ``state``, with the program's
    readings: each step's loss, the per-leaf norm of the first gradient
    as the optimizer received it, the per-leaf norm of the change; each
    leaf under its reference name."""
    leaf_name = ctx.cell.program.leaf_name
    first_grad, change = _norms_fn()
    losses, grads = [], {}
    for step in range(1, CHECKED_STEPS + 1):
        state, metrics = step_fn(state, next_batch())
        losses.append({k: float(v) for k, v in metrics.items()}["loss"])
        if step == 1:
            grads = _by_name(first_grad(state["opt"]["m"], 0.9), leaf_name)
    start = jax.jit(m.init)(key)
    deltas = _by_name(change(state["params"], start), leaf_name)
    return state, losses, grads, deltas


def checked_rows(ctx: Ctx) -> list[np.ndarray]:
    """The rows of the checked steps, one batch a step, as the stream
    delivers them."""
    t = ctx.cell.config["train"]
    vocab = ctx.cell.reference.dims(ctx.cell.config).vocab
    r = rows(ctx, t["batch"] * CHECKED_STEPS, t["seq"], vocab)
    return [r[i * t["batch"]:(i + 1) * t["batch"]] for i in range(CHECKED_STEPS)]


def reference_readings(ctx: Ctx, dm, fp8: bool = False) -> dict:
    cfg, reference = ctx.cell.config, ctx.cell.reference
    key = jax.random.PRNGKey(traffic.key_seed(ctx.seed))
    with jax.default_device(ctx.devices[0]):
        return reference.train_readings(
            dm, key, checked_rows(ctx), reference.AdamW(lr=cfg["train"]["lr"]),
            param_dtype=jnp.dtype(cfg["precision"]["param_dtype"]), fp8=fp8)


def compare(losses, grads, deltas, ref, limits) -> dict:
    """The three numbers, each beside its limit."""
    loss_gap = max(abs(a - b) for a, b in zip(losses, ref["losses"]))
    rg, rd = ref["grad_norms"], ref["delta_norms"]
    med_g = float(np.median(list(rg.values())))
    med_d = float(np.median(list(rd.values())))
    grad_gap = max(abs(grads[n] - rg[n]) / max(rg[n], med_g) for n in rg)
    moved = [n for n in rd if rg[n] >= 1e-3 * med_g]
    delta_gap = max(abs(deltas[n] - rd[n]) / max(rd[n], med_d) for n in moved)
    return {
        "loss_gap": {"value": loss_gap, "limit": limits["loss_gap"]},
        "first_grad_norm_gap": {"value": grad_gap, "limit": limits["first_grad_norm_gap"]},
        "change_norm_gap": {"value": delta_gap, "limit": limits["change_norm_gap"]},
    }
