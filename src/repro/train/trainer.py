"""Training jobs — the paper's Algorithm 1 on a JAX mesh.

Two layers:

* :func:`build_train_step` — the pjit'd SPMD step for the model zoo:
  in/out shardings derived from model + optimizer pspecs, optional
  microbatch gradient accumulation, donated state.
* :class:`TrainingJob` — the Kafka-ML training Job (paper §IV-C): fetch
  model spec from the registry, block on the control topic for its
  deployment_id, read the stream (train/eval split per validation_rate),
  train, upload trained artifact + metrics back to the registry.
  Checkpoints embed the stream offsets; ``resume=True`` restarts exactly
  where a killed job died (fault tolerance, paper §II/§V).

Plus :func:`dp_train_step` — a manual-DP (shard_map) step with int8
compressed gradient all-reduce for the pure data-parallel regime.

Jobs accept any :class:`~repro.core.log.StreamBackend`: against a
replicated :class:`~repro.core.cluster.BrokerCluster` the control topic and
the stream ranges a job reads both survive broker loss, so a stream
ingested at ``acks='all'`` remains trainable — and replayable to new
deployments (§V) — after any single broker dies. With
``ingest(idempotent=True)`` the stream a job trains on is additionally
**exactly-once**: client retries during ingest can neither duplicate a
training record nor re-announce the stream (a duplicated control message
would re-trigger training), and ``wait_for_control`` rides out
mid-election windows instead of dying on them — see DESIGN.md §7.
"""

from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Any, Callable, Iterator, Mapping

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.control import ControlMessage, poll_control
from repro.core.controller import ClusterError
from repro.core.log import StreamBackend
from repro.core.metrics import MetricsRegistry
from repro.core.registry import Registry
from repro.data.pipeline import (
    BatchIterator,
    ShardedFeeder,
    StreamDataset,
    StreamingBatchIterator,
    device_feed,
)
from repro.models.model import StreamModel
from repro.models.policy import Policy
from repro.train import checkpoint as ckpt_lib
from repro.train.compression import compressed_psum_mean
from repro.train.optimizer import Optimizer, adamw

__all__ = ["TrainingJob", "build_train_step", "dp_train_step", "make_state"]


# ------------------------------------------------------------- SPMD pjit step
def make_state(model: StreamModel, opt: Optimizer, rng) -> dict:
    params = model.init(rng)
    return {"params": params, "opt": opt.init(params)}


def state_pspecs(model: StreamModel, opt: Optimizer) -> dict:
    pspecs = model.param_pspecs()
    return {"params": pspecs, "opt": opt.state_pspecs(pspecs)}


def _to_microbatches(x: jax.Array, k: int, dp: int) -> jax.Array:
    """(B, ...) -> (k, B/k, ...) such that every microbatch spans every
    data shard.

    A plain reshape would turn the (contiguously) batch-sharded dim into a
    sharded *microbatch* dim — each accumulation step would then live on
    1/dp of the devices. Instead split per-shard rows across microbatches:
    shard d's rows [d*B/dp, ...) are dealt round-robin to the k steps, so
    each (B/k)-row microbatch keeps the full P(batch_axes) sharding.
    (This permutes which rows share a microbatch; rows are i.i.d. samples.)
    """
    b = x.shape[0]
    bl = b // (dp * k)
    y = x.reshape((dp, k, bl) + x.shape[1:])
    y = jnp.moveaxis(y, 1, 0)  # (k, dp, bl, ...)
    return y.reshape((k, dp * bl) + x.shape[1:])


def build_train_step(
    model: StreamModel,
    opt: Optimizer,
    *,
    microbatches: int = 1,
    donate: bool = True,
    mesh: Mesh | None = None,
):
    """Returns (step_fn, state_shardings). step_fn(state, batch) -> (state, metrics)."""

    def loss_fn(params, batch):
        loss, metrics = model.loss(params, batch)
        return loss, metrics

    def step(state, batch):
        b0 = jax.tree.leaves(batch)[0].shape[0]
        dp = model.policy.dp_degree
        k = min(microbatches, max(b0 // max(dp, 1), 1))  # each microbatch must cover DP
        if k > 1:

            def micro(acc, mb):
                (loss, metrics), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    state["params"], mb
                )
                acc_g, acc_loss = acc
                acc_g = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32) / k, acc_g, g
                )
                return (acc_g, acc_loss + loss / k), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state["params"]
            )
            mbs = jax.tree.map(lambda x: _to_microbatches(x, k, dp), batch)
            (grads, loss), _ = jax.lax.scan(
                micro, (zeros, jnp.float32(0.0)), mbs,
                unroll=True if model.policy.unroll else 1,
            )
            metrics = {"loss": loss}
        else:
            (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                state["params"], batch
            )
        new_params, new_opt = opt.update(grads, state["opt"], state["params"])
        return {"params": new_params, "opt": new_opt}, {
            **metrics,
            "loss": metrics["loss"],
        }

    if mesh is None:
        return jax.jit(step, donate_argnums=(0,) if donate else ()), None

    specs = state_pspecs(model, opt)
    shardings = jax.tree.map(
        lambda sp: NamedSharding(mesh, sp), specs, is_leaf=lambda x: isinstance(x, P)
    )
    batch_sharding = NamedSharding(mesh, P(model.policy.batch_axes))
    fn = jax.jit(
        step,
        in_shardings=(shardings, batch_sharding),
        out_shardings=(shardings, None),
        donate_argnums=(0,) if donate else (),
    )
    return fn, shardings


# --------------------------------------------------------- manual-DP variant
def dp_train_step(
    loss_fn: Callable,
    opt: Optimizer,
    mesh: Mesh,
    axis: str = "data",
    compress: bool = True,
):
    """Pure data parallelism with explicit (optionally int8-compressed)
    gradient all-reduce — params replicated, batch sharded over ``axis``."""
    from jax.experimental.shard_map import shard_map

    def local_step(state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p, b: loss_fn(p, b), has_aux=True
        )(state["params"], batch)
        if compress:
            grads = compressed_psum_mean(grads, axis)
        else:
            n = jax.lax.axis_size(axis)
            grads = jax.tree.map(
                lambda g: (jax.lax.psum(g.astype(jnp.float32), axis) / n).astype(g.dtype),
                grads,
            )
        loss = jax.lax.pmean(loss, axis)
        new_params, new_opt = opt.update(grads, state["opt"], state["params"])
        return {"params": new_params, "opt": new_opt}, {"loss": loss}

    rep = P()
    state_specs = None  # replicated everywhere

    def wrapped(state, batch):
        return shard_map(
            local_step,
            mesh=mesh,
            in_specs=(jax.tree.map(lambda _: rep, state), jax.tree.map(lambda _: P(axis), batch)),
            out_specs=(jax.tree.map(lambda _: rep, state), {"loss": rep}),
            check_rep=False,
        )(state, batch)

    return jax.jit(wrapped, donate_argnums=(0,))


# ------------------------------------------------------------- Training Job
@dataclasses.dataclass
class TrainResult:
    metrics: dict[str, float]
    eval_metrics: dict[str, float]
    steps: int
    control: ControlMessage


class TrainingJob:
    """Paper §IV-C Algorithm 1, with checkpoint/restart fault tolerance.

    One Job trains one model of a deployed configuration. ``run`` blocks
    on the control topic until a control message targets this deployment,
    then trains over the referenced stream ranges.
    """

    def __init__(
        self,
        log: StreamBackend,
        registry: Registry,
        deployment_id: str,
        model_id: str,
        *,
        loss_fn: Callable,  # (params, batch) -> (loss, metrics)
        init_fn: Callable,  # rng -> params
        opt: Optimizer | None = None,
        ckpt_dir: str | None = None,
        ckpt_every: int = 50,
        seed: int = 0,
        isolation_level: str | None = None,
    ):
        self.log = log
        self.registry = registry
        self.deployment_id = deployment_id
        self.model_id = model_id
        self.loss_fn = loss_fn
        self.init_fn = init_fn
        self.opt = opt or adamw(1e-3)
        self.ckpt_dir = ckpt_dir
        self.ckpt_every = ckpt_every
        self.seed = seed
        # "read_committed" pairs with ingest(transactional=True): the job
        # only ever acts on a control message whose whole stream is
        # durably committed — a crashed (aborted) ingest announces nothing
        self.isolation_level = isolation_level
        self.manager = (
            ckpt_lib.CheckpointManager(ckpt_dir) if ckpt_dir is not None else None
        )

    # ---------------------------------------------------------------- control
    def wait_for_control(self, poll_interval: float = 0.0, max_polls: int = 1000):
        """Algorithm 1's readControlStreams loop.

        On a cluster, the control topic can be momentarily unreadable
        mid-election (leaderless partition, no controller quorum); that
        counts as an empty poll and the loop retries — the same
        skip-and-retry contract the consumer-group read path uses — so a
        waiting training job survives a broker or controller failover
        instead of dying before its stream is even announced.
        """
        offset = 0
        for _ in range(max_polls):
            try:
                msg, offset = poll_control(
                    self.log, self.deployment_id, offset,
                    isolation=self.isolation_level,
                )
            except ClusterError:
                msg = None  # control topic unavailable mid-election
            if msg is not None:
                return msg
            if poll_interval:
                time.sleep(poll_interval)
        raise TimeoutError(
            f"no control message for deployment {self.deployment_id!r}"
        )

    # ------------------------------------------------------------------- run
    def run(
        self,
        *,
        batch_size: int,
        epochs: int = 1,
        resume: bool = False,
        max_steps: int | None = None,
        prefetch: int = 2,  # batches assembled ahead of the device step
        streaming: bool = False,
        fetch_records: int = 4096,
        crash_after: int | None = None,  # fault-injection hook for tests
    ) -> TrainResult:
        """Train over the announced stream.

        ``streaming=False`` (default) materializes the stream on the host
        (``StreamDataset.split()``) and trains with a seeded global
        shuffle. ``streaming=True`` is the broker→device path of
        DESIGN.md §10: a :class:`StreamingBatchIterator` polls the
        consumer ``fetch_records`` records at a time, zero-copy decodes,
        and :func:`device_feed` double-buffers ``jax.device_put`` so the
        next poll+decode+transfer overlaps the running device step —
        peak host memory is O(fetch_records), not O(stream), and resume
        fast-forwards by offset arithmetic instead of replaying batches.
        Streaming trains in stream order (no global shuffle — that would
        require exactly the materialization streaming avoids); both modes
        yield a deterministic batch sequence, so checkpoints resume
        exactly either way.
        """
        msg = self.wait_for_control()

        # jitted, so init's f32 draws fuse into the cast instead of being
        # materialised next to the params
        params = jax.jit(self.init_fn)(jax.random.PRNGKey(self.seed))
        state = {"params": params, "opt": self.opt.init(params)}
        start_step = 0
        if resume and self.manager is not None and self.manager.latest() is not None:
            state, offsets, meta = ckpt_lib.restore(self.ckpt_dir, state)
            start_step = int(meta.get("next_step", 0))

        # the state is donated: one copy of params + optimizer state
        @partial(jax.jit, donate_argnums=(0,))
        def step_fn(state, batch):
            (loss, metrics), grads = jax.value_and_grad(self.loss_fn, has_aux=True)(
                state["params"], batch
            )
            new_params, new_opt = self.opt.update(grads, state["opt"], state["params"])
            return {"params": new_params, "opt": new_opt}, metrics

        eval_arrays: dict[str, np.ndarray] | None = None
        if streaming:
            it = StreamingBatchIterator(
                self.log, msg, batch_size, split="train", epochs=None,
                fetch_records=fetch_records,
            )
            # resume = offset arithmetic: no records are fetched, decoded,
            # or transferred for the fast-forwarded prefix
            it.fast_forward(start_step)
        else:
            ds = StreamDataset(self.log, msg)
            train_arrays, eval_arrays = ds.split()
            it = BatchIterator(
                train_arrays, batch_size, seed=self.seed, epochs=None,
                shuffle=True, prefetch=prefetch,
            )
        steps_per_epoch = it.steps_per_epoch()
        total = max_steps if max_steps is not None else epochs * steps_per_epoch

        metrics = {}
        # step spans and throughput (nothing on backends with no registry):
        # each train_step span holds the feed's feed_wait and the loss
        # read-back, train_sync, and records train_step_seconds{deployment}
        reg = getattr(self.log, "metrics", None) or MetricsRegistry(enabled=False)
        records = reg.counter("train_records_total", deployment=self.deployment_id)
        # batch assembly overlaps the device step (prefetch is a bounded
        # background queue over the same deterministic batch sequence);
        # streaming additionally overlaps the device_put dispatch
        if streaming:
            stream = device_feed(iter(it), depth=prefetch)
        else:
            stream = iter(it)
        try:
            if not streaming:
                # deterministic resume: fast-forward the shuffled stream
                for _ in range(start_step):
                    next(stream)
            for step_i in range(start_step, total):
                with reg.span("train_step", id=step_i, keyed=True,
                              deployment=self.deployment_id):
                    nxt = next(stream)
                    batch = (
                        nxt if streaming
                        else {k: jnp.asarray(v) for k, v in nxt.items()}
                    )
                    state, m = step_fn(state, batch)
                    with reg.span("train_sync", keyed=True,
                                  deployment=self.deployment_id):
                        metrics = {k: float(v) for k, v in m.items()}
                records.inc(batch_size)
                done = step_i + 1
                if self.manager is not None and done % self.ckpt_every == 0:
                    self.manager.save_async(
                        done,
                        state,
                        offsets={str(r): r.end for r in msg.ranges},
                        meta={"next_step": done, "deployment_id": self.deployment_id},
                    )
                if crash_after is not None and done >= crash_after:
                    self.manager and self.manager.wait()
                    raise RuntimeError(f"injected crash after step {done}")
        finally:
            # the epochs=None stream is infinite: stop its prefetch worker
            close = getattr(stream, "close", None)
            if close is not None:
                close()
        if self.manager is not None:
            self.manager.save_async(
                total, state, offsets={str(r): r.end for r in msg.ranges},
                meta={"next_step": total, "deployment_id": self.deployment_id},
            )
            self.manager.wait()

        eval_metrics = {}
        n_eval = int(round(msg.total_msg * msg.validation_rate))
        if streaming:
            if msg.validation_rate > 0 and n_eval > 0:
                # bounded-memory eval: stream the tail split in batches and
                # average the metric means (equal-size batches, so the
                # average of means is exact up to the dropped remainder)
                acc: dict[str, float] = {}
                seen = 0
                ev = StreamingBatchIterator(
                    self.log, msg, min(batch_size, n_eval), split="eval",
                    epochs=1, fetch_records=fetch_records,
                )
                for eb in device_feed(iter(ev), depth=prefetch):
                    _, em = self.loss_fn(state["params"], eb)
                    for k, v in em.items():
                        acc[k] = acc.get(k, 0.0) + float(v)
                    seen += 1
                eval_metrics = {k: v / seen for k, v in acc.items()}
        elif msg.validation_rate > 0 and next(iter(eval_arrays.values())).shape[0] > 0:
            eb = {k: jnp.asarray(v) for k, v in eval_arrays.items()}
            _, em = self.loss_fn(state["params"], eb)
            eval_metrics = {k: float(v) for k, v in em.items()}

        artifact = None
        if self.ckpt_dir is not None:
            artifact = self.ckpt_dir
        self.registry.upload_result(
            self.deployment_id,
            self.model_id,
            metrics,
            eval_metrics,
            input_format=msg.input_format,
            input_config=msg.input_config,
            artifact_path=artifact,
        )
        self._final_state = state
        return TrainResult(metrics, eval_metrics, total, msg)
