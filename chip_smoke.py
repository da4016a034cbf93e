"""Chip smoke run: the streaming serving and training paths on a TPU.

    python chip_smoke.py             # one chip: serve, correctness, train
    python chip_smoke.py --chips 4   # four serving replicas, one per chip

The one-chip run drives ``LMServingGroup`` -> ``ContinuousLMEngine`` ->
the paged KV cache at yi-6b's published widths and full depth (bf16
params, random weights from a seed) behind a replicated three-broker
cluster, checks the paged logits against the dense prefill/decode path,
and runs a few ``TrainingJob.run(streaming=True)`` steps fed from a token
topic on the same cluster. ``--chips 4`` runs only the replica phase and
the device-0 run it is compared with.

Each phase prints its facts on lines of their own; the last line of
stdout is one JSON object naming the device. The run fails (non-zero
exit, no JSON line) when JAX finds no TPU, and whenever a phase fails.
Everything runs in this one process: a chip belongs to one process.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time
from pathlib import Path
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import repro.configs as configs  # noqa: E402
import repro.core as core  # noqa: E402
import repro.data as data  # noqa: E402
from repro.core.metrics import default_registry  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models.model import ArchConfig, StreamModel  # noqa: E402
from repro.models.policy import Policy  # noqa: E402
from repro.serve import (  # noqa: E402
    ContinuousLMEngine,
    LMServingGroup,
    Request,
    decode_completion,
    encode_request,
    tenant_key,
)
from repro.train import TrainingJob, adamw  # noqa: E402

ARCH = "yi-6b"
SEED = 0

# paged cache of the serving engine: 8 slots over 256 blocks of 16 tokens,
# up to 64 blocks (1024 tokens) per row
ENGINE = dict(n_slots=8, n_blocks=256, block_size=16, max_blocks=64)
N_REQUESTS = 16
# one prompt length per admission bucket (3, 7 and 13 blocks): the
# admission prefill compiles once per distinct prompt length
PROMPT_LENS = (40, 100, 200)
MAX_NEW = (4, 16)  # generated tokens per request, inclusive range
N_TENANTS = 8

# paged vs dense logits, teacher-forced, bf16 weights: random-weight
# logits are about N(0, 1) after the final norm, and the two paths
# differ in batch width and reduction order, so bf16 rounding moves a
# logit by a few hundredths. A wrong position, mask or block table moves
# it by O(1).
N_FORCED = 7  # decode positions after the prompt's last one
LOGIT_TOL = 0.25

# training: yi-6b widths with the depth cut to what one v5e holds. The
# donated state (bf16 params, f32 AdamW moments) is 8.70 GB at 2 layers
# and the step's temporaries 3.75 GB (grads, saved activations; attention
# saves only q, k, v, its output and logsumexp), compiled for a described
# v5e
TRAIN_LAYERS = 2
TRAIN_BATCH = 2
TRAIN_SEQ = 1024
TRAIN_STEPS = 4


def say(phase: str, **facts) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in facts.items()), flush=True)


class CompileLog:
    """Counts XLA backend compiles, and their seconds, inside a ``with``."""

    _EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self.seconds = 0.0

    def _listen(self, event: str, secs: float, **_) -> None:
        if event == self._EVENT:
            self.count += 1
            self.seconds += secs

    def __enter__(self) -> "CompileLog":
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._listen)


def memory(device) -> dict:
    """The device's peak bytes in use so far and its limit (empty where
    the backend keeps no such count, as on the CPU)."""
    stats = device.memory_stats() or {}
    keys = ("peak_bytes_in_use", "bytes_limit")
    return {k: stats[k] for k in keys if k in stats}


def tree_bytes(tree) -> int:
    return sum(leaf.nbytes for leaf in jax.tree.leaves(tree))


def make_requests(cfg: ArchConfig, rng, prompt_lens=PROMPT_LENS) -> list[Request]:
    return [
        Request(
            rid,
            rng.integers(0, cfg.vocab, int(rng.choice(prompt_lens))).astype(np.int32),
            int(rng.integers(MAX_NEW[0], MAX_NEW[1] + 1)),
            tenant=int(rng.integers(0, N_TENANTS)),
        )
        for rid in range(N_REQUESTS)
    ]


def serve_requests(log, group, requests, cfg, req_topic, resp_topic) -> float:
    """Produce ``requests`` keyed by tenant, drain the group, read the
    completions back read_committed and check them: every request answered
    exactly once, with ``max_new`` tokens in ``[0, vocab)``. Returns the
    wall seconds of the drain."""
    for r in requests:
        log.produce(req_topic, encode_request(r), key=tenant_key(r.tenant))
    t0 = time.perf_counter()
    served = group.drain()
    wall = time.perf_counter() - t0
    answers: list[tuple[int, int, np.ndarray]] = []
    for part in range(log.num_partitions(resp_topic)):
        off, end = 0, log.end_offset(resp_topic, part)
        while off < end:
            batch = log.read(resp_topic, part, off, 64, isolation="read_committed")
            answers.extend(decode_completion(buf) for buf in batch.values)
            off = batch.next_offset
    want = {r.req_id: r for r in requests}
    got = sorted(rid for rid, _, _ in answers)
    if served != len(requests) or got != sorted(want):
        raise AssertionError(f"served {served}, answered ids {got}, want {sorted(want)}")
    for rid, tenant, gen in answers:
        r = want[rid]
        if tenant != r.tenant or len(gen) != r.max_new:
            raise AssertionError(f"request {rid}: tenant {tenant}, {len(gen)} tokens")
        if gen.min() < 0 or gen.max() >= cfg.vocab:
            raise AssertionError(f"request {rid}: token outside [0, {cfg.vocab})")
    return wall


class Served(NamedTuple):
    engine: ContinuousLMEngine
    requests: list[Request]


def serve_phase(log, cfg: ArchConfig, seed: int = SEED) -> Served:
    """One continuous engine behind a transactional serving group over a
    4-partition request topic; returns the engine for the correctness
    phase."""
    model = StreamModel(cfg, Policy())
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    say("serve", arch=cfg.name, layers=cfg.n_layers, d_model=cfg.d_model,
        param_bytes=tree_bytes(params), param_dtype=Policy().param_dtype)
    engine = ContinuousLMEngine(model, params, **ENGINE)
    log.create_topic("lm-requests", core.LogConfig(num_partitions=4))
    log.create_topic("lm-responses", core.LogConfig(num_partitions=4))
    group = LMServingGroup(
        log, [engine], input_topic="lm-requests", response_topic="lm-responses",
        transactional=True,
    )
    requests = make_requests(cfg, np.random.default_rng(seed))
    wall = serve_requests(log, group, requests, cfg, "lm-requests", "lm-responses")
    say("serve", served=len(requests), wall_s=wall,
        tokens=sum(r.max_new for r in requests), **memory(jax.devices()[0]))
    return Served(engine, requests)


def paged_logits(engine: ContinuousLMEngine, tokens: np.ndarray, n_prompt: int) -> np.ndarray:
    """Logits at positions ``n_prompt - 1 ..`` through the engine's own
    jitted admission and decode, with ``tokens[n_prompt:]`` forced. Uses a
    free slot and gives it back."""
    row = engine.slots.index(None)
    need = -(-len(tokens) // engine.block_size)
    blocks = engine.blocks.reserve(need)
    bt_row = np.zeros(engine.max_blocks, np.int32)
    bt_row[:need] = blocks
    n_prefill = -(-n_prompt // engine.block_size)
    first, caches = engine._admit(
        engine.params, engine.caches, tokens[None, :n_prompt], np.int32(row),
        np.asarray(blocks[:n_prefill], np.int32), bt_row, np.int32(n_prompt),
    )
    # each step's logits are read before the next step is queued, as the
    # engine reads its tokens: every queued step holds a copy of the cache
    out = [np.asarray(first, np.float32)]
    for p in range(n_prompt, len(tokens)):
        tok = np.zeros((engine.n_slots, 1), np.int32)
        pos = np.zeros(engine.n_slots, np.int32)
        tok[row, 0], pos[row] = tokens[p], p
        lg, caches = engine._decode(engine.params, caches, tok, pos)
        out.append(np.asarray(lg[row, 0], np.float32))
    engine.caches = engine._clear(caches, np.int32(row))
    engine.blocks.release(blocks)
    return np.stack(out)


def correctness_phase(served: Served, seed: int = SEED) -> float:
    """Teacher-force one prompt of each length through the engine's paged
    path and the dense path (``prefill`` + ``decode_step`` on a dense
    cache, as ``LMEngine`` runs it); fail when a logit differs by more
    than ``LOGIT_TOL``. Returns the largest difference."""
    engine = served.engine
    model, params = engine.model, engine.params
    s_cache = engine.max_blocks * engine.block_size
    prefill = jax.jit(
        lambda p, t: model.prefill(p, {"tokens": t}, s_cache, cache_dtype=jnp.float32)
    )
    decode = jax.jit(model.decode_step)
    rng = np.random.default_rng(seed + 1)
    prompts = {len(r.prompt): r.prompt for r in served.requests}
    worst, scale = 0.0, 0.0
    for plen in sorted(prompts):
        forced = rng.integers(0, model.cfg.vocab, N_FORCED).astype(np.int32)
        tokens = np.concatenate([prompts[plen], forced])
        paged = paged_logits(engine, tokens, plen)
        lg, cache = prefill(params, tokens[None, :plen])
        dense = [lg[0]]
        for p in range(plen, len(tokens)):
            lg, cache = decode(params, cache, tokens[None, p : p + 1], np.int32(p))
            dense.append(lg[0, 0])
        dense = np.stack([np.asarray(x, np.float32) for x in dense])
        if not (np.isfinite(paged).all() and np.isfinite(dense).all()):
            raise AssertionError(f"prompt of {plen}: non-finite logits")
        worst = max(worst, float(np.abs(paged - dense).max()))
        scale = max(scale, float(np.abs(dense).max()))
    say("correctness", prompts=len(prompts), positions=N_FORCED + 1,
        max_abs_logit_err=worst, tol=LOGIT_TOL, max_abs_logit=scale,
        **memory(jax.devices()[0]))
    if not worst <= LOGIT_TOL:
        raise AssertionError(f"paged vs dense logits differ by {worst} > {LOGIT_TOL}")
    return worst


def attention_calls() -> dict[str, int]:
    """Traced attention calls so far, by the path each took on a TPU."""
    reg = default_registry()
    return {p: reg.counter_value("attention_calls_total", path=p)
            for p in ("flash", "chunked")}


def train_phase(log, cfg: ArchConfig, n_layers: int = TRAIN_LAYERS, seed: int = SEED) -> dict:
    """A few streaming training steps at ``cfg``'s widths with the depth cut
    to ``n_layers``, fed from a token topic on ``log``. Returns the final
    metrics."""
    cut = dataclasses.replace(cfg, n_layers=min(n_layers, cfg.n_layers))
    model = StreamModel(cut, Policy())
    say("train", arch=cut.name, layers=cut.n_layers, of_layers=cfg.n_layers,
        batch=TRAIN_BATCH, seq=TRAIN_SEQ, steps=TRAIN_STEPS,
        live_bytes_before=tree_bytes(jax.live_arrays()))
    registry = core.Registry()
    spec = registry.register_model(cut.name)
    dep = registry.deploy(registry.create_configuration([spec.model_id]).config_id, "train")
    rng = np.random.default_rng(seed)
    n = TRAIN_BATCH * TRAIN_STEPS
    tokens = rng.integers(0, cut.vocab, (n, TRAIN_SEQ)).astype(np.int32)
    log.create_topic("lm-tokens", core.LogConfig(num_partitions=1))
    data.ingest(
        log, "lm-tokens", data.RawCodec("int32", (TRAIN_SEQ,), "int32", ()),
        {"data": tokens, "label": np.zeros(n, np.int32)}, dep.deployment_id,
    )
    job = TrainingJob(
        log, registry, dep.deployment_id, spec.model_id,
        loss_fn=lambda p, b: model.loss(p, {"tokens": b["data"]}),
        init_fn=model.init, opt=adamw(1e-4), seed=seed,
    )
    calls_before = attention_calls()
    t0 = time.perf_counter()
    res = job.run(batch_size=TRAIN_BATCH, max_steps=TRAIN_STEPS, streaming=True)
    wall = time.perf_counter() - t0
    loss = res.metrics["loss"]
    steps = log.metrics.recent_spans("train_step")
    calls = {f"attention_{p}": n - calls_before[p] for p, n in attention_calls().items()}
    say("train", steps=res.steps, loss=loss, wall_s=wall,
        last_step_s=steps[-1]["total_s"] if steps else None,
        **calls, **memory(jax.devices()[0]))
    if res.steps != TRAIN_STEPS or not np.isfinite(loss):
        raise AssertionError(f"{res.steps} steps, loss {loss}")
    return res.metrics


def replica_phase(log, cfg: ArchConfig, devices, seed: int = SEED) -> float:
    """One engine per device behind one serving group over an 8-partition
    topic. Each engine's params and cache must sit on its own device, and
    each request's first-token logits must match the device-0 engine's.
    Returns the largest logit difference."""
    model = StreamModel(cfg, Policy())
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    engines = [
        ContinuousLMEngine(model, jax.device_put(params, d), **ENGINE) for d in devices
    ]
    del params
    for i, (eng, dev) in enumerate(zip(engines, devices)):
        where = {d for leaf in jax.tree.leaves((eng.params, eng.caches)) for d in leaf.devices()}
        if where != {dev}:
            raise AssertionError(f"replica {i}: arrays on {where}, want {dev}")
    log.create_topic("lm-requests", core.LogConfig(num_partitions=8))
    log.create_topic("lm-responses", core.LogConfig(num_partitions=8))
    group = LMServingGroup(
        log, engines, input_topic="lm-requests", response_topic="lm-responses",
        transactional=True,
    )
    # one prompt length: one admission compile per replica
    requests = make_requests(cfg, np.random.default_rng(seed), PROMPT_LENS[1:2])
    wall = serve_requests(log, group, requests, cfg, "lm-requests", "lm-responses")
    served_by = {rid: i for i, eng in enumerate(engines) for rid in eng.first_token_s}
    per_replica = [sum(1 for i in served_by.values() if i == k) for k in range(len(engines))]
    replica_bytes = tree_bytes(engines[0].params)
    peaks = [memory(d).get("peak_bytes_in_use") for d in devices]
    say("replicas", devices=len(devices), served=len(requests), wall_s=wall,
        per_replica=per_replica, param_bytes=replica_bytes, peak_bytes_in_use=peaks)
    if any(p is not None and p < replica_bytes for p in peaks):
        raise AssertionError(f"a device peaked below one replica: {peaks}")
    worst = 0.0
    for r in requests:
        mine = paged_logits(engines[served_by[r.req_id]], r.prompt, len(r.prompt))
        ref = paged_logits(engines[0], r.prompt, len(r.prompt))
        worst = max(worst, float(np.abs(mine - ref).max()))
    say("replicas", compared=len(requests), max_abs_logit_err=worst, tol=LOGIT_TOL)
    if not worst <= LOGIT_TOL:
        raise AssertionError(f"replica logits differ from device 0 by {worst}")
    return worst


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # libtpu logs nowhere
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing run")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but {len(devices)} devices")
    cache_dir = enable_compile_cache()
    say("device", kind=dev.device_kind, count=len(devices), jax=jax.__version__,
        compile_cache=cache_dir)
    cfg = configs.get(ARCH)
    log = core.BrokerCluster(3)
    if args.chips == 4:
        with CompileLog() as c:
            replica_phase(log, cfg, devices[:4])
        say("replicas", compiles=c.count, compile_s=c.seconds)
    else:
        with CompileLog() as c:
            served = serve_phase(log, cfg)
        say("serve", compiles=c.count, compile_s=c.seconds)
        with CompileLog() as c:
            correctness_phase(served)
        say("correctness", compiles=c.count, compile_s=c.seconds)
        del served  # frees the serving params and cache before training
        gc.collect()
        with CompileLog() as c:
            train_phase(log, cfg)
        say("train", compiles=c.count, compile_s=c.seconds)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
    }}))


if __name__ == "__main__":
    main()
