"""Serving cells: open-loop LM requests through the broker.

One ``LMServingGroup(transactional=True)`` over one
``ContinuousLMEngine`` per chip, behind a three-broker ``BrokerCluster``
with tenant-keyed request and response topics. A producer thread sends
each request when it is due; the serving loop calls ``poll_all``; a
client thread reads the response topic ``read_committed`` and stamps
each completion as it becomes readable. Latency runs from the moment a
request was due to that stamp.

Set-up sends one request of every prompt length of the mix to every
replica, so every program the window runs is compiled before it opens.
After the window the loop serves on until every request due in it is
answered (or the mix's ``grace_s``, a minute by default, has passed),
and the run is judged:

- exactly once: every request due in the window answered once,
  ``read_committed``, for its tenant, with ``max_new`` tokens in range.
  A mix that offers more than the chips can serve sets
  ``owes_unconsumed: false``: a request still in the topic past the
  group's committed offset at the end is then neither answered nor owed,
  while every request the group consumed and committed is owed;
- the tokens: a sample of finished requests, drawn from the seed and
  holding the longest, is run through the float32 reference teacher-
  forced on the served tokens; the gaps by which each served token's
  reference logit lies below the reference's best are held to the
  configuration's limits (``gap_numbers``).
"""

from __future__ import annotations

import gc
import threading
import time

import jax
import numpy as np

import repro.core as core
from bench.kinds.common import Ctx, TracedWindow
from bench.lib import traffic
from bench.lib.probes import CompileLog, peak_bytes, tree_bytes
from repro.models.model import quantize_params
from repro.serve import (
    ContinuousLMEngine,
    LMServingGroup,
    Request,
    decode_completion,
    encode_request,
    tenant_key,
)

REQ, RESP = "lm-requests", "lm-responses"
WARM_RID = 1 << 30  # warm-up requests' ids, apart from the window's
GRACE_S = 60.0  # how long after the window the run waits for answers
CLIENT_IDLE_S = 0.002


class Producer(threading.Thread):
    """Sends each planned request when it is due, timing the produce call."""

    def __init__(self, log, plan, t0: float):
        super().__init__(name="bench-producer", daemon=True)
        self.log, self.plan, self.t0 = log, plan, t0
        self.sent: dict[int, float] = {}
        self.produce_s: dict[int, float] = {}
        self.placed: dict[int, tuple[int, int]] = {}  # rid -> (partition, offset)
        self.error: BaseException | None = None

    def run(self) -> None:
        try:
            for p in self.plan:
                wait = self.t0 + p.due_s - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                rec = encode_request(Request(p.rid, p.prompt, p.max_new, tenant=p.tenant))
                a = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.produce"):
                    placed = self.log.produce(REQ, rec, key=tenant_key(p.tenant))
                b = time.perf_counter()
                self.sent[p.rid], self.produce_s[p.rid] = a, b - a
                self.placed[p.rid] = placed
        except BaseException as e:  # reported by the serving loop
            self.error = e


class Client(threading.Thread):
    """Reads completions ``read_committed`` and stamps each one."""

    def __init__(self, log, offsets: list[int]):
        super().__init__(name="bench-client", daemon=True)
        self.log, self.offsets = log, list(offsets)
        self.done: dict[int, tuple[float, int, np.ndarray]] = {}
        self.duplicates = 0
        self.stop = threading.Event()
        self.error: BaseException | None = None

    def run(self) -> None:
        try:
            while not self.stop.is_set():
                got = 0
                for part, off in enumerate(self.offsets):
                    with jax.profiler.TraceAnnotation("bench.client_fetch"):
                        batch = self.log.read(RESP, part, off, 1024, isolation="read_committed")
                    now = time.perf_counter()
                    for buf in batch.values:
                        rid, tenant, gen = decode_completion(buf)
                        if rid in self.done:
                            self.duplicates += 1
                        else:
                            self.done[rid] = (now, tenant, gen)
                    self.offsets[part] = batch.next_offset
                    got += len(batch)
                if not got:
                    time.sleep(CLIENT_IDLE_S)
        except BaseException as e:  # reported by the serving loop
            self.error = e


def _end_offsets(log, topic: str) -> list[int]:
    return [log.end_offset(topic, p) for p in range(log.num_partitions(topic))]


def _build(ctx: Ctx):
    cfg, mix = ctx.cell.config, ctx.cell.mix
    extra = {"weights_int8": True} if ctx.variant == "program_int8" else {}
    m = ctx.cell.program.model(cfg, **extra)
    key = jax.random.PRNGKey(traffic.key_seed(ctx.seed))
    engines = []
    for dev in ctx.devices[: ctx.cell.chips]:
        init = m.init if not extra else (lambda k: quantize_params(m.init(k)))
        params = jax.jit(init, out_shardings=jax.sharding.SingleDeviceSharding(dev))(key)
        engines.append(ContinuousLMEngine(m, params, **cfg["serve"]))
    log = core.BrokerCluster(3)
    parts = mix["request_partitions"]
    log.create_topic(REQ, core.LogConfig(num_partitions=parts))
    log.create_topic(RESP, core.LogConfig(num_partitions=parts))
    group = LMServingGroup(log, engines, input_topic=REQ, response_topic=RESP,
                           transactional=True)
    return m, engines, log, group


def _warm(ctx: Ctx, log, group, vocab: int) -> int:
    """Every prompt length of the mix through every replica, end to end."""
    r = traffic.rng(ctx.seed, 0)
    rid = WARM_RID
    for w in group.workers:
        part = group.group.assignment(w.worker_id)[0].partition
        for n in traffic.prompt_table(ctx.cell.mix):
            req = Request(rid, r.integers(0, vocab, n).astype(np.int32), 2)
            log.produce(REQ, encode_request(req), partition=part)
            rid += 1
    want = rid - WARM_RID
    served = 0
    while served < want:
        got = group.drain()
        if not got:
            raise RuntimeError(f"warm-up served {served} of {want}")
        served += got
    return want


def _counters(engines) -> tuple[int, int]:
    return sum(e.lane_steps for e in engines), sum(e.useful_steps for e in engines)


def window(ctx: Ctx, engines, log, group, plan, traced: TracedWindow | None) -> dict:
    """Offer ``plan`` open loop for ``ctx.seconds``, then serve on until
    every request due in the window is answered or the mix's grace has
    passed. Returns what was measured; the engines' counters are taken at
    the window's close."""
    trace_s = min(float(ctx.cell.mix["trace_seconds"]), ctx.seconds)
    grace = float(ctx.cell.mix.get("grace_s", GRACE_S))
    client = Client(log, _end_offsets(log, RESP))
    lane0, useful0 = _counters(engines)
    snap = {}
    client.start()
    t0 = time.perf_counter() + 0.01
    t_end = t0 + ctx.seconds
    trace_from = t0 + (ctx.seconds - trace_s) / 2  # mid-window: steady load
    producer = Producer(log, plan, t0)
    producer.start()
    want = {p.rid for p in plan}
    with CompileLog() as compiles:
        while True:
            now = time.perf_counter()
            # the trace opens and closes between two poll_all calls, each of
            # which drains its engines: it holds whole requests
            if traced and traced.t0 is None and now >= trace_from:
                traced.start()
                snap["start"] = _counters(engines)
            elif traced and traced.running and now >= traced.t0 + trace_s:
                snap["stop"] = _counters(engines)
                traced.stop()
            if now >= t_end and "end" not in snap:
                snap["end"] = _counters(engines)
            if now >= t_end and (want <= client.done.keys() or now >= t_end + grace):
                break
            for t in (producer, client):
                if t.error is not None:
                    raise RuntimeError(f"{t.name} failed") from t.error
            with jax.profiler.TraceAnnotation("bench.poll_all"):
                got = group.poll_all()
            if not got:
                with jax.profiler.TraceAnnotation("bench.wait"):
                    time.sleep(0.0005)
    if traced and traced.running:
        snap["stop"] = _counters(engines)
        traced.stop()
    lane1, useful1 = snap["end"]
    producer.join(timeout=GRACE_S)
    # everything published before the loop ended is read before the
    # client stops, so that no answer is judged missing for being unread
    ends = _end_offsets(log, RESP)
    deadline = time.perf_counter() + GRACE_S
    while any(o < e for o, e in zip(client.offsets, ends)) and time.perf_counter() < deadline:
        time.sleep(CLIENT_IDLE_S)
    client.stop.set()
    client.join(timeout=GRACE_S)
    if producer.is_alive() or client.is_alive():
        raise RuntimeError("producer or client thread did not end")
    first_token = {}
    for e in engines:
        first_token.update(e.first_token_s)
    due = {p.rid: t0 + p.due_s for p in plan}
    answered = {rid: v for rid, v in client.done.items() if rid in due}
    # a request at or past its partition's committed offset was never
    # consumed: the group owes it nothing yet
    committed = [group.group.committed(core.TopicPartition(REQ, p))
                 for p in range(log.num_partitions(REQ))]
    unconsumed = {rid for rid in due if rid not in answered
                  and (rid not in producer.placed
                       or producer.placed[rid][1] >= committed[producer.placed[rid][0]])}
    out = {
        "t0": t0, "t_end": t_end, "due": due, "answered": answered, "unconsumed": unconsumed,
        "duplicates": client.duplicates, "compiles": compiles.count,
        "lateness": traffic.lateness([due[r] for r in producer.sent],
                                     list(producer.sent.values())),
        "requests": [
            {"rid": p.rid, "prompt_len": len(p.prompt), "max_new": p.max_new,
             "due": due[p.rid], "produce_s": producer.produce_s.get(p.rid),
             "first_token": first_token.get(p.rid),
             "done": answered[p.rid][0] if p.rid in answered else None}
            for p in plan
        ],
        "window": {"t0": t0, "t1": t_end, "lane_steps": lane1 - lane0,
                   "useful_steps": useful1 - useful0},
    }
    if traced:
        (l0, u0), (l1, u1) = snap["start"], snap["stop"]
        out["traced"] = {
            "t0": traced.t0, "t1": traced.t1, "lane_steps": l1 - l0, "useful_steps": u1 - u0,
            "admitted": [(len(p.prompt), p.max_new) for p in plan
                         if traced.t0 <= first_token.get(p.rid, -1.0) <= traced.t1],
        }
    return out


def latencies_ms(w: dict) -> list[float]:
    return [(w["answered"][r][0] - w["due"][r]) * 1e3 for r in w["due"] if r in w["answered"]]


def run(ctx: Ctx, peak: dict) -> dict:
    cfg, mix = ctx.cell.config, ctx.cell.mix
    dm = ctx.cell.reference.dims(cfg)
    m, engines, log, group = _build(ctx)
    plan = traffic.schedule(mix, ctx.seconds, ctx.seed, dm.vocab)
    n_warm = _warm(ctx, log, group, dm.vocab)
    ctx.say(f"replicas={len(engines)} param_bytes={tree_bytes(engines[0].params)} "
            f"warm_requests={n_warm} planned={len(plan)}")
    traced = TracedWindow(ctx.out_dir) if ctx.trace else None
    w = window(ctx, engines, log, group, plan, traced)
    peak_mem = peak_bytes(ctx.devices[: ctx.cell.chips])
    answered = w["answered"]
    ctx.say(f"answered={len(answered)}/{len(plan)} unconsumed={len(w['unconsumed'])} "
            f"duplicates={w['duplicates']} "
            f"window_compiles={w['compiles']} lateness={w['lateness']} peak_bytes={peak_mem}")
    lat = latencies_ms(w)
    e2e = {}
    if lat:
        e2e["req_latency_p50_ms"] = float(np.percentile(lat, 50))
        e2e["req_latency_p95_ms"] = float(np.percentile(lat, 95))
    rd = {"dims": dm, "counts": ctx.cell.reference, "precision": cfg["precision"],
          "chips": len(engines),
          "window_s": ctx.seconds, "requests": w["requests"], "window": w["window"]}
    if traced:
        rd["traced"] = w["traced"]
    del group, engines, log, m
    gc.collect()
    checks, failed, sample, gaps = judge(ctx, dm, plan, w)
    return {"e2e": e2e, "readings": rd, "checks": checks, "attempted": len(plan),
            "failed": failed, "memory_peak_bytes": peak_mem, "traced": traced,
            "window_open": w["t0"], "sample": sample, "gaps": gaps}


def judge(ctx: Ctx, dm, plan, w: dict):
    """Exactly once, then the served tokens against the reference. Runs
    after the program's state is freed."""
    answered = w["answered"]
    wrong = []
    for p in plan:
        if p.rid not in answered:
            continue
        _, tenant, gen = answered[p.rid]
        if tenant != p.tenant or len(gen) != p.max_new or gen.min() < 0 or gen.max() >= dm.vocab:
            wrong.append(p.rid)
    # in a mix that offers more than the chips serve, a request still in
    # the topic at the close is neither answered nor owed
    owed = set(w["due"]) if ctx.cell.mix.get("owes_unconsumed", True) else \
        set(w["due"]) - w["unconsumed"]
    unanswered = len(owed - answered.keys())
    sample = _sample(ctx, plan, answered)
    checks = {
        "unanswered": {"value": unanswered, "limit": 0},
        "duplicates": {"value": w["duplicates"], "limit": 0},
        "wrong_answers": {"value": len(wrong), "limit": 0},
    }
    t = time.perf_counter()
    gaps = _gaps(ctx, dm, sample) if sample else []
    ctx.say(f"reference_s={time.perf_counter() - t:.3f} requests_compared={len(sample)}")
    want = ctx.cell.mix["check_tokens"]
    checks["served_tokens_compared"] = {"value": sum(len(g) for g in gaps), "limit": want,
                                        "at_least": True}
    if gaps:
        limits = ctx.cell.config["limits"]
        for name, value in gap_numbers(np.concatenate(gaps)).items():
            if name in limits:
                checks[name] = {"value": value, "limit": limits[name]}
    return checks, unanswered + len(wrong), sample, gaps


def gap_numbers(every: np.ndarray) -> dict[str, float]:
    """What the served-token gaps are judged by: the widest; the mean over
    every token compared; the share of tokens that are not the
    reference's first choice. A configuration holds those it gives a
    limit."""
    return {"served_logit_gap": float(every.max()),
            "served_logit_gap_mean": float(every.mean()),
            "served_flip_share": float((every > 0).mean())}


def _sample(ctx: Ctx, plan, answered) -> list[tuple[np.ndarray, np.ndarray]]:
    """Finished requests drawn from the seed, the longest first, until
    they hold ``check_tokens`` served tokens."""
    done = [p for p in plan if p.rid in answered]
    if not done:
        return []
    longest = max(done, key=lambda p: (len(p.prompt) + p.max_new, -p.rid))
    rest = [p for p in done if p is not longest]
    order = [longest] + [rest[i] for i in traffic.rng(ctx.seed, traffic.STREAM_SAMPLE)
                         .permutation(len(rest))]
    out, tokens = [], 0
    for p in order:
        if tokens >= ctx.cell.mix["check_tokens"]:
            break
        out.append((p.prompt, answered[p.rid][2]))
        tokens += len(answered[p.rid][2])
    return out


def _gaps(ctx: Ctx, dm, sample) -> list[np.ndarray]:
    serve = ctx.cell.config["serve"]
    t_max = serve["max_blocks"] * serve["block_size"]
    p_max = ctx.cell.mix["max_new"]["max"]
    reference = ctx.cell.reference
    key = jax.random.PRNGKey(traffic.key_seed(ctx.seed))
    with jax.default_device(ctx.devices[0]):
        params = jax.jit(lambda k: reference.init_params(dm, k))(key)
        return reference.served_gaps(params, dm, sample, t_max, p_max)
