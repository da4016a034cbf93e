"""Wave-batching LM engine: correctness vs unbatched generation + streaming."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
import repro.core as core
from repro.models.model import StreamModel
from repro.models.policy import Policy
from repro.serve.lm_engine import LMEngine, Request, serve_stream

PLEN, GEN = 12, 6


@pytest.fixture(scope="module")
def lm():
    cfg = C.get_reduced("yi-6b")
    model = StreamModel(cfg, Policy(param_dtype="float32", compute_dtype="float32"))
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _reference_generate(model, params, prompt: np.ndarray, n: int) -> np.ndarray:
    """Unbatched greedy decode — the oracle."""
    logits, cache = model.prefill(
        params, {"tokens": jnp.asarray(prompt[None, :])}, PLEN + n + 2,
        cache_dtype=jnp.float32,
    )
    tok = int(np.asarray(jnp.argmax(logits, -1))[0])
    out = [tok]
    for i in range(1, n):
        lg, cache = model.decode_step(
            params, cache, jnp.asarray([[tok]], jnp.int32), jnp.int32(PLEN + i - 1)
        )
        tok = int(np.asarray(jnp.argmax(lg[:, 0], -1))[0])
        out.append(tok)
    return np.array(out, np.int32)


@pytest.mark.slow
def test_wave_batched_matches_unbatched(lm):
    cfg, model, params = lm
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab, (5, PLEN)).astype(np.int32)
    engine = LMEngine(model, params, n_slots=4, s_cache=PLEN + GEN + 2)
    for i, p in enumerate(prompts):
        engine.submit(Request(i, p, GEN))
    results = dict(engine.run_until_drained())
    assert len(results) == 5 and engine.waves == 2  # 4 slots -> 2 waves
    for i, p in enumerate(prompts):
        want = _reference_generate(model, params, p, GEN)
        np.testing.assert_array_equal(results[i], want)


def test_early_stop_frees_lane_accounting(lm):
    cfg, model, params = lm
    rng = np.random.default_rng(1)
    engine = LMEngine(model, params, n_slots=2, s_cache=PLEN + GEN + 2)
    engine.submit(Request(0, rng.integers(0, cfg.vocab, PLEN).astype(np.int32), 2))
    engine.submit(Request(1, rng.integers(0, cfg.vocab, PLEN).astype(np.int32), GEN))
    results = dict(engine.run_until_drained())
    assert len(results[0]) == 2 and len(results[1]) == GEN
    assert 0.0 < engine.lane_utilization <= 1.0


def test_serve_stream_roundtrip(lm):
    cfg, model, params = lm
    rng = np.random.default_rng(2)
    log = core.StreamLog()
    log.create_topic("prompts")
    prompts = rng.integers(0, cfg.vocab, (6, PLEN)).astype(np.int32)
    log.produce_batch("prompts", [p.tobytes() for p in prompts])
    engine = LMEngine(model, params, n_slots=4, s_cache=PLEN + GEN + 2)
    served = serve_stream(engine, log, "prompts", "out", PLEN, max_new=GEN)
    assert served == 6
    recs = log.read("out", 0, 0, 10).to_matrix().view(np.int32).reshape(6, GEN + 1)
    assert sorted(recs[:, 0].tolist()) == list(range(6))
    # spot-check one completion against the oracle
    row = recs[recs[:, 0] == 3][0]
    want = _reference_generate(model, params, prompts[3], GEN)
    np.testing.assert_array_equal(row[1 : 1 + GEN], want)


# ----------------------------------------------------- continuous batching
from repro.serve.lm_engine import (  # noqa: E402
    ContinuousLMEngine,
    KVBlockTable,
    LMServingGroup,
    Request as Req,
    decode_completion,
    decode_request,
    encode_completion,
    encode_request,
    tenant_key,
)


def _continuous(model, params, n_slots=4):
    return ContinuousLMEngine(
        model, params, n_slots=n_slots, n_blocks=32, block_size=8, max_blocks=8
    )


def _mixed_requests(cfg, rng, n=9):
    """Mixed prompt lengths and budgets, grouped by length so the wave
    engine (equal-length waves) can serve the same set."""
    reqs, rid = [], 0
    for plen in (8, PLEN, 16):
        for _ in range(n // 3):
            reqs.append(Request(
                rid, rng.integers(0, cfg.vocab, plen).astype(np.int32),
                int(rng.integers(3, 9)),
            ))
            rid += 1
    return reqs


def test_continuous_matches_wave_greedy(lm):
    """THE parity pin: continuous batching emits token-identical greedy
    completions to the wave engine on a mixed-length request set."""
    cfg, model, params = lm
    reqs = _mixed_requests(cfg, np.random.default_rng(7))
    wave = LMEngine(model, params, n_slots=4, s_cache=64)
    for r in reqs:
        wave.submit(r)
    ref = dict(wave.run_until_drained())
    cont = _continuous(model, params)
    for r in reqs:
        cont.submit(r)
    got = dict(cont.run_until_drained())
    assert sorted(got) == sorted(ref)
    for rid in ref:
        np.testing.assert_array_equal(got[rid], ref[rid])
    # mixed lengths + spread max_new: continuous wastes fewer lane steps
    assert cont.lane_utilization > wave.lane_utilization


def test_continuous_matches_unbatched_reference(lm):
    cfg, model, params = lm
    rng = np.random.default_rng(3)
    prompts = rng.integers(0, cfg.vocab, (3, PLEN)).astype(np.int32)
    cont = _continuous(model, params, n_slots=2)
    for i, p in enumerate(prompts):
        cont.submit(Request(i, p, GEN))
    got = dict(cont.run_until_drained())
    for i, p in enumerate(prompts):
        np.testing.assert_array_equal(
            got[i], _reference_generate(model, params, p, GEN)
        )


def test_vector_pos_decode_matches_scalar(lm):
    """decode_step with a per-row position vector equals the scalar
    (lockstep) path when every row sits at the same position."""
    cfg, model, params = lm
    rng = np.random.default_rng(4)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab, (2, PLEN)).astype(np.int32))
    lg_s, cache_s = model.prefill(
        params, {"tokens": prompts}, PLEN + 4, cache_dtype=jnp.float32
    )
    cache_v = jax.tree.map(lambda a: a, cache_s)
    tok = jnp.argmax(lg_s, -1)[:, None]
    for i in range(3):
        lg1, cache_s = model.decode_step(
            params, cache_s, tok, jnp.int32(PLEN + i)
        )
        lg2, cache_v = model.decode_step(
            params, cache_v, tok, jnp.full((2,), PLEN + i, jnp.int32)
        )
        np.testing.assert_allclose(
            np.asarray(lg1), np.asarray(lg2), rtol=1e-5, atol=1e-5
        )
        tok = jnp.argmax(lg1[:, 0], -1)[:, None]


def test_slot_recycling_isolation(lm):
    """Admission mid-decode must not perturb in-flight rows: a request
    decodes to the same tokens alone and with churn around it."""
    cfg, model, params = lm
    rng = np.random.default_rng(5)
    target = Request(99, rng.integers(0, cfg.vocab, PLEN).astype(np.int32), GEN)
    solo = _continuous(model, params, n_slots=2)
    solo.submit(target)
    want = dict(solo.run_until_drained())[99]

    churn = _continuous(model, params, n_slots=2)
    churn.submit(target)
    out = churn.step()  # target admitted + one decode step
    # now admit short neighbours mid-flight; they finish and recycle
    # (freeing + reusing blocks) while the target is still decoding
    for i in range(4):
        churn.submit(Request(
            i, rng.integers(0, cfg.vocab, 8).astype(np.int32), 2
        ))
    while churn.qsize() or churn.active:
        out.extend(churn.step())
    got = dict(out)
    assert sorted(got) == [0, 1, 2, 3, 99]
    np.testing.assert_array_equal(got[99], want)


def test_block_table_reserve_release():
    bt = KVBlockTable(5)  # blocks 1..4 allocatable, 0 reserved scratch
    a = bt.reserve(2)
    b = bt.reserve(2)
    assert a == [1, 2] and b == [3, 4] and bt.reserve(1) is None
    bt.release(a)
    assert bt.free_blocks == 2 and 0 not in bt.reserve(2)
    with pytest.raises(ValueError):
        KVBlockTable(1)


def test_continuous_rejects_oversized_request(lm):
    cfg, model, params = lm
    cont = _continuous(model, params)  # capacity 8 blocks * 8 = 64 tokens
    with pytest.raises(ValueError):
        cont.submit(Request(0, np.zeros(60, np.int32), 16))


def test_submit_is_threadsafe(lm):
    import threading

    cfg, model, params = lm
    rng = np.random.default_rng(6)
    cont = _continuous(model, params)
    prompts = rng.integers(0, cfg.vocab, (8, 8)).astype(np.int32)

    def feed(lo, hi):
        for i in range(lo, hi):
            cont.submit(Request(i, prompts[i], 3))

    threads = [threading.Thread(target=feed, args=(i * 4, i * 4 + 4)) for i in range(2)]
    for t in threads:
        t.start()
    got = {}
    while any(t.is_alive() for t in threads) or cont.qsize() or cont.active:
        got.update(cont.run_until_drained())
    for t in threads:
        t.join()
    got.update(cont.run_until_drained())
    assert sorted(got) == list(range(8))


def test_request_codec_roundtrip():
    req = Req(12, np.arange(7, dtype=np.int32), 5, tenant=3)
    back = decode_request(encode_request(req))
    assert (back.req_id, back.tenant, back.max_new) == (12, 3, 5)
    np.testing.assert_array_equal(back.prompt, req.prompt)
    rid, tenant, gen = decode_completion(
        encode_completion(12, 3, np.array([4, 5, 6], np.int32))
    )
    assert (rid, tenant) == (12, 3)
    np.testing.assert_array_equal(gen, [4, 5, 6])


def test_serving_group_roundtrip_bare_log(lm):
    """Keyed requests through a 1-worker serving group on a bare
    StreamLog (non-transactional): all completions land keyed on the
    response topic and match the engine run directly."""
    cfg, model, params = lm
    rng = np.random.default_rng(8)
    log = core.StreamLog()
    log.create_topic("lmreq")
    reqs = [
        Request(i, rng.integers(0, cfg.vocab, 8 + 4 * (i % 2)).astype(np.int32),
                3 + i % 3, tenant=i % 2)
        for i in range(5)
    ]
    for r in reqs:
        log.produce("lmreq", encode_request(r), key=tenant_key(r.tenant))
    group = LMServingGroup(
        log, [_continuous(model, params)],
        input_topic="lmreq", response_topic="lmresp",
    )
    assert group.drain() == 5

    ref_engine = _continuous(model, params)
    for r in reqs:
        ref_engine.submit(r)
    ref = dict(ref_engine.run_until_drained())

    got = {}
    off, end = 0, log.end_offset("lmresp", 0)
    while off < end:
        batch = log.read("lmresp", 0, off, 64)
        for buf in batch.values:
            rid, tenant, gen = decode_completion(buf)
            assert tenant == rid % 2
            got[rid] = gen
        off = batch.next_offset
    assert sorted(got) == sorted(ref)
    for rid in ref:
        np.testing.assert_array_equal(got[rid], ref[rid])


def test_serving_tick_spans_and_request_stamps(lm):
    """Each publishing tick of a worker is serve_poll -> serve_submit ->
    serve_drain -> serve_publish; each request's queue span ends before
    its first token, which comes before the end of the serve_publish
    that carried it (its completion stamp)."""
    import time

    from repro.core.metrics import default_registry

    cfg, model, params = lm
    rng = np.random.default_rng(11)
    c = core.BrokerCluster(3)
    c.create_topic("lmreq", core.LogConfig(num_partitions=1))
    for i in range(5):
        r = Req(i, rng.integers(0, cfg.vocab, 8).astype(np.int32), 2 + i % 3)
        c.produce("lmreq", encode_request(r), key=tenant_key(r.tenant))
    engine = _continuous(model, params, n_slots=2)
    group = LMServingGroup(c, [engine], input_topic="lmreq", response_topic="lmresp",
                           transactional=True)
    t0 = time.perf_counter_ns()
    assert group.drain() == 5

    spans = c.metrics.recent_spans()
    publishes = [s for s in spans if s["span"] == "serve_publish"]
    ticks = {s["parent"] for s in publishes}
    for tick in (s for s in spans if s["seq"] in ticks):
        assert tick["span"] == "serve_tick" and tick["labels"] == {"worker": "worker-0"}
        kids = sorted((s for s in spans if s["parent"] == tick["seq"]),
                      key=lambda s: s["start_ns"])
        assert [k["span"] for k in kids] == [
            "serve_poll", "serve_submit", "serve_drain", "serve_publish"]
        bounds = [tick["start_ns"]] + [t for k in kids for t in (k["start_ns"], k["end_ns"])]
        assert bounds == sorted(bounds) and bounds[-1] <= tick["end_ns"]
    published = {rid: s["end_ns"] for s in publishes for rid in s["labels"]["ids"]}

    mine = [s for s in default_registry().recent_spans() if s["start_ns"] >= t0]
    queued = {s["id"]: s["end_ns"] for s in mine if s["span"] == "engine_queue"}
    admits = {s["id"] for s in mine if s["span"] == "engine_admit"}
    finishes = [s["id"] for s in mine if s["span"] == "engine_finish"]
    decodes = [s["labels"]["rows"] for s in mine if s["span"] == "engine_decode"]
    assert sorted(published) == sorted(queued) == sorted(admits) == sorted(finishes)
    assert sorted(published) == list(range(5))
    assert decodes and all(1 <= n <= 2 for n in decodes)
    for rid in range(5):
        first = engine.first_token_s[rid] * 1e9
        assert queued[rid] <= first <= published[rid]
