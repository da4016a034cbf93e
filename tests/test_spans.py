"""Spans on the profiler's clock (DESIGN.md §9).

- the bridge: a ``with`` span opens a ``repro.<name>`` TraceAnnotation
  while a JAX profiler session records, nested as the code nests, and
  none outside a trace or from a disabled registry;
- the span log: parent links (per thread, across registries), shared
  ids, the traced flag and session number, ``record_span``, the bound;
- the broker -> device feed's spans and counters, and the training
  loop's ``train_step`` / ``train_sync`` spans.
"""

import threading
import time
from pathlib import Path

import jax
import numpy as np
import pytest

import repro.core as core
import repro.data as data
from repro.core.metrics import (
    SPAN_LOG_BOUND,
    TRACE_PREFIX,
    MetricsRegistry,
    default_registry,
)
from repro.data.pipeline import PrefetchIterator, StreamingBatchIterator, device_feed


def annotations(log_dir: Path) -> dict[str, list[tuple[int, int, dict]]]:
    """The ``repro.*`` host events of the trace under ``log_dir``:
    name -> [(start_ns, end_ns, stats)]."""
    from jax.profiler import ProfileData

    [path] = sorted(Path(log_dir).rglob("*.xplane.pb"))
    out: dict[str, list] = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(TRACE_PREFIX):
                    out.setdefault(e.name, []).append(
                        (int(e.start_ns), int(e.end_ns), dict(e.stats)))
    return out


# ------------------------------------------------------------------ bridge
def test_with_spans_land_in_the_trace_nested(tmp_path):
    r = MetricsRegistry()
    with jax.profiler.trace(str(tmp_path)):
        with r.span("outer", id=7, stage="a"):
            with r.span("inner", rows=3):
                time.sleep(0.002)
    got = annotations(tmp_path)
    [(o0, o1, ostats)] = got["repro.outer"]
    [(i0, i1, istats)] = got["repro.inner"]
    assert o0 <= i0 < i1 <= o1
    assert i1 - i0 >= 2_000_000
    assert ostats["id"] == 7 and ostats["stage"] == "a"
    assert istats["rows"] == 3
    outer, inner = r.recent_spans("outer")[0], r.recent_spans("inner")[0]
    assert inner["parent"] == outer["seq"]
    assert outer["traced"] and inner["traced"]


def test_no_annotation_outside_a_trace_or_from_a_disabled_registry(tmp_path):
    on, off = MetricsRegistry(), MetricsRegistry(enabled=False)
    with on.span("before"):
        pass
    with jax.profiler.trace(str(tmp_path)):
        with off.span("disabled"):
            pass
        loose = on.span("loose")  # ended by a later call: log only
        with on.span("during"):
            pass
        loose.end()
        on.record_span("queued", time.perf_counter_ns() - 1000, time.perf_counter_ns())
    with on.span("after"):
        pass
    names = set(annotations(tmp_path))
    assert "repro.during" in names
    assert not names & {"repro.before", "repro.disabled", "repro.loose",
                        "repro.queued", "repro.after"}
    traced = {s["span"]: s["traced"] for s in on.recent_spans()}
    assert traced == {"before": False, "during": True, "loose": True,
                      "queued": True, "after": False}
    assert off.recent_spans() == [] and off.snapshot()["histograms"] == {}


def test_each_trace_is_a_new_session(tmp_path):
    r = MetricsRegistry()
    for i in range(2):
        with jax.profiler.trace(str(tmp_path / str(i))):
            for _ in range(2):
                with r.span("traced"):
                    pass
        with r.span("between"):
            pass
    first, second = (s["session"] for s in r.recent_spans("traced")[::2])
    assert [s["session"] for s in r.recent_spans("traced")] == [first, first, second, second]
    assert second > first >= 1
    assert all(s["session"] is None and not s["traced"] for s in r.recent_spans("between"))


# ---------------------------------------------------------------- span log
def test_parent_links_follow_the_thread_across_registries():
    a, b = MetricsRegistry(), MetricsRegistry()
    with a.span("step", id=5) as step:
        with b.span("wait", id=5):
            pass
        loose = b.span("loose")
        loose.end()
        other = threading.Thread(target=lambda: b.span("elsewhere").end())
        other.start()
        other.join()
        b.record_span("queued", 10, 25, id=5, parent=step)
        b.record_span("orphan", 10, 25)
    [s] = a.recent_spans("step")
    recs = {x["span"]: x for x in b.recent_spans()}
    assert s["seq"] == step.seq and s["parent"] is None
    assert recs["wait"]["parent"] == recs["loose"]["parent"] == step.seq
    assert recs["queued"]["parent"] == step.seq
    assert recs["elsewhere"]["parent"] is None and recs["orphan"]["parent"] is None
    assert {s["id"], recs["wait"]["id"], recs["queued"]["id"]} == {5}
    w = recs["wait"]
    assert s["start_ns"] <= w["start_ns"] <= w["end_ns"] <= s["end_ns"]
    assert (recs["queued"]["start_ns"], recs["queued"]["end_ns"]) == (10, 25)
    assert recs["queued"]["total_s"] == pytest.approx(15e-9)
    assert b.histogram("queued_seconds").count == 1


def test_keyed_spans_label_their_series_and_ids_never_do():
    r = MetricsRegistry()
    with r.span("train_step", id=3, keyed=True, deployment="d") as sp:
        sp.phase("fetch")
    with r.span("op", id=4, worker="w"):
        pass
    assert set(r.snapshot()["histograms"]) == {
        'train_step_seconds{deployment="d"}',
        'train_step_fetch_seconds{deployment="d"}',
        "op_seconds",
    }
    assert r.recent_spans("op")[0]["labels"] == {"worker": "w"}


def test_span_log_is_bounded():
    r = MetricsRegistry()
    for i in range(SPAN_LOG_BOUND + 10):
        r.record_span("x", i, i + 1, id=i)
    recs = r.recent_spans()
    assert len(recs) == SPAN_LOG_BOUND
    assert recs[0]["id"] == 10 and recs[-1]["id"] == SPAN_LOG_BOUND + 9
    assert r.histogram("x_seconds").count == SPAN_LOG_BOUND + 10


def test_an_error_closes_the_span_and_its_annotation(tmp_path):
    r = MetricsRegistry()
    with jax.profiler.trace(str(tmp_path)):
        with pytest.raises(ValueError):
            with r.span("outer"):
                with r.span("failing"):
                    raise ValueError("boom")
        with r.span("next"):
            pass
    got = annotations(tmp_path)
    [(o0, o1, _)] = got["repro.outer"]
    [(f0, f1, _)] = got["repro.failing"]
    [(n0, _, _)] = got["repro.next"]
    assert o0 <= f0 <= f1 <= o1 <= n0
    assert [s["outcome"] for s in r.recent_spans()] == ["error", "error", "ok"]
    assert r.recent_spans("next")[0]["parent"] is None  # the stack unwound


# -------------------------------------------------------------------- feed
def test_prefetch_wait_is_spanned_and_an_empty_queue_counted():
    gate = threading.Event()

    def source():
        gate.wait()
        yield 1

    reg = default_registry()
    before = reg.counter_value("prefetch_empty_total", name="gated")
    it = PrefetchIterator(source(), depth=1, name="gated")
    timer = threading.Timer(0.05, gate.set)
    timer.start()
    try:
        assert next(it) == 1
    finally:
        timer.join()
        it.close()
    assert reg.counter_value("prefetch_empty_total", name="gated") == before + 1
    wait = [s for s in reg.recent_spans("feed_wait") if s["labels"] == {"name": "gated"}][-1]
    assert wait["total_s"] >= 0.04
    assert reg.histogram("feed_wait_seconds", name="gated").count >= 1


def token_stream(log, n: int, seq: int, name: str):
    log.create_topic(name, core.LogConfig(num_partitions=1))
    rows = np.arange(n * seq, dtype=np.int32).reshape(n, seq)
    return rows, data.ingest(
        log, name, data.RawCodec("int32", (seq,), "int32", ()),
        {"data": rows, "label": np.zeros(n, np.int32)}, name,
    )


def test_feed_spans_on_a_broker_cluster():
    c = core.BrokerCluster(3)
    rows, msg = token_stream(c, 40, 8, "feed-tokens")
    reg = default_registry()
    batches_before = reg.counter_value("feed_batches_total")
    t0 = time.perf_counter_ns()
    it = StreamingBatchIterator(c, msg, 4, epochs=1, fetch_records=16)
    consumer = MetricsRegistry()
    with consumer.span("consume") as outer:
        got = [np.asarray(b["data"]) for b in device_feed(iter(it), depth=2)]
    np.testing.assert_array_equal(np.concatenate(got), rows)
    spans = [s for s in reg.recent_spans() if s["start_ns"] >= t0]
    by_name: dict[str, list] = {}
    for s in spans:
        by_name.setdefault(s["span"], []).append(s)
    assert len(got) == 10
    assert reg.counter_value("feed_batches_total") - batches_before == 10
    assert len(by_name["feed_put"]) == len(by_name["feed_assemble"]) == 10
    assert len(by_name["feed_decode"]) >= 3  # 40 records, 16 a fetch
    assert len(by_name["feed_fetch"]) > len(by_name["feed_decode"])  # and the last, empty
    waits = [s for s in by_name["feed_wait"] if s["labels"] == {"name": "device_feed"}]
    # every next() of the consumer, the one that found the end included,
    # on the consuming thread; the worker's spans are not
    assert len(waits) == 11 and all(s["parent"] == outer.seq for s in waits)
    worker = [s for k in ("feed_fetch", "feed_decode", "feed_assemble", "feed_put")
              for s in by_name[k]]
    assert all(s["parent"] != outer.seq for s in worker)


def test_training_job_spans_its_steps():
    from repro.configs import copd_mlp
    from repro.data.formats import AvroCodec, FieldSpec
    from repro.train import TrainingJob, adamw

    c = core.BrokerCluster(3)
    reg = core.Registry()
    spec = reg.register_model("copd-mlp")
    dep = reg.deploy(reg.create_configuration([spec.model_id]).config_id, "train")
    codec = AvroCodec([FieldSpec("data", "float32", (copd_mlp.N_FEATURES,))],
                      [FieldSpec("label", "int32", ())])
    c.create_topic("copd", core.LogConfig(num_partitions=1))
    data.ingest(c, "copd", codec, copd_mlp.synth_dataset(), dep.deployment_id)
    job = TrainingJob(c, reg, dep.deployment_id, spec.model_id,
                      loss_fn=copd_mlp.loss_fn, init_fn=copd_mlp.init,
                      opt=adamw(1e-2), seed=3)
    t0 = time.perf_counter_ns()
    job.run(batch_size=10, max_steps=5, streaming=True)
    m, d = c.metrics, dep.deployment_id
    assert m.histogram("train_step_seconds", deployment=d).count == 5
    assert m.counter_value("train_records_total", deployment=d) == 50
    snap = m.snapshot()
    assert not any(k.startswith("train_records_per_s") for k in snap["gauges"])
    assert [k for k in snap["histograms"] if k.startswith("train_step_seconds")] == [
        f'train_step_seconds{{deployment="{d}"}}']
    steps = m.recent_spans("train_step")
    assert [s["id"] for s in steps] == list(range(5))
    seqs = {s["seq"] for s in steps}
    syncs = m.recent_spans("train_sync")
    assert len(syncs) == 5 and {s["parent"] for s in syncs} == seqs
    waits = [s for s in default_registry().recent_spans("feed_wait")
             if s["start_ns"] >= t0 and s["parent"] in seqs]
    assert len(waits) == 5 and all(s["labels"] == {"name": "device_feed"} for s in waits)
