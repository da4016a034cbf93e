"""Observability plane: metrics registry, reporter daemon, LSO-aware lag.

Covers DESIGN.md §9:

* registry primitives — counters, gauges, geometric-bucket histograms
  (p50/p99 estimates), lazy gauge callbacks, trace spans with phases,
  Prometheus-style text rendering, JSON snapshot round-trips;
* disabled mode — a ``metrics_enabled=False`` cluster hands out shared
  no-op instruments and records no series at all (the benchmark baseline);
* cluster instrumentation — produce/fetch/replication throughput, acks=all
  commit latency, 2PC spans (BeginTxn→prepare→markers→complete) with
  commit/abort/timeout counters;
* the pinned acceptance criterion: a ``read_committed`` consumer behind
  an open transaction reports lag capped at the LSO, never negative;
* the ``MetricsReporter`` daemon — lifecycle mirroring
  ``ReplicationService`` (idempotent start/stop, context manager), and
  snapshots on the replicated ``__metrics`` topic that a plain consumer
  can decode;
* the ``ControlLogger.replay`` isolation bugfix — replaying the announce
  of an aborted ingest transaction raises instead of handing a new
  deployment a stream no committed reader can see.
"""

import json
import time

import pytest

from repro.core.cluster import (
    METRICS_TOPIC,
    BrokerCluster,
    ClusterConsumer,
    ClusterProducer,
    MetricsReporter,
)
from repro.core.consumer import ConsumerGroup
from repro.core.control import (
    ControlLogger,
    ControlMessage,
    StreamRange,
    poll_control,
    send_control,
)
from repro.core.log import LogConfig, StreamLog, TopicPartition
from repro.core.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    series_key,
)


def wait_until(cond, timeout=10.0, interval=0.005, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {msg}")


def make_cluster(parts=2, **kw):
    c = BrokerCluster(3, default_acks="all", **kw)
    c.create_topic("t", LogConfig(num_partitions=parts, replication_factor=3))
    return c


# ------------------------------------------------------------------ registry
class TestRegistry:
    def test_series_key_sorts_labels(self):
        assert series_key("m") == "m"
        assert series_key("m", {"b": 1, "a": "x"}) == 'm{a="x",b="1"}'

    def test_counter_gauge_roundtrip(self):
        r = MetricsRegistry()
        r.counter("c", topic="t").inc()
        r.counter("c", topic="t").inc(4)
        assert r.counter_value("c", topic="t") == 5
        assert r.counter_value("c", topic="other") == 0
        r.gauge("g").set(2.5)
        r.gauge("g").inc(0.5)
        assert r.gauge_value("g") == 3.0
        # same labels -> same instrument instance
        assert r.counter("c", topic="t") is r.counter("c", topic="t")

    def test_histogram_percentiles_bounded_error(self):
        h = Histogram("h")
        for ms in range(1, 101):  # 1ms .. 100ms uniform
            h.record(ms / 1000.0)
        s = h.stats()
        assert s["count"] == 100
        assert s["min"] == pytest.approx(0.001)
        assert s["max"] == pytest.approx(0.100)
        # factor-2 buckets: estimate within 2x of the true quantile
        assert 0.050 <= s["p50"] <= 0.101
        assert 0.099 <= s["p99"] <= 0.101  # capped at the observed max
        assert h.percentile(0.5) == s["p50"]

    def test_histogram_empty_and_single(self):
        h = Histogram("h")
        assert h.stats() == {"count": 0, "sum": 0.0}
        assert h.percentile(0.99) == 0.0
        h.record(0.25)
        s = h.stats()
        assert s["count"] == 1 and s["p50"] == pytest.approx(0.25)

    def test_gauge_fn_lazy_and_fault_tolerant(self):
        r = MetricsRegistry()
        calls = []
        r.gauge_fn("lazy", lambda: calls.append(1) or 7.0)
        r.gauge_fn("dead", lambda: 1 / 0)
        assert calls == []  # not evaluated until snapshot
        snap = r.snapshot()
        assert calls == [1]
        assert snap["gauges"]["lazy"] == 7.0
        assert "dead" not in snap["gauges"]  # broken callback skipped
        assert r.gauge_value("lazy") == 7.0
        assert r.gauge_value("dead") == 0.0

    def test_span_phases_and_recent(self):
        r = MetricsRegistry()
        sp = r.span("op", pid=3)
        sp.phase("prepare")
        sp.phase("markers")
        sp.end("commit")
        assert sp.end("commit") == 0.0  # idempotent
        [rec] = r.recent_spans("op")
        assert rec["outcome"] == "commit"
        assert [p["phase"] for p in rec["phases"]] == ["prepare", "markers"]
        assert rec["labels"] == {"pid": 3}
        assert r.histogram("op_seconds").count == 1
        assert r.histogram("op_prepare_seconds").count == 1

    def test_span_context_manager_records_error_outcome(self):
        r = MetricsRegistry()
        with pytest.raises(RuntimeError):
            with r.span("op"):
                raise RuntimeError("boom")
        assert r.recent_spans("op")[0]["outcome"] == "error"

    def test_snapshot_is_json_safe_and_decodes(self):
        r = MetricsRegistry()
        r.counter("c").inc(3)
        r.gauge("g", topic="t").set(1.5)
        r.histogram("h").record(0.01)
        payload = r.encode_snapshot()
        snap = MetricsRegistry.decode_snapshot(payload)
        assert snap["counters"]["c"] == 3
        assert snap["gauges"]['g{topic="t"}'] == 1.5
        assert snap["histograms"]["h"]["count"] == 1
        assert snap["ts"] > 0

    def test_render_text_prometheus_shape(self):
        r = MetricsRegistry()
        r.counter("reqs_total", topic="t").inc(2)
        r.histogram("lat_seconds").record(0.001)
        txt = r.render_text()
        assert "# TYPE reqs_total counter" in txt
        assert 'reqs_total{topic="t"} 2' in txt
        assert "lat_seconds_count 1" in txt
        assert "lat_seconds_p99" in txt

    def test_disabled_registry_is_inert(self):
        r = MetricsRegistry(enabled=False)
        r.counter("c").inc(10)
        r.gauge("g").set(5)
        r.histogram("h").record(1.0)
        r.gauge_fn("f", lambda: 1.0)
        sp = r.span("op")
        sp.phase("x")
        sp.end()
        snap = r.snapshot()
        assert snap["counters"] == {} and snap["gauges"] == {}
        assert snap["histograms"] == {}
        assert r.recent_spans() == []
        # shared null instruments: no per-call allocation churn
        assert r.counter("a") is r.counter("b")


# --------------------------------------------------------- log / cluster wiring
class TestClusterInstrumentation:
    def test_log_append_read_metrics(self):
        log = StreamLog()
        log.metrics = MetricsRegistry()
        log.create_topic("t", LogConfig(num_partitions=1))
        log.produce_batch("t", [b"a", b"b", b"c"], partition=0)
        log.read("t", 0, 0, 10)
        m = log.metrics
        assert m.counter_value("log_append_records_total") == 3
        assert m.counter_value("log_read_records_total") == 3
        assert m.histogram("log_append_seconds").count >= 1
        st = log.stats()
        assert st["partitions"] == 1 and st["retained_records"] == 3

    def test_produce_fetch_commit_latency_series(self):
        c = make_cluster(parts=1)
        c.produce_batch("t", [b"x"] * 8, partition=0, acks="all")
        cons = ClusterConsumer(c)
        cons.fetch("t", 0, 0)
        m = c.metrics
        assert m.counter_value(
            "produce_records_total", topic="t", partition=0
        ) == 8
        assert m.counter_value(
            "fetch_records_total", topic="t", partition=0
        ) == 8
        assert m.histogram("produce_latency_seconds").count >= 1
        assert m.histogram("fetch_latency_seconds").count >= 1
        # acks=all waits for the full ISR: commit latency was measured
        assert m.histogram("commit_latency_seconds").count >= 1
        txt = c.metrics_text()
        assert "produce_records_total" in txt

    def test_cluster_disabled_mode_records_nothing(self):
        c = BrokerCluster(3, metrics_enabled=False)
        c.create_topic("t", LogConfig(num_partitions=1, replication_factor=3))
        c.produce_batch("t", [b"x"] * 4, partition=0)
        snap = c.metrics_snapshot()
        assert snap["counters"] == {} and snap["histograms"] == {}
        assert c.metrics_text().strip() == ""

    def test_2pc_span_commit_and_abort_counters(self):
        c = make_cluster(parts=2)
        prod = ClusterProducer(c, transactional_id="tx")
        prod.begin_txn()
        prod.send_batch("t", [b"a"], partition=0)
        prod.send_batch("t", [b"b"], partition=1)
        prod.commit_txn()
        m = c.metrics
        assert m.counter_value("txn_commit_total") == 1
        [rec] = m.recent_spans("txn_2pc")
        assert rec["outcome"] == "commit"
        phases = [p["phase"] for p in rec["phases"]]
        assert phases == ["prepare", "markers", "complete"]
        assert all(p["seconds"] >= 0 for p in rec["phases"])
        assert m.histogram("txn_2pc_seconds").count == 1
        assert m.histogram("txn_2pc_prepare_seconds").count == 1
        prod.begin_txn()
        prod.send_batch("t", [b"dead"], partition=0)
        prod.abort_txn()
        assert m.counter_value("txn_abort_total") == 1
        assert m.recent_spans("txn_2pc")[-1]["outcome"] == "abort"

    def test_replication_and_gauge_callbacks_in_snapshot(self):
        c = make_cluster(parts=1)
        c.produce_batch("t", [b"x"] * 5, partition=0, acks="all")
        m = c.metrics
        assert m.counter_value(
            "replication_records_total", topic="t", partition=0
        ) >= 5
        snap = c.metrics_snapshot()
        # lazy per-broker gauges evaluated only here
        assert snap["gauges"]['log_segments{broker="0"}'] >= 1
        assert 'controller_apply_lag' in snap["gauges"]


# ------------------------------------------------------------ LSO-aware lag
class TestLsoAwareLag:
    def test_read_committed_lag_capped_at_lso_behind_open_txn(self):
        """Acceptance criterion: 10 committed records, 5 more parked
        behind an open transaction — a read_committed consumer at offset
        10 has lag 0 (not -0, not 5); a read_uncommitted one sees 5."""
        c = make_cluster(parts=1)
        c.produce_batch("t", [b"c%d" % i for i in range(10)], partition=0)
        prod = ClusterProducer(c, transactional_id="tx")
        prod.begin_txn()
        prod.send_batch("t", [b"open%d" % i for i in range(5)], partition=0)
        # transaction left open: LSO pinned at 10, HW advances to 15
        rc = ClusterConsumer(c, group_id="g-rc",
                             isolation_level="read_committed")
        ru = ClusterConsumer(c, group_id="g-ru")
        rc.commit(TopicPartition("t", 0), 10)
        ru.commit(TopicPartition("t", 0), 10)
        assert rc.lag("t", 0) == 0
        assert ru.lag("t", 0) == 5
        # never negative, even with an explicit position past the LSO
        assert rc.lag("t", 0, offset=12) == 0
        # commit releases the parked records (plus the marker offset)
        prod.commit_txn()
        assert rc.lag("t", 0) == 6  # 5 records + 1 marker offset

    def test_lag_after_commit_includes_marker_offset(self):
        """Companion pin for the arithmetic above: committing a 5-record
        transaction advances the LSO past the records AND the commit
        marker, so offsets are raw log offsets (Kafka semantics)."""
        c = make_cluster(parts=1)
        prod = ClusterProducer(c, transactional_id="tx")
        prod.begin_txn()
        prod.send_batch("t", [b"a"] * 5, partition=0)
        prod.commit_txn()
        rc = ClusterConsumer(c, group_id="g",
                             isolation_level="read_committed")
        assert rc.lag("t", 0) == 6  # 5 records + 1 marker offset
        assert c.metrics.gauge_value(
            "consumer_lag", group="g", topic="t", partition=0
        ) == 6.0

    def test_group_consumer_lag_per_partition(self):
        c = make_cluster(parts=2)
        for p in range(2):
            c.produce_batch("t", [b"x"] * (3 + p), partition=p)
        group = ConsumerGroup(c, "workers", ["t"])
        member = group.join("w0")
        lags = member.lag()
        assert lags == {
            TopicPartition("t", 0): 3,
            TopicPartition("t", 1): 4,
        }
        while member.poll(max_records=64):
            pass
        member.commit()
        assert member.lag() == {
            TopicPartition("t", 0): 0,
            TopicPartition("t", 1): 0,
        }
        assert group.rebalances >= 1
        assert c.metrics.counter_value(
            "consumer_rebalances_total", group="workers"
        ) == group.rebalances

    def test_group_lag_on_bare_stream_log(self):
        log = StreamLog()
        log.create_topic("t", LogConfig(num_partitions=1))
        log.produce_batch("t", [b"a", b"b"], partition=0)
        group = ConsumerGroup(log, "g", ["t"])
        member = group.join("w0")
        assert member.lag() == {TopicPartition("t", 0): 2}


# ------------------------------------------------------------- the reporter
class TestMetricsReporter:
    def test_start_stop_idempotent(self):
        c = make_cluster(parts=1)
        rep = MetricsReporter(c, interval_s=0.01)
        assert rep.start() is rep.start()
        assert rep.running
        rep.stop()
        rep.stop()
        assert not rep.running
        # restartable after stop
        rep.start()
        assert rep.running
        rep.stop()
        assert rep.errors == []

    def test_context_manager(self):
        c = make_cluster(parts=1)
        with MetricsReporter(c, interval_s=0.01) as rep:
            assert rep.running
            wait_until(lambda: rep.published >= 2, msg="snapshots published")
        assert not rep.running
        assert rep.errors == []

    def test_snapshots_decodable_by_plain_consumer(self):
        c = make_cluster(parts=1)
        c.produce_batch("t", [b"x"] * 4, partition=0)
        rep = c.start_metrics_reporter(interval_s=0.01)
        wait_until(lambda: rep.published >= 2, msg="snapshots published")
        c.stop_metrics_reporter()
        assert not rep.running
        # the __metrics topic is a normal replicated topic
        assert METRICS_TOPIC in c.topics()
        cons = ClusterConsumer(c, group_id="scraper")
        batch = cons.fetch(METRICS_TOPIC, 0, 0)
        assert len(batch) >= 1
        snap = json.loads(bytes(batch.values[0]))
        assert set(snap) == {"ts", "counters", "gauges", "histograms"}
        assert snap["counters"]['produce_records_total{partition="0",topic="t"}'] == 4


# --------------------------------------------------- replay isolation bugfix
class TestReplayHonorsIsolation:
    def _announce_txn_stream(self, c, deployment_id, *, commit):
        """Transactional ingest by hand: 6 data records + their announce
        in one transaction, committed or aborted."""
        prod = ClusterProducer(
            c, transactional_id=f"ingest-{deployment_id}"
        )
        prod.begin_txn()
        _, first, last = prod.send_batch(
            "t", [b"d%d" % i for i in range(6)], partition=0
        )
        msg = ControlMessage(
            deployment_id=deployment_id, topic="t", input_format="RAW",
            input_config={}, validation_rate=0.0, total_msg=6,
            ranges=[StreamRange("t", 0, first, last - first + 1)],
        )
        send_control(c, msg, producer=prod)
        if commit:
            prod.commit_txn()
        else:
            prod.abort_txn()
        return msg

    def test_replay_of_aborted_ingest_raises(self):
        """Pinned repro: a default-isolation ControlLogger holds the
        aborted ingest's announce in history; replaying it used to
        re-send coordinates whose records no committed reader sees."""
        c = make_cluster(parts=1)
        self._announce_txn_stream(c, "dead", commit=False)
        logger = ControlLogger(c)  # default isolation sees the announce
        hist = logger.latest_for("dead")
        assert hist is not None  # the bug's precondition
        with pytest.raises(ValueError, match="read_committed"):
            logger.replay(hist, "new-dep")
        # nothing was re-announced
        found, _ = poll_control(c, "new-dep")
        assert found is None

    def test_replay_of_open_txn_ingest_raises(self):
        c = make_cluster(parts=1)
        prod = ClusterProducer(c, transactional_id="ingest-open")
        prod.begin_txn()
        _, first, last = prod.send_batch("t", [b"a", b"b"], partition=0)
        msg = ControlMessage(
            deployment_id="open", topic="t", input_format="RAW",
            input_config={}, validation_rate=0.0, total_msg=2,
            ranges=[StreamRange("t", 0, first, last - first + 1)],
        )
        send_control(c, msg, producer=prod)
        logger = ControlLogger(c)
        hist = logger.latest_for("open")
        assert hist is not None
        with pytest.raises(ValueError, match="read_committed"):
            logger.replay(hist, "new-dep")
        prod.abort_txn()

    def test_replay_of_committed_ingest_succeeds(self):
        c = make_cluster(parts=1)
        self._announce_txn_stream(c, "alive", commit=True)
        logger = ControlLogger(c)
        hist = logger.latest_for("alive")
        out = logger.replay(hist, "new-dep")
        assert out.deployment_id == "new-dep"
        found, _ = poll_control(c, "new-dep")
        assert found is not None and found.ranges == hist.ranges
