"""The span readers: ``bench/lib/spans.py`` and the two feed metrics, on
a synthetic span log with known self times, and on the program's own log
in a rehearsed traced run of the training cell."""

import pytest

from bench.lib import spans, spec
from test_rehearsal import rehearse

MS = 1_000_000  # ns


def span(seq, name, start_ms, end_ms, /, parent=None, session=2, **labels):
    return {"span": name, "labels": labels, "outcome": "ok",
            "total_s": (end_ms - start_ms) / 1e3, "phases": [], "seq": seq,
            "parent": parent, "id": None, "start_ns": start_ms * MS,
            "end_ns": end_ms * MS, "traced": session is not None, "session": session}


# session 1: an older trace; None: untraced; session 2: the newest trace.
# In session 2 the feed worker places two batches: the first fetches,
# decodes and assembles inside a feed_put-free stretch, and one feed_put
# holds a child that covers 1 ms of its 3.
LOG = [
    span(1, "feed_put", 0, 50, session=1),
    span(2, "feed_wait", 0, 90, session=1, name="device_feed"),
    span(3, "feed_put", 100, 104, session=None),
    span(10, "feed_fetch", 200, 206),
    span(11, "feed_decode", 206, 208),
    span(12, "feed_assemble", 208, 209),
    span(13, "feed_put", 209, 212),
    span(14, "inner", 210, 211, parent=13),
    span(15, "feed_assemble", 212, 213),
    span(16, "feed_put", 213, 215),
    span(20, "train_step", 200, 300),
    span(21, "feed_wait", 200, 204, parent=20, name="device_feed"),
    span(22, "feed_wait", 300, 302, name="device_feed"),
    span(23, "feed_wait", 300, 350, name="stream-batch"),
]


@pytest.fixture
def synthetic(monkeypatch):
    monkeypatch.setattr(spans, "program_log", lambda: list(LOG))


def test_traced_keeps_the_newest_session_only():
    assert [s["seq"] for s in spans.traced(LOG)] == list(range(10, 17)) + [20, 21, 22, 23]
    assert spans.traced([s for s in LOG if s["session"] != 2]) == [LOG[0], LOG[1]]
    assert spans.traced([LOG[2]]) == []


def test_self_time_leaves_out_what_children_cover():
    own = spans.self_ns(LOG)
    assert own[13] == 2 * MS and own[14] == 1 * MS
    assert own[20] == 96 * MS  # its feed_wait child covers 4 of 100
    overlapping = [span(1, "p", 0, 10), span(2, "a", 2, 6, parent=1),
                   span(3, "b", 4, 8, parent=1), span(4, "c", 9, 12, parent=1)]
    assert spans.self_ns(overlapping)[1] == 3 * MS  # 2-8 and 9-10 covered


def test_feed_wait_is_the_mean_device_feed_wait(synthetic):
    read = spec.reader("feed_wait_ms.train")
    assert read({"trace": object()}) == pytest.approx(3.0)  # (4 + 2) / 2
    assert read({}) is None


def test_feed_host_is_self_time_per_batch_placed(synthetic):
    read = spec.reader("feed_host_ms.train")
    # (6 + 2 + 1 + (3 - 1) + 1 + 2) ms over two feed_put spans
    assert read({"trace": object()}) == pytest.approx(7.0)
    assert read({}) is None


def test_readers_find_nothing_in_a_log_without_sessions(monkeypatch):
    """The form of the span ring before the span log: nothing to read."""
    old = [{"span": "feed_put", "labels": {}, "outcome": "ok", "total_s": 0.1, "phases": []}]
    monkeypatch.setattr(spans, "program_log",
                        lambda: [s for s in old if "session" in s])
    for name in ("feed_wait_ms.train", "feed_host_ms.train"):
        assert spec.reader(name)({"trace": object()}) is None


def test_a_traced_training_run_reports_both_positive(tmp_path):
    res = rehearse("yi6b-l2.train.stream", True, tmp_path)
    assert res["correct"] is True
    for name in ("feed_wait_ms.train", "feed_host_ms.train"):
        assert res["metrics"][name]["unit"] == "ms"
        assert res["metrics"][name]["value"] > 0
