"""Time the train loop waits for its next batch, in ms per ``next()``:
the mean of the program's ``feed_wait`` spans of the ``device_feed``
iterator opened in the traced session."""

from bench.lib import spans


def read(rd):
    if rd.get("trace") is None:
        return None
    waits = [s["total_s"] for s in spans.traced(spans.program_log())
             if s["span"] == "feed_wait" and s["labels"].get("name") == "device_feed"]
    return sum(waits) / len(waits) * 1e3 if waits else None
