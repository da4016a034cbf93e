"""Host time of the broker -> device feed, in ms per batch: the summed
self time of the program's ``feed_fetch``, ``feed_decode``,
``feed_assemble`` and ``feed_put`` spans opened in the traced session,
over the number of ``feed_put`` spans (one a batch placed)."""

from bench.lib import spans

FEED = ("feed_fetch", "feed_decode", "feed_assemble", "feed_put")


def read(rd):
    if rd.get("trace") is None:
        return None
    log = spans.program_log()
    own = spans.self_ns(log)
    feed = [s for s in spans.traced(log) if s["span"] in FEED]
    puts = sum(s["span"] == "feed_put" for s in feed)
    return sum(own[s["seq"]] for s in feed) / puts / 1e6 if puts else None
