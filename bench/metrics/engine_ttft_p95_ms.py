"""95th percentile, in ms, from a request's due time to the engine's own
first-token stamp (``ContinuousLMEngine.first_token_s``, taken after the
admission's argmax has synced): queueing in the broker and the serving
loop plus the admission prefill."""

from bench.lib.readings import percentile


def read(rd):
    waits = [(r["first_token"] - r["due"]) * 1e3
             for r in rd.get("requests", ()) if r["first_token"] is not None]
    return percentile(waits, 95)
