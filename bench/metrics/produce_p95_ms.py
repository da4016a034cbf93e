"""95th percentile of the producer's ``produce`` call per request, in ms:
the broker's append path (``BrokerCluster.produce``, replication factor
3, acks all), timed by the benchmark's clock around each call."""

from bench.lib.readings import percentile


def read(rd):
    times = [r["produce_s"] * 1e3 for r in rd.get("requests", ()) if r["produce_s"] is not None]
    return percentile(times, 95)
