"""The serving step's share of the chip's peak, in %: model operations of
the traced admissions and decode steps over their device time at the
peak rate (``bench.lib.readings.serve_mfu``). Beside the decode roofline,
for the median latency."""

from bench.lib.readings import serve_mfu


def read(rd):
    return serve_mfu(rd)
