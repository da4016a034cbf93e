"""Admission prefills' share of their roofline, in %: the least time of
each admission inside the traced window (the larger of its operations
over the peak rate and its bytes over the bandwidth), summed, over the
admission program's device time."""

from bench.lib import flops
from bench.lib.readings import traced_requests

PROGRAM = r"^jit_paged_admit$"


def read(rd):
    got = traced_requests(rd, PROGRAM)
    if got is None:
        return None
    secs, _, admitted = got
    dm, c, p, peak = rd["dims"], rd["counts"], rd["precision"], rd["peak"]
    least = sum(
        flops.least_seconds(c.prefill_flops(dm, n),
                            c.prefill_bytes(dm, n, p["param_dtype"], p["kv_cache_dtype"]),
                            peak)
        for n, _ in admitted)
    return 100.0 * least / secs
