"""Decode steps' share of their roofline, in %: the least time of the
traced decode steps over their device time.

Least time: the larger of their operations over the peak rate and their
bytes over the memory bandwidth; bytes are every weight once per step in
the configuration's parameter dtype plus each active row's live keys and
values in its ``kv_cache_dtype``, whatever the implementation moves.
The rows are those of the requests admitted inside the traced window
(the serving loop drains its engine on every tick, so the trace holds
whole requests)."""

from bench.lib import flops
from bench.lib.readings import traced_requests

PROGRAM = r"^jit_decode_step$"


def read(rd):
    got = traced_requests(rd, PROGRAM)
    if got is None:
        return None
    secs, calls, admitted = got
    dm, p = rd["dims"], rd["precision"]
    contexts = [c for plen, n in admitted for c in flops.decode_contexts(plen, n)]
    ops, nbytes = rd["counts"].decode_totals(dm, calls, contexts, p["param_dtype"],
                                             p["kv_cache_dtype"])
    return 100.0 * flops.least_seconds(ops, nbytes, rd["peak"]) / secs
