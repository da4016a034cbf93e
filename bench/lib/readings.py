"""Helpers the per-layer metric readers share.

A driver hands every reader the same readings ``rd``: ``dims`` and
``precision`` of the configuration, ``counts`` (the module that the
configuration names as its ``reference``, whose count functions take
``dims``), ``peak`` of the device, ``chips``,
``window_s``; for serving ``requests`` (per request: ``prompt_len``,
``max_new``, ``due``, ``produce_s``, ``first_token``, ``done``, times on
the host's ``perf_counter``), ``window`` (engine counters over the
window) and, in a traced run, ``traced`` (its bounds, counters and the
``(prompt_len, max_new)`` of the requests admitted inside it); for
training ``train``; in a traced run ``trace``, the reduced profile
(``bench.lib.trace.Summary``).
"""

from __future__ import annotations

import numpy as np

from bench.lib import flops


def percentile(values, q: float) -> float | None:
    return float(np.percentile(np.asarray(values, np.float64), q)) if len(values) else None


def traced_requests(rd: dict, program: str):
    """(device seconds, calls) of ``program`` in the trace and the
    requests admitted inside the traced window, or None where there is
    nothing to read."""
    tr, traced = rd.get("trace"), rd.get("traced")
    if tr is None or traced is None or not traced["admitted"]:
        return None
    secs, calls = tr.programs(program)
    if not calls or secs <= 0:
        return None
    return secs, calls, traced["admitted"]


ADMIT, DECODE = r"^jit_paged_admit$", r"^jit_decode_step$"


def serve_mfu(rd: dict) -> float | None:
    """The serving step's share of the chip's peak, in %: model operations
    of the admissions and decode steps of the requests admitted inside the
    traced window over the admission and decode programs' device time at
    the peak rate."""
    a = traced_requests(rd, ADMIT)
    d = traced_requests(rd, DECODE)
    if a is None or d is None:
        return None
    dm, counts, admitted = rd["dims"], rd["counts"], a[2]
    ops = sum(counts.prefill_flops(dm, n) for n, _ in admitted)
    ops += counts.decode_flops(dm, [c for n, k in admitted for c in flops.decode_contexts(n, k)])
    return 100.0 * ops / ((a[0] + d[0]) * rd["peak"]["bf16_flops_per_s"])
