"""Reduce the program's span log to per-layer numbers.

The program logs each closed span (``repro.core.metrics``): its name and
labels, its bounds on ``time.perf_counter_ns()``, its parent span, and
whether a profiler session was recording when it opened, and which. A
traced run reads the spans of the newest traced session, the one its own
trace recorded. A program that keeps no such log gives no spans, and the
readers built on this give None.
"""

from __future__ import annotations

from collections import defaultdict


def program_log() -> list[dict]:
    """The span log of the program's process-wide registry, where the
    pipeline records its spans; empty where the program has none."""
    try:
        from repro.core.metrics import default_registry
    except ImportError:
        return []
    return [s for s in default_registry().recent_spans() if "session" in s]


def traced(log: list[dict]) -> list[dict]:
    """The spans of ``log`` opened in its newest traced session."""
    sessions = [s["session"] for s in log if s["traced"]]
    if not sessions:
        return []
    newest = max(sessions)
    return [s for s in log if s["traced"] and s["session"] == newest]


def self_ns(log: list[dict]) -> dict[int, int]:
    """Each span's self time in ns (by its ``seq``): its duration less
    the part of it that its children in ``log`` cover."""
    children: dict[int, list[tuple[int, int]]] = defaultdict(list)
    for s in log:
        if s["parent"] is not None:
            children[s["parent"]].append((s["start_ns"], s["end_ns"]))
    out = {}
    for s in log:
        lo, hi = s["start_ns"], s["end_ns"]
        covered, edge = 0, lo
        for a, b in sorted(children.get(s["seq"], ())):
            a, b = max(a, edge), min(b, hi)
            if b > a:
                covered += b - a
                edge = b
        out[s["seq"]] = hi - lo - covered
    return out
