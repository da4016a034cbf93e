"""Reduce a JAX profiler trace (``.xplane.pb``) to device numbers.

Read with ``jax.profiler.ProfileData``. On a TPU each chip is a plane
``/device:TPU:<n>`` whose line ``XLA Ops`` holds the operations that ran
on it and whose line ``XLA Modules`` holds each execution of a compiled
program. On the CPU backend, which the tests record, the operations are
host events that carry an ``hlo_module`` stat; each execution is the span
of one ``run_id``'s operations.

- busy: the union of the intervals in which an operation ran, per chip;
- program time: the summed durations of a program's executions, and
  their count;
- idle gaps: the complement of busy inside the window, each named by the
  innermost ``bench.*`` host annotation open at its midpoint.

The window is the host annotation ``bench.window`` when the trace holds
one, else the span of all device operations.
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from pathlib import Path

WINDOW = "bench.window"
PREFIX = "bench."
_SUFFIX = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class Trace:
    ops: dict[str, list[tuple[int, int, str, str]]]  # chip -> (start, end, op, module)
    runs: dict[str, list[tuple[int, int, str]]]  # chip -> (start, end, module)
    host: list[tuple[int, int, str]]  # bench.* annotations: (start, end, name)


def program_name(name: str) -> str:
    """``jit_decode_step(12)`` -> ``jit_decode_step``."""
    return _SUFFIX.sub("", name)


def op_name(name: str) -> str:
    """A TPU op event is named by its HLO text; keep the instruction's
    name: ``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


# control flow whose body's operations are listed on their own
_CONTAINERS = ("while", "conditional", "call")


def find_xplane(log_dir: str | Path) -> Path:
    found = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str | Path) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    ops: dict[str, list] = defaultdict(list)
    runs: dict[str, list] = defaultdict(list)
    host: list = []
    cpu_runs: dict[tuple, list[int]] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            mods = [(int(e.start_ns), int(e.end_ns), program_name(e.name))
                    for e in (lines["XLA Modules"].events if "XLA Modules" in lines else ())]
            mods.sort()
            runs[plane.name] = mods
            for e in (lines["XLA Ops"].events if "XLA Ops" in lines else ()):
                s, t = int(e.start_ns), int(e.end_ns)
                ops[plane.name].append((s, t, op_name(e.name), _enclosing(mods, s)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        host.append((int(e.start_ns), int(e.end_ns), e.name))
                        continue
                    stats = dict(e.stats)
                    if "hlo_module" not in stats:
                        continue
                    # CPU backend: the operations are host events
                    chip = f"/device:CPU:{stats.get('device_ordinal', 0)}"
                    s, t = int(e.start_ns), int(e.end_ns)
                    mod = str(stats["hlo_module"])
                    ops[chip].append((s, t, e.name, mod))
                    span = cpu_runs.setdefault((chip, mod, stats.get("run_id")), [s, t])
                    span[0], span[1] = min(span[0], s), max(span[1], t)
    for (chip, mod, _), (s, t) in cpu_runs.items():
        runs[chip].append((s, t, mod))
    for chip in runs:
        runs[chip].sort()
    return Trace(dict(ops), dict(runs), sorted(host))


def _enclosing(mods: list[tuple[int, int, str]], t: int) -> str:
    lo, hi = 0, len(mods)
    while lo < hi:  # last execution starting at or before t
        mid = (lo + hi) // 2
        if mods[mid][0] <= t:
            lo = mid + 1
        else:
            hi = mid
    if lo and mods[lo - 1][0] <= t < mods[lo - 1][1]:
        return mods[lo - 1][2]
    return ""


def _union(intervals) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, t in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [(s, t) for s, t in out]


def window_of(tr: Trace) -> tuple[int, int]:
    marks = [(s, t) for s, t, n in tr.host if n == WINDOW]
    if marks:
        return marks[-1]
    spans = [(s, t) for chip in tr.ops.values() for s, t, _, _ in chip]
    if not spans:
        raise ValueError("trace holds no device operation and no window mark")
    return min(s for s, _ in spans), max(t for _, t in spans)


def _clip(s: int, t: int, w0: int, w1: int) -> int:
    return max(0, min(t, w1) - max(s, w0))


def _open_annotations(host, times: list[int]) -> list[str]:
    """For each of the sorted ``times``, the innermost (latest begun)
    ``bench.*`` annotation open then, or ``none``: one sweep."""
    out, active, i = [], [], 0
    marks = [h for h in host if h[2] != WINDOW]
    for t in times:
        while i < len(marks) and marks[i][0] <= t:
            active.append(marks[i])
            i += 1
        active = [a for a in active if a[1] > t]
        out.append(max(active)[2] if active else "none")
    return out


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float  # mean over the chips that ran anything
    chips: int
    program_s: dict[str, float]  # per program, mean over chips
    program_calls: dict[str, int]  # per program, summed over chips
    top_ops: list[tuple[str, float]]
    idle_by_host: list[tuple[str, float]]

    def programs(self, pattern: str) -> tuple[float, int]:
        """Device seconds (mean per chip) and calls of the programs whose
        name matches ``pattern``."""
        rx = re.compile(pattern)
        secs = sum(v for k, v in self.program_s.items() if rx.search(k))
        calls = sum(v for k, v in self.program_calls.items() if rx.search(k))
        return secs, calls


def summarize(tr: Trace, top: int = 10) -> Summary:
    w0, w1 = window_of(tr)
    chips = [c for c, ops in tr.ops.items() if any(_clip(s, t, w0, w1) for s, t, _, _ in ops)]
    n = max(len(chips), 1)
    busy = 0
    op_time: dict[str, float] = defaultdict(float)
    idle: dict[str, float] = defaultdict(float)
    for chip in chips:
        merged = _union((s, t) for s, t, _, _ in tr.ops[chip])
        busy += sum(_clip(s, t, w0, w1) for s, t in merged)
        for s, t, name, mod in tr.ops[chip]:
            if name.split(".")[0] not in _CONTAINERS:
                op_time[f"{mod}:{name}" if mod else name] += _clip(s, t, w0, w1) / 1e9 / n
        gaps, edge = [], w0
        for s, t in merged + [(w1, w1)]:
            if s > edge and edge < w1:
                gaps.append((edge, min(s, w1)))
            edge = max(edge, t)
        names = _open_annotations(tr.host, [(g0 + g1) // 2 for g0, g1 in gaps])
        for (g0, g1), name in zip(gaps, names):
            idle[name] += (g1 - g0) / 1e9 / n
    prog_s: dict[str, float] = defaultdict(float)
    prog_n: dict[str, int] = defaultdict(int)
    for chip in chips:
        for s, t, mod in tr.runs.get(chip, ()):
            if w0 <= (s + t) // 2 < w1:
                prog_s[mod] += (t - s) / 1e9 / n
                prog_n[mod] += 1
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return Summary(
        window_s=(w1 - w0) / 1e9,
        busy_s=busy / 1e9 / n,
        chips=len(chips),
        program_s=dict(prog_s),
        program_calls=dict(prog_n),
        top_ops=rank(op_time),
        idle_by_host=rank(idle),
    )
