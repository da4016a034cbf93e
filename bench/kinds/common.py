"""What every cell driver shares: the run's context and the traced
window. The program's model, the reference and the counts come from the
two modules that the cell's configuration names (``Cell.program``,
``Cell.reference``)."""

from __future__ import annotations

import dataclasses
import shutil
import sys
import time
from pathlib import Path

import jax

from bench.lib import trace as trace_lib
from bench.lib.spec import Cell


@dataclasses.dataclass
class Ctx:
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    devices: list
    out_dir: Path  # scratch for traces, ignored by git
    started: float  # perf_counter at process start
    variant: str = "program"  # "program_int8": the program's int8-weight path

    def say(self, *parts) -> None:
        print(f"[{self.cell.name}]", *parts, file=sys.stderr, flush=True)


class TracedWindow:
    """A profiler trace of part of the window, marked ``bench.window``.
    Python calls are not traced; host spans are the ``bench.*``
    annotations of the drivers."""

    def __init__(self, out_dir: Path):
        self.dir = out_dir / "trace"
        self.t0 = self.t1 = None
        self._mark = None

    def start(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self._mark = jax.profiler.TraceAnnotation(trace_lib.WINDOW)
        self._mark.__enter__()
        self.t0 = time.perf_counter()

    def stop(self) -> None:
        self.t1 = time.perf_counter()
        self._mark.__exit__(None, None, None)
        jax.profiler.stop_trace()

    @property
    def running(self) -> bool:
        return self.t0 is not None and self.t1 is None

    def summary(self) -> trace_lib.Summary:
        return trace_lib.summarize(trace_lib.load(trace_lib.find_xplane(self.dir)))
