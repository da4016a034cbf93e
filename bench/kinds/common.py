"""What every cell driver shares: the run's context, the program's model
built from a configuration file, and the traced window."""

from __future__ import annotations

import dataclasses
import shutil
import sys
import time
from pathlib import Path

import jax

from bench.lib import trace as trace_lib
from bench.lib.reference import Dims
from bench.lib.spec import Cell
from repro.models.model import ArchConfig, StreamModel
from repro.models.policy import Policy


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


def arch(config: dict) -> ArchConfig:
    """The program's architecture from a configuration file's published
    (hf) keys."""
    dm = Dims.from_config(config)
    return ArchConfig(
        name=config["name"], d_model=dm.d_model, n_layers=dm.n_layers,
        n_heads=dm.n_heads, n_kv_heads=dm.n_kv_heads, head_dim=dm.head_dim,
        d_ff=dm.d_ff, vocab=dm.vocab, rope_theta=dm.rope_theta, norm_eps=dm.norm_eps,
    )


def model(config: dict, **policy) -> StreamModel:
    p = config["precision"]
    return StreamModel(arch(config), Policy(
        param_dtype=p["param_dtype"], compute_dtype=p["compute_dtype"],
        kv_cache_dtype=p["kv_cache_dtype"], **policy,
    ))


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
