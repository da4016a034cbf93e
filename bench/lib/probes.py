"""Small probes of the JAX runtime: compile events, device memory, sizes."""

from __future__ import annotations

import jax


class CompileLog:
    """Counts XLA backend compiles, and their seconds, inside a ``with``."""

    _EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self.seconds = 0.0

    def _listen(self, event: str, secs: float, **_) -> None:
        if event == self._EVENT:
            self.count += 1
            self.seconds += secs

    def __enter__(self) -> "CompileLog":
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._listen)


def memory(device) -> dict:
    """The device's peak bytes in use so far and its limit (empty where
    the backend keeps no such count, as on the CPU)."""
    stats = device.memory_stats() or {}
    keys = ("peak_bytes_in_use", "bytes_limit")
    return {k: stats[k] for k in keys if k in stats}


def peak_bytes(devices) -> int:
    """The peak on the fullest of ``devices`` (0 where none is counted)."""
    return max((memory(d).get("peak_bytes_in_use", 0) for d in devices), default=0)


def tree_bytes(tree) -> int:
    return sum(leaf.nbytes for leaf in jax.tree.leaves(tree))
