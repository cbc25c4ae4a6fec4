"""Device guard and JAX compile accounting.

``require_tpu`` and ``CompileStats`` are frozen copies from
``chip_smoke.py`` at commit bcb7c9a (the guard exits instead of printing a
result, as the benchmark's contract asks).
"""
from __future__ import annotations

import sys

import jax


class CompileStats:
    """Compilations seen through JAX's monitoring events.

    ``backend_compile_duration`` fires for every program JAX hands to the
    backend, persistent-cache hits included; ``cache_hits`` fires for the
    hits alone, so requests minus hits is what XLA compiled."""

    def __init__(self):
        self.requests = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.seconds += duration

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple[int, float, int]:
        return self.requests, self.seconds, self.cache_hits

    def since(self, snap: tuple[int, float, int]) -> dict:
        req, sec, hits = snap
        hits = self.cache_hits - hits
        return {"compile_s": self.seconds - sec,
                "compiles": self.requests - req - hits,
                "cache_hits": hits,
                "programs": self.requests - req}


def require_tpu(n_chips: int) -> list:
    """The first ``n_chips`` TPU devices, or exit non-zero with no result:
    nothing here runs on a CPU fallback."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"vdc bench: JAX reports platform {devs[0].platform!r}, not "
                 "a TPU; nothing here runs on a CPU fallback")
    if len(devs) < n_chips:
        sys.exit(f"vdc bench: the cell asks for {n_chips} chips, JAX sees "
                 f"{len(devs)}")
    return devs[:n_chips]
