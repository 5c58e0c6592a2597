"""Small instruments shared by the mode runners."""
from __future__ import annotations

import jax


class CompileCounter:
    """Counts compile requests (compiled anew or loaded from the persistent
    cache) while ``on`` is set.  JAX keeps listeners for the process, so
    one listener serves the newest counter."""

    _current = None

    def __init__(self):
        self.on = False
        self.requests = 0
        self.cache_hits = 0
        if CompileCounter._current is None:
            jax.monitoring.register_event_listener(CompileCounter._event)
        CompileCounter._current = self

    @staticmethod
    def _event(event: str, **kwargs) -> None:
        self = CompileCounter._current
        if not self.on:
            return
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device, 0 where not reported."""
    return int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in jax.local_devices()))


def note(msg: str) -> None:
    print(msg, flush=True)
