"""Backend compilations and persistent-cache lookups, from JAX's own
monitoring events.  Snapshots taken around the measured window show
whether anything compiled inside it; those taken at the end of set-up show
whether the run found its programs in the compile cache."""
from __future__ import annotations

from collections import Counter

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileStats:
    def __init__(self):
        from jax import monitoring
        self.events: Counter = Counter()
        self.compile_s = 0.0
        self.compiles = 0
        monitoring.register_event_listener(
            lambda event, **kw: self.events.update([event]))
        monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.compile_s += duration
            self.compiles += 1

    def snapshot(self) -> dict:
        ev = self.events
        return {"compiles": self.compiles,
                "compile_s": self.compile_s,
                "cache_hits": ev["/jax/compilation_cache/cache_hits"],
                "cache_misses": ev["/jax/compilation_cache/cache_misses"]}


def kernel_calls(lowered) -> int:
    """Compiled Pallas kernels in a lowered program (an interpreted
    kernel lowers to plain HLO and leaves no custom call)."""
    return lowered.as_text().count("tpu_custom_call")
