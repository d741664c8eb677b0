"""XLA's own compile counters, from ``jax.monitoring``: backend compiles,
their seconds, compile requests and persistent-cache hits. They see every
program of the process, whichever cache of the engine it went through."""
import jax


class XlaCompileClock:
    def __init__(self):
        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += seconds

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"xla_compiles": self.compiles,
                "xla_compile_seconds": self.seconds,
                "xla_persistent_cache_hits": self.cache_hits}
