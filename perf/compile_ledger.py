"""Process-wide compile accounting off ``jax.monitoring``.

How many programs were requested, how many the persistent cache served, and
the seconds spent in backend compiles (a cache hit spends almost none). The
harness snapshots it when set-up ends: any request after that is a compile
inside the measured window, and the run fails its own check.

Copied from ``chip_smoke._CompileLedger`` so that the yardstick does not move
when the program does (PERF.md, Open questions, lists the original).
"""

_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
_HIT = "/jax/compilation_cache/cache_hits"
_BACKEND = "/jax/core/compile/backend_compile_duration"


class CompileLedger:
    def __init__(self):
        import jax.monitoring

        self.requests = self.hits = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event, **_kw):
        if event == _REQUEST:
            self.requests += 1
        elif event == _HIT:
            self.hits += 1

    def _on_duration(self, event, duration, **_kw):
        if event == _BACKEND:
            self.compile_s += float(duration)

    def snapshot(self):
        return self.requests, self.hits, self.compile_s

    def since(self, snap):
        """(programs requested, served from the cache, seconds compiling)
        since ``snap``."""
        r, h, s = self.snapshot()
        return r - snap[0], h - snap[1], s - snap[2]
