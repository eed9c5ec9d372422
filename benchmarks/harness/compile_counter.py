"""Executables JAX compiled, as opposed to fetched from the persistent cache:
compile requests minus cache hits (``jax.monitoring`` events).  A copy of
``chip_smoke.py:CompileCounter``."""

from __future__ import annotations

import threading


class CompileCounter:

    def __init__(self):
        import jax
        self._mu = threading.Lock()   # ship threads compile too
        self.requests = 0
        self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            with self._mu:
                self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            with self._mu:
                self.hits += 1

    def snapshot(self):
        with self._mu:
            return self.requests, self.hits

    def compiled(self):
        with self._mu:
            return self.requests - self.hits
