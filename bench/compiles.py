"""Count XLA backend compiles inside a ``with`` block, by program name.

JAX reports ``/jax/core/compile/backend_compile_duration`` for every
program it makes ready, a persistent compile-cache hit included; a hit is
also reported as ``/jax/compilation_cache/cache_hits`` on the same thread
just before.  A hit is not a compile.  Nothing may compile inside the
measured window; the counts are printed on an earlier line.
"""

from __future__ import annotations

import threading
from typing import List


class Compiles:
    def __enter__(self):
        import jax.monitoring
        self.compiled: List[str] = []     # programs compiled
        self.cached: List[str] = []       # programs read from the cache
        self._hit = threading.local()
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        return self

    def __exit__(self, *exc) -> None:
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)

    @property
    def n(self) -> int:
        return len(self.compiled)

    def _on_event(self, event: str, **kwargs) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self._hit.flag = True

    def _on_duration(self, event: str, secs: float, **kwargs) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            name = str(kwargs.get("fun_name", "?"))
            if getattr(self._hit, "flag", False):
                self.cached.append(name)
            else:
                self.compiled.append(name)
            self._hit.flag = False

    def summary(self) -> str:
        return (f"{self.n} backend compiles {sorted(set(self.compiled))}, "
                f"{len(self.cached)} from the persistent cache "
                f"{sorted(set(self.cached))}")
