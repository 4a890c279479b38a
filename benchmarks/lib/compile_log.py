"""Counts backend compiles and persistent-cache hits in a window.

Copied from ``chip_smoke.py`` (PR 21): it is how a run proves that
nothing compiled inside the measured window.
"""

from __future__ import annotations


class CompileLog:
    def __init__(self):
        import jax.monitoring as mon
        self.events = []            # (fun_name, seconds)
        self.cache = {"hits": 0, "misses": 0}
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((kw.get("fun_name", "?"), secs))

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache["misses"] += 1

    def window(self):
        """Start a window; the returned closure gives its summary."""
        n0, c0 = len(self.events), dict(self.cache)

        def summary():
            ev = self.events[n0:]
            return {"compiles": len(ev),
                    "compile_s": sum(e[1] for e in ev),
                    "cache_hits": self.cache["hits"] - c0["hits"],
                    "cache_misses": self.cache["misses"] - c0["misses"],
                    "names": [e[0] for e in ev]}
        return summary
