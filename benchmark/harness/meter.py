"""Compile accounting from `jax.monitoring`: seconds spent tracing,
lowering and compiling (or loading from the persistent cache), and the
cache's hits and misses."""

from __future__ import annotations


class CompileMeter:
    """Totals since the process began. `trace` + `lower` (Python: jaxpr,
    then StableHLO) are paid by every run; `backend` is XLA's compile or,
    on a cache hit, the load (`retrieval` is the part spent reading).
    `compiles` counts backend compile-or-load events: one per program."""

    STAGES = {
        "/jax/core/compile/jaxpr_trace_duration": "trace",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
        "/jax/core/compile/backend_compile_duration": "backend",
        "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval",
    }
    EVENTS = {
        "/jax/compilation_cache/cache_hits": "hits",
        "/jax/compilation_cache/cache_misses": "misses",
    }

    def __init__(self):
        import jax.monitoring as mon

        self.totals = dict.fromkeys(
            [*self.STAGES.values(), *self.EVENTS.values(), "compiles"], 0
        )
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration_secs, **_kw):
        stage = self.STAGES.get(event)
        if stage:
            self.totals[stage] += duration_secs
            if stage == "backend":
                self.totals["compiles"] += 1

    def _on_event(self, event, **_kw):
        if event in self.EVENTS:
            self.totals[self.EVENTS[event]] += 1

    def snapshot(self):
        return dict(self.totals)

    def since(self, before=None):
        """What happened since `before` (a snapshot); compile_s = trace +
        lower + backend, seconds."""
        d = {k: v - (before or {}).get(k, 0) for k, v in self.totals.items()}
        d["compile"] = d["trace"] + d["lower"] + d["backend"]
        return d
