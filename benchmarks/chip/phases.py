"""What a run's set-up is made of: the seconds of each phase, and what
JAX compiled, read back from its persistent cache, or compiled again.

JAX counts a cache *miss* only when it writes an entry
(``jax/_src/compilation_cache.py``), so a program under the write
thresholds is neither a hit nor a miss. The honest count of compiles is
backend-compile events minus cache hits, and the honest cost is the sum
of ``backend_compile_duration`` minus the time spent reading entries
back. ``install`` must run before the first jit."""

import time

_COMPILE = "/jax/core/compile/backend_compile_duration"
_HIT = "/jax/compilation_cache/cache_hits"
_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"


class CompileLog:
    """Every backend compile of the process: (program, seconds, hit)."""

    def __init__(self):
        self.programs = []          # (fun_name, seconds, was_cache_hit)
        self.requests = 0
        self.hits = 0
        self.retrieval_s = 0.0
        self._hit_pending = False

    def install(self):
        import jax
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        return self

    def _on_event(self, event, **_):
        if event == _REQUEST:
            self.requests += 1
        elif event == _HIT:
            self.hits += 1
            self._hit_pending = True

    def _on_duration(self, event, seconds, **kw):
        if event == _RETRIEVAL:
            self.retrieval_s += seconds
        elif event == _COMPILE:
            # the hit, if any, was recorded inside this compile's span
            self.programs.append((kw.get("fun_name", "?"), seconds,
                                  self._hit_pending))
            self._hit_pending = False

    def mark(self):
        """A point in the log: ``since(a, b)`` is what came between two."""
        return (len(self.programs), self.requests, self.hits,
                self.retrieval_s)

    def since(self, mark, upto=None):
        n0, requests0, hits0, retrieval0 = mark
        n1, requests1, hits1, retrieval1 = upto or self.mark()
        programs = self.programs[n0:n1]
        total = sum(s for _, s, _ in programs)
        retrieval = retrieval1 - retrieval0
        return {
            "compile_events": len(programs),
            "requests_use_cache": requests1 - requests0,
            "cache_hits": hits1 - hits0,
            "compiles": len(programs) - (hits1 - hits0),
            "backend_compile_s": total,
            "retrieval_s": retrieval,
            "compile_s": max(0.0, total - retrieval),
            "compiled": [[name, round(s, 4)] for name, s, hit in programs
                         if not hit],
        }


class Phases:
    """Seconds of each named phase of set-up, on the monotonic clock,
    counted from the first statement of ``run.py``."""

    def __init__(self, t0):
        self.t0 = t0
        self._last = t0
        self.seconds = {}

    def mark(self, name):
        now = time.monotonic()
        self.seconds[name] = self.seconds.get(name, 0.0) + now - self._last
        self._last = now
        return now


def peak_bytes(devices):
    """Peak device memory on the fullest chip. The TPU runtime keeps the
    programs' scratch (``bytes_reserved``: activations, temporaries)
    apart from live arrays (``bytes_in_use``: weights, optimizer state,
    caches), so the peak is the sum of the two peaks; the CPU reports
    nothing, which reads as 0."""
    def one(d):
        stats = d.memory_stats() or {}
        return (stats.get("peak_bytes_in_use", 0)
                + stats.get("peak_bytes_reserved", 0))
    return max(one(d) for d in devices)
