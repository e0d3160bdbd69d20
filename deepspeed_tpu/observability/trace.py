"""Structured trace spans: host wall-clock intervals, Chrome-trace dump.

Zero-dependency tracing for the train/serve hot paths. A ``span("fwd")``
context manager records one host wall-clock interval into a bounded ring
buffer; when JAX is importable each span also enters a
``jax.profiler.TraceAnnotation`` so the same names line up with device
ops in an xprof capture. The buffer dumps as Chrome-trace / Perfetto
JSON (``trace_events`` format, stdlib ``json`` only — load it in
``chrome://tracing`` or https://ui.perfetto.dev).

Host-sync discipline (the PR-2 TS002 rule): spans read
``time.perf_counter_ns`` only — entering/leaving a span NEVER touches
the device. Device-accurate step time comes from ``DeviceProbe``, whose
single ``jax.block_until_ready`` runs on a bounded cadence exactly like
the PR-4 divergence sentinel's host read; its ``host_reads`` counter is
what the trace-probe tests assert on.

The disabled path is near-free: ``span()`` is one module-global load, an
``is None`` check, and a shared no-op context manager — no allocation,
no clock read (measured by the microbenchmark in
tests/unit/test_observability.py).
"""

import json
import os
import threading
import time
from collections import deque

# Module-global active tracer. None = disabled; `span()` then returns the
# shared no-op below. Engines flip this per step to honor the configured
# capture window (Observability.begin_step).
_TRACER = None


class _NullSpan:
    """Shared no-op span: the entire disabled-path cost."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False


_NULL_SPAN = _NullSpan()


def span(name, args=None):
    """One trace span. Usage::

        with span("fwd"):
            ...

    ``args`` (an optional dict) lands in the Chrome-trace event's
    ``args`` field. Near-free when no tracer is active.
    """
    tracer = _TRACER
    if tracer is None:
        return _NULL_SPAN
    return tracer.span(name, args)


def active_tracer():
    """The currently active Tracer, or None when tracing is off."""
    return _TRACER


def begin_span(name, args=None):
    """A span no ``with`` block can hold, because it opens in one call
    and closes in a later one (the caller's turn between two
    ``advance()`` calls, a pass of the collector between its two
    callbacks): opened here, closed by ``end()`` on what this returns,
    which is None when no tracer is active. Such a span may overlap
    others on its thread without nesting in them: each is recorded as
    an interval of its own, in the ring and on the profiler's line."""
    tracer = _TRACER
    if tracer is None:
        return None
    return tracer.span(name, args).__enter__()


def activate(tracer):
    """Route ``span()`` calls to ``tracer`` until ``deactivate()``."""
    global _TRACER
    _TRACER = tracer


def deactivate():
    global _TRACER
    _TRACER = None


class _Span:
    __slots__ = ("_tracer", "_name", "_args", "_t0", "_ann")

    def __init__(self, tracer, name, args):
        self._tracer = tracer
        self._name = name
        self._args = args
        self._ann = None
        self._t0 = 0

    def __enter__(self):
        ann_cls = self._tracer._annotation_cls
        if ann_cls is not None:
            self._ann = ann_cls(self._name)
            self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        dur = time.perf_counter_ns() - self._t0
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
            self._ann = None
        self._tracer._record(self._name, self._t0, dur,
                             threading.get_ident(), self._args)
        return False

    def end(self):
        """Close a span opened by ``begin_span``."""
        self.__exit__(None, None, None)


class Tracer:
    """Bounded span recorder. Events are ``(name, t0_ns, dur_ns, tid,
    args)`` tuples in a ring buffer; the oldest drop first (``dropped``
    counts evictions, surfaced in the trace metadata, the
    ``trace/spans_dropped_total`` registry counter, and the
    ``format_summary`` footer). Counter samples (memory tracks) ride the
    same buffer with ``dur_ns=None`` and export as Chrome-trace "C"
    events."""

    def __init__(self, max_events: int = 100_000, annotate_device: bool = True):
        self.events = deque(maxlen=max(1, int(max_events)))
        self.dropped = 0
        self._annotation_cls = None
        if annotate_device:
            try:
                from jax.profiler import TraceAnnotation
                self._annotation_cls = TraceAnnotation
            except ImportError:
                # no jax in this process (e.g. the dependency-free lint
                # job): host spans still record, xprof alignment is off
                self._annotation_cls = None

    def span(self, name, args=None):
        return _Span(self, name, args)

    def _record(self, name, t0_ns, dur_ns, tid, args):
        if len(self.events) == self.events.maxlen:
            self.dropped += 1
        self.events.append((name, t0_ns, dur_ns, tid, args))

    def record_complete(self, name, t0_ns, dur_ns, args=None):
        """Record an interval that was measured OUTSIDE a ``span()``
        context — e.g. a queue wait reconstructed at admit time from
        the request's submit stamp, or a decode residency closed at
        harvest. Host clock arithmetic only; exports as a normal "X"
        event."""
        self._record(name, int(t0_ns), int(dur_ns),
                     threading.get_ident(), args)

    def record_counter(self, name, value):
        """One counter-track sample (a Chrome-trace "C" event): the
        instantaneous ``value`` under series ``name`` — memory gauges on
        the same timeline as the spans."""
        self._record(name, time.perf_counter_ns(), None,
                     threading.get_ident(), {"value": float(value)})

    def clear(self):
        self.events.clear()
        self.dropped = 0


class DeviceProbe:
    """Bounded-cadence device-time probe (the PR-4 sentinel discipline
    applied to timing): ``maybe_block`` drains outstanding async device
    work with ONE ``jax.block_until_ready`` every ``interval`` calls and
    records the wait as a ``device_probe`` span. ``host_reads`` counts
    every sync this probe ever performed — the trace-probe test asserts
    the instrumented step path adds exactly these, and nothing else."""

    def __init__(self, interval: int):
        self.interval = int(interval)
        self.host_reads = 0
        self.last_wait_s = None

    def maybe_block(self, value, ordinal: int):
        """Sync on ``value`` iff ``ordinal`` hits the cadence. Returns
        the wait in seconds, or None when the probe stayed asleep."""
        if self.interval <= 0 or value is None:
            return None
        if ordinal % self.interval != 0:
            return None
        import jax
        t0 = time.perf_counter()
        with span("device_probe"):
            jax.block_until_ready(value)
        self.host_reads += 1
        self.last_wait_s = time.perf_counter() - t0
        return self.last_wait_s


# ---------------------------------------------------------------------------
# Chrome-trace (trace_events) serialization + per-phase summaries
# ---------------------------------------------------------------------------

def chrome_trace_events(events):
    """Ring-buffer tuples -> Chrome-trace "X" (complete) event dicts.
    Timestamps/durations are microseconds per the trace_events spec;
    thread ids compress to small ordinals so Perfetto tracks stay
    readable."""
    tids = {}
    pid = os.getpid()
    out = []
    for name, t0_ns, dur_ns, tid, args in events:
        if dur_ns is None:
            # counter-track sample (Tracer.record_counter): a "C" event
            # whose args hold the series value — Perfetto renders these
            # as the memory-counter tracks
            out.append({"name": name, "ph": "C", "ts": t0_ns / 1e3,
                        "pid": pid, "tid": tids.setdefault(tid, len(tids)),
                        "args": dict(args) if args else {}})
            continue
        ev = {"name": name, "ph": "X", "ts": t0_ns / 1e3, "dur": dur_ns / 1e3,
              "pid": pid, "tid": tids.setdefault(tid, len(tids))}
        if args:
            ev["args"] = dict(args)
        out.append(ev)
    return out


def write_chrome_trace(events, path, metadata=None):
    """Dump spans as Chrome-trace JSON (``{"traceEvents": [...]}``).
    ``events`` is a Tracer's buffer (or any iterable of its tuples)."""
    payload = {"traceEvents": chrome_trace_events(events),
               "displayTimeUnit": "ms"}
    if metadata:
        payload["metadata"] = dict(metadata)
    with open(path, "w") as f:
        json.dump(payload, f)
    return path


def _phase_stats(durs_ms):
    from .metrics import percentile
    s = sorted(durs_ms)
    n = len(s)
    return {
        "count": n,
        "total_ms": sum(s),
        "mean_ms": sum(s) / n,
        "p50_ms": percentile(s, 50),
        "p95_ms": percentile(s, 95),
        "max_ms": s[-1],
    }


def summarize(events):
    """Per-phase timing table data: {span name: {count, total_ms,
    mean_ms, p50_ms, p95_ms, max_ms}}, ordered by total time."""
    per = {}
    for name, _t0, dur_ns, _tid, _args in events:
        if dur_ns is None:      # counter samples have no duration
            continue
        per.setdefault(name, []).append(dur_ns / 1e6)
    stats = {name: _phase_stats(durs) for name, durs in per.items()}
    return dict(sorted(stats.items(), key=lambda kv: -kv[1]["total_ms"]))


def format_summary(summary, dropped: int = 0) -> str:
    """Render a summarize() dict as the per-phase text table.
    ``dropped`` (a Tracer's eviction count) prints as a footer so a
    truncated capture is never silently read as complete."""
    if not summary:
        table = "(no trace spans recorded)"
    else:
        width = max(len("phase"), max(len(n) for n in summary))
        lines = [f"{'phase':<{width}}  {'count':>6}  {'total ms':>10}  "
                 f"{'mean ms':>9}  {'p50 ms':>9}  {'p95 ms':>9}  "
                 f"{'max ms':>9}"]
        for name, s in summary.items():
            lines.append(f"{name:<{width}}  {s['count']:>6}  "
                         f"{s['total_ms']:>10.2f}  {s['mean_ms']:>9.3f}  "
                         f"{s['p50_ms']:>9.3f}  {s['p95_ms']:>9.3f}  "
                         f"{s['max_ms']:>9.3f}")
        table = "\n".join(lines)
    if dropped:
        table += (f"\n({dropped} spans dropped — ring buffer full; raise "
                  "observability.trace_buffer_events or narrow the window)")
    return table


def summarize_trace_file(path):
    """Per-phase summary recovered from a trace.json on disk (the
    ``ds_tpu_report`` path: a fresh process inspecting a prior capture).
    Accepts both the dict form written here and a bare event array."""
    with open(path) as f:
        payload = json.load(f)
    events = payload.get("traceEvents", payload) \
        if isinstance(payload, dict) else payload
    per = {}
    for ev in events:
        if ev.get("ph") == "X" and "dur" in ev:
            per.setdefault(ev["name"], []).append(float(ev["dur"]) / 1e3)
    stats = {name: _phase_stats(durs) for name, durs in per.items()}
    return dict(sorted(stats.items(), key=lambda kv: -kv[1]["total_ms"]))
