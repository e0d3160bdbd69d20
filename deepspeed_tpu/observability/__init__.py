"""Unified observability: trace spans, metrics registry, MFU accounting.

Three layers behind one ``observability`` config block
(docs/observability.md):

- **Trace spans** (trace.py): ``span("fwd")`` host wall-clock intervals
  in a bounded ring buffer, xprof-aligned via
  ``jax.profiler.TraceAnnotation``, dumpable as Chrome-trace JSON.
- **Metrics registry** (metrics.py): counters/gauges/histograms shared
  by engine throughput, ServingMetrics, and resilience counters;
  flushed through the MonitorMaster fan-out on the metrics cadence.
- **Performance accounting** (perf.py): step-time p50/p95, tokens/sec,
  and MFU against the chip peak-FLOPs table, fed by the static
  per-model FLOPs estimator (profiling/flops_profiler).

``Observability`` below bundles the three for the engines: it gates the
module-global tracer to the configured capture window, runs the
bounded-cadence device probe, and owns the flush cadence. Everything
obeys the no-per-step-host-sync rule (ds_tpu_lint TS002 gates this
package at zero findings).
"""

from .config import ExportConfig, MemoryConfig, ObservabilityConfig
from .export import (MetricsScrapeClient, TelemetryServer, build_statusz,
                     parse_prometheus, prometheus_name, render_prometheus)
from .fleet import (FleetTelemetryAggregator, FlightRecorder,
                    breakdown_from_trace, format_waterfall, make_trace_id,
                    per_request_breakdown, stitch_chrome_traces,
                    write_stitched_trace)
from .goodput import (CATEGORIES as GOODPUT_TAXONOMY, GoodputLedger,
                      classify_spans, format_goodput, get_ledger,
                      reset_ledger)
from .memory import (MemoryAccountant, device_memory_stats,
                     estimate_forward_memory_bytes, format_memory_report,
                     get_accountant, is_oom_error, oom_forensics,
                     tree_bytes, write_oom_forensics)
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry, Table,
                      collective_tally, diff_snapshots,
                      format_snapshot_diff, get_registry)
from .perf import (CHIP_PEAK_TFLOPS, PerfAccountant, detect_chip,
                   resolve_peak_flops)
from .programs import (ProgramRegistry, TrackedProgram,
                       format_program_table, get_program_registry,
                       track_program)
from .trace import (DeviceProbe, Tracer, activate, active_tracer,
                    begin_span, chrome_trace_events, deactivate, format_summary, span,
                    summarize, summarize_trace_file, write_chrome_trace)

__all__ = [
    "ObservabilityConfig", "MemoryConfig", "ExportConfig", "Observability",
    "Counter", "Gauge", "Histogram", "Table", "MetricsRegistry",
    "get_registry",
    "GoodputLedger", "GOODPUT_TAXONOMY", "classify_spans", "format_goodput",
    "get_ledger", "reset_ledger",
    "MetricsScrapeClient", "TelemetryServer", "build_statusz",
    "parse_prometheus", "prometheus_name", "render_prometheus",
    "FleetTelemetryAggregator", "FlightRecorder", "breakdown_from_trace",
    "format_waterfall", "make_trace_id", "per_request_breakdown",
    "stitch_chrome_traces", "write_stitched_trace",
    "collective_tally", "diff_snapshots", "format_snapshot_diff",
    "CHIP_PEAK_TFLOPS", "PerfAccountant", "detect_chip",
    "resolve_peak_flops",
    "MemoryAccountant", "get_accountant", "tree_bytes",
    "device_memory_stats", "estimate_forward_memory_bytes",
    "format_memory_report", "is_oom_error", "oom_forensics",
    "write_oom_forensics",
    "ProgramRegistry", "TrackedProgram", "format_program_table",
    "get_program_registry", "track_program",
    "DeviceProbe", "Tracer", "activate", "active_tracer", "begin_span",
    "chrome_trace_events", "deactivate", "format_summary", "span",
    "summarize", "summarize_trace_file", "write_chrome_trace",
]


class Observability:
    """Engine-facing bundle: window-gated tracer + registry + perf.

    ``begin_step(step)`` opens/closes the trace window (activating the
    module-global tracer so ``span()`` call sites across the codebase
    light up together); ``end_step(step, sync_value, tokens)`` runs the
    bounded-cadence device probe and feeds the step-time window.
    """

    def __init__(self, config: ObservabilityConfig, *,
                 steps_per_print: int = 10, registry=None):
        self.config = config
        self.registry = registry if registry is not None else get_registry()
        self.tracer = Tracer(max_events=config.trace_buffer_events)
        self.probe = DeviceProbe(config.probe_interval)
        self.perf = PerfAccountant(window=config.perf_window,
                                   peak_flops=resolve_peak_flops(config))
        # the process-wide accountant (train + serve share one table);
        # this bundle's config block tunes it
        self.memory = get_accountant()
        self.memory.config = config.memory
        # arm the process-wide goodput ledger so the engine's timed()
        # call sites record (goodput.py; host clock reads only). NOT
        # cached on self: reset_ledger() (bench measurement windows)
        # rebinds the module global, and a snapshot must read whatever
        # ledger the timed() sites are currently feeding.
        get_ledger().start()
        self.metrics_interval = (config.metrics_interval
                                 if config.metrics_interval is not None
                                 else max(1, int(steps_per_print)))
        self._window_open = False
        self._dropped_exported = 0

    def window_contains(self, step: int) -> bool:
        cfg = self.config
        if not cfg.trace or step < cfg.trace_start_step:
            return False
        return (cfg.trace_num_steps <= 0
                or step < cfg.trace_start_step + cfg.trace_num_steps)

    def begin_step(self, step: int):
        """Honor the capture window for the step about to run. Cheap
        host arithmetic; flips the module tracer only on window edges.
        An externally activated tracer (ds_tpu_bench --trace) owns the
        span stream for the whole process — the window never steals it
        or shuts it off."""
        want = self.window_contains(step)
        if want and not self._window_open:
            cur = active_tracer()
            if cur is None or cur is self.tracer:
                activate(self.tracer)
                self._window_open = True
        elif not want and self._window_open:
            if active_tracer() is self.tracer:
                deactivate()
            self._window_open = False

    def end_step(self, step: int, sync_value=None, tokens=None):
        """Post-step hook: device probe on its bounded cadence, then the
        wall-clock step-time sample, then (on the same bounded cadence —
        zero additional syncs) one live memory sample. No other host
        sync happens here."""
        waited = self.probe.maybe_block(sync_value, step)
        self.perf.on_step(tokens)
        mem_cfg = self.config.memory
        if mem_cfg.enabled:
            if mem_cfg.poll_interval > 0:
                if step % mem_cfg.poll_interval == 0:
                    self.memory.sample_live(step)
            elif waited is not None:      # ride the probe cadence
                self.memory.sample_live(step)

    def close(self):
        """Release the module tracer if this bundle holds it."""
        if self._window_open and active_tracer() is self.tracer:
            deactivate()
        self._window_open = False

    # -- reporting ---------------------------------------------------------
    def trace_summary(self) -> dict:
        return summarize(self.tracer.events)

    def write_trace(self, path: str) -> str:
        self._export_dropped()
        meta = {"dropped_events": self.tracer.dropped}
        return write_chrome_trace(self.tracer.events, path, metadata=meta)

    def _export_dropped(self):
        """Sync the tracer's eviction count into the registry counter
        (``trace/spans_dropped_total``) — counters are monotonic, so
        only the delta since the last export is added."""
        delta = self.tracer.dropped - self._dropped_exported
        if delta > 0:
            self.registry.counter("trace/spans_dropped_total").inc(delta)
            self._dropped_exported = self.tracer.dropped

    def snapshot(self) -> dict:
        """Registry snapshot + perf summary + probe counters + memory
        attribution + the compiled-program table, JSON-able (the
        ``ds_tpu_trace --metrics-out`` / ``ds_tpu_report`` payload)."""
        self._export_dropped()
        top = self.config.memory.top_buffers
        return {
            "registry": self.registry.snapshot(),
            "perf": self.perf.summary(),
            "goodput": get_ledger().breakdown(),
            "probe": {"interval": self.probe.interval,
                      "host_reads": self.probe.host_reads,
                      "last_wait_s": self.probe.last_wait_s},
            "trace": {"events_buffered": len(self.tracer.events),
                      "events_dropped": self.tracer.dropped},
            "memory": self.memory.report(top),
            "programs": get_program_registry().table(),
        }
