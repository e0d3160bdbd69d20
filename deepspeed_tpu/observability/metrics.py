"""Process metrics registry: counters, gauges, histograms, tables.

ONE registry (``get_registry()``) is shared by every telemetry producer
— engine throughput/perf accounting, ``ServingMetrics`` mirrors, and the
resilience event counters — so "what is this process doing" is a single
``snapshot()`` instead of four private buffers. Values are plain host
floats/ints: recording a metric never touches the device (the monitor
buffering in runtime/engine.py owns the one batched device_get per
flush cadence).

Stdlib-only so the registry works in dependency-free contexts (the lint
job, ``ds_tpu_report`` on a login node).
"""

import json
import time
from array import array
from collections import deque
from typing import Callable, Dict, Optional

DEFAULT_HISTOGRAM_WINDOW = 512


def percentile(values, q):
    """Nearest-rank percentile (rounded index over the sorted values);
    None on empty input. The one percentile implementation every
    telemetry producer shares — trace summaries, the registry
    histograms, perf accounting, and ServingMetrics all delegate here
    so the same q over the same data always picks the same element."""
    if not values:
        return None
    s = sorted(values)
    idx = min(len(s) - 1, max(0, int(round(q / 100.0 * (len(s) - 1)))))
    return s[idx]


class Counter:
    """Monotonic event count."""
    __slots__ = ("name", "value")

    def __init__(self, name: str):
        self.name = name
        self.value = 0

    def inc(self, n=1):
        self.value += n


class Gauge:
    """Last-write-wins instantaneous value."""
    __slots__ = ("name", "value")

    def __init__(self, name: str):
        self.name = name
        self.value = None

    def set(self, v):
        self.value = v


class Histogram:
    """Sliding-window distribution (p50/p95 over the most recent
    ``window`` observations — the long-lived-server convention; all-time
    count/sum ride along)."""
    __slots__ = ("name", "window", "count", "total")

    def __init__(self, name: str, window: int = DEFAULT_HISTOGRAM_WINDOW):
        self.name = name
        self.window = deque(maxlen=max(1, int(window)))
        self.count = 0
        self.total = 0.0

    def observe(self, v):
        self.window.append(float(v))
        self.count += 1
        self.total += float(v)

    def percentile(self, q):
        return percentile(self.window, q)

    def summary(self) -> dict:
        out = {"count": self.count, "sum": self.total}
        if self.window:
            out["mean"] = sum(self.window) / len(self.window)
            out["p50"] = self.percentile(50)
            out["p95"] = self.percentile(95)
            out["max"] = max(self.window)
        return out


class Table:
    """Fixed-width ring of integer rows: a log that keeps the most
    recent ``rows`` records whole, where a histogram keeps one number
    of each. Preallocated (8 bytes a cell); ``write`` copies one row in
    and allocates nothing: the row is an ``array('q')`` of the table's
    width, which the writer keeps and fills again."""
    __slots__ = ("name", "columns", "rows", "count", "_cells")

    def __init__(self, name: str, columns, rows: int):
        self.name = name
        self.columns = tuple(columns)
        if not self.columns or len(set(self.columns)) != len(self.columns):
            raise ValueError(f"table {name!r} needs distinct column names, "
                             f"got {self.columns}")
        self.rows = max(1, int(rows))
        self.count = 0                  # rows ever written
        self._cells = array("q", bytes(8 * self.rows * len(self.columns)))

    def write(self, row):
        width = len(self.columns)
        if len(row) != width:
            raise ValueError(f"table {self.name!r} has {width} columns, "
                             f"the row has {len(row)}")
        at = (self.count % self.rows) * width
        self._cells[at:at + width] = row
        self.count += 1

    def __len__(self):
        """Rows retained."""
        return min(self.count, self.rows)

    def read(self):
        """The retained rows, oldest first, one tuple each in the order
        of ``columns``."""
        width, kept = len(self.columns), len(self)
        first = self.count - kept
        out = []
        for i in range(first, self.count):
            at = (i % self.rows) * width
            out.append(tuple(self._cells[at:at + width]))
        return out

    def summary(self) -> dict:
        """What a snapshot carries of a table: its shape, how much of
        what was written it still holds, and the newest row. Constant
        time, because a scrape runs beside the loop that writes; the
        rows themselves are ``read()``'s."""
        out = {"columns": list(self.columns), "capacity": self.rows,
               "count": self.count, "retained": len(self)}
        if self.count:
            width = len(self.columns)
            at = ((self.count - 1) % self.rows) * width
            out["last"] = dict(zip(self.columns,
                                   self._cells[at:at + width]))
        return out


class MetricsRegistry:
    """Named-instrument registry. ``counter``/``gauge``/``histogram``/
    ``table`` get-or-create (a name keeps its first kind; a kind clash raises);
    ``register_collector`` attaches a callable polled at snapshot time
    for subsystems that already keep their own state (ServingMetrics)."""

    def __init__(self):
        self._counters: Dict[str, Counter] = {}
        self._gauges: Dict[str, Gauge] = {}
        self._hists: Dict[str, Histogram] = {}
        self._tables: Dict[str, Table] = {}
        self._collectors: Dict[str, Callable[[], dict]] = {}
        self._snapshot_seq = 0

    def _check_free(self, name, own):
        for kind, table in (("counter", self._counters),
                            ("gauge", self._gauges),
                            ("histogram", self._hists),
                            ("table", self._tables)):
            if table is not own and name in table:
                raise ValueError(
                    f"metric {name!r} already registered as a {kind}")

    def counter(self, name: str) -> Counter:
        if name not in self._counters:
            self._check_free(name, self._counters)
            self._counters[name] = Counter(name)
        return self._counters[name]

    def gauge(self, name: str) -> Gauge:
        if name not in self._gauges:
            self._check_free(name, self._gauges)
            self._gauges[name] = Gauge(name)
        return self._gauges[name]

    def histogram(self, name: str,
                  window: int = DEFAULT_HISTOGRAM_WINDOW) -> Histogram:
        if name not in self._hists:
            self._check_free(name, self._hists)
            self._hists[name] = Histogram(name, window)
        return self._hists[name]

    def table(self, name: str, columns=None, rows: int = 0
              ) -> Optional[Table]:
        """Get-or-create with ``columns`` and ``rows``; a table that is
        there keeps its size, and other columns than it has raise. With
        no ``columns``: the table if some writer made it, else None — a
        reader never creates one."""
        found = self._tables.get(name)
        if found is None and columns is not None:
            self._check_free(name, self._tables)
            found = self._tables[name] = Table(name, columns, rows)
        elif found is not None and columns is not None \
                and tuple(columns) != found.columns:
            raise ValueError(
                f"table {name!r} already registered with columns "
                f"{found.columns}, not {tuple(columns)}")
        return found

    def register_collector(self, name: str, fn: Callable[[], dict]):
        """``fn()`` returns a flat {metric: value} dict merged into
        snapshots under ``collected.<name>``."""
        self._collectors[name] = fn

    def snapshot(self) -> dict:
        """JSON-able state of every instrument (plus collector polls).
        The ``meta`` header stamps a monotonic capture sequence number
        and wall-clock/monotonic times so two snapshots of the same
        process diff meaningfully (which came first, how far apart)."""
        self._snapshot_seq += 1
        out = {
            "meta": {"capture_seq": self._snapshot_seq,
                     "captured_at_unix": time.time(),
                     "captured_at_monotonic_s": time.monotonic()},
            "counters": {n: c.value for n, c in sorted(self._counters.items())},
            "gauges": {n: g.value for n, g in sorted(self._gauges.items())
                       if g.value is not None},
            "histograms": {n: h.summary()
                           for n, h in sorted(self._hists.items())},
            "tables": {n: t.summary()
                       for n, t in sorted(self._tables.items())},
        }
        if self._collectors:
            out["collected"] = {n: fn()
                                for n, fn in sorted(self._collectors.items())}
        return out

    def to_events(self, step: int):
        """Flatten to monitor-fan-out ``(label, value, step)`` events.
        Histograms emit their p50/p95 under ``<name>/p50`` etc."""
        events = []
        for n, c in sorted(self._counters.items()):
            events.append((n, c.value, step))
        for n, g in sorted(self._gauges.items()):
            if g.value is not None:
                events.append((n, g.value, step))
        for n, h in sorted(self._hists.items()):
            p50, p95 = h.percentile(50), h.percentile(95)
            if p50 is not None:
                events.append((f"{n}/p50", p50, step))
                events.append((f"{n}/p95", p95, step))
        return events

    def flush_to_monitor(self, monitor, step: int):
        """Hand the current values to a MonitorMaster-like fan-out
        (host floats only; gated to the caller's cadence)."""
        if monitor is None or not getattr(monitor, "enabled", False):
            return
        events = self.to_events(step)
        if events:
            monitor.write_events(events)

    def write_json(self, path: str):
        with open(path, "w") as f:
            json.dump(self.snapshot(), f, indent=1, default=str)
        return path

    def reset(self):
        """Drop every instrument and collector (test isolation)."""
        self._counters.clear()
        self._gauges.clear()
        self._hists.clear()
        self._tables.clear()
        self._collectors.clear()
        self._snapshot_seq = 0


_DEFAULT_REGISTRY: Optional[MetricsRegistry] = None


def get_registry() -> MetricsRegistry:
    """The process-wide shared registry."""
    global _DEFAULT_REGISTRY
    if _DEFAULT_REGISTRY is None:
        _DEFAULT_REGISTRY = MetricsRegistry()
    return _DEFAULT_REGISTRY


# ---------------------------------------------------------------------------
# Collective-traffic tally (the comm/ instrumentation's host side)
# ---------------------------------------------------------------------------
#
# comm.py wrappers record here AT TRACE TIME — once per compile, never
# per executed step (in-jit collectives cannot be host-timed without a
# sync, the TS002 rule). The process tally is keyed "op:axis" so the
# registry separates ICI-bound (model/fsdp/...) from DCN-bound (data
# across slices) traffic; TrackedProgram diffs the tally around a
# compiling dispatch to attribute the traced bytes to that program
# (programs.py), turning the static record into a per-call estimate.

_COLLECTIVE_TALLY: Dict[str, int] = {}


def record_traced_collective(op: str, axis: str, nbytes: int):
    """One collective traced: bump the process tally and the registry's
    bytes-by-collective counters. Host ints only — callable from inside
    a jit trace (it runs at trace time, not at execution time)."""
    key = f"{op}:{axis}"
    _COLLECTIVE_TALLY[key] = _COLLECTIVE_TALLY.get(key, 0) + int(nbytes)
    reg = get_registry()
    reg.counter(f"comm/traced_calls/{key}").inc()
    reg.counter(f"comm/traced_bytes/{key}").inc(int(nbytes))


def collective_tally() -> Dict[str, int]:
    """Snapshot of the cumulative traced-collective bytes by op:axis."""
    return dict(_COLLECTIVE_TALLY)


def diff_collective_tally(before: Dict[str, int]) -> Dict[str, int]:
    """Per-key growth of the tally since ``before`` (a
    ``collective_tally()`` snapshot) — what one compiling dispatch
    traced."""
    return {k: v - before.get(k, 0)
            for k, v in _COLLECTIVE_TALLY.items()
            if v - before.get(k, 0) > 0}


# ---------------------------------------------------------------------------
# Snapshot diffing (ds_tpu_report --diff)
# ---------------------------------------------------------------------------

def diff_snapshots(a: dict, b: dict) -> dict:
    """Diff two metrics snapshots (engine ``metrics_snapshot()`` payloads
    or bare registry snapshots): counters as deltas, gauges as
    before -> after. Ordering comes from the ``meta`` capture stamps —
    ``capture_seq`` when both snapshots came from one process, the
    monotonic clock otherwise; when ``b`` predates ``a`` the inputs are
    swapped and the result says so. ``elapsed_s`` (the monotonic delta)
    turns counter deltas into rates where available."""
    ra, rb = a.get("registry", a), b.get("registry", b)
    ma, mb = ra.get("meta") or {}, rb.get("meta") or {}

    def stamp(m):
        # unix wall clock first: the only stamp meaningful ACROSS
        # processes (a restarted run's capture_seq starts over at 1);
        # capture_seq breaks same-process ties taken within one wall
        # tick, monotonic breaks whatever is left
        return (m.get("captured_at_unix") or 0.0,
                m.get("capture_seq") or 0,
                m.get("captured_at_monotonic_s") or 0.0)

    swapped = stamp(mb) < stamp(ma)
    if swapped:
        ra, rb, ma, mb = rb, ra, mb, ma
    # same-process pair (the seq advanced and the monotonic clock agrees)
    # -> the monotonic delta is the precise elapsed; across processes the
    # clocks share no epoch, so fall back to the unix wall delta
    mono_a = ma.get("captured_at_monotonic_s")
    mono_b = mb.get("captured_at_monotonic_s")
    seq_a, seq_b = ma.get("capture_seq") or 0, mb.get("capture_seq") or 0
    elapsed = None
    if (mono_a is not None and mono_b is not None
            and seq_b > seq_a and mono_b >= mono_a):
        elapsed = mono_b - mono_a
    elif (ma.get("captured_at_unix") is not None
            and mb.get("captured_at_unix") is not None):
        elapsed = mb["captured_at_unix"] - ma["captured_at_unix"]
    counters = {}
    ca, cb = ra.get("counters") or {}, rb.get("counters") or {}
    for name in sorted(set(ca) | set(cb)):
        before, after = ca.get(name, 0), cb.get(name, 0)
        entry = {"before": before, "after": after, "delta": after - before}
        if elapsed and elapsed > 0:
            entry["per_s"] = entry["delta"] / elapsed
        counters[name] = entry
    gauges = {}
    ga, gb = ra.get("gauges") or {}, rb.get("gauges") or {}
    for name in sorted(set(ga) | set(gb)):
        gauges[name] = {"before": ga.get(name), "after": gb.get(name)}
    hists = {}
    ha, hb = ra.get("histograms") or {}, rb.get("histograms") or {}
    for name in sorted(set(ha) | set(hb)):
        sa, sb = ha.get(name) or {}, hb.get(name) or {}
        hists[name] = {
            "count_delta": sb.get("count", 0) - sa.get("count", 0),
            "sum_delta": sb.get("sum", 0.0) - sa.get("sum", 0.0),
            "p50_before": sa.get("p50"), "p50_after": sb.get("p50"),
            "p95_before": sa.get("p95"), "p95_after": sb.get("p95"),
        }
    return {
        "meta": {"from": ma, "to": mb, "elapsed_s": elapsed,
                 "swapped_inputs": swapped},
        "counters": counters,
        "gauges": gauges,
        "histograms": hists,
    }


def format_snapshot_diff(diff: dict) -> str:
    """Text rendering of ``diff_snapshots`` (the ``ds_tpu_report --diff``
    output): only moved counters, only changed gauges."""
    meta = diff["meta"]
    header = "snapshot diff"
    if meta.get("elapsed_s") is not None:
        header += f" over {meta['elapsed_s']:.3f}s"
    if meta.get("swapped_inputs"):
        header += " (inputs were newest-first; swapped)"
    lines = [header, "counters (delta):"]
    moved = {n: e for n, e in diff["counters"].items() if e["delta"]}
    for name, e in moved.items():
        rate = f"  ({e['per_s']:.3f}/s)" if "per_s" in e else ""
        lines.append(f"  {name}: +{e['delta']}{rate}")
    if not moved:
        lines.append("  (none moved)")
    lines.append("gauges (before -> after):")
    changed = {n: g for n, g in diff["gauges"].items()
               if g["before"] != g["after"]}
    for name, g in changed.items():
        lines.append(f"  {name}: {g['before']} -> {g['after']}")
    if not changed:
        lines.append("  (none changed)")
    return "\n".join(lines)
