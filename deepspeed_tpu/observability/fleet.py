"""Fleet-wide request tracing + telemetry aggregation.

PR 12 scaled serving past one engine; this module scales the PR-5/7/8
observability plane with it. Three instruments, all host-side (the
TS002 zero-host-sync rule holds — every stamp here is scheduler
arithmetic on clocks the engines already keep):

- **Trace ids + flight recorder**: every request carries a
  ``trace_id`` (``make_trace_id`` — deterministic, replayable) that
  follows it across replicas, through the worker line-JSON protocol
  and the handoff wire format. ``FlightRecorder`` keeps a bounded ring
  of request lifecycle events (submit/admit/first_token/handoff/
  preempt/shed/finish) stamped on the deterministic step clock; it
  rides the existing partial-snapshot/crash path, so a dead fleet
  leaves a reconstructable last-N-requests timeline.
- **Per-request waterfall**: ``per_request_breakdown`` turns recorder
  events into a queue→prefill→handoff→decode stage table whose
  per-request stage sums telescope EXACTLY to the request's
  end-to-end steps (monotone stage marks — a missing or out-of-order
  mark collapses its stage to zero rather than breaking the sum).
  ``breakdown_from_trace`` applies the same staging to a recorded
  span stream (wall milliseconds) for post-hoc trace analysis.
- **Trace stitching + telemetry aggregation**: ``stitch_chrome_traces``
  merges per-replica span dumps into ONE Chrome trace with one process
  lane per replica (cross-process ``perf_counter`` clocks share no
  epoch, so each lane is normalized to its own start);
  ``FleetTelemetryAggregator`` polls every replica — process replicas
  via the PR-12 ``MetricsScrapeClient``, in-process replicas via
  direct snapshot — on a bounded cadence and merges the samples into
  one fleet-level view with per-replica ``up``/staleness, the data
  plane scrape-driven routing will consume.

Stdlib only, like every module in this package.
"""

import threading
import time
import zlib
from collections import deque
from typing import Callable, Dict, List, Optional

from .metrics import percentile

# waterfall stages, in lifecycle order; each stage's mark names the
# event that ENDS it (the chain starts at "submit"). "handoff" ends
# when the prefill side EXPORTS the KV payload; "wire" spans
# export→inject — the steps the handoff spent crossing hosts (staged,
# backlogged, retried, or crossing the federation wire). For a
# non-disaggregated request both stages clamp to zero, so the five
# stages still telescope exactly to total_steps for every request.
STAGES = ("queue", "prefill", "handoff", "wire", "decode")
_STAGE_END_EVENT = {
    "queue": "admit",
    "prefill": "first_token",
    "handoff": "handoff_export",
    "wire": "handoff_inject",
    # decode ends at whichever terminal event the request reached
}
TERMINAL_EVENTS = ("finished", "shed", "timeout", "cancelled")

DEFAULT_RECORDER_EVENTS = 256


def make_trace_id(request_id, ordinal: int = 0) -> str:
    """Deterministic per-request trace id: a crc32 fold of the request
    id plus the submit ordinal (two submissions reusing one id stay
    distinguishable). Python ``hash()`` is salted per process and would
    break cross-process stitching — the engine rng-fold lesson."""
    fold = zlib.crc32(repr((request_id, int(ordinal))).encode())
    return f"t{int(ordinal) & 0xFFFFFF:06x}{fold & 0xFFFFFFFF:08x}"


class FlightRecorder:
    """Bounded ring of request lifecycle events.

    Each event is a plain JSON-able dict ``{event, request_id,
    trace_id, replica_id, iteration, unix_ts, ...extra}``; the oldest
    drop first (``dropped`` counts evictions, surfaced in ``snapshot``
    so a truncated timeline is never read as complete). ``capacity=0``
    disables recording entirely (every ``record`` is a no-op)."""

    def __init__(self, capacity: int = DEFAULT_RECORDER_EVENTS):
        self.capacity = max(0, int(capacity))
        self.events = deque(maxlen=self.capacity or 1)
        self.recorded = 0
        self.dropped = 0

    def record(self, event: str, *, request_id=None, trace_id=None,
               replica_id=None, iteration=None, **extra):
        if self.capacity <= 0:
            return
        if len(self.events) == self.events.maxlen:
            self.dropped += 1
        ev = {"event": event, "request_id": request_id,
              "trace_id": trace_id, "replica_id": replica_id,
              "iteration": iteration, "unix_ts": time.time()}
        if extra:
            ev.update(extra)
        self.events.append(ev)
        self.recorded += 1

    def clear(self):
        self.events.clear()
        self.recorded = 0
        self.dropped = 0

    def snapshot(self) -> dict:
        """JSON-able dump (the partial-snapshot/crash-path payload)."""
        return {"capacity": self.capacity, "recorded": self.recorded,
                "dropped": self.dropped, "events": list(self.events)}


# ---------------------------------------------------------------------------
# Per-request latency waterfall
# ---------------------------------------------------------------------------

def _stage_marks(evs: List[dict]) -> Optional[dict]:
    """Lifecycle marks for one request's events: first occurrence of
    each stage boundary, last terminal event. None when the request
    never submitted or never reached a terminal state."""
    marks = {}
    for ev in evs:
        it = ev.get("iteration")
        if it is None:
            continue
        name = ev["event"]
        if name in TERMINAL_EVENTS:
            marks["_terminal"] = int(it)
            marks["_status"] = name
        elif name not in marks:
            marks[name] = int(it)
    if "submit" not in marks or "_terminal" not in marks:
        return None
    return marks


def per_request_breakdown(events, include_requests: bool = True) -> dict:
    """Per-request stage waterfall from flight-recorder events.

    Stages run queue (submit→admit), prefill (admit→first_token),
    handoff (first_token→handoff_export), wire (handoff_export→
    handoff_inject — the steps the KV payload spent in flight between
    replicas; both zero when the request never crossed a replica
    boundary), decode (→terminal). Marks are made
    monotone (``max`` against the previous boundary), so per-request
    stage sums are EXACTLY ``terminal - submit`` — the request's
    end-to-end steps — no matter which marks are missing. Returns
    ``{"requests": {trace_id: {stage: steps, ..., "total_steps",
    "status", "request_id"}}, "stages": {stage: {count, p50, p95,
    mean}}, "requests_complete": N}``."""
    per: Dict[str, List[dict]] = {}
    for ev in events:
        tid = ev.get("trace_id")
        if tid is not None:
            per.setdefault(tid, []).append(ev)
    requests = {}
    stage_samples: Dict[str, List[int]] = {s: [] for s in STAGES}
    for tid, evs in per.items():
        marks = _stage_marks(evs)
        if marks is None:
            continue          # still in flight (or recorder evicted it)
        prev = marks["submit"]
        row = {}
        for stage in STAGES:
            end_event = _STAGE_END_EVENT.get(stage)
            end = (marks.get(end_event) if end_event is not None
                   else marks["_terminal"])
            end = prev if end is None else max(prev, end)
            end = min(end, marks["_terminal"])
            row[stage] = end - prev
            prev = end
        row["total_steps"] = marks["_terminal"] - marks["submit"]
        row["status"] = marks["_status"]
        row["request_id"] = next(
            (e.get("request_id") for e in evs
             if e.get("request_id") is not None), None)
        requests[tid] = row
        for stage in STAGES:
            stage_samples[stage].append(row[stage])
    stages = {}
    for stage, vals in stage_samples.items():
        if vals:
            stages[stage] = {"count": len(vals),
                             "p50": percentile(vals, 50),
                             "p95": percentile(vals, 95),
                             "mean": sum(vals) / len(vals)}
    out = {"stages": stages, "requests_complete": len(requests)}
    if include_requests:
        out["requests"] = requests
    return out


# span name -> waterfall stage, for the trace-file variant
_SPAN_STAGE = {
    "serving/queue_wait": "queue",
    "serving/prefill_chunk": "prefill",
    "serving/handoff_export": "handoff",
    "serving/handoff_inject": "wire",
    "serving/decode_residency": "decode",
}


def breakdown_from_trace(trace) -> dict:
    """The waterfall recovered from a (stitched) Chrome trace: "X"
    events carrying ``args.trace_id`` are grouped per request and their
    durations summed per stage (wall milliseconds — a recorded span
    stream has no step clock). ``trace`` is the payload dict, a bare
    event list, or a path to either on disk."""
    if isinstance(trace, str):
        import json
        with open(trace) as f:
            trace = json.load(f)
    events = trace.get("traceEvents", trace) \
        if isinstance(trace, dict) else trace
    per: Dict[str, Dict[str, float]] = {}
    lanes: Dict[str, set] = {}
    for ev in events:
        if ev.get("ph") != "X":
            continue
        args = ev.get("args") or {}
        tid = args.get("trace_id")
        stage = _SPAN_STAGE.get(ev.get("name"))
        if tid is None or stage is None:
            continue
        row = per.setdefault(tid, {s: 0.0 for s in STAGES})
        row[stage] += float(ev.get("dur", 0.0)) / 1e3
        lanes.setdefault(tid, set()).add(ev.get("pid"))
    stage_samples: Dict[str, List[float]] = {s: [] for s in STAGES}
    for tid, row in per.items():
        row["total_ms"] = sum(row[s] for s in STAGES)
        row["lanes"] = len(lanes[tid])
        for s in STAGES:
            stage_samples[s].append(row[s])
    stages = {}
    for stage, vals in stage_samples.items():
        if vals:
            stages[stage] = {"count": len(vals),
                             "p50": percentile(vals, 50),
                             "p95": percentile(vals, 95),
                             "mean": sum(vals) / len(vals)}
    return {"requests": per, "stages": stages,
            "requests_complete": len(per), "unit": "ms"}


def format_waterfall(breakdown: dict, unit: str = "steps") -> str:
    """Render a breakdown's per-stage table (the /statusz,
    ``ds_tpu_report --fleet``, and BENCH-artifact rendering)."""
    stages = breakdown.get("stages") or {}
    if not stages:
        return "(no completed traced requests)"
    unit = breakdown.get("unit", unit)
    width = max(len("stage"), max(len(s) for s in stages))
    lines = [f"{'stage':<{width}}  {'count':>6}  {'p50':>9}  "
             f"{'p95':>9}  {'mean':>9}   ({unit})"]
    for stage in STAGES:
        s = stages.get(stage)
        if s is None:
            continue
        lines.append(f"{stage:<{width}}  {s['count']:>6}  "
                     f"{s['p50']:>9.2f}  {s['p95']:>9.2f}  "
                     f"{s['mean']:>9.2f}")
    lines.append(f"({breakdown.get('requests_complete', 0)} requests "
                 "completed with trace marks)")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# Chrome-trace stitching (one lane per replica)
# ---------------------------------------------------------------------------

def stitch_chrome_traces(dumps, normalize: bool = True) -> dict:
    """Merge per-replica span dumps into ONE Chrome trace.

    ``dumps`` is ``[(label, events)]`` where ``events`` is a
    ``chrome_trace_events`` list or a ``{"traceEvents": [...]}``
    payload. Each dump becomes its own process lane (``pid`` = dump
    ordinal, named via "M" metadata events, ordered top-to-bottom as
    given). Cross-process ``perf_counter`` clocks share no epoch, so
    ``normalize=True`` (default) rebases every lane to its own first
    timestamp — lanes align at t=0, and within-lane timing plus the
    per-request ``trace_id`` args (the cross-lane join key) are what
    carry meaning."""
    out = []
    for pid, (label, events) in enumerate(dumps):
        if isinstance(events, dict):
            events = events.get("traceEvents") or []
        out.append({"name": "process_name", "ph": "M", "pid": pid,
                    "args": {"name": str(label)}})
        out.append({"name": "process_sort_index", "ph": "M", "pid": pid,
                    "args": {"sort_index": pid}})
        base = min((float(e["ts"]) for e in events if "ts" in e),
                   default=0.0) if normalize else 0.0
        for ev in events:
            ev = dict(ev)
            ev["pid"] = pid
            if normalize and "ts" in ev:
                ev["ts"] = float(ev["ts"]) - base
            out.append(ev)
    return {"traceEvents": out, "displayTimeUnit": "ms"}


def write_stitched_trace(dumps, path: str, normalize: bool = True) -> str:
    import json
    with open(path, "w") as f:
        json.dump(stitch_chrome_traces(dumps, normalize=normalize), f)
    return path


# ---------------------------------------------------------------------------
# Fleet telemetry aggregation
# ---------------------------------------------------------------------------

# substrings marking per-replica statistics that do NOT add across
# replicas (percentiles, means, rates/fractions, capacities, clocks):
# summing two replicas' p50s produces a latency no replica ever saw,
# and a merged view an operator alerts on must never contain one
_NON_ADDITIVE = ("_p50", "_p95", "_p99", "_mean", "_max", "_rate",
                 "_frac", "fraction", "utilization", "quantile=",
                 "staleness", "qos_level", "slot_cap", "page_len",
                 "elapsed_s", "capture_seq", "_interval", "_unix",
                 "_monotonic")


def additive_metric(key: str) -> bool:
    """True when ``key`` names a metric whose per-replica values sum
    meaningfully at the fleet level (counters, token/byte/request
    totals, queue depth, slot occupancy counts)."""
    return not any(tok in key for tok in _NON_ADDITIVE)


def merge_numeric(samples: Dict) -> dict:
    """Sum the numeric values across per-replica samples (the
    fleet-totals view: counters add, depth/occupancy gauges add).
    Non-numeric payloads and non-additive statistics (percentiles,
    means, rates — ``additive_metric``) are skipped; ``ds_tpu_``-
    prefixed scrape names are normalized so scraped and direct samples
    merge under one key space."""
    merged: Dict[str, float] = {}
    for sample in samples.values():
        if not isinstance(sample, dict):
            continue
        for key, value in sample.items():
            if isinstance(value, bool) or not isinstance(value,
                                                         (int, float)):
                continue
            if not additive_metric(key):
                continue
            if key.startswith("ds_tpu_"):
                key = key[len("ds_tpu_"):]
            merged[key] = merged.get(key, 0) + value
    return merged


class FleetTelemetryAggregator:
    """Bounded-cadence poll of every replica's telemetry into one
    fleet-level snapshot.

    Sources are registered per replica: ``add_scrape`` (a process
    replica's ``/metrics`` endpoint, read through the hardened
    ``MetricsScrapeClient`` — one transient failure is retried, the
    staleness stamp tells a dead replica from one dropped scrape) or
    ``add_direct`` (an in-process replica's host-dict snapshot
    callable). ``poll()`` runs on the FLEET's cadence (the manager
    calls it every ``aggregate_every_steps`` fleet steps) — never per
    engine step, never on the device."""

    def __init__(self, stale_after_s: float = 30.0):
        self.stale_after_s = float(stale_after_s)
        self.replicas: Dict[int, dict] = {}
        self.polls = 0
        self._poll_thread: Optional[threading.Thread] = None

    # -- source registration ----------------------------------------------
    def _entry(self, replica_id: int) -> dict:
        return self.replicas.setdefault(int(replica_id), {
            "mode": None, "up": False, "dead": False, "sample": None,
            "last_success_unix": None, "scrapes_ok": 0,
            "scrapes_failed": 0,
        })

    def add_scrape(self, replica_id: int, base_url: Optional[str] = None,
                   timeout_s: float = 2.0, client=None):
        """Register a /metrics scrape source: pass an existing
        ``MetricsScrapeClient`` (the fleet reuses each ProcessReplica's
        cached one, so health sweeps and aggregator polls accumulate
        ONE ``last_success_unix`` staleness stamp) or a ``base_url`` to
        build a fresh one."""
        if client is None:
            if base_url is None:
                raise ValueError("add_scrape needs base_url or client")
            from .export import MetricsScrapeClient
            client = MetricsScrapeClient(base_url, timeout_s=timeout_s)
        e = self._entry(replica_id)
        e["mode"] = "scrape"
        e["client"] = client
        return client

    def add_direct(self, replica_id: int, fn: Callable[[], dict]):
        e = self._entry(replica_id)
        e["mode"] = "direct"
        e["fn"] = fn

    def mark_dead(self, replica_id: int):
        """A replica the manager declared dead stops being polled; its
        last sample stays visible (the work it served must not vanish
        from the merged view) but ``up`` reads False forever."""
        if replica_id in self.replicas:
            self.replicas[replica_id]["dead"] = True
            self.replicas[replica_id]["up"] = False

    def forget(self, replica_id: int):
        """Drop a replica's entry entirely — the manager's bounded
        corpse history prunes old dead replicas, and their last samples
        leave the merged view with them (a supervised fleet restarts
        without bound; the aggregator must not grow with it)."""
        self.replicas.pop(int(replica_id), None)

    # -- the poll ----------------------------------------------------------
    def poll_async(self):
        """Fire one poll on a daemon thread — the serving data plane
        must never block on an unresponsive replica's HTTP scrape
        (timeout x retry could stall a fleet step for seconds). If the
        previous poll is still draining, this tick is skipped: the
        staleness stamps already tell that story."""
        t = self._poll_thread
        if t is not None and t.is_alive():
            return
        t = threading.Thread(target=self.poll, daemon=True,
                             name="ds-tpu-fleet-aggregator")
        self._poll_thread = t
        t.start()

    def poll(self) -> dict:
        """Pull one sample per live source. A failed pull marks the
        replica down for this round WITHOUT discarding its last sample;
        the staleness stamp is what distinguishes "down one round" from
        "gone". Safe off-thread: entries are host dicts mutated
        whole-value, and registration during a poll is tolerated (the
        iteration snapshot below)."""
        self.polls += 1
        for e in list(self.replicas.values()):
            if e["dead"] or e["mode"] is None:
                continue
            sample = None
            if e["mode"] == "scrape":
                sample = e["client"].gauges()
            else:
                try:
                    sample = e["fn"]()
                except RuntimeError:
                    # the one concurrent-mutation retry every snapshot
                    # reader in this package gets; a second failure is
                    # a missed poll, never a dead fleet step
                    try:
                        sample = e["fn"]()
                    except RuntimeError:
                        sample = None
            if sample is None:
                e["up"] = False
                e["scrapes_failed"] += 1
                continue
            e["up"] = True
            e["scrapes_ok"] += 1
            e["sample"] = sample
            e["last_success_unix"] = (
                e["client"].last_success_unix if e["mode"] == "scrape"
                else time.time())
        return self.snapshot()

    def healthy(self, replica_id) -> bool:
        """Dispatch-health verdict for the router: False when the
        replica is marked dead, its ``up`` gauge is down, or its last
        successful sample is older than ``stale_after_s``. A replica
        that has never been polled reads healthy until its first
        FAILED poll — a fresh spawn must not be quarantined before its
        first scrape window."""
        e = self.replicas.get(int(replica_id))
        if e is None:
            return True
        if e["dead"]:
            return False
        last = e["last_success_unix"]
        if last is None:
            return e["scrapes_failed"] == 0
        if not e["up"]:
            return False
        return (time.time() - last) <= self.stale_after_s

    def merged(self) -> dict:
        return merge_numeric({rid: e.get("sample")
                              for rid, e in self.replicas.items()})

    def snapshot(self) -> dict:
        """The fleet-telemetry section: per-replica liveness/staleness
        plus the merged totals. JSON-able host state only."""
        now = time.time()
        replicas = {}
        for rid, e in sorted(self.replicas.items()):
            last = e["last_success_unix"]
            staleness = (now - last) if last is not None else None
            replicas[str(rid)] = {
                "mode": e["mode"], "up": bool(e["up"]),
                "dead": bool(e["dead"]),
                "last_success_unix": last,
                "staleness_s": staleness,
                "stale": (staleness is None
                          or staleness > self.stale_after_s),
                "scrapes_ok": e["scrapes_ok"],
                "scrapes_failed": e["scrapes_failed"],
                "sample": e["sample"],
            }
        return {"polls": self.polls, "stale_after_s": self.stale_after_s,
                "replicas": replicas, "merged": self.merged()}

    def gauges(self) -> dict:
        """Per-replica up/staleness + merged totals as flat gauge pairs
        — what the manager folds into the router process's registry
        snapshot so the merged ``/metrics`` carries the fleet section."""
        out = {}
        now = time.time()
        for rid, e in sorted(self.replicas.items()):
            out[f"fleet/replica/{rid}/up"] = 1 if e["up"] else 0
            last = e["last_success_unix"]
            if last is not None:
                out[f"fleet/replica/{rid}/staleness_s"] = now - last
        for key, value in sorted(self.merged().items()):
            out[f"fleet/merged/{key}"] = value
        return out
