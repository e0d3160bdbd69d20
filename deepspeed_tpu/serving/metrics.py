"""Buffered serving metrics.

Follows the PR-2 no-per-step-host-sync rule: every value here is either
host scheduler state (queue depth, slot assignment) or derived from
token arrays the engine ALREADY read back for streaming — recording a
metric never adds a device sync. Events buffer host-side and flush to
the MonitorMaster fan-out (TensorBoard/W&B/CSV) once per
``metrics_interval`` engine iterations.

Glossary (docs/serving.md has the full definitions):
- ttft: submit -> first streamed token (wall seconds; *_steps is the
  engine-iteration count, deterministic run-to-run)
- queue_depth: requests waiting for a slot, sampled per iteration
- slot_occupancy: fraction of slots holding a live request at dispatch
- throughput: generated tokens / wall seconds since the first submit
"""

import gc
import time
import weakref
from array import array
from collections import deque
from typing import Optional

from ..observability import goodput
from ..observability.fleet import FlightRecorder
from ..observability.metrics import get_registry
from ..observability.metrics import percentile as _percentile_impl
from ..observability.trace import begin_span

# sliding window for the percentile histories: a long-lived server must
# not grow per-request lists (or sort all-time history per snapshot)
# forever — p50/p95 over the most recent completions is the serving-
# dashboard convention anyway
HISTORY_WINDOW = 4096

# retained fault-log entries (watchdog fires, OOM sheds, recoveries):
# the /statusz breadcrumb trail, capped so a flapping fault can't grow
# the snapshot without bound
FAULT_LOG_LIMIT = 32


# The host loop's log, ``serving/iterations`` in the process registry:
# one row an ``advance()`` that had work, nanoseconds on
# ``time.perf_counter_ns`` unless said. ``caller`` | ``empty`` is the
# time from the previous such call's exit to this one's entry, by
# whether requests were in flight or queued at that exit; ``admit`` ..
# ``other`` partition entry -> exit exactly (``IN_ADVANCE``); ``gc`` is
# collector time anywhere in the row's interval and overlaps the phase
# it interrupted. Counts: the rows the decode dispatch advanced, the
# pages of the prefill chunks, compile events of the program registry,
# whether every read-back's arrays were ready before the blocking read
# (1 only if there was one), whether a tracer was active at entry.
ITERATION_COLUMNS = (
    "t_entry", "caller", "empty", "admit", "prefill_dispatch",
    "decode_dispatch", "readback", "harvest", "other", "gc",
    "rows_decoding", "chunk_pages", "compiles", "ready", "traced")
(T_ENTRY, CALLER, EMPTY, ADMIT, PREFILL_DISPATCH, DECODE_DISPATCH, READBACK,
 HARVEST, OTHER, GC, ROWS_DECODING, CHUNK_PAGES, COMPILES, READY,
 TRACED) = range(len(ITERATION_COLUMNS))
IN_ADVANCE = slice(ADMIT, OTHER + 1)
# over 70 s of the fastest cell's iterations (4.6 ms each): 1.97 MB
ITERATION_ROWS = 16_384
# an iteration whose host time (entry -> exit, less a read-back that
# waited for arrays not yet ready) passes this is a stall: the newest few
# are kept with their phase for /statusz; the rows have them all
HOST_STALL_NS = 50_000_000
HOST_STALL_LOG = 32

_now = time.perf_counter_ns


class PhaseClock:
    """The one clock of ``ServingEngine.advance()``: a cursor ``t`` and
    the column time is accruing to. Each boundary reads the clock once
    (``switch``) and adds the time since the last read to the phase
    that ends there, so the phases of a row sum to its wall time with
    nothing between them; the same stamps feed the two ``advance_*``
    histograms, the goodput ledger's ``compute`` and the stall log
    (``exit``). Always on. Outside ``advance()`` the cursor rests in
    ``caller`` or ``empty`` and ``switch`` leaves it there: a harvest
    the caller asks for between two iterations (``set_slot_cap``) is
    the caller's time."""

    def __init__(self, metrics):
        self._metrics = metrics
        reg = metrics.registry
        self.table = None if reg is None else reg.table(
            "serving/iterations", ITERATION_COLUMNS, ITERATION_ROWS)
        # (the program registry counts in the process registry, always)
        self._compiles = get_registry().counter("programs/compiles_total")
        self._blank = array("q", [0] * len(ITERATION_COLUMNS))
        self.row = array("q", self._blank)
        self.phase = EMPTY
        self.inside = False
        self.gc_ns = 0              # added to by the collector's hook
        self._compiles_at_entry = 0
        self._ready = None          # no read-back yet | all ready so far
        self._caller_span = None
        self.t = _now()

    def enter(self, traced: bool):
        """``advance()`` entry, with work: the caller's turn ends."""
        self.end_caller_span()
        now = _now()
        row = self.row
        row[self.phase] += now - self.t
        row[T_ENTRY] = now
        row[TRACED] = traced
        self.t = now
        self.phase = OTHER
        self.inside = True
        self._compiles_at_entry = self._compiles.value

    def switch(self, phase: int) -> int:
        """The phase that was running ends here and ``phase`` begins;
        returns the one that ended, for a nested phase to hand back."""
        prev = self.phase
        if self.inside:
            now = _now()
            self.row[prev] += now - self.t
            self.t = now
            self.phase = phase
        return prev

    def begin_readback(self, ready: bool) -> int:
        """``switch(READBACK)``, noting whether the arrays about to be
        read had already answered ``is_ready()``."""
        if self.inside:
            self._ready = ready and self._ready is not False
        return self.switch(READBACK)

    def exit(self, busy: bool):
        """``advance()`` exit: the row is written and every account that
        is kept from these stamps is fed. ``busy``: requests are in
        flight or queued, so the time to the next entry is the caller's
        and, while a tracer is active, spanned ``serving/caller``."""
        now = _now()
        row = self.row
        row[self.phase] += now - self.t
        self.t = now
        self.inside = False
        row[GC], self.gc_ns = self.gc_ns, 0
        row[COMPILES] = self._compiles.value - self._compiles_at_entry
        ready = row[READY] = int(bool(self._ready))
        self._ready = None
        readback = row[READBACK]
        compute = (row[PREFILL_DISPATCH] + row[DECODE_DISPATCH] + readback
                   + row[HARVEST])
        total = compute + row[ADMIT] + row[OTHER]
        self._metrics.on_advance(total, readback)
        goodput.note("compute", compute / 1e9)
        if total - (0 if ready else readback) > HOST_STALL_NS:
            self._metrics.on_host_stall(row)
        if self.table is not None:
            self.table.write(row)
        row[:] = self._blank
        self.phase = CALLER if busy else EMPTY
        if busy:
            self._caller_span = begin_span("serving/caller")

    def end_caller_span(self):
        span, self._caller_span = self._caller_span, None
        if span is not None:
            span.end()


class _CollectorHook:
    """Python's collector, timed while any engine's clock watches: one
    entry of ``gc.callbacks`` for the process, so a pass is stamped once
    whatever the number of engines — into each watching clock's ``gc``
    column, the histogram ``serving/gc_pause_ms``, a counter per
    generation and, while a tracer is active, a ``serving/gc`` span from
    the pass's start to its stop, inside whatever phase it interrupted.
    It runs only when the collector does. Clocks are held weakly: when
    the last one has gone, closed or not, the hook takes itself out."""

    def __init__(self):
        self.clocks = weakref.WeakSet()
        self._t0 = 0
        self._span = None

    def __call__(self, phase, info):
        if phase == "start":
            self._span = begin_span("serving/gc",
                                    {"generation": info["generation"]})
            self._t0 = _now()
            return
        took = _now() - self._t0
        span, self._span = self._span, None
        if span is not None:
            span.end()
        clocks = list(self.clocks)
        if not clocks:              # every engine went without close()
            gc.callbacks.remove(self)
            return
        for clock in clocks:
            clock.gc_ns += took
        reg = get_registry()
        reg.histogram("serving/gc_pause_ms").observe(took / 1e6)
        reg.counter(f"serving/gc_collections/gen{info['generation']}").inc()

    def watch(self, clock: PhaseClock):
        self.clocks.add(clock)
        if self not in gc.callbacks:
            gc.callbacks.append(self)

    def unwatch(self, clock: PhaseClock):
        self.clocks.discard(clock)
        if not self.clocks and self in gc.callbacks:
            gc.callbacks.remove(self)


collector_hook = _CollectorHook()


def _percentile(values, q):
    """Nearest-rank percentile without numpy (values non-empty) — the
    shared observability implementation."""
    return _percentile_impl(values, q)


class ServingMetrics:
    def __init__(self, monitor=None, interval: int = 50,
                 history_window: int = HISTORY_WINDOW, registry=None,
                 flight_recorder_events: int = 256):
        self.monitor = monitor
        self.interval = max(1, int(interval))
        self.history_window = max(1, int(history_window))
        # bounded request-lifecycle ring (observability/fleet.py): the
        # last-N-requests timeline the partial-snapshot/crash path dumps
        # — admit/preempt/handoff/shed/finish with trace_ids, stamped on
        # the deterministic engine clock. 0 disables.
        self.flight = FlightRecorder(flight_recorder_events)
        # mirror into the process-wide observability registry so one
        # snapshot covers train + serve + resilience; registry=False
        # opts out (isolated tests)
        self.registry = get_registry() if registry is None else (
            registry or None)
        self.reset()
        if self.registry is not None:
            # weakly bound: a torn-down engine's metrics must not be
            # kept alive (or polled as current) by the process registry
            import weakref
            ref = weakref.ref(self)

            def _collect():
                m = ref()
                return m.snapshot() if m is not None else {}
            self.registry.register_collector("serving", _collect)
        self.clock = PhaseClock(self)

    def reset(self):
        self.flight.clear()
        self.requests_submitted = 0
        self.requests_admitted = 0
        self.requests_finished = 0
        self.requests_timed_out = 0    # queued past deadline_steps
        self.requests_cancelled = 0    # client cancel() (queued or active)
        self.requests_rejected = 0     # refused at submit (budget/queue cap)
        self.requests_shed = 0         # QoS shed (SLO admission / ladder /
                                       # OOM containment) — explicit status,
                                       # never a silent TTL expiry
        self.requests_preempted = 0    # preempted-to-queue events (priority
                                       # preemption, scale-down drain,
                                       # recovery requeue)
        self.requests_resumed = 0      # re-admissions after preemption
        self.recoveries = 0            # requeue-and-re-prefill recoveries
        self.handoffs_exported = 0     # prefilled requests shipped to a
                                       # decode replica (fleet prefill role)
        self.handoffs_imported = 0     # page-handoffs continued here
        self.handoff_tokens_imported = 0
                                       # prompt tokens whose prefill this
                                       # engine NEVER ran (page transfer)
        self.shed_by_reason = {}       # reason -> count (qos.SHED_*)
        self.faults = []               # [{kind, detail, iteration}] capped
                                       # at FAULT_LOG_LIMIT (watchdog/oom/
                                       # recovery breadcrumbs for /statusz)
        self.per_class = {}            # qos class name -> counters + ttft
        self.qos_level = None          # latest ladder level (engine sample)
        self.slot_cap = None           # latest admissible-slot cap
        self.tokens_generated = 0
        self.prefills = 0
        self.decode_iterations = 0
        # speculative decoding (serving/speculation.py): token-level
        # proposer outcomes — proposed = entered verification,
        # accepted = emitted to the request, rejected = rolled back
        self.spec_proposed_tokens = 0
        self.spec_accepted_tokens = 0
        self.spec_rejected_tokens = 0
        self.wasted_slot_steps = 0     # inactive slots carried through decode
        # paged mode: the prefill-FLOPs ledger — computed counts prompt
        # tokens that actually ran through a prefill program (chunked),
        # reused counts tokens satisfied copy-free from the prefix cache.
        # Their sum over admitted requests equals total prompt tokens, so
        # reused/total IS the recomputation skipped by prefix sharing.
        self.prefill_chunks = 0
        self.prefill_chunk_pages = 0
        self.prefill_tokens_computed = 0
        self.prefill_tokens_reused = 0
        self._snapshots_seen = {}         # a snapshot table's counts, as
                                          # last folded into the registry
        self.paged_stats: Optional[dict] = None   # latest manager.stats()
        self.ttft_s = deque(maxlen=self.history_window)
        self.ttft_steps = deque(maxlen=self.history_window)
        # under-load slice: only completions whose request arrived while
        # others waited or all slots were busy (request.submitted_under_load)
        self.ttft_steps_under_load = deque(maxlen=self.history_window)
        self.latency_s = deque(maxlen=self.history_window)
        self.queue_depth_sum = 0
        self.queue_depth_max = 0
        self.occupancy_sum = 0.0
        self.busy_slots_max = 0        # peak concurrent admitted requests
        self.samples = 0
        self.started_at: Optional[float] = None
        self._events = []
        # the newest iterations past HOST_STALL_NS
        self.host_stall_log = deque(maxlen=HOST_STALL_LOG)

    # -- per-class accounting ----------------------------------------------
    def _cls(self, request) -> Optional[dict]:
        """The per-class bucket for a request (None when it carries no
        QoS class — priority-free traffic stays out of the breakdown)."""
        name = getattr(request, "qos_class", None) if request is not None \
            else None
        if name is None:
            return None
        c = self.per_class.get(name)
        if c is None:
            c = {"submitted": 0, "admitted": 0, "finished": 0,
                 "timed_out": 0, "shed": 0, "preempted": 0, "resumed": 0,
                 "ttft_steps": deque(maxlen=self.history_window)}
            self.per_class[name] = c
        return c

    def class_ttft_p95(self, class_name: str):
        """p95 TTFT (steps, deterministic) of one class's recent
        completions — the SLO-admission signal (None = no data yet)."""
        c = self.per_class.get(class_name)
        if not c or not c["ttft_steps"]:
            return None
        return _percentile(c["ttft_steps"], 95)

    def ttft_under_load_p95(self):
        """p95 of the under-load TTFT population in steps (the ladder's
        latency signal; None until under-load completions exist)."""
        if not self.ttft_steps_under_load:
            return None
        return _percentile(self.ttft_steps_under_load, 95)

    # -- flight recorder ---------------------------------------------------
    def _flight(self, event, request, iteration=None, **extra):
        """One lifecycle breadcrumb into the bounded recorder ring
        (host ints + the request's own stamps — no clock beyond the
        recorder's wall stamp, never a device read)."""
        if request is None:
            return
        self.flight.record(event, request_id=request.request_id,
                           trace_id=getattr(request, "trace_id", None),
                           iteration=iteration, **extra)

    # -- engine hooks ------------------------------------------------------
    def on_submit(self, request=None):
        if self.started_at is None:
            self.started_at = time.perf_counter()
        self.requests_submitted += 1
        c = self._cls(request)
        if c is not None:
            c["submitted"] += 1
        self._flight("submit", request,
                     iteration=getattr(request, "submitted_iteration",
                                       None))

    def on_admit(self, request=None, shared_tokens: int = 0,
                 state_restored: Optional[bool] = None,
                 state_missed: bool = False):
        """``state_restored`` (a model with recurrent state only: None
        otherwise): the admission starts from the state stored with its
        last shared page, or (False) from zeros. ``state_missed`` (a
        snapshot pool only): it matched deeper in the prefix cache than
        a snapshot let it start, and its hit was shortened."""
        self.requests_admitted += 1
        self.prefills += 1
        self.prefill_tokens_reused += shared_tokens
        if self.registry is not None:
            self.registry.counter("serving/prefill_tokens_reused").inc(
                shared_tokens)
            if state_restored is not None:
                self.registry.counter(
                    "serving/state_snapshots_restored" if state_restored
                    else "serving/state_resets").inc(1)
            if state_missed:
                self.registry.counter("serving/state_restore_missed").inc(1)
        c = self._cls(request)
        if c is not None:
            c["admitted"] += 1
        self._flight("admit", request,
                     iteration=getattr(request, "admitted_iteration",
                                       None))

    def on_prefill_chunk(self, tokens_computed: int,
                         state_snapshots: int = 0, pages: int = 1,
                         snapshot_table=None):
        """``state_snapshots``: the pages this chunk filled whole, each
        stored with the recurrent state at its end (0 for a model
        without such state). ``pages``: the width of the chunk program
        that ran, padding counted — over the chunks, the mean width the
        server chose. ``snapshot_table``: a snapshot pool's, whose
        counts are brought up to date (``on_state_snapshots``)."""
        self.prefill_chunks += 1
        self.prefill_chunk_pages += pages
        self.clock.row[CHUNK_PAGES] += pages
        self.prefill_tokens_computed += tokens_computed
        if self.registry is not None:
            self.registry.counter("serving/prefill_tokens_computed").inc(
                tokens_computed)
            self.registry.counter("serving/prefill_chunks").inc()
            self.registry.counter("serving/prefill_chunk_pages").inc(pages)
            if state_snapshots:
                self.registry.counter("serving/state_snapshots_stored").inc(
                    state_snapshots)
        if snapshot_table is not None:
            self.on_state_snapshots(snapshot_table)

    def on_state_snapshots(self, table):
        """After a chunk of a model with a snapshot pool
        (``paging/snapshots.py SnapshotTable``): the table's running
        counts of entries taken and evicted, brought up to date, and the
        entries in use."""
        if self.registry is None:
            return
        for name, total in (("taken", table.taken),
                            ("evicted", table.evicted)):
            # the process's counter outlives this engine and its table
            self.registry.counter(f"serving/state_snapshots_{name}").inc(
                total - self._snapshots_seen.get(name, 0))
            self._snapshots_seen[name] = total
        self.registry.gauge("serving/state_snapshots_in_use").set(
            table.in_use)

    def on_decode_dispatch(self, busy_slots: int, num_slots: int):
        """One decode dispatch over ``num_slots`` rows of which
        ``busy_slots`` hold a DECODING request (a paged slot still
        waiting for its prefill chunks rides along masked and is not
        busy). The registry pair sums to the batch occupancy."""
        self.decode_iterations += 1
        self.wasted_slot_steps += num_slots - busy_slots
        self.clock.row[ROWS_DECODING] = busy_slots
        if self.registry is not None:
            self.registry.counter("serving/decode_slots_busy").inc(busy_slots)
            self.registry.counter("serving/decode_slots_offered").inc(
                num_slots)

    def on_decode_harvest(self, rows_walked: Optional[int]):
        """One kernel-path paged decode dispatch, read back: the rows
        whose token the program kept (not -1) are the rows it was active
        for, and only those were handed a non-zero length for the paged
        kernel to walk. Against ``serving/decode_slots_offered``: the
        share of the batch the kernel read pages for. None (the gather
        path walks no pages by row) counts nothing."""
        if self.registry is not None and rows_walked is not None:
            self.registry.counter("serving/paged_rows_walked").inc(
                rows_walked)

    def on_latent_walk(self, tokens: Optional[int]):
        """One decode dispatch over a latent page pool, read back: the
        pooled tokens the rows that decoded attended, summed (the host's
        own count: a row's prompt and what it had generated). Times the
        layers and a token's latent bytes it is what the latent kernel
        had to read. None (a pool of K and V heads) counts nothing."""
        if self.registry is not None and tokens is not None:
            self.registry.counter("serving/latent_tokens_walked").inc(tokens)

    def on_ring_walk(self, walk):
        """One decode dispatch over a pool with window rings, read back:
        ``(pooled tokens, ring tokens, rows)`` — what each call over the
        one layer's shared pages read (the rows' contexts, summed), what
        each call over a ring read (a context and the token itself, at
        most the window) and the columns each ring was written. Times a
        token's K/V bytes they are what those calls had to move. None (a
        pool without rings) counts nothing."""
        if self.registry is not None and walk is not None:
            pooled, ring, rows = walk
            self.registry.counter("serving/shared_kv_tokens_walked").inc(
                pooled)
            self.registry.counter("serving/ring_tokens_read").inc(ring)
            self.registry.counter("serving/ring_tokens_written").inc(rows)

    def on_split_chunk(self, width: int, cross_positions: Optional[int],
                       ring_tokens: Optional[int]):
        """One chunk program of a module whose upper layers keep nothing
        and run on ``cross_positions`` of the chunk's ``width`` positions
        (models/phi4flash.py: one, where the logits are read); the
        chunk's ``ring_tokens`` live tokens went onto each window ring.
        None, None (every other model) counts nothing."""
        if self.registry is None:
            return
        if cross_positions is not None:
            self.registry.counter("serving/self_decoder_positions").inc(width)
            self.registry.counter("serving/cross_decoder_positions").inc(
                cross_positions)
        if ring_tokens is not None:
            self.registry.counter("serving/ring_tokens_written").inc(
                ring_tokens)

    def on_moe_counts(self, counts, shared_rows=None):
        """One dispatch's routing, ``[L, E]``: the token-expert pairs
        each layer's router sent to each expert (rows that held no
        request are in none). Per layer call: the pairs, the experts
        that got at least one row — whose weights the grouped matmul
        had to read — and the largest group; the mean group is
        ``moe/assignments / (moe/expert_calls * E)``. ``shared_rows``:
        of a model with shared experts, the live rows that went through
        them, summed over its expert layers (``moe/shared_expert_rows``;
        the counters above count routed experts only)."""
        if self.registry is not None:
            reg = self.registry
            reg.counter("moe/assignments").inc(int(counts.sum()))
            reg.counter("moe/expert_calls").inc(int(counts.shape[0]))
            reg.counter("moe/experts_touched").inc(int((counts > 0).sum()))
            reg.counter("moe/experts_offered").inc(int(counts.size))
            reg.counter("moe/load_max").inc(int(counts.max(axis=1).sum()))
            if shared_rows is not None:
                reg.counter("moe/shared_expert_rows").inc(int(shared_rows))

    # always-on host-loop accounting (process registry, so a reader that
    # runs after the engine is gone still finds it): host clock
    # arithmetic on stamps the engine took anyway, never a device touch
    def on_queue_wait(self, wait_ns: int):
        """Submit (or preempt) -> admitted, once per admission."""
        if self.registry is not None:
            self.registry.histogram("serving/queue_wait_ms").observe(
                wait_ns / 1e6)

    def on_prefill_wait(self, wait_ns: int):
        """Admitted -> its first prefill chunk dispatched: in paged mode
        a request holds a slot from admission but takes its turn at
        ``max_chunks_per_iter`` chunks an iteration."""
        if self.registry is not None:
            self.registry.histogram("serving/prefill_wait_ms").observe(
                wait_ns / 1e6)

    def on_advance(self, total_ns: int, readback_ns: int):
        """One ``advance()`` that had work: the part of it spent blocked
        on device->host reads, and the rest (the host's own time). The
        phase clock's sums: ``serving/iterations`` has the split."""
        if self.registry is not None:
            self.registry.histogram("serving/advance_readback_ms").observe(
                readback_ns / 1e6)
            self.registry.histogram("serving/advance_host_ms").observe(
                (total_ns - readback_ns) / 1e6)

    def on_host_stall(self, row):
        """One iteration past ``HOST_STALL_NS`` on the host's side (a
        row of ``serving/iterations``, before it is written): the newest
        are kept with when they began, how long their phases took and
        which took longest — what an operator asks first."""
        phases = row[IN_ADVANCE]
        longest = max(range(len(phases)), key=phases.__getitem__)
        self.host_stall_log.append({
            "t_entry_ns": row[T_ENTRY], "ms": sum(phases) / 1e6,
            "phase": ITERATION_COLUMNS[ADMIT + longest],
            "phase_ms": phases[longest] / 1e6, "gc_ms": row[GC] / 1e6,
            "readback_ready": bool(row[READY])})

    def on_token(self, n: int = 1):
        """``n`` EMITTED tokens streamed to requests. With speculation
        an accepted verification step emits several tokens in one
        decode iteration, so token counters and throughput take the
        emitted count — ``decode_iterations`` (and every ``*_steps``
        percentile) stays iteration-denominated; their ratio is the
        speculation speedup."""
        self.tokens_generated += n

    def on_spec(self, proposed: int, accepted: int):
        """One slot's speculation outcome at harvest: ``proposed``
        tokens went into the verification step, ``accepted`` of them
        were emitted (the bonus token is NOT counted here — acceptance
        rate measures the proposer, not the free argmax). Mirrored into
        the shared registry so /metrics and /statusz carry the
        ``spec/*`` series without a snapshot call."""
        self.spec_proposed_tokens += proposed
        self.spec_accepted_tokens += accepted
        self.spec_rejected_tokens += proposed - accepted
        if self.registry is not None:
            self.registry.counter("spec/proposed_tokens").inc(proposed)
            self.registry.counter("spec/accepted_tokens").inc(accepted)
            self.registry.counter("spec/rejected_tokens").inc(
                proposed - accepted)

    def on_timeout(self, request):
        self.requests_timed_out += 1
        c = self._cls(request)
        if c is not None:
            c["timed_out"] += 1
        self._flight("timeout", request,
                     iteration=request.finished_iteration)

    def on_cancel(self, request):
        self.requests_cancelled += 1
        self._flight("cancelled", request,
                     iteration=request.finished_iteration)

    def on_reject(self):
        self.requests_rejected += 1

    def on_shed(self, request, reason=None):
        """Explicit QoS shed (admission refusal, ladder sweep, or OOM
        containment) — counted overall, per reason, and per class, and
        mirrored into the shared registry so /metrics and /statusz show
        the shed rate without a snapshot call."""
        self.requests_shed += 1
        key = reason or "unspecified"
        self.shed_by_reason[key] = self.shed_by_reason.get(key, 0) + 1
        c = self._cls(request)
        if c is not None:
            c["shed"] += 1
        if self.registry is not None:
            self.registry.counter("serving/requests_shed").inc()
        self._flight("shed", request,
                     iteration=request.finished_iteration,
                     reason=key)

    def on_preempt(self, request, reason="priority"):
        self.requests_preempted += 1
        c = self._cls(request)
        if c is not None:
            c["preempted"] += 1
        if self.registry is not None:
            self.registry.counter("serving/requests_preempted").inc()
        self._flight("preempt", request,
                     iteration=request.preempted_iteration,
                     reason=reason, tokens_retained=len(request.tokens))

    def on_resume(self, request):
        self.requests_resumed += 1
        c = self._cls(request)
        if c is not None:
            c["resumed"] += 1
        if self.registry is not None:
            self.registry.counter("serving/requests_resumed").inc()
        self._flight("resume", request,
                     iteration=request.admitted_iteration)

    def on_handoff_export(self, request):
        """One prefilled request shipped out as a page handoff (the
        fleet's disaggregated prefill role). The request leaves this
        engine mid-flight — its completion lands on the decode replica's
        ledger, so export is its terminal event HERE."""
        self.handoffs_exported += 1
        if self.registry is not None:
            self.registry.counter("serving/handoffs_exported").inc()
        self._flight("handoff_export", request,
                     iteration=request.first_token_iteration)

    def on_handoff_import(self, request, prefill_tokens: int):
        """One page handoff continued on this engine: counts as an
        admission (the request occupies a slot from here on) plus the
        prompt tokens whose prefill this engine skipped entirely —
        the zero-recompute figure the acceptance test asserts."""
        self.requests_admitted += 1
        self.handoffs_imported += 1
        self.handoff_tokens_imported += prefill_tokens
        c = self._cls(request)
        if c is not None:
            c["admitted"] += 1
        if self.registry is not None:
            self.registry.counter("serving/handoffs_imported").inc()
        self._flight("handoff_inject", request,
                     iteration=request.admitted_iteration,
                     prefill_tokens=prefill_tokens)

    def on_fault(self, kind: str, detail: str, iteration: int):
        """One containment event (watchdog fire, OOM shed, recovery):
        appended to the capped fault log and counted in the registry —
        the acceptance surface for "the events are visible in /statusz
        and the metrics snapshot"."""
        self.faults.append({"kind": kind, "detail": detail,
                            "iteration": iteration})
        del self.faults[:-FAULT_LOG_LIMIT]
        if self.registry is not None:
            self.registry.counter(f"serving/faults/{kind}").inc()

    def on_recover(self, kind: str, reason: str, requeued: int,
                   iteration: int):
        self.recoveries += 1
        self.on_fault("recovery",
                      f"{kind}: {reason} ({requeued} requests requeued)",
                      iteration)

    def on_finish(self, request):
        self.requests_finished += 1
        # retroactive first_token mark + the terminal event: together
        # with submit/admit above these give the recorder (and
        # per_request_breakdown) a complete stage chain per request
        if request.first_token_iteration is not None:
            self._flight("first_token", request,
                         iteration=request.first_token_iteration)
        self._flight("finished", request,
                     iteration=request.finished_iteration,
                     tokens=len(request.tokens))
        if request.ttft_s is not None:
            self.ttft_s.append(request.ttft_s)
        if (request.first_token_iteration is not None
                and request.submitted_iteration is not None):
            steps = (request.first_token_iteration
                     - request.submitted_iteration)
            self.ttft_steps.append(steps)
            if getattr(request, "submitted_under_load", False):
                self.ttft_steps_under_load.append(steps)
            c = self._cls(request)
            if c is not None:
                c["ttft_steps"].append(steps)
                c["finished"] += 1
        else:
            c = self._cls(request)
            if c is not None:
                c["finished"] += 1
        if request.latency_s is not None:
            self.latency_s.append(request.latency_s)

    def sample(self, queue_depth: int, busy_slots: int, num_slots: int,
               iteration: int, paged: Optional[dict] = None,
               qos_level: Optional[int] = None,
               slot_cap: Optional[int] = None):
        self.queue_depth_sum += queue_depth
        self.queue_depth_max = max(self.queue_depth_max, queue_depth)
        self.occupancy_sum += busy_slots / max(1, num_slots)
        self.busy_slots_max = max(self.busy_slots_max, busy_slots)
        self.samples += 1
        if qos_level is not None:
            self.qos_level = qos_level
        if slot_cap is not None:
            self.slot_cap = slot_cap
        if self.registry is not None:
            # live scheduler state as registry GAUGES (host ints from the
            # scheduler, zero device reads): the SLO-admission data plane
            # and the /metrics serving_queue_depth / serving_active_slots
            # series — previously reachable only via internal state
            self.registry.gauge("serving/queue_depth").set(queue_depth)
            self.registry.gauge("serving/active_slots").set(busy_slots)
            if qos_level is not None:
                self.registry.gauge("serving/qos_level").set(qos_level)
            if slot_cap is not None:
                self.registry.gauge("serving/slot_cap").set(slot_cap)
        if paged is not None:
            self.paged_stats = paged    # host allocator arithmetic only
        if self.monitor is not None and getattr(self.monitor, "enabled",
                                                False):
            self._events.extend([
                ("serving/queue_depth", queue_depth, iteration),
                ("serving/slot_occupancy",
                 busy_slots / max(1, num_slots), iteration),
                ("serving/tokens_generated", self.tokens_generated,
                 iteration),
                ("serving/requests_finished", self.requests_finished,
                 iteration),
            ])
            if qos_level is not None:
                self._events.extend([
                    ("serving/qos_level", qos_level, iteration),
                    ("serving/requests_shed", self.requests_shed,
                     iteration),
                    ("serving/requests_preempted", self.requests_preempted,
                     iteration),
                ])
            if paged is not None:
                self._events.append(("serving/page_utilization",
                                     paged["page_utilization"], iteration))
                if "prefix_hit_rate" in paged:
                    self._events.append(("serving/prefix_hit_rate",
                                         paged["prefix_hit_rate"],
                                         iteration))
            if len(self._events) >= 4 * self.interval:
                self.flush()

    def flush(self):
        """Hand buffered events to the monitor fan-out (host floats only —
        no device reads happen here)."""
        if self._events and self.monitor is not None:
            events, self._events = self._events, []
            self.monitor.write_events(events)

    # -- reporting ---------------------------------------------------------
    def snapshot(self) -> dict:
        """Aggregate dict (the BENCH_serving payload). Counters are
        all-time; ttft/latency percentiles cover the most recent
        ``history_window`` completions."""
        elapsed = (time.perf_counter() - self.started_at
                   if self.started_at is not None else 0.0)
        out = {
            "requests_submitted": self.requests_submitted,
            "requests_admitted": self.requests_admitted,
            "requests_finished": self.requests_finished,
            "requests_timed_out": self.requests_timed_out,
            "requests_cancelled": self.requests_cancelled,
            "requests_rejected": self.requests_rejected,
            "requests_shed": self.requests_shed,
            "requests_preempted": self.requests_preempted,
            "requests_resumed": self.requests_resumed,
            "recoveries": self.recoveries,
            "tokens_generated": self.tokens_generated,
            "prefills": self.prefills,
            "decode_iterations": self.decode_iterations,
            "wasted_slot_steps": self.wasted_slot_steps,
            "elapsed_s": elapsed,
            "throughput_tokens_per_s": (self.tokens_generated / elapsed
                                        if elapsed > 0 else 0.0),
            "queue_depth_mean": (self.queue_depth_sum / self.samples
                                 if self.samples else 0.0),
            "queue_depth_max": self.queue_depth_max,
            "slot_occupancy_mean": (self.occupancy_sum / self.samples
                                    if self.samples else 0.0),
            "concurrent_requests_peak": self.busy_slots_max,
        }
        if self.spec_proposed_tokens:
            out["spec_proposed_tokens"] = self.spec_proposed_tokens
            out["spec_accepted_tokens"] = self.spec_accepted_tokens
            out["spec_rejected_tokens"] = self.spec_rejected_tokens
            out["spec_acceptance_rate"] = (self.spec_accepted_tokens
                                           / self.spec_proposed_tokens)
            # emitted tokens per decode dispatch — the speculation
            # speedup figure (1.0 = the non-speculative engine)
            out["tokens_per_decode_iteration"] = (
                self.tokens_generated / max(1, self.decode_iterations))
        if self.handoffs_exported or self.handoffs_imported:
            out["handoffs_exported"] = self.handoffs_exported
            out["handoffs_imported"] = self.handoffs_imported
            out["handoff_tokens_imported"] = self.handoff_tokens_imported
        if self.prefill_chunks or self.prefill_tokens_reused:
            total = self.prefill_tokens_computed + self.prefill_tokens_reused
            out["prefill_chunks"] = self.prefill_chunks
            out["prefill_chunk_pages"] = self.prefill_chunk_pages
            out["prefill_tokens_computed"] = self.prefill_tokens_computed
            out["prefill_tokens_reused"] = self.prefill_tokens_reused
            out["prefill_recompute_skipped_frac"] = (
                self.prefill_tokens_reused / total if total else 0.0)
        if self.paged_stats is not None:
            # latest allocator/prefix-tree view (page_utilization,
            # prefix_hit_rate, ...) — the PR-5 registry collector exports
            # these as gauges via this snapshot
            out.update(self.paged_stats)
        for name, vals in (("ttft_s", self.ttft_s),
                           ("ttft_steps", self.ttft_steps),
                           ("ttft_steps_under_load",
                            self.ttft_steps_under_load),
                           ("latency_s", self.latency_s)):
            if vals:
                out[f"{name}_p50"] = _percentile(vals, 50)
                out[f"{name}_p95"] = _percentile(vals, 95)
                out[f"{name}_mean"] = sum(vals) / len(vals)
        if self.qos_level is not None:
            out["qos_level"] = self.qos_level
        if self.slot_cap is not None:
            out["slot_cap"] = self.slot_cap
        if self.shed_by_reason:
            for reason, n in sorted(self.shed_by_reason.items()):
                out[f"shed/{reason}"] = n
        if self.host_stall_log:
            out["host_stall_log"] = list(self.host_stall_log)
        if self.faults:
            # breadcrumb list (capped): /statusz and the BENCH artifact
            # show WHAT fired, not just that a counter moved
            out["faults"] = list(self.faults)
        if self.flight.events:
            # the last-N-requests lifecycle timeline (bounded ring):
            # rides every snapshot, so the partial-snapshot/crash path
            # dumps it for free — a dead engine leaves a reconstructable
            # tail of admits/preempts/handoffs/sheds/finishes
            out["flight_recorder"] = self.flight.snapshot()
        # per-priority-class breakdown as flat numeric keys so the
        # registry collector, /metrics (Prometheus), /statusz, and
        # ds_tpu_report all surface it without schema changes
        for name, c in sorted(self.per_class.items()):
            for key in ("submitted", "admitted", "finished", "timed_out",
                        "shed", "preempted", "resumed"):
                out[f"class/{name}/{key}"] = c[key]
            if c["submitted"]:
                out[f"class/{name}/shed_rate"] = c["shed"] / c["submitted"]
            if c["ttft_steps"]:
                out[f"class/{name}/ttft_steps_p50"] = _percentile(
                    c["ttft_steps"], 50)
                out[f"class/{name}/ttft_steps_p95"] = _percentile(
                    c["ttft_steps"], 95)
        return out
