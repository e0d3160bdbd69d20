"""Continuous-batching serving engine over a block-paged KV pool.

Reference frame: DeepSpeed-Inference (arXiv:2207.00032) wins at-scale
transformer serving at the scheduling/KV-cache layer, not the kernel
layer; on TPU the extra constraint is that decode SHAPES must never
change across requests (every new shape is an XLA recompile). The
engine therefore decodes a fixed batch of ``num_slots`` rows whose K/V
lives in a global page pool with one page table per slot
(serving/paging/, the ``serving.paging`` block) and drives exactly TWO
compiled programs, each through the manager that defines them and owns
what they read and write (serving/paging/manager.py ``PagedKVManager``:
the engine hands over what only it knows and reads a ``ProgramResult``):

- ``serving/chunk_prefill``: prefill one page-aligned chunk of one
  request into its slot's pages; the last chunk samples the first
  token — one jit specialization per chunk width;
- ``serving/paged_decode``: ONE masked single-token decode step over
  the full slot batch — per-slot lengths, per-slot positions, per-slot
  eos/budget completion. Compiles once, ever.

Requests queue host-side (scheduler.py) and are admitted BETWEEN decode
steps into a free slot once the pool has the PAGES to cover them;
shared prompt prefixes are referenced copy-free from a radix cache,
long prompts prefill chunk by chunk interleaved with decode, and
finished slots recycle immediately. Token readback is pipelined: the
host reads step k's tokens while the device runs step k+1
(``pipeline_depth``), so streaming never serializes device and host.
Metrics derive from those already-read tokens plus host scheduler
state — no extra per-step syncs (PR-2 rule). Output is token-exact vs
``generate()``.

QoS mode (``serving.qos`` block, serving/qos.py): requests carry a
``priority``; a high-priority queue head past its class's
``preempt_after_steps`` preempts the lowest-priority active request
BACK TO THE QUEUE (device row masked via the cancel machinery,
generated tokens retained — resumption re-prefills prompt + partial
output, which the paged prefix cache serves page-granularly).
Admission consults live step-clock signals against per-class SLO
targets and sheds early with explicit ``shed`` status; a deterministic
degradation ladder (shed lowest class -> shrink chunk budget -> refuse
admits) runs on the decode-step clock so decisions replay bit-exactly.
Fault containment: a hung-decode watchdog (armed around dispatch +
readback, the resilience/preemption.py pattern), a RESOURCE_EXHAUSTED
guard on admit/chunk-prefill that sheds the offender with an
``oom_forensics`` dump, and ``recover()`` — requeue-and-re-prefill of
every queued + active request over a rebuilt device state. With the
block absent the pre-QoS FIFO engine runs untouched.
"""

import contextlib
import os
import threading
import time
from collections import deque
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..inference.generation import _sampling_mode
from ..observability.goodput import get_ledger as _goodput_ledger
from ..observability.fleet import make_trace_id
from ..observability.memory import get_accountant, is_oom_error, oom_forensics
from ..observability.trace import active_tracer as _active_tracer
from ..observability.trace import span as _span
from ..utils.logging import log_dist
from .config import ServingConfig
from . import qos as qos_mod
from .qos import QosController
from .request import PREEMPTED, Request
from .scheduler import FifoScheduler
from .metrics import (ADMIT, DECODE_DISPATCH, HARVEST, OTHER,
                      PREFILL_DISPATCH, ServingMetrics, collector_hook)
from .paging.config import chunk_pages
from .paging.manager import PagedKVManager
from .speculation import NgramProposer


def _counts_read(counts):
    """The arrays a harvest reads back beside a dispatch's tokens: an
    expert layer's router counts, or nothing for a module without one."""
    return [] if counts is None else [counts]


class ServingEngine:
    """Continuous-batching serving over a fixed slot batch.

    Usage::

        eng = ServingEngine(module, params, ServingConfig(num_slots=8,
                                                          max_len=1024))
        reqs = [eng.submit(prompt, max_new_tokens=64) for prompt in work]
        eng.run()                      # or: interleave submit()/advance()
        reqs[0].output_tokens          # streamed per token via on_token=

    Construct directly, from ``InferenceEngine.serve()``, or from a
    config dict's ``serving`` block via ``from_config``.
    """

    def __init__(self, module, params, config: Optional[ServingConfig] = None,
                 *, param_transform=None, monitor=None, rng=None,
                 trace_scope=contextlib.nullcontext, **overrides):
        if config is None:
            config = ServingConfig(**overrides)
        elif isinstance(config, dict):
            config = ServingConfig(**{**config, **overrides})
        elif overrides:
            raise ValueError("pass knobs either via config= or as keyword "
                             "overrides, not both")
        self.config = config.validate()
        self.module = module
        self.params = params
        self._param_transform = param_transform
        # entered around each iteration's device dispatches, where the
        # programs trace: InferenceEngine.serve() passes the scope that
        # makes its mesh the global one (the mesh is read at trace time)
        self._trace_scope = trace_scope
        if self.config.weights_int8:
            # checkpoint->int8 weight-only serving (serving.quantize.
            # weights): the shared module_inject pipeline step — direct
            # int8 {"q","scale"} kernels for QDense-based modules (the
            # fused-dequant Pallas matmul consumes them; weights stay
            # int8 in HBM across the whole decode loop), per-step
            # dequant transform otherwise. Params already quantized by
            # an InferenceEngine pass through untouched.
            from ..module_inject.module_quantize import (
                quantize_for_serving, quantized_nbytes)
            self.params, transform = quantize_for_serving(
                module, self.params,
                min_size=self.config.quantize.min_size)
            if transform is not None:
                if self._param_transform is not None:
                    raise ValueError(
                        "serving.quantize.weights cannot compose with an "
                        "external param_transform on a module without "
                        "supports_quantized_kernels")
                self._param_transform = transform
            nb = quantized_nbytes(self.params)
            log_dist(
                f"serving int8 weights: {nb['quantized'] / 1e6:.1f}MB vs "
                f"{nb['dense_equivalent'] / 1e6:.1f}MB dense", ranks=[0])
        # a quantized tree with no way to consume it fails DEEP inside
        # flax on the {"q","scale"} dict leaves — refuse up front with
        # the fix spelled out instead (e.g. an InferenceEngine that
        # transform-quantized a plain module, then ServingEngine built
        # directly on its params without forwarding param_transform)
        if self._param_transform is None and not getattr(
                type(module), "supports_quantized_kernels", False):
            from ..models.layers import _is_qleaf
            if any(_is_qleaf(leaf) for leaf in jax.tree.leaves(
                    self.params, is_leaf=_is_qleaf)):
                raise ValueError(
                    "params contain int8 {'q','scale'} nodes but the "
                    "module does not declare supports_quantized_kernels "
                    "and no param_transform was given — pass the "
                    "dequantizing param_transform (InferenceEngine."
                    "serve() forwards it automatically)")

        model_max = getattr(getattr(module, "config", None), "max_seq_len",
                            None)
        if model_max is not None and self.config.max_len > model_max:
            raise ValueError(
                f"serving.max_len={self.config.max_len} exceeds the "
                f"model's max_seq_len {model_max}")

        n = self.config.num_slots
        # the manager owns the page pool, the allocator, the prefix cache
        # and the page tables
        self._paged = PagedKVManager(self.module, self.params, self.config)
        self._init_device_state()
        self._rng = rng if rng is not None else jax.random.PRNGKey(
            self.config.seed)
        # every decode dispatch folds its iteration into this one key
        # inside the program: made once, not by two small dispatches an
        # iteration that the device then waits for
        self._decode_rng = jax.random.fold_in(self._rng, 2**31)
        self._mode = _sampling_mode(self.config.temperature,
                                    self.config.top_k, self.config.top_p)
        # -1 when eos is disabled: sampled tokens are always >= 0, so the
        # device-side comparison simply never fires (no structure flag,
        # no branch, one executable either way)
        self._eos = jnp.int32(self.config.eos_token_id
                              if self.config.eos_token_id is not None else -1)

        self.scheduler = FifoScheduler(self.config)
        self.metrics = ServingMetrics(
            monitor=monitor, interval=self.config.metrics_interval,
            flight_recorder_events=self.config.flight_recorder_events)
        self._slot_req = [None] * n       # host view of slot -> Request
        self._free = deque(range(n))
        self._pending = deque()           # in-flight readbacks, FIFO
        # the host loop's one clock (serving/metrics.py PhaseClock): it
        # names every nanosecond from one advance() exit to the next and
        # writes the row of ``serving/iterations``; the collector's
        # passes are timed while this engine lives (close() ends that)
        self._clock = self.metrics.clock
        collector_hook.watch(self._clock)
        self._chunk_counts = {}           # slot -> router counts of its
                                          # prefill chunks so far (device)
        self._iteration = 0
        self._seq = 0
        # QoS plane (serving/qos.py): priority preemption, SLO shedding,
        # the degradation ladder, and the hung-decode watchdog. None when
        # the block is absent — the FIFO engine runs untouched.
        self._qos = (QosController(self.config.qos)
                     if self.config.qos_enabled else None)
        # self-speculative decode plane (serving/speculation.py): the
        # host n-gram proposer + ONE batched verification program. None
        # when the block is absent/disabled — the one-token decode loop
        # runs untouched, bit-identical to the pre-speculation engine.
        self._spec = (NgramProposer(self.config.speculation)
                      if self.config.spec_enabled else None)
        if self._spec is not None:
            # a rejected candidate's K/V is simply overwritten; the state
            # a multi-token step advanced would have to be rolled back
            self._paged.refuse_state(
                "serving.speculation (a multi-token verification step)")
            self._paged.refuse_latent(
                "serving.speculation (a multi-token verification step)")
        self._slot_cap = n                # admissible slots (autoscaling
                                          # drains above the cap via the
                                          # preemption path; compiled
                                          # shapes never change)
        # disaggregated-fleet prefill role (serving/fleet/): the engine
        # runs chunked prefill + first token only, never dispatches a
        # decode, and stages every prefilled request for a page-granular
        # KV handoff to a decode replica (set via set_prefill_role)
        self.prefill_only = False
        self._handoff_ready = []          # [(slot, req)] awaiting export
        self._handoff_injected = {}       # request_id -> injected Request
                                          # (bounded; the idempotence
                                          # guard — a re-sent payload
                                          # dedupes even after the
                                          # original already finished)
        self._preempts_this_iter = 0
        self._watchdog = None
        self._watchdog_report = None      # set by the watchdog thread;
                                          # advance() runs recovery on it
        self.on_watchdog_fatal = None     # escalation hook for a TRULY
                                          # hung dispatch (flag never
                                          # consumed); None = os._exit(70)
        self.last_oom_forensics = None    # latest RESOURCE_EXHAUSTED dump
        self._restart_watchdog()
        self._account_memory()
        # arm the process goodput ledger (observability/goodput.py): the
        # phase clock hands it each iteration's dispatch, read-back and
        # harvest time as compute, the rest surfaces as scheduler_idle
        _goodput_ledger().start()
        self.telemetry = None             # live endpoint; start_telemetry()
        log_dist(f"serving engine: {n} slots x {self.config.cache_len} "
                 "tokens", ranks=[0])

    def _init_device_state(self):
        """(Re)build the slot-state arrays and the prefill queue beside a
        fresh page pool. Called at construction and, after the manager's
        ``reset()``, from ``recover()`` — shapes are identical both
        times, so every compiled program stays cached."""
        n = self.config.num_slots
        self._prefill_tasks = deque()   # [slot, req, prompt, max_new,
                                        #  next chunk's start]
        self._state = {
            "lengths": jnp.zeros((n,), jnp.int32),
            "last_token": jnp.zeros((n,), jnp.int32),
            "active": jnp.zeros((n,), bool),
            "remaining": jnp.zeros((n,), jnp.int32),
        }

    def _restart_watchdog(self):
        """Arm (or re-arm after a fire) the hung-decode watchdog — the
        resilience/preemption.py daemon-thread pattern with a recovery
        abort_fn instead of a process abort: a fire flags the engine,
        which runs ``recover()`` at the next advance() instead of dying."""
        if self._watchdog is not None:
            self._watchdog.stop()
            self._watchdog = None
        qcfg = self.config.qos
        if (self._qos is not None and qcfg.watchdog_timeout_s is not None):
            from ..runtime.resilience.preemption import Watchdog
            self._watchdog = Watchdog(
                self, qcfg.watchdog_timeout_s,
                abort_fn=self._on_watchdog_fire).start()

    def _on_watchdog_fire(self, report: str):
        """Watchdog-thread callback: record only — no device calls from a
        foreign thread. The engine loop picks the flag up at its next
        advance() and runs requeue-and-re-prefill recovery there.

        That soft path only helps a SLOW dispatch (one that eventually
        returns). A truly wedged one never reaches the next advance(), so
        a second timeout window arms here: if the flag is still
        unconsumed after another ``watchdog_timeout_s``, the dispatch is
        hung for real and the escalation path runs —
        ``on_watchdog_fatal(report)`` when the operator set one (the
        serve CLI emits its partial snapshot there), else ``os._exit``
        with the resilience watchdog's exit code so the fleet layer
        restarts the process instead of waiting forever."""
        self._watchdog_report = report
        self.metrics.on_fault(
            "watchdog",
            f"decode dispatch stalled past "
            f"{self.config.qos.watchdog_timeout_s}s", self._iteration)
        timer = threading.Timer(self.config.qos.watchdog_timeout_s,
                                self._watchdog_escalate, args=(report,))
        timer.daemon = True
        timer.start()

    def _watchdog_escalate(self, report: str):
        if self._watchdog_report is None:
            return      # flag consumed: the dispatch completed and soft
                        # recovery ran (or is about to) — nothing is hung
        log_dist("serving: decode dispatch still hung one full watchdog "
                 "window after the fire — escalating", ranks=[0])
        if self.on_watchdog_fatal is not None:
            self.on_watchdog_fatal(report)
        else:
            # the main thread is, by definition, stuck: mirror the
            # resilience Watchdog's clean abort with its restartable code
            os._exit(70)

    def _account_memory(self):
        """Tag the engine's resident device buffers in the process HBM
        accountant (observability/memory.py) and publish the serving
        memory gauges. Shape metadata only — no device reads. The pool's
        share, its gather scratch and a model's state bytes are the
        manager's to state (``PagedKVManager.account``)."""
        acct = get_accountant()
        acct.account("serving/params", self.params)
        acct.account("serving/state", self._state)
        self._paged.account(acct, self.metrics.registry)

    def memory_report(self) -> dict:
        """Serving-side memory block (the BENCH_serving artifact embeds
        this next to the ``perf`` block): subsystem attribution plus the
        derived KV-pool resident / decode-gather transient figures.
        ``kv_pool_resident_bytes`` reflects the PAGE dtype (int8 pools
        weigh their int8 pages + scale planes), ``params_bytes`` the
        int8-vs-dense weight story, and the transient figure reads 0 on
        the paged-attention kernel path (no gather exists to charge)."""
        from ..module_inject.module_quantize import quantized_nbytes
        acct = get_accountant()
        return {
            "by_subsystem": {
                tag: info["bytes"]
                for tag, info in acct.report()["by_subsystem"].items()
                if tag.startswith("serving/")},
            "kv_pool_resident_bytes": acct.subsystem_bytes("serving/kv_pool"),
            "params_bytes": quantized_nbytes(self.params),
            "decode_gather_transient_bytes":
                self._paged.decode_gather_transient_bytes(),
            "kv_page_dtype": (
                "int8" if self._paged.kv_quant
                else jnp.dtype(self._paged.dequant_dtype).name),
            "paged_kernel": self._paged.use_kernel,
        }

    def close(self):
        """Release this engine's accountant attribution (the serving
        mirror of ``DeepSpeedEngine.destroy()``): a torn-down engine's
        KV pool and weights must not linger in ``mem/*`` gauges or a
        later OOM forensics dump. Explicit like destroy() — a newer
        serving engine re-states the ``serving/*`` tags, so an implicit
        ``__del__`` could wipe its successor's figures. Idempotent."""
        telemetry = self.telemetry
        if telemetry is not None:
            self.telemetry = None
            telemetry.stop()   # never serve a torn-down engine's state
        if self._watchdog is not None:
            self._watchdog.stop()
            self._watchdog = None
        collector_hook.unwatch(self._clock)
        self._clock.end_caller_span()
        acct = get_accountant()
        for tag in ("serving/params", "serving/kv_pool", "serving/state"):
            acct.discard(tag)
        acct.registry.gauge("mem/kv_pool_resident").set(0)
        acct.registry.gauge("mem/decode_gather_transient").set(0)

    # -- live telemetry ----------------------------------------------------
    def metrics_snapshot(self) -> dict:
        """JSON-able process state as seen from the serving side: the
        shared registry (whose ``collected.serving`` block is this
        engine's own metrics), the goodput breakdown, memory attribution
        and the compiled-program table — the /statusz payload and the
        serving analog of ``DeepSpeedEngine.metrics_snapshot``."""
        from ..observability.metrics import get_registry
        from ..observability.programs import get_program_registry
        out = {"registry": get_registry().snapshot(),
               "goodput": _goodput_ledger().breakdown(),
               "serving": self.metrics.snapshot(),
               "memory": get_accountant().report(),
               "programs": get_program_registry().table()}
        if self._qos is not None:
            out["qos"] = self._qos.snapshot()
        return out

    def start_telemetry(self, port: int = 0, host: str = "127.0.0.1"):
        """Serve /metrics + /healthz + /statusz for this engine from a
        daemon thread (observability/export.py; ``bin/ds_tpu_serve
        --metrics-port``). ``port=0`` binds an ephemeral port — read the
        bound one from the returned server's ``.port``. Host-only reads;
        a scrape never syncs the device."""
        if self.telemetry is not None:
            return self.telemetry
        from ..observability.export import TelemetryServer
        self.telemetry = TelemetryServer(self.metrics_snapshot, host=host,
                                         port=port).start()
        log_dist(f"serving telemetry: http://{host}:{self.telemetry.port}"
                 "/metrics (+/healthz /statusz)", ranks=[0])
        return self.telemetry

    # -- client API --------------------------------------------------------
    def submit(self, prompt, max_new_tokens: Optional[int] = None,
               request_id=None, on_token=None,
               deadline_steps: Optional[int] = None,
               priority: int = 0,
               trace_id: Optional[str] = None) -> Request:
        """Queue one request; returns its live ``Request`` handle.

        ``trace_id`` threads a distributed trace identity through
        (the fleet router stamps one per request so spans join across
        replicas); when absent the engine derives a deterministic one
        from the request id + submit ordinal.

        ``deadline_steps`` is a queue TTL on the engine-iteration clock:
        a request still queued after that many iterations completes with
        ``timeout`` status instead of waiting forever (resolution order:
        this argument, then the QoS class default, then
        ``serving.default_deadline_steps``; None = no deadline).

        ``priority`` (higher = more important) orders admission when the
        ``serving.qos`` block is on; SLO-aware admission may return the
        handle already in ``shed`` status instead of queueing it — an
        explicit early refusal the client can retry elsewhere, instead
        of a silent queue-TTL expiry. Admitted requests run to
        completion unless priority preemption pushes them back to the
        queue (tokens retained; they resume token-exactly under greedy
        sampling)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if max_new_tokens is None:
            max_new_tokens = self.config.default_max_new_tokens
        qos_cls = (self._qos.config.class_for(priority)
                   if self._qos is not None else None)
        if deadline_steps is None and qos_cls is not None:
            deadline_steps = qos_cls.deadline_steps
        if deadline_steps is None:
            deadline_steps = self.config.default_deadline_steps
        try:
            self.scheduler.validate_request(prompt.shape[0], max_new_tokens)
        except ValueError:
            self.metrics.on_reject()
            raise
        if request_id is None:
            request_id = self._seq
        if trace_id is None:
            trace_id = make_trace_id(request_id, self._seq)
        req = Request(prompt, max_new_tokens, request_id, on_token=on_token,
                      deadline_steps=deadline_steps, priority=priority,
                      trace_id=trace_id)
        if qos_cls is not None:
            req.qos_class = qos_cls.name
        req.submitted_iteration = self._iteration
        # the p95-TTFT-under-load population: requests that arrived while
        # others were already waiting or every slot was occupied
        req.submitted_under_load = bool(
            self.scheduler.depth or self._peek_free_slot() is None)
        req._seq = self._seq
        self._seq += 1
        if self._qos is not None:
            ok, reason = self._qos.admit(
                qos_cls,
                class_ttft_p95=self.metrics.class_ttft_p95(qos_cls.name),
                under_load=req.submitted_under_load)
            if not ok:
                self.metrics.on_submit(req)
                req._shed(self._iteration, reason)
                self.metrics.on_shed(req, reason)
                return req
        try:
            self.scheduler.add(req)
        except RuntimeError:
            self.metrics.on_reject()
            raise
        self.metrics.on_submit(req)
        return req

    def cancel(self, request_id) -> bool:
        """Cancel one request by id: a queued request leaves the queue, an
        active one releases its slot immediately (its device row is
        deactivated; already-dispatched decode steps for it are dropped at
        harvest). Returns False when no live request carries the id."""
        req = self.scheduler.remove(request_id)
        if req is not None:
            req._cancelled(self._iteration)
            self.metrics.on_cancel(req)
            return True
        for slot, req in enumerate(self._slot_req):
            if req is not None and req.request_id == request_id:
                # deactivate the device-side row so in-flight/future decode
                # iterations mask this slot out, drop any unfinished
                # prefill chunks, then recycle it
                self._mask_row(slot)
                self._prefill_tasks = deque(
                    t for t in self._prefill_tasks if t[0] != slot)
                self._vacate(slot)
                req._cancelled(self._iteration)
                self.metrics.on_cancel(req)
                return True
        return False

    def run(self, max_iterations: Optional[int] = None):
        """Drive admissions/decode/harvest until every submitted request
        has finished (or ``max_iterations`` engine iterations elapse)."""
        it = 0
        while self.busy:
            self.advance()
            it += 1
            if max_iterations is not None and it >= max_iterations:
                break
        self.metrics.flush()

    @property
    def busy(self) -> bool:
        return bool(self.scheduler.depth or self._pending
                    or any(r is not None for r in self._slot_req))

    @property
    def num_free_slots(self) -> int:
        """Free ADMISSIBLE slots (below the autoscaling slot cap)."""
        return sum(1 for s in self._free if s < self._slot_cap)

    @property
    def iteration(self) -> int:
        """Engine decode-iteration counter — the deterministic clock the
        load harness schedules arrivals against."""
        return self._iteration

    @property
    def qos_level(self) -> Optional[int]:
        """Current degradation-ladder level (None when QoS is off)."""
        return self._qos.level if self._qos is not None else None

    @property
    def slot_cap(self) -> int:
        """Admissible-slot cap (autoscaling; <= num_slots)."""
        return self._slot_cap

    # -- engine loop -------------------------------------------------------
    def advance(self):
        """One engine iteration: run any pending fault recovery, evaluate
        the QoS ladder, expire overdue queued requests, admit into free
        slots (reserving their pages; preempting lower classes for an
        at-risk high-priority head), run at most ``max_chunks_per_iter``
        prefill chunks, dispatch one decode over the slot batch, harvest
        readbacks beyond the pipeline depth. Safe to call when idle
        (no-op).

        An iteration that had work accounts for its own time in the
        process registry, from the phase clock's stamps: a row of
        ``serving/iterations`` (every phase from the previous exit to
        this one), ``serving/advance_readback_ms`` (blocked on
        device->host reads) and ``serving/advance_host_ms`` (the rest).
        A call on an empty server writes nothing: its time is part of
        the next row's ``empty``."""
        clock = self._clock
        had_work = self.busy
        if had_work:
            clock.enter(_active_tracer() is not None)
        else:
            clock.end_caller_span()     # its requests were cancelled
        try:
            with _span("serving/advance", {"iteration": self._iteration}):
                self._advance()
        finally:
            if had_work:
                clock.exit(self.busy)

    def _advance(self):
        clock = self._clock
        if self._watchdog_report is not None:
            report, self._watchdog_report = self._watchdog_report, None
            self.recover("hung decode dispatch", kind="watchdog",
                         detail=report)
        self._preempts_this_iter = 0
        if self._qos is not None:
            self._qos_tick()
        self._expire_queued()
        # the watchdog covers everything that can block on the device:
        # prefill dispatches, the decode dispatch, and readbacks
        if self._watchdog is not None:
            self._watchdog.step_started()
        try:
            with self._trace_scope():
                clock.switch(ADMIT)
                self._admit()
                clock.switch(PREFILL_DISPATCH)
                self._run_prefill_chunks()
                clock.switch(DECODE_DISPATCH)
                if self.prefill_only:
                    # prefill role: no decode ever dispatches (the decode
                    # replica owns generation past token 1), but the
                    # deterministic iteration clock still ticks —
                    # deadline sweeps and the fleet's lockstep replay
                    # depend on it
                    dispatched = False
                    self._iteration += 1
                else:
                    dispatched = self._dispatch_decode()
                clock.switch(OTHER)
            # keep at most pipeline_depth dispatches in flight; drain fully
            # when nothing new was dispatched (tail of the workload)
            target = self.config.pipeline_depth if dispatched else 0
            while len(self._pending) > target:
                self._harvest_one()
        finally:
            if self._watchdog is not None:
                self._watchdog.step_finished()
        busy = sum(r is not None for r in self._slot_req)
        self.metrics.sample(
            self.scheduler.depth, busy, self.config.num_slots,
            self._iteration,
            paged=self._paged.stats(),
            qos_level=(self._qos.level if self._qos is not None else None),
            slot_cap=self._slot_cap)
        if self._iteration % self.config.metrics_interval == 0:
            self.metrics.flush()

    def _qos_tick(self):
        """One degradation-ladder evaluation on the decode-step clock,
        plus the queued-request shed sweep the current level implies.
        Inputs are host scheduler state and step-denominated percentiles
        only — decisions replay bit-exactly for a replayed trace."""
        self._qos.observe(
            iteration=self._iteration,
            queue_depth=self.scheduler.depth,
            ttft_p95_steps=self.metrics.ttft_under_load_p95(),
            free_frac=1.0 - self._paged.stats()["page_utilization"])
        pred = self._qos.queued_shed_predicate()
        if pred is not None:
            for req in self.scheduler.shed_queued(pred):
                req._shed(self._iteration, qos_mod.SHED_LADDER)
                self.metrics.on_shed(req, qos_mod.SHED_LADDER)

    def _expire_queued(self):
        """Deadline sweep on the deterministic iteration clock: overdue
        queued requests complete with ``timeout`` status. Only requests
        that never started are swept — preempted ones hold generated
        tokens and resume instead (scheduler.expire exempts them)."""
        for req in self.scheduler.expire(self._iteration):
            req._timed_out(self._iteration)
            self.metrics.on_timeout(req)

    def _req_rng(self, req):
        """Stable per-request rng fold: python hash() is salted per
        process and would break sampled-output reproducibility across
        runs."""
        if isinstance(req.request_id, int):
            fold = req.request_id
        else:
            import zlib
            fold = zlib.crc32(repr(req.request_id).encode())
        return jax.random.fold_in(self._rng, fold % (2**31))

    # -- per-request distributed tracing -----------------------------------
    def _record_queue_wait(self, req):
        """The period the request ACTUALLY spent queued this time —
        submit -> first admit, or preempt -> re-admit for a resumption
        (measuring from submit again would fold the prior RUNNING period
        into the queue stage) — into the ``serving/queue_wait_ms``
        histogram always, and as the retroactive ``serving/queue_wait``
        span while a tracer is active. Host clock arithmetic on stamps
        the request already carries plus one clock read — never a device
        touch."""
        t0 = (req.preempted_at_ns if req.preempted_at_ns is not None
              else req.submitted_at_ns)
        wait_ns = max(0, time.perf_counter_ns() - t0)
        self.metrics.on_queue_wait(wait_ns)
        tracer = _active_tracer()
        if tracer is not None:
            tracer.record_complete(
                "serving/queue_wait", t0, wait_ns,
                {"request_id": req.request_id, "trace_id": req.trace_id,
                 "resumed": req.preempted_at_ns is not None})

    def _record_residency(self, req):
        """Emit the retroactive ``serving/decode_residency`` span
        (admit -> finish): how long the request held its slot."""
        tracer = _active_tracer()
        if tracer is None or req.admitted_at_ns is None:
            return
        now = time.perf_counter_ns()
        tracer.record_complete(
            "serving/decode_residency", req.admitted_at_ns,
            max(0, now - req.admitted_at_ns),
            {"request_id": req.request_id, "trace_id": req.trace_id,
             "tokens": len(req.tokens)})

    # -- free-slot bookkeeping (autoscaling cap aware) ---------------------
    def _peek_free_slot(self) -> Optional[int]:
        """First free slot below the admissible cap (None when all taken
        or drained by a scale-down)."""
        for s in self._free:
            if s < self._slot_cap:
                return s
        return None

    def _take_slot(self, slot: int):
        self._free.remove(slot)

    def _vacate(self, slot: int):
        """Give the slot back: its page references return to the pool
        (prefix-published pages stay alive through the radix tree's own
        reference) and the slot is free to admit into."""
        self._paged.release_slot(slot)
        self._slot_req[slot] = None
        self._free.append(slot)

    def _mask_row(self, slot: int):
        """Deactivate the slot's device row: decode iterations in flight
        or to come mask it out."""
        self._state = {
            **self._state,
            "active": self._state["active"].at[slot].set(False),
            "remaining": self._state["remaining"].at[slot].set(0),
        }

    # -- priority preemption -----------------------------------------------
    def _try_preempt_for(self, head: Request, need: str = "slot") -> bool:
        """Free capacity for an at-risk high-priority queue head by
        preempting the lowest-priority active request back to the queue.
        ``need`` names the starved resource — ``"slot"`` (no free
        slot) or ``"pages"`` (the pool cannot cover the head) —
        so the retry signal matches what admission actually checks: a
        free slot alone never un-starves a page-starved head. Returns
        True when admission should be retried. Deterministic: runs on
        the engine clock, bounded by ``max_preemptions_per_iter``."""
        if self._qos is None or not self._qos.config.preemption:
            return False
        if (self._preempts_this_iter
                >= self._qos.config.max_preemptions_per_iter):
            return False
        head_cls = self._qos.config.class_for(head.priority)
        if not self._qos.head_at_risk(head, head_cls, self._iteration):
            return False
        # drain in-flight work first: the victim's already-dispatched
        # tokens are real continuations that must be retained for resume,
        # and a completion may free slots/pages outright (no preemption
        # needed)
        drained = bool(self._pending)
        while self._pending:
            self._harvest_one()
        if need == "slot" and self._peek_free_slot() is not None:
            return True
        if need == "pages" and drained:
            return True     # completions may have released pages: retry
                            # admission before spending the preempt budget
        victim_slot = None
        for slot, r in enumerate(self._slot_req):
            if r is None or r.done or r.priority >= head.priority:
                continue
            if victim_slot is None:
                victim_slot = slot
                continue
            v = self._slot_req[victim_slot]
            # lowest priority first; among ties the most recently admitted
            # loses (least sunk work discarded), then the highest slot —
            # a total order, so the same state always picks the same victim
            if ((r.priority, -(r.admitted_iteration or 0), -slot)
                    < (v.priority, -(v.admitted_iteration or 0),
                       -victim_slot)):
                victim_slot = slot
        if victim_slot is None:
            return False
        self._preempt_slot(victim_slot, reason="priority")
        self._preempts_this_iter += 1
        return True

    def _preempt_slot(self, slot: int, reason: str):
        """Preempt one active request back to the queue: mask its device
        row (the cancel machinery — in-flight decode steps drop it), free
        its slot/pages, and requeue it at the front of its class with
        generated tokens retained. Call only with ``self._pending``
        drained — undelivered tokens would otherwise be lost to the
        resume prompt."""
        req = self._slot_req[slot]
        self._mask_row(slot)
        self._prefill_tasks = deque(
            t for t in self._prefill_tasks if t[0] != slot)
        self._vacate(slot)
        # close this RUNNING period's residency span now: resumption
        # re-stamps admitted_at_ns, so each slot tenancy is recorded
        # exactly once (queue_wait's preempt->re-admit twin)
        self._record_residency(req)
        req._preempted(self._iteration)
        self.scheduler.requeue(req)
        self.metrics.on_preempt(req, reason)
        log_dist(f"serving: preempted request {req.request_id!r} "
                 f"(slot {slot}, {len(req.tokens)} tokens retained, "
                 f"reason={reason})", ranks=[0])

    def _admit(self):
        """This iteration's admissions, under one ``serving/admission``
        span: queue peeks, slot and page reservation, chunk planning.
        Admission gates on free PAGES, not free slots: a page-starved
        queue head stays queued (class order preserved) until running
        requests release pages, the prefix cache evicts, or — with QoS
        on — an at-risk high-priority head preempts a lower class's
        pages free."""
        args = {"queue_depth": self.scheduler.depth, "admitted": 0}
        before = self.metrics.requests_admitted
        with _span("serving/admission", args):
            self._admit_ready()
            args["admitted"] = self.metrics.requests_admitted - before

    def _admit_ready(self):
        while True:
            req = self.scheduler.peek()
            if req is None:
                return
            slot = self._peek_free_slot()
            if slot is None:
                if self._try_preempt_for(req):
                    continue        # a slot (or a completion) freed up
                return
            # resumption re-prefills prompt + retained partial output;
            # for a fresh request these are just prompt / max_new_tokens
            prompt = req.effective_prompt()
            max_new = req.remaining_budget()
            admission = self._paged.try_admit(slot, prompt, max_new)
            if admission is None:           # page-starved head
                if self._try_preempt_for(req, need="pages"):
                    continue                # preemption released pages
                return
            self.scheduler.next_request()   # actually pop it
            self._take_slot(slot)
            self._record_queue_wait(req)
            resumed = req.status == PREEMPTED
            self._slot_req[slot] = req
            req._admitted(slot, self._iteration)
            self.metrics.on_admit(req, *admission)
            if resumed:
                self.metrics.on_resume(req)
            # the plan is where the next chunk starts: the non-shared
            # tail, cut into chunks as they are dispatched. Always at
            # least one — the prefix match caps at the last prefill
            # token, whose logits seed sampling
            self._prefill_tasks.append([slot, req, prompt, max_new,
                                        admission.shared_tokens])

    def _chunk_width(self, tokens_left: int) -> int:
        """Tokens of the head request's next chunk program, a page
        multiple covering at most the ``tokens_left`` of its EFFECTIVE
        prompt (original + any retained partial output for a
        resumption): a fixed ``prefill_chunk`` cut to the pages left, or
        the width ``chunk_pages`` chooses from the counts this iteration
        holds — so chunk widths (the only prefill jit axis) come from a
        bounded set either way."""
        page = self._paged.page_len
        pages_left = -(-tokens_left // page)
        fixed = self.config.paging.prefill_chunk
        if fixed is not None:
            return min(fixed // page, pages_left) * page
        # a prefill replica never decodes: a slot staged for handoff
        # holds pages, not a row that waits for this chunk
        decoding = 0 if self.prefill_only else self._decoding_slots(
            sum(r is not None for r in self._slot_req))
        return page * chunk_pages(
            len(self._prefill_tasks), decoding, pages_left,
            degraded=self._qos is not None and self._qos.degraded)

    def _run_prefill_chunks(self):
        """Run at most ``max_chunks_per_iter`` prefill chunk programs
        this iteration (the degradation ladder shrinks the budget at
        level >= 2), FIFO across admitted-but-unprefilled requests — the
        chunked-prefill contract: a long prompt never stalls the decode
        batch by more than this many chunk programs per decode dispatch.
        Each chunk's width is cut here, when it is dispatched."""
        budget = self.config.paging.max_chunks_per_iter
        if self._qos is not None:
            budget = self._qos.max_chunks(budget)
        while budget > 0 and self._prefill_tasks:
            task = self._prefill_tasks[0]
            slot, req, prompt, max_new, start = task
            p_len = int(prompt.shape[0])
            width = self._chunk_width(p_len - start)
            task[4] = start + width
            is_last = task[4] >= p_len
            ok = self._dispatch_chunk(slot, req, prompt, max_new, start,
                                      width, is_last=is_last)
            if not ok:
                return          # OOM containment reset the queue state
            if is_last:
                self._prefill_tasks.popleft()
            budget -= 1

    def compile_chunk_programs(self):
        """Lower and compile the chunk program at every width
        ``chunk_pages`` may choose, from shapes, running nothing:
        ``InferenceEngine.serve()`` calls this before the first request,
        so that a width's first dispatch — which may come hours in, when
        a queue first builds — neither traces nor compiles. A width not
        asked for ahead (a fixed ``prefill_chunk``'s one, every width of
        an engine built directly) is compiled by the same call at its
        first dispatch: the manager keeps one table of executables."""
        with self._trace_scope():
            self._paged.compile_chunks(
                self.module, self.params, self._state, self._rng, self._eos,
                self._mode, self._param_transform)

    def _first_chunk(self, slot: int, req):
        """Stamp the admission's first chunk (the wait from admission is
        telemetry: kept out of the dispatch, which decides nothing on a
        clock) and forget a preempted prefill's router counts."""
        self._chunk_counts.pop(slot, None)
        req.first_chunk_at_ns = time.perf_counter_ns()
        self.metrics.on_prefill_wait(
            req.first_chunk_at_ns - req.admitted_at_ns)

    def _dispatch_chunk(self, slot: int, req, prompt, max_new: int,
                        start: int, width: int, is_last: bool) -> bool:
        """Prefill one page-aligned chunk of one request. Mid-chunks only
        fill pages; the LAST chunk also samples the first token (read
        back pipelined, like a decode's) and publishes the prompt's full
        pages to the prefix cache. Same program either way — ``is_last``
        is a traced flag, not a jit specialization. Returns False when a
        RESOURCE_EXHAUSTED was contained (the caller must stop driving
        the now-reset prefill queue)."""
        p_len = int(prompt.shape[0])
        real = min(start + width, p_len) - start
        padded = np.zeros((1, width), np.int32)
        padded[0, :real] = prompt[start:start + real]
        mgr = self._paged
        pages = width // mgr.page_len
        if req.first_chunk_at_ns is None:
            self._first_chunk(slot, req)
        try:
            with _span("serving/prefill_chunk",
                       {"slot": slot, "request_id": req.request_id,
                        "trace_id": req.trace_id,
                        "start": start, "tokens": real, "pages": pages,
                        "last": bool(is_last)}):
                out = mgr.prefill_chunk(
                    self.module, self.params, self._state, slot, padded,
                    start, p_len, max_new, is_last, self._req_rng(req),
                    self._eos, self._mode, self._param_transform)
        except Exception as e:
            if not is_oom_error(e):
                raise
            self._shed_on_oom(req, "chunk_prefill", e)
            return False
        self._state = out.state
        self.metrics.on_prefill_chunk(real, out.state_pages, pages=pages,
                                      snapshot_table=out.snapshot_table)
        self.metrics.on_split_chunk(width, out.cross_positions,
                                    real if mgr.has_rings else None)
        if out.counts is not None:
            # an expert layer's routing of this chunk: read back with the
            # first token, by when every earlier chunk has finished
            self._chunk_counts.setdefault(slot, []).append(out.counts)
        if is_last:
            # pages below the prompt's full-page boundary are immutable
            # from here (decode appends strictly past them): publish them
            # for copy-free reuse by later identical prefixes
            mgr.publish(slot, prompt)
            self._pending.append(("admit", slot, req, out.tokens, out.done,
                                  self._chunk_counts.pop(slot, [])))
        return True

    def _decoding_slots(self, busy: int) -> int:
        """Of ``busy`` held slots, those a decode dispatch advances: a
        slot whose prefill chunks are still queued rides the batch
        masked."""
        return busy - len(self._prefill_tasks)

    def _dispatch_decode(self) -> bool:
        if all(r is None for r in self._slot_req):
            return False
        if self._spec is not None:
            proposals = self._collect_proposals()
            if proposals is not None:
                return self._dispatch_spec_verify(*proposals)
            if all(r is None for r in self._slot_req):
                return False    # the proposal drain finished every slot
        snapshot = list(self._slot_req)
        busy = sum(r is not None for r in snapshot)
        # active request count on the span: trace captures show how full
        # each decode dispatch ran (the SLO-reconstruction groundwork)
        with _span("serving/decode_iter", {"active_requests": busy,
                                           "iteration": self._iteration}):
            out = self._paged.decode(
                self.module, self.params, self._state, self._decode_rng,
                self._iteration, self._eos, self._mode,
                self._param_transform)
        self._state = out.state
        self.metrics.on_decode_dispatch(self._decoding_slots(busy),
                                        self.config.num_slots)
        self._pending.append(("decode", snapshot, out.tokens, out.done,
                              _counts_read(out.counts)))
        self._iteration += 1
        return True

    # -- self-speculative decoding (serving/speculation.py) ----------------
    def _collect_proposals(self):
        """This iteration's host-side speculation proposals: ``(props
        [slots, K], counts [slots])`` numpy arrays, or None when no slot
        proposes — the iteration then rides the existing one-token
        decode program untouched. Drains in-flight readbacks first (the
        proposer matches against each slot's CURRENT prompt+generated
        frontier, which pipelining lags by ``pipeline_depth`` tokens) —
        the latency price of draft-free self-speculation, paid only on
        iterations that actually propose."""
        kmax = self.config.speculation.max_spec_tokens
        if self._qos is not None:
            # the first rung of the degradation ladder: speculation
            # sheds from the FIRST overloaded iteration — strictly
            # before any request does
            kmax = self._qos.max_spec_tokens(kmax)
        if kmax <= 0 or not self._mode[0]:     # shed, or non-greedy
            return None
        if not any(r is not None and not r.done and r.tokens
                   for r in self._slot_req):
            return None
        while self._pending:
            self._harvest_one()
        n = self.config.num_slots
        width = self.config.speculation.max_spec_tokens
        props = np.zeros((n, width), np.int32)
        counts = np.zeros((n,), np.int32)
        with _span("serving/spec_propose", {"iteration": self._iteration}):
            for slot, req in enumerate(self._slot_req):
                # proposable: running with its first token already
                # harvested (mid-chunked-prefill slots have none) and
                # at least 2 tokens of budget left (with 1 remaining a
                # plain decode already finishes the request)
                if req is None or req.done or not req.tokens:
                    continue
                budget = min(kmax, req.remaining_budget() - 1)
                if budget <= 0:
                    continue
                seq = np.concatenate([np.asarray(req.prompt, np.int32),
                                      np.asarray(req.tokens, np.int32)])
                got = self._spec.propose(seq, budget)
                if got.shape[0]:
                    props[slot, :got.shape[0]] = got
                    counts[slot] = got.shape[0]
        if not counts.any():
            return None
        return props, counts

    def _dispatch_spec_verify(self, props, counts) -> bool:
        """Dispatch the ONE batched verification program over the slot
        batch: every proposing slot's ``[last_token, proposals]`` block
        runs one multi-token decode step at its own frontier;
        non-proposing slots ride along masked (``counts == 0`` accepts
        zero proposals, emitting exactly the one token a plain decode
        step would). Counts as one decode iteration on the step clock —
        TTFT/steps percentiles stay iteration-denominated while token
        counters take the full emitted count at harvest."""
        snapshot = list(self._slot_req)
        busy = sum(r is not None for r in snapshot)
        with _span("serving/spec_verify",
                   {"active_requests": busy, "iteration": self._iteration,
                    "proposed_tokens": int(counts.sum())}):
            out = self._paged.spec_verify(
                self.module, self.params, self._state, props, counts,
                self._decode_rng, self._iteration, self._eos, self._mode,
                self._param_transform)
        self._state = out.state
        self.metrics.on_decode_dispatch(self._decoding_slots(busy),
                                        self.config.num_slots)
        self._pending.append(("spec", snapshot, out.tokens, out.done, counts))
        self._iteration += 1
        return True

    def _read_back(self, *arrays):
        """The blocking device->host reads of one harvest: the only
        place an iteration waits for the device. Timed always (the
        iteration's ``readback`` phase) and spanned, so the ``harvest``
        phase and ``serving/harvest``'s self time are token emission and
        the callers' ``on_token`` callbacks. Whether the tokens had
        arrived before the read is asked first and costs no sync: a long
        read of ready arrays is the host's, of unready ones the
        device's or the queue's."""
        outer = self._clock.begin_readback(arrays[0].is_ready())
        with _span("serving/readback"):
            out = [np.asarray(a) for a in arrays]
        self._clock.switch(outer)
        return out

    def _harvest_one(self):
        """Read back the oldest in-flight dispatch (blocks only on work
        dispatched >= pipeline_depth iterations ago) and stream its
        tokens/completions to their requests."""
        outer = self._clock.switch(HARVEST)
        try:
            self._harvest(self._pending.popleft())
        finally:
            self._clock.switch(outer)

    def _harvest(self, entry):
        harvest_args = {"kind": entry[0],
                        "active_requests": sum(r is not None
                                               for r in self._slot_req)}
        if entry[0] == "admit":
            # first-token harvests are per-request: carry the trace id
            # so the stitched fleet trace joins them to their admit
            harvest_args["request_id"] = entry[2].request_id
            harvest_args["trace_id"] = entry[2].trace_id
        with _span("serving/harvest", harvest_args):
            if entry[0] == "admit":
                _, slot, req, tok, done, counts = entry
                if req.done:     # cancelled between dispatch and readback
                    return
                tok, done, *counts = self._read_back(tok, done, *counts)
                self._fold_moe_counts(counts)
                req._emit(int(tok), self._iteration)
                self.metrics.on_token()
                if bool(done):
                    self._finish(slot, req)
                elif self.prefill_only:
                    # prefill role: mask the device row (this engine
                    # never decodes it) and stage the slot for a page
                    # handoff — pages stay allocated until export
                    self._mask_row(slot)
                    self._handoff_ready.append((slot, req))
                return
            if entry[0] == "spec":
                # speculative verification readback: toks is
                # [slots, K+1] with position i >= 0 iff emitted — the
                # accepted proposal prefix plus the bonus token, in
                # order. Token counters take the EMITTED count (k+1 per
                # accepted step); the iteration clock already ticked
                # exactly once at dispatch.
                _, snapshot, toks, done, counts = entry
                toks, done = self._read_back(toks, done)
                for slot, req in enumerate(snapshot):
                    if req is None or req.done:
                        continue
                    emitted = 0
                    for i in range(toks.shape[1]):
                        if toks[slot, i] < 0:
                            break
                        req._emit(int(toks[slot, i]), self._iteration)
                        emitted += 1
                    if emitted:
                        self.metrics.on_token(emitted)
                        if counts[slot]:
                            self.metrics.on_spec(int(counts[slot]),
                                                 emitted - 1)
                    if done[slot]:
                        self._finish(slot, req)
                return
            _, snapshot, toks, done, counts = entry
            toks, done, *counts = self._read_back(toks, done, *counts)
            self._fold_moe_counts(counts)
            # before the tokens are emitted: what the pool's kernel walked
            rows, latent, rings = self._paged.decode_walked(toks, snapshot)
            self.metrics.on_decode_harvest(rows)
            self.metrics.on_latent_walk(latent)
            self.metrics.on_ring_walk(rings)
            for slot, req in enumerate(snapshot):
                if req is None or req.done:  # empty, or cancelled in flight
                    continue
                if toks[slot] >= 0:
                    req._emit(int(toks[slot]), self._iteration)
                    self.metrics.on_token()
                if done[slot]:
                    self._finish(slot, req)

    def _fold_moe_counts(self, counts):
        """An expert layer's routing of the dispatches just read back
        (``[L, E]`` each), folded into the process registry; a module
        with shared experts says how many rows its counts mean went
        through them (``shared_expert_rows``)."""
        if counts:
            shared = getattr(self.module, "shared_expert_rows", None)
            with _span("serving/moe_counts", {"dispatches": len(counts)}):
                for c in counts:
                    self.metrics.on_moe_counts(
                        c, shared(c) if shared is not None else None)

    def _finish(self, slot: int, req: Request):
        self._record_residency(req)
        req._finished(self._iteration)
        self.metrics.on_finish(req)
        self._vacate(slot)

    # -- fault containment + recovery --------------------------------------
    def _shed_on_oom(self, req: Request, where: str, err: Exception):
        """RESOURCE_EXHAUSTED containment: dump the allocation-failure
        post-mortem (observability/memory.py oom_forensics — the
        attributed-buffer view, not a bare error string), shed the
        offending request with explicit status, and rebuild the device
        state via ``recover()`` so the engine keeps serving everyone
        else. The jitted prefill program donates its pool and state
        operands, so after a failed call those buffers cannot be trusted
        — a full device-state rebuild is the only safe continuation."""
        report = oom_forensics(
            reason=f"serving {where} RESOURCE_EXHAUSTED "
                   f"(request {req.request_id!r}): {str(err)[:200]}")
        self.last_oom_forensics = report
        req._shed(self._iteration, qos_mod.SHED_OOM)
        self.metrics.on_shed(req, qos_mod.SHED_OOM)
        self.metrics.on_fault("oom", f"{where}: request {req.request_id!r} "
                              "shed after RESOURCE_EXHAUSTED",
                              self._iteration)
        log_dist(f"serving: RESOURCE_EXHAUSTED during {where} — request "
                 f"{req.request_id!r} shed, forensics captured, engine "
                 "recovering", ranks=[0])
        self.recover(f"oom during {where}", kind="oom",
                     detail=str(err)[:500])

    def recover(self, reason: str, kind: str = "restart",
                detail: Optional[str] = None):
        """Requeue-and-re-prefill recovery — the serving engine restart.

        Drops in-flight readbacks (their tokens were never streamed, so
        re-prefill regenerates them exactly), rebuilds the device-side
        pool and state from scratch (same shapes: every compiled
        program stays cached), and pushes every live admitted request
        back to the queue in original arrival order with its generated
        tokens retained. Queued requests are untouched. The next
        ``advance()`` re-admits and re-prefills prompt + partial output —
        token-exact under greedy sampling, page-granular prefix-cache
        hits making the recompute cheap."""
        self._pending.clear()
        self._handoff_ready.clear()   # staged slots are requeued below —
                                      # their page contents are stale
        victims = [r for r in self._slot_req
                   if r is not None and not r.done]
        n = self.config.num_slots
        self._slot_req = [None] * n
        self._free = deque(range(n))
        self._paged.reset()
        self._init_device_state()
        # requeue_front in reverse arrival order: the earliest-submitted
        # victim ends up at its class head, restoring FIFO-within-class
        for r in sorted(victims, key=lambda r: r._seq or 0, reverse=True):
            r._preempted(self._iteration)
            self.scheduler.requeue(r)
            self.metrics.on_preempt(r, kind)
        self.metrics.on_recover(kind, reason, len(victims), self._iteration)
        self._restart_watchdog()   # a fired watchdog thread is one-shot
        log_dist(f"serving: recovered ({kind}: {reason}) — device state "
                 f"rebuilt, {len(victims)} active requests requeued for "
                 "re-prefill", ranks=[0])
        if detail:
            log_dist(f"serving: recovery detail: {detail.splitlines()[0]}",
                     ranks=[0])

    # -- elastic capacity (autoscaling hooks) ------------------------------
    def set_slot_cap(self, n: int) -> int:
        """Set the admissible-slot cap (the in-process scale axis the
        elasticity autoscaler drives). Scale-down DRAINS: active requests
        in slots above the cap are preempted back to the queue via the
        normal preemption path — tokens retained, resumed later in an
        admissible slot — never dropped. Compiled shapes are untouched
        (decode always runs the full ``num_slots`` batch; capped slots
        ride along masked). Returns the applied cap."""
        n = max(1, min(int(n), self.config.num_slots))
        if n == self._slot_cap:
            return n
        old, self._slot_cap = self._slot_cap, n
        if n < old:
            drained = [s for s in range(n, self.config.num_slots)
                       if self._slot_req[s] is not None]
            if drained:
                while self._pending:    # retain in-flight tokens first
                    self._harvest_one()
                for slot in drained:
                    r = self._slot_req[slot]
                    if r is not None and not r.done:
                        self._preempt_slot(slot, reason="scale_down")
        log_dist(f"serving: slot cap {old} -> {n} "
                 f"(of {self.config.num_slots} compiled slots)", ranks=[0])
        return n

    # -- disaggregated prefill/decode handoff (serving/fleet/) -------------
    def set_prefill_role(self, on: bool = True):
        """Flip the engine into (or out of) the disaggregated fleet's
        prefill role: admissions and chunked prefill run normally, the
        decode program never dispatches, and every prefilled request
        stages in ``take_handoff_ready()`` for a page-granular KV
        transfer to a decode replica."""
        self.prefill_only = bool(on)

    def take_handoff_ready(self):
        """Pop the requests whose prefill (and first token) completed and
        now await export — ``[(slot, req)]``. Slots stay allocated (pages
        pinned) until ``export_handoff``; entries whose request was
        cancelled or requeued in the meantime are dropped here."""
        out, self._handoff_ready = self._handoff_ready, []
        return [(s, r) for s, r in out
                if self._slot_req[s] is r and not r.done]

    def export_handoff(self, slot: int, req: Request) -> dict:
        """Serialize one prefilled request as a page-granular handoff
        payload (docs/serving.md "Handoff wire format"): the prefilled
        pages' contents, the page-table run length, and the request +
        sampler state a decode replica needs to continue token-exactly.
        Frees the slot — the pages travel as values, not references."""
        from .fleet.handoff import HANDOFF_VERSION
        # what was prefilled = the effective prompt at admission; tokens
        # holds exactly one post-prefill sample (the handoff fires at
        # first-token harvest), so the frontier is one behind it
        prefill_len = len(req.prompt) + len(req.tokens) - 1
        remaining = req.max_new_tokens - len(req.tokens)
        with _span("serving/handoff_export",
                   {"request_id": req.request_id,
                    "trace_id": req.trace_id,
                    "prefill_len": prefill_len}):
            kv, n_filled = self._paged.export_slot(slot, prefill_len)
            payload = {
                "version": HANDOFF_VERSION,
                "page_len": self._paged.page_len,
                "kv_quant": self._paged.kv_quant,
                "prefill_len": prefill_len,
                "n_pages_filled": n_filled,
                "kv": kv,
                "state": {"last_token": int(req.tokens[-1]),
                          "remaining": int(remaining)},
                "request": {"request_id": req.request_id,
                            "trace_id": req.trace_id,
                            "prompt": np.asarray(req.prompt, np.int32),
                            "generated": list(req.tokens),
                            "max_new_tokens": int(req.max_new_tokens),
                            "priority": int(req.priority)},
            }
            self._vacate(slot)
        self.metrics.on_handoff_export(req)
        return payload

    def inject_handoff(self, payload: dict,
                       request: Optional[Request] = None,
                       on_token=None) -> Optional[Request]:
        """Import a handoff payload into a free slot and continue decode
        from it — ZERO prefill recompute (no prefill program runs; the
        transferred pages are written in place with the page-table-update
        dispatch pattern, so every compiled program stays cached).
        Returns the live ``Request`` rebuilt from the payload (the ONE
        payload->Request mapping — callers pass ``on_token=`` to wire
        streaming instead of rebuilding it themselves; ``request=``
        threads a fully prepared handle through when one exists), or
        None when no slot/pages are free — the caller retries on a
        later step. Token-exact under greedy sampling: decode continues
        from the transferred KV + last token exactly as the prefilling
        engine would have."""
        from .fleet.handoff import COMPAT_HANDOFF_VERSIONS
        if payload.get("version") not in COMPAT_HANDOFF_VERSIONS:
            raise ValueError(
                f"unknown handoff payload version {payload.get('version')!r}"
                f" (this build speaks {COMPAT_HANDOFF_VERSIONS})")
        if (payload["page_len"] != self._paged.page_len
                or payload.get("kv_quant") != self._paged.kv_quant):
            raise ValueError(
                "handoff wire-format mismatch: payload page_len="
                f"{payload['page_len']}/kv_quant={payload.get('kv_quant')!r}"
                f" vs pool page_len={self._paged.page_len}/kv_quant="
                f"{self._paged.kv_quant!r} — fleet replicas must share "
                "one serving config")
        st = payload["state"]
        rq = payload["request"]
        # idempotence guard: a payload re-sent after an AMBIGUOUS
        # failure (reply lost or timed out mid-inject) must not run the
        # same request twice — if its id was already injected here
        # (still decoding, requeued by QoS/preemption, or ALREADY
        # finished before the retry landed), hand the existing request
        # back instead of double-injecting
        dup = self._handoff_injected.get(rq["request_id"])
        if dup is None:
            dup = next((r for r in self._slot_req
                        if r is not None
                        and r.request_id == rq["request_id"]), None)
        if dup is None:
            dup = next((r for r in self.scheduler.queued()
                        if r.request_id == rq["request_id"]), None)
        if dup is not None:
            from ..observability.metrics import get_registry
            get_registry().counter("serving/handoff_dedup").inc()
            return dup
        slot = self._peek_free_slot()
        if slot is None:
            return None
        prefill_len = int(payload["prefill_len"])
        remaining = int(st["remaining"])
        total = self._paged.pages_for(prefill_len, remaining)
        # the trace identity travels in the payload (v2); a v1 payload
        # carries none and gets a fresh deterministic id here
        trace_id = rq.get("trace_id") or make_trace_id(
            rq["request_id"], self._seq)
        with _span("serving/handoff_inject",
                   {"request_id": rq["request_id"], "trace_id": trace_id,
                    "prefill_len": prefill_len}):
            if not self._paged.import_slot(slot, payload["kv"],
                                           int(payload["n_pages_filled"]),
                                           total):
                return None
        if request is None:
            request = Request(np.asarray(rq["prompt"], np.int32),
                              rq["max_new_tokens"], rq["request_id"],
                              on_token=on_token,
                              priority=rq.get("priority", 0),
                              trace_id=trace_id)
            request.tokens = list(rq["generated"])
        elif request.trace_id is None:
            request.trace_id = trace_id
        if request.submitted_iteration is None:
            request.submitted_iteration = self._iteration
        self._take_slot(slot)
        self._slot_req[slot] = request
        request._admitted(slot, self._iteration)
        self._state = {
            "lengths": self._state["lengths"].at[slot].set(prefill_len),
            "last_token": self._state["last_token"].at[slot].set(
                st["last_token"]),
            "active": self._state["active"].at[slot].set(True),
            "remaining": self._state["remaining"].at[slot].set(remaining),
        }
        # publish the imported prompt's full pages to THIS replica's
        # prefix cache: later handoffs/admits of the same prefix family
        # reference them copy-free, exactly like a local prefill would
        prefilled = np.concatenate(
            [np.asarray(rq["prompt"], np.int32),
             np.asarray(rq["generated"][:-1], np.int32)]) \
            if len(rq["generated"]) > 1 else np.asarray(rq["prompt"],
                                                        np.int32)
        self._paged.publish(slot, prefilled)
        self.metrics.on_handoff_import(request, prefill_len)
        # remember the injection (bounded) so a duplicate payload is
        # recognized even after this request finishes and leaves the
        # slot/queue scans above
        self._handoff_injected[request.request_id] = request
        while len(self._handoff_injected) > 256:
            self._handoff_injected.pop(
                next(iter(self._handoff_injected)))
        return request

    # -- construction helpers ---------------------------------------------
    @classmethod
    def from_config(cls, module, params, ds_config, **kwargs):
        """Build from a DeepSpeedConfig (or raw dict) carrying a
        ``serving`` block; monitor backends configured in the same dict
        receive the buffered serving metrics."""
        from ..runtime.config import DeepSpeedConfig
        if isinstance(ds_config, dict):
            ds_config = DeepSpeedConfig.from_dict(ds_config)
        serving = getattr(ds_config, "serving", None) or ServingConfig()
        monitor = kwargs.pop("monitor", None)
        if monitor is None:
            from ..monitor.monitor import MonitorMaster
            master = MonitorMaster(ds_config)
            monitor = master if master.enabled else None
        engine = cls(module, params, serving, monitor=monitor, **kwargs)
        # the observability.export block lights the endpoint up for
        # config-built serving engines, mirroring the training engine
        obs = getattr(ds_config, "observability", None)
        if obs is not None and obs.export.enabled:
            engine.start_telemetry(port=obs.export.port, host=obs.export.host)
        return engine
