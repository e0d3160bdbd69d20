"""Replica manager + fleet front-end: the layer above one engine.

``ServingFleet`` owns N supervised replicas (``fleet/replica.py``), a
prefix-affinity router (``fleet/router.py``), the disaggregated
prefill->decode page-handoff pump, dead-replica failover, and the
closed autoscaling loop (``elasticity/serving_autoscaler.py``
``target_replicas`` finally ACTS here: sustained backlog spawns
replicas, scale-down drains through the PR-10 preemption/slot-cap path).

The fleet runs on its own deterministic step clock: one ``advance()``
advances every live replica one engine iteration (lockstep), then moves
handoffs, detects deaths, and evaluates scaling. Every decision reads
host ints snapshotted on that clock, so a replayed trace reproduces the
same dispatch/handoff/failover sequence bit-exactly — the engine-level
replay discipline, one level up.

Clients hold ``FleetRequest`` handles: one stable object per request no
matter how many replicas serve it (prefill -> decode handoff, failover
re-prefill). Tokens stream into the handle from whichever replica
currently owns the request; under greedy sampling the merged stream is
bit-equal to a single uninterrupted engine (the QoS resume guarantee,
inherited wholesale).
"""

import time
from collections import deque
from dataclasses import replace
from typing import Dict, List, Optional

import numpy as np

from ...observability.fleet import (FleetTelemetryAggregator,
                                    FlightRecorder, make_trace_id,
                                    per_request_breakdown)
from ...observability.metrics import get_registry, percentile
from ...utils.logging import log_dist
from ..request import Request
from .config import FleetConfig
from .handoff import (HandoffError, deserialize_handoff,
                      serialize_handoff, stamp_handoff, verify_handoff)
from .replica import (LocalReplica, ProcessReplica, ReplicaCrash,
                      ReplicaDead)
from .router import Router
from .supervision import ReplicaSupervisor, SupervisionConfig

TERMINAL = ("finished", "timeout", "cancelled", "shed")
LOG_LIMIT = 4096     # dispatch/handoff log entries kept (replay asserts
                     # run over bounded traces; a long-lived server must
                     # not grow them forever)
DEAD_REPLICAS_KEPT = 16   # corpse history: dead replicas stay readable
                          # in snapshots (their served work must not
                          # vanish) up to this many; older ones are
                          # pruned — a supervised fleet restarts without
                          # bound and must not do O(ever-spawned) work
                          # per step


class FleetRequest:
    """One client request as the FLEET sees it: a stable handle whose
    tokens/status survive handoffs and replica deaths. Field names
    mirror ``serving.request.Request`` so the bench/CLI reporting paths
    work on either."""

    def __init__(self, prompt, max_new_tokens: int, request_id,
                 priority: int = 0, on_token=None, trace_id=None):
        self.request_id = request_id
        # the distributed trace identity: stamped by the fleet at
        # submit, propagated to every replica that ever serves this
        # request (worker protocol + handoff wire) so one id joins its
        # spans and lifecycle events fleet-wide
        self.trace_id = trace_id
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        self.max_new_tokens = int(max_new_tokens)
        self.priority = int(priority)
        self.on_token = on_token
        self.tokens: List[int] = []
        self.status = "queued"
        self.shed_reason: Optional[str] = None
        self.replica_id: Optional[int] = None   # current owner (None
                                                # while a handoff is in
                                                # transit)
        self.prefill_replica_id: Optional[int] = None
        self.handoffs = 0
        self.failovers = 0
        self.preemptions = 0
        self.weights_version = 0    # version of the replica that served
                                    # this request (rolling updates bump
                                    # it; the parity tests split on it)
        self._inner: Optional[Request] = None   # local-backend engine req
        self.submitted_at = time.perf_counter()
        self.first_token_at: Optional[float] = None
        self.finished_at: Optional[float] = None
        # fleet-clock stamps (deterministic run-to-run)
        self.submitted_iteration: Optional[int] = None
        self.admitted_iteration: Optional[int] = None
        self.first_token_iteration: Optional[int] = None
        self.finished_iteration: Optional[int] = None

    @property
    def done(self) -> bool:
        return self.status in TERMINAL

    @property
    def output_tokens(self) -> List[int]:
        return list(self.tokens)

    @property
    def ttft_s(self) -> Optional[float]:
        if self.first_token_at is None:
            return None
        return self.first_token_at - self.submitted_at

    @property
    def latency_s(self) -> Optional[float]:
        if self.finished_at is None:
            return None
        return self.finished_at - self.submitted_at

    def remaining_budget(self) -> int:
        return self.max_new_tokens - len(self.tokens)

    def effective_prompt(self) -> np.ndarray:
        if not self.tokens:
            return self.prompt
        return np.concatenate(
            [self.prompt, np.asarray(self.tokens, np.int32)])

    def __repr__(self):
        return (f"FleetRequest(id={self.request_id!r}, "
                f"status={self.status}, replica={self.replica_id}, "
                f"generated={len(self.tokens)}/{self.max_new_tokens}, "
                f"handoffs={self.handoffs}, failovers={self.failovers})")


class ServingFleet:
    """N supervised replicas behind one prefix-affinity front end.

    Usage (the single-engine surface, one level up)::

        fleet = ServingFleet(module, params, cfg)   # cfg.fleet block set
        reqs = [fleet.submit(p, max_new_tokens=32) for p in prompts]
        fleet.run()
        reqs[0].output_tokens
        fleet.close()

    ``backend="process"`` ignores ``module/params`` and spawns
    ``fleet/worker.py`` subprocesses from ``spec`` (model/checkpoint +
    serving config dict) — each its own device world and telemetry
    endpoint.
    """

    def __init__(self, module, params, config, *, spec: Optional[dict] =
                 None, monitor=None):
        from ..config import ServingConfig
        if isinstance(config, dict):
            config = ServingConfig(**config)
        self.config = config.validate()
        if not self.config.fleet_enabled:
            raise ValueError("ServingFleet needs an enabled serving.fleet "
                             "block (plain ServingEngine serves without "
                             "one)")
        self.fcfg: FleetConfig = self.config.fleet
        self._module = module
        self._params = params
        # replicas never see the fleet block: a replica IS the leaf
        self._replica_config = replace(self.config, fleet=None)
        self._spec = spec
        if self.fcfg.backend == "process" and spec is None:
            raise ValueError(
                "backend='process' needs spec= (model/checkpoint + "
                "serving config dict) — workers rebuild the engine from "
                "it")
        # -- federation (remote peers + HTTP front-end + rolling) ----------
        self.fedcfg = self.fcfg.federation
        self._peers = list(self.fedcfg.peers) if self.fedcfg else []
        if self._peers and spec is None:
            raise ValueError(
                "serving.fleet.federation.peers needs spec= — remote "
                "workers rebuild their engine from it over the wire")
        self._lineage_peer: Dict[int, str] = {}   # lineage -> address:
                                                  # a remote restart is a
                                                  # RE-DIAL of its peer
        self._lineage_epoch: Dict[int, int] = {}  # lineage -> incarnation
                                                  # epoch stamped into
                                                  # every request so a
                                                  # zombie's delayed
                                                  # reply is fenced
        self._draining = set()      # rids excluded from dispatch while a
                                    # rolling update drains them
        self._frontend = None       # FleetFrontend (drained each step)
        self.rolling = None         # in-flight RollingUpdate
        self.weights_version = 0    # bumped when a rolling update lands
        self.rolling_updates = 0    # completed updates
        self.rolling_swaps = 0      # individual replicas swapped
        self.router = Router(self.fcfg, self.config.paging.page_len)
        self._replicas: Dict[int, object] = {}
        self._next_rid = 0
        self._failed = set()            # rids whose failover already ran
        self._handles: Dict[object, FleetRequest] = {}   # LIVE handles
        self._handoff_backlog = deque() # [{"payload","handle","attempts",
                                        #   "not_before"}]
        self._iteration = 0
        self.dispatch_log: List[tuple] = []   # (request_id, replica_id)
        self.handoff_log: List[tuple] = []    # (request_id, src, dst) —
                                              # capped at LOG_LIMIT
        self.handoffs_completed = 0           # monotonic (the log trims)
        self.failovers = 0
        self.replicas_spawned = 0
        self.replicas_retired = 0
        self.dead_replicas = 0
        self.requests_submitted = 0
        self.requests_finished = 0
        self.requests_shed = 0
        self.last_scale_decision: Optional[dict] = None
        self.telemetry = None
        # -- supervision (the self-healing layer) --------------------------
        self.scfg: SupervisionConfig = self.fcfg.supervision
        self._supervised = bool(self.scfg.enabled)
        self.supervisor = ReplicaSupervisor(self.scfg)
        self._lineage: Dict[int, int] = {}   # rid -> lineage id
        self.replica_restarts = 0       # incarnations respawned
        self.handoffs_dropped = 0       # payloads past the retry budget
        self.handoff_retries = 0        # FAILED injection attempts
        self.degraded = False           # prefill pool empty: decode
                                        # replicas run their own chunked
                                        # prefill until one returns
        self.degraded_entered = 0
        self._orphans = deque()         # handles waiting for a restart
                                        # (no dispatchable replica when
                                        # they needed one)
        self._protocol_errors_pruned = 0
                                        # protocol errors carried from
                                        # pruned corpses (the snapshot
                                        # counter must never decrease)
        self.chaos_corrupt_handoffs = 0 # chaos hook: truncate the next N
                                        # handoff payloads in transit
                                        # (models wire corruption)
        self.chaos_flip_handoff_bits = 0
                                        # chaos hook: flip ONE byte in
                                        # the next N handoff payloads
                                        # AFTER the digest stamp — the
                                        # flipped-bit case only the v3
                                        # integrity digest catches
        self.handoffs_rejected_corrupt = 0
                                        # payloads refused by the
                                        # pre-injection digest gate (a
                                        # flipped bit never enters a KV
                                        # pool)
        self._stale_fence_pruned = [0, 0]
                                        # [stale_epoch, duplicate] reply
                                        # counts carried from pruned
                                        # corpses (snapshot counters
                                        # must never decrease)
        # fleet-level flight recorder: request lifecycle events on the
        # FLEET step clock (submit/admit/first_token/handoff/failover/
        # terminal) — the per-request waterfall's input and the crash
        # path's last-N-requests timeline
        self.recorder = FlightRecorder(self.fcfg.flight_recorder_events)
        # bounded-cadence telemetry aggregator: every replica's metrics
        # (scraped or direct) merged into one fleet view served from
        # the router process
        self._aggregator = (
            FleetTelemetryAggregator(stale_after_s=self.fcfg.stale_after_s)
            if self.fcfg.aggregate_telemetry else None)
        # declarative SLO watch (observability/slo.py): evaluated on
        # the aggregation cadence against a sample built from the
        # fleet's own books — deterministic on the fleet step clock
        self.slo_watch = None
        if self.fcfg.slo is not None and self.fcfg.slo.enabled:
            from ...observability.slo import SloWatch
            self.slo_watch = SloWatch.from_config(self.fcfg.slo)
        self._scaler = None
        if self.fcfg.autoscale:
            from ...elasticity.serving_autoscaler import (
                ServingAutoscaleConfig, ServingAutoscaler)
            from ...observability.metrics import MetricsRegistry
            self._scale_registry = MetricsRegistry()
            self._scaler = ServingAutoscaler(
                engine=None,
                config=ServingAutoscaleConfig(
                    min_slots=1, max_replicas=self.fcfg.max_replicas),
                registry=self._scale_registry,
                replica_slots=self.config.num_slots)
        for i in range(self.fcfg.replicas):
            # peers fill the LEADING replica ids so role_for assigns
            # disaggregated roles to remote peers exactly as to locals
            self._spawn_replica(
                peer=self._peers[i] if i < len(self._peers) else None)
        self.replicas_spawned = 0       # construction is not a scale-up
        log_dist(
            f"serving fleet: {len(self._replicas)} replicas "
            f"({self.fcfg.backend}, router={self.fcfg.router}"
            f"{', disaggregated ' + str(self.fcfg.prefill_replicas) + ' prefill' if self.fcfg.disaggregate else ''})",
            ranks=[0])

    # -- replica lifecycle -------------------------------------------------
    def _spawn_replica(self, role: Optional[str] = None,
                       lineage: Optional[int] = None,
                       peer: Optional[str] = None):
        rid = self._next_rid
        self._next_rid += 1
        role = role or self.fcfg.role_for(rid)
        if lineage is None:
            lineage = self.supervisor.register(role)
        if peer is None:
            # a remote lineage restarts by RE-DIALING its peer: the
            # engine on the other end survives a dropped connection
            peer = self._lineage_peer.get(lineage)
        self._lineage[rid] = lineage
        # the aggregator needs a scrape target, so a process/remote
        # replica under aggregation always gets an endpoint even when
        # per-replica telemetry wasn't asked for explicitly
        want_port = (self.fcfg.replica_telemetry
                     or self._aggregator is not None)
        if peer is not None:
            from .federation.remote import RemoteReplica
            self._lineage_peer[lineage] = peer
            # per-incarnation epoch: every re-dial of this lineage gets
            # the next epoch, so a pre-restart incarnation's delayed
            # reply can never be applied by its successor
            epoch = self._lineage_epoch.get(lineage, -1) + 1
            self._lineage_epoch[lineage] = epoch
            fed = self.fedcfg
            rep = RemoteReplica(
                rid, role, peer,
                {**self._spec,
                 "telemetry_port": 0 if want_port else None,
                 # bugfix: the worker must bind /metrics on the dialed
                 # interface, and the router scrapes that same host —
                 # no localhost assumption on either end
                 "telemetry_host": peer.rpartition(":")[0],
                 "trace": self.fcfg.replica_trace},
                connect_timeout_s=fed.connect_timeout_s,
                reply_timeout_s=fed.reply_timeout_s,
                max_frame_bytes=fed.max_frame_bytes,
                epoch=epoch,
                heartbeat_timeout_s=fed.heartbeat_timeout_s,
                send_timeout_s=fed.send_timeout_s)
        elif self.fcfg.backend == "process":
            rep = ProcessReplica(rid, role,
                                 {**self._spec,
                                  "telemetry_port": 0 if want_port
                                  else None,
                                  "trace": self.fcfg.replica_trace},
                                 reply_timeout_s=self.fcfg
                                 .worker_reply_timeout_s)
        else:
            rep = LocalReplica(rid, role, self._module, self._params,
                               self._replica_config,
                               telemetry=self.fcfg.replica_telemetry)
        # spawns during/after a rolling update serve the NEW weights
        # (the update stamps _module/_params/_spec at start)
        rep.weights_version = (self.rolling.version
                               if self.rolling is not None
                               and not self.rolling.done
                               else self.weights_version)
        self._replicas[rid] = rep
        if self._aggregator is not None:
            if rep.backend != "inprocess" and rep.telemetry_port:
                # reuse the replica's cached client: health sweeps and
                # aggregator polls accumulate one staleness stamp
                self._aggregator.add_scrape(rid, client=rep.scrape_client)
            else:
                self._aggregator.add_direct(rid, rep.metrics_sample)
        self.replicas_spawned += 1
        return rep

    def kill_replica(self, rid: int):
        """Hard-kill one replica (the chaos/failover hook): the next
        ``advance()`` detects the death and requeues its in-flight
        requests through the router."""
        self._replicas[rid].kill()

    def _alive(self, roles=None) -> List[int]:
        return [rid for rid, rep in sorted(self._replicas.items())
                if rep.alive and (roles is None or rep.role in roles)]

    def _stats(self, rids) -> List:
        out = []
        for r in rids:
            s = self._replicas[r].stats()
            if self._replicas[r].backend == "remote":
                # scrape-driven routing (the deferred PR-12 half): a
                # remote peer's synchronous stats ride the advance
                # reply, but between replies its aggregator sample is
                # the fresher load signal — stamp it so the router can
                # weigh both (scraped off-step, read on-step: for a
                # given scrape history the route replays bit-exactly)
                s.scraped_load = self._scraped_load(r)
            out.append(s)
        return out

    def _scraped_load(self, rid) -> Optional[float]:
        if self._aggregator is None:
            return None
        entry = self._aggregator.replicas.get(rid)
        sample = entry.get("sample") if entry else None
        if not sample:
            return None
        total, seen = 0.0, False
        for suffix in ("serving_queue_depth", "serving_active_slots"):
            for key, value in sample.items():
                if key.endswith(suffix):
                    total += float(value)
                    seen = True
                    break
        return total if seen else None

    def _submit_roles(self):
        if not self.fcfg.disaggregate:
            return ("full",)
        # degraded disaggregation: with the prefill pool empty, decode
        # replicas temporarily take submissions end-to-end (their own
        # chunked prefill) instead of stranding the queue
        return ("decode",) if self.degraded else ("prefill",)

    def _dispatchable(self, rids: List[int]) -> List[int]:
        """Filter a live-replica list down to the ones the aggregated
        telemetry considers dispatch-healthy (``up`` and not stale).
        Never empties the list on telemetry alone — with every replica
        stale the fleet still dispatches rather than bricking on its
        own observability plane. Replicas a rolling update is draining
        are excluded first (they finish what they own, take nothing
        new), with the same never-empty fallback."""
        undrained = [r for r in rids if r not in self._draining]
        rids = undrained if undrained else rids
        if self._aggregator is None:
            return rids
        healthy = [r for r in rids if self._aggregator.healthy(r)]
        return healthy if healthy else rids

    def _park(self, handle: FleetRequest):
        """No dispatchable replica right now but capacity is coming
        back (a pending restart, or degraded mode about to cover the
        missing role): hold the handle until it does (re-dispatched
        FIFO from ``advance()``)."""
        handle.replica_id = None
        self._handles[handle.request_id] = handle
        self._orphans.append(handle)
        self.recorder.record("parked", request_id=handle.request_id,
                             trace_id=handle.trace_id,
                             iteration=self._iteration)

    def _can_wait_for_capacity(self) -> bool:
        """Parking beats raising when capacity will return: a restart
        is scheduled, or the fleet is disaggregated with live decode
        replicas (degraded mode covers a lost prefill pool on the next
        fleet step)."""
        if not self._supervised:
            return False
        if self.supervisor.pending():
            return True
        return bool(self.fcfg.disaggregate and self._alive(("decode",)))

    # -- client API --------------------------------------------------------
    def submit(self, prompt, max_new_tokens: Optional[int] = None,
               request_id=None, priority: int = 0,
               on_token=None, trace_id=None) -> FleetRequest:
        """Route one request to a replica (prefix affinity or least
        loaded) and return its fleet-level handle. ``trace_id`` lets a
        front-end mint the id at accept time (so the HTTP reply can
        carry it before dispatch); None derives it here as before."""
        if max_new_tokens is None:
            max_new_tokens = self.config.default_max_new_tokens
        if request_id is None:
            request_id = f"f{self.requests_submitted}"
        eligible = self._dispatchable(self._alive(self._submit_roles()))
        if not eligible and not self._can_wait_for_capacity():
            raise RuntimeError("fleet: no live replica accepts submissions")
        handle = FleetRequest(prompt, max_new_tokens, request_id,
                              priority=priority, on_token=on_token,
                              trace_id=trace_id or make_trace_id(
                                  request_id, self.requests_submitted))
        handle.submitted_iteration = self._iteration
        self.requests_submitted += 1
        if not eligible:
            self.recorder.record("submit", request_id=request_id,
                                 trace_id=handle.trace_id,
                                 replica_id=None,
                                 iteration=self._iteration,
                                 prompt_len=int(handle.prompt.shape[0]))
            self._park(handle)      # supervision will bring one back
            return handle
        target = self.router.route(
            np.asarray(prompt, np.int32), self._stats(eligible),
            step=self._iteration, request_id=request_id)
        self.dispatch_log.append((request_id, target))
        del self.dispatch_log[:-LOG_LIMIT]
        self.recorder.record("submit", request_id=request_id,
                             trace_id=handle.trace_id, replica_id=target,
                             iteration=self._iteration,
                             prompt_len=int(handle.prompt.shape[0]))
        self._dispatch(handle, target, handle.prompt, max_new_tokens)
        return handle

    def _on_token_cb(self, handle: FleetRequest):
        def cb(_req, token):
            if handle.first_token_at is None:
                handle.first_token_at = time.perf_counter()
                handle.first_token_iteration = self._iteration
                self.recorder.record(
                    "first_token", request_id=handle.request_id,
                    trace_id=handle.trace_id,
                    replica_id=handle.replica_id,
                    iteration=self._iteration)
            handle.tokens.append(int(token))
            if handle.on_token is not None:
                handle.on_token(handle, int(token))
        return cb

    def _dispatch(self, handle: FleetRequest, rid: int, prompt,
                  max_new: int):
        rep = self._replicas[rid]
        handle.replica_id = rid
        handle.weights_version = getattr(rep, "weights_version", 0)
        if handle.prefill_replica_id is None:
            handle.prefill_replica_id = rid
        if rep.backend == "inprocess":
            inner = rep.submit(prompt, max_new,
                               request_id=handle.request_id,
                               priority=handle.priority,
                               on_token=self._on_token_cb(handle),
                               trace_id=handle.trace_id)
            handle._inner = inner
            if inner.done:          # QoS shed/refused at submit
                self._finalize(handle, inner.status, inner.shed_reason)
                return
        else:
            try:
                reply = rep.submit(prompt, max_new,
                                   request_id=handle.request_id,
                                   priority=handle.priority,
                                   trace_id=handle.trace_id)
            except ReplicaDead:
                # undetected death discovered at dispatch time (e.g. an
                # OOM-killed worker between health sweeps): reroute NOW
                # — the request must not ride a corpse or get lost; the
                # death sweep reaps the replica next advance. Bounded:
                # each retry excludes one more dead replica.
                eligible = self._dispatchable(
                    self._alive(self._submit_roles()))
                if not eligible:
                    if self._can_wait_for_capacity():
                        self._park(handle)
                        return
                    raise RuntimeError(
                        "fleet: no live replica accepts submissions")
                target = self.router.route(
                    prompt, self._stats(eligible), step=self._iteration,
                    request_id=handle.request_id)
                self.dispatch_log.append((handle.request_id, target))
                del self.dispatch_log[:-LOG_LIMIT]
                return self._dispatch(handle, target, prompt, max_new)
            if reply.get("status") in TERMINAL:
                self._finalize(handle, reply["status"], None)
                return
        self._handles[handle.request_id] = handle

    def _finalize(self, handle: FleetRequest, status: str,
                  shed_reason=None):
        handle.status = status
        handle.shed_reason = shed_reason
        handle.finished_at = time.perf_counter()
        handle.finished_iteration = self._iteration
        handle._inner = None
        if status == "finished":
            self.requests_finished += 1
        elif status == "shed":
            self.requests_shed += 1
        self.recorder.record(status, request_id=handle.request_id,
                             trace_id=handle.trace_id,
                             replica_id=handle.replica_id,
                             iteration=self._iteration,
                             tokens=len(handle.tokens),
                             handoffs=handle.handoffs,
                             failovers=handle.failovers,
                             shed_reason=shed_reason)
        self._handles.pop(handle.request_id, None)

    # -- the fleet step ----------------------------------------------------
    def advance(self):
        """One fleet iteration: respawn replicas whose restart backoff
        elapsed, detect deaths and fail their requests over, advance
        every live replica one engine step (lockstep), harvest
        completions, pump page handoffs, run the health sweep and the
        autoscaler on their cadences."""
        if self._frontend is not None:
            # HTTP arrivals enter the deterministic clock HERE, in FIFO
            # mailbox order — handler threads never touch the fleet
            self._frontend.drain(self)
        self._supervise_tick()
        for rid, rep in sorted(self._replicas.items()):
            if not rep.alive and rid not in self._failed:
                self._fail_replica(rid)
        if not self._alive():
            if self._supervised and self.supervisor.pending():
                # every incarnation is down but restarts are scheduled:
                # this step only advances the backoff clock
                self._iteration += 1
                return
            raise RuntimeError(
                "fleet: every replica is dead — nothing left to serve "
                "the backlog")
        self._update_degraded()
        if self.fcfg.disaggregate and self.busy:
            for role in ("prefill", "decode"):
                if self._alive((role,)):
                    continue
                if role == "prefill" and self.degraded:
                    continue     # decode replicas are covering prefill
                if self._supervised and \
                        self.supervisor.pending((role, "full")):
                    continue     # a restart is due: wait, don't brick
                # a one-sided fleet can neither prefill nor finish and
                # nothing is coming back: fail loudly (containment =
                # partial snapshot + restart) instead of spinning on a
                # stalled backlog
                raise RuntimeError(
                    f"fleet: disaggregated fleet lost every {role} "
                    "replica — in-flight work cannot complete")
        self._redispatch_orphans()
        if self.rolling is not None and not self.rolling.done:
            self.rolling.tick(self)
        handoff_ready = []   # [(rid, id)] from process replicas
        for rid in self._alive():
            rep = self._replicas[rid]
            if rep.backend == "inprocess":
                try:
                    rep.advance()
                except Exception as e:   # ds-tpu: lint-ok[PY001] — the
                    # supervision boundary: ANY engine fault mid-advance
                    # (the ReplicaCrash chaos hook or a real XLA/host
                    # error) is one replica's death, not the fleet's
                    if not self._supervised:
                        raise    # PR-12 semantics: in-process crashes
                                 # are fatal without supervision
                    # contain it: the crashed engine is discarded
                    # wholesale (state untrustworthy), its requests fail
                    # over with tokens retained, and supervision decides
                    # restart vs crash-loop retirement
                    rep.alive = False
                    log_dist(f"fleet: replica {rid} crashed mid-advance "
                             f"({type(e).__name__}: {e}) — containing",
                             ranks=[0])
                    self._fail_replica(rid)
                    continue
            else:
                try:
                    reply = rep.advance()
                except ReplicaDead:
                    continue     # detected at the top of the next step
                except RuntimeError as e:
                    # the worker answered the advance op with a typed
                    # error reply: its ENGINE faulted mid-step (the pipe
                    # itself is fine, but the engine state is suspect) —
                    # one replica's fault must not kill the fleet loop
                    if not self._supervised:
                        raise
                    rep.alive = False
                    log_dist(f"fleet: replica {rid} advance failed "
                             f"({e}) — containing", ranks=[0])
                    self._fail_replica(rid)
                    continue
                self._apply_worker_reply(rid, reply)
                handoff_ready.extend((rid, hid)
                                     for hid in reply.get("handoff_ready",
                                                          []))
        self._record_admissions()
        self._harvest_local()
        self._pump_handoffs(handoff_ready)
        if self._iteration % self.fcfg.health_every_steps == 0:
            self._health_sweep()
        if self._scaler is not None and \
                self._iteration % self.fcfg.autoscale_every_steps == 0:
            self._autoscale_tick()
        if self._aggregator is not None and \
                self._iteration % self.fcfg.aggregate_every_steps == 0:
            # off-thread: a wedged replica endpoint (scrape timeout x
            # retry) must never stall the dispatch/harvest data plane
            self._aggregator.poll_async()
        if self.slo_watch is not None and \
                self._iteration % self.fcfg.aggregate_every_steps == 0:
            for rec in self.slo_watch.evaluate(self.slo_sample(),
                                               self._iteration):
                self.recorder.record(f"slo_{rec['event']}",
                                     iteration=self._iteration,
                                     rule=rec["rule"])
                log_dist(f"fleet: slo {rec['event']} rule="
                         f"{rec['rule']} step={rec['step']}", ranks=[0])
        self._iteration += 1

    @property
    def iteration(self) -> int:
        """Fleet step counter — the deterministic clock traces replay
        against (the fleet mirror of ``ServingEngine.iteration``)."""
        return self._iteration

    @property
    def busy(self) -> bool:
        return (bool(self._handles) or bool(self._handoff_backlog)
                or bool(self._orphans))

    # -- supervision (restart, backoff, crash-loop, degraded mode) ---------
    def _supervise_tick(self):
        """Spawn every lineage whose restart backoff elapsed. A spawn
        that fails (a worker that dies at init, say) reports straight
        back to the supervisor — it counts as another crash, so a
        deterministic init-crasher backs off and eventually retires
        instead of spinning the fleet step."""
        if not self._supervised:
            return
        for lid, role in self.supervisor.take_due(self._iteration):
            try:
                rep = self._spawn_replica(role=role, lineage=lid)
            except Exception as e:   # ds-tpu: lint-ok[PY001] — a failed
                # respawn must feed the crash-loop detector, never kill
                # the fleet step serving the survivors
                verdict = self.supervisor.on_death(lid, self._iteration)
                if verdict == "retired":
                    self._note_crash_loop_retirement(lid, role)
                log_dist(f"fleet: restart of lineage {lid} ({role}) "
                         f"failed ({e}) — {verdict}", ranks=[0])
                continue
            self.replica_restarts += 1
            get_registry().counter("fleet/replica_restarts").inc()
            self.recorder.record("replica_restarted",
                                 replica_id=rep.replica_id,
                                 iteration=self._iteration, lineage=lid)
            log_dist(f"fleet: supervision respawned lineage {lid} as "
                     f"replica {rep.replica_id} ({role})", ranks=[0])

    def _note_crash_loop_retirement(self, lid: int, role: str):
        self.replicas_retired += 1
        get_registry().counter("fleet/replicas_retired").inc()
        self.recorder.record("replica_retired", replica_id=None,
                             iteration=self._iteration, lineage=lid,
                             crash_loop=True)
        log_dist(f"fleet: lineage {lid} ({role}) crash-looped "
                 f"(> {self.scfg.max_restarts} deaths within "
                 f"{self.scfg.crash_window_steps} steps) — permanently "
                 "retired; serving continues on the survivors",
                 ranks=[0])

    def _update_degraded(self):
        """Degraded disaggregation: when the prefill pool empties while
        decode replicas survive, submissions run end-to-end on decode
        replicas (their own chunked prefill) instead of stranding the
        queue; exits automatically the step a prefill replica returns."""
        if not (self.fcfg.disaggregate and self._supervised):
            return
        prefill = self._alive(("prefill",))
        decode = self._alive(("decode",))
        if not self.degraded and not prefill and decode:
            self.degraded = True
            self.degraded_entered += 1
            get_registry().gauge("fleet/degraded_mode").set(1)
            get_registry().counter("fleet/degraded_entered").inc()
            self.recorder.record("degraded_enter",
                                 iteration=self._iteration)
            log_dist("fleet: prefill pool empty — degraded mode: decode "
                     "replicas run their own chunked prefill until a "
                     "prefill replica returns", ranks=[0])
        elif self.degraded and prefill:
            self.degraded = False
            get_registry().gauge("fleet/degraded_mode").set(0)
            self.recorder.record("degraded_exit",
                                 iteration=self._iteration)
            log_dist("fleet: prefill replica back — leaving degraded "
                     "mode", ranks=[0])

    def _redispatch_orphans(self):
        """Re-dispatch requests that were parked with no dispatchable
        replica (FIFO on the fleet clock — deterministic re-admission
        through the ordinary router/failover path, tokens retained)."""
        while self._orphans:
            eligible = self._dispatchable(
                self._alive(self._submit_roles()))
            if not eligible:
                return
            handle = self._orphans.popleft()
            if handle.done:
                continue
            target = self.router.route(
                handle.effective_prompt(), self._stats(eligible),
                step=self._iteration, request_id=handle.request_id)
            self.dispatch_log.append((handle.request_id, target))
            del self.dispatch_log[:-LOG_LIMIT]
            self._dispatch(handle, target, handle.effective_prompt(),
                           handle.remaining_budget())

    def run(self, max_iterations: Optional[int] = None):
        it = 0
        while self.busy:
            self.advance()
            it += 1
            if max_iterations is not None and it >= max_iterations:
                break

    def _record_admissions(self):
        """Stamp the fleet-clock admit mark for handles whose replica
        admitted them this step (in-process: the inner request
        transitioned out of the queue during ``rep.advance()``; process
        replicas report admitted ids in their advance reply). First
        admission only — the waterfall's queue stage ends exactly
        once."""
        for handle in self._handles.values():
            inner = handle._inner
            if (handle.admitted_iteration is None and inner is not None
                    and inner.admitted_iteration is not None):
                self._mark_admitted(handle)

    def _mark_admitted(self, handle: FleetRequest):
        handle.admitted_iteration = self._iteration
        self.recorder.record("admit", request_id=handle.request_id,
                             trace_id=handle.trace_id,
                             replica_id=handle.replica_id,
                             iteration=self._iteration)

    # -- harvest -----------------------------------------------------------
    def _harvest_local(self):
        for handle in list(self._handles.values()):
            inner = handle._inner
            if inner is not None and inner.done:
                self._finalize(handle, inner.status, inner.shed_reason)

    def _apply_worker_reply(self, rid: int, reply: dict):
        for hid in reply.get("admitted", []):
            handle = self._handles.get(hid)
            if (handle is not None and handle.replica_id == rid
                    and handle.admitted_iteration is None):
                self._mark_admitted(handle)
        for hid, token, _it in reply.get("events", []):
            handle = self._handles.get(hid)
            if handle is None or handle.replica_id != rid:
                continue
            if handle.first_token_at is None:
                handle.first_token_at = time.perf_counter()
                handle.first_token_iteration = self._iteration
                self.recorder.record("first_token", request_id=hid,
                                     trace_id=handle.trace_id,
                                     replica_id=rid,
                                     iteration=self._iteration)
            handle.tokens.append(int(token))
            if handle.on_token is not None:
                handle.on_token(handle, int(token))
        for rec in reply.get("finished", []):
            handle = self._handles.get(rec["id"])
            if handle is not None and handle.replica_id == rid:
                self._finalize(handle, rec["status"],
                               rec.get("shed_reason"))

    # -- disaggregated handoff pump ---------------------------------------
    def _stage_handoff(self, payload: dict, handle):
        """Queue one exported payload for injection. The integrity
        digest is stamped HERE for the in-process path (remote exports
        arrive digest-verified off the wire), so every staged payload
        is verifiable at injection time. Chaos hooks model transit
        damage: a truncated blob, or a single flipped byte the v3
        digest alone can catch."""
        if "digest" not in payload:
            stamp_handoff(payload)
        if self.chaos_corrupt_handoffs > 0:
            self.chaos_corrupt_handoffs -= 1
            blob = serialize_handoff(payload)
            payload = {"_truncated": blob[:max(8, len(blob) // 3)],
                       "request": payload["request"]}
        elif self.chaos_flip_handoff_bits > 0 and payload.get("kv"):
            self.chaos_flip_handoff_bits -= 1
            payload = dict(payload)
            payload["kv"] = [dict(rec) for rec in payload["kv"]]
            rec = payload["kv"][0]
            name = sorted(rec)[0]
            arr = np.ascontiguousarray(rec[name]).copy()
            arr.view(np.uint8).flat[0] ^= 0xFF   # the flipped bit
            rec[name] = arr
        if self.fedcfg is not None \
                and self.fedcfg.outbound_queue_limit > 0:
            # backpressure: a wedged/starved decode pool must cost
            # bounded memory — past the bound the OLDEST staged payload
            # is dropped and its request re-prefills through failover
            while len(self._handoff_backlog) >= \
                    self.fedcfg.outbound_queue_limit:
                oldest = self._handoff_backlog.popleft()
                self.handoffs_dropped += 1
                get_registry().counter("fleet/handoffs_dropped").inc()
                old_handle = oldest["handle"]
                log_dist(
                    "fleet: outbound handoff queue over "
                    f"{self.fedcfg.outbound_queue_limit} entries — "
                    "dropping the oldest payload "
                    f"({oldest['payload'].get('request', {}).get('request_id')!r}) "
                    "and re-prefilling through failover", ranks=[0])
                if old_handle is not None and not old_handle.done:
                    self._failover(old_handle)
        self._handoff_backlog.append(
            {"payload": payload, "handle": handle, "attempts": 0,
             "not_before": 0, "exported_at": self._iteration})

    def _pump_handoffs(self, process_ready):
        """Export every staged prefill and inject into the least-loaded
        dispatch-healthy decode replica. Backlog discipline
        (deterministic — FIFO on the fleet clock):

        - page/slot STARVATION on the target is backpressure, not a
          failure: the payload retries next step, unbudgeted;
        - injection ERRORS (corrupt payload, dead replica, worker error
          reply) are retried with exponential fleet-step backoff and a
          bounded budget (``supervision.handoff_max_retries``); past it
          the payload is dropped and the request re-prefills through
          the ordinary failover path — tokens retained, token-exact,
          never stranded."""
        for rid in self._alive(("prefill",)):
            rep = self._replicas[rid]
            if rep.backend != "inprocess":
                continue
            for slot, req in rep.take_handoff_ready():
                handle = self._handles.get(req.request_id)
                payload = rep.export_handoff(slot, req)
                if handle is not None:
                    handle.replica_id = None       # in transit
                self._record_handoff_export(payload, rid)
                self._stage_handoff(payload, handle)
        for rid, hid in process_ready:
            rep = self._replicas[rid]
            if not rep.alive:
                continue
            handle = self._handles.get(hid)
            try:
                payload = rep.export_handoff_by_id(hid)
            except ReplicaDead:
                continue       # the death sweep requeues from the handle
            except (HandoffError, RuntimeError, ValueError) as e:
                # the export failed without killing the pipe: a torn
                # blob (HandoffError/binascii), or the worker's op_export
                # faulted and answered with a typed error reply
                # (RuntimeError). The staged state is gone either way —
                # nothing to retry; re-prefill the request elsewhere
                # rather than letting one replica's fault crash the
                # fleet loop
                log_dist(f"fleet: handoff export from replica {rid} "
                         f"failed ({e}) — failing the request over",
                         ranks=[0])
                self._count_if_digest_reject(e)
                self.handoffs_dropped += 1
                get_registry().counter("fleet/handoffs_dropped").inc()
                if handle is not None and not handle.done:
                    self._failover(handle)
                continue
            if handle is not None:
                handle.replica_id = None
            self._record_handoff_export(payload, rid)
            self._stage_handoff(payload, handle)
        retry = deque()
        while self._handoff_backlog:
            ent = self._handoff_backlog.popleft()
            if ent["not_before"] > self._iteration:
                retry.append(ent)       # still backing off
                continue
            payload, handle = ent["payload"], ent["handle"]
            if handle is not None and handle.done:
                continue    # finished via an earlier (ambiguously
                            # reported) injection: nothing left to send
            decode = self._dispatchable(self._alive(("decode",)))
            # refresh load per injection: a burst of handoffs must fan
            # out across decode replicas, not pile onto one snapshot
            target = self.router.pick_least_loaded(self._stats(decode)) \
                if decode else None
            if target is None:
                retry.append(ent)       # no target yet: wait, free
                continue
            rep = self._replicas[target]
            error = None
            try:
                accepted = self._inject(rep, payload, handle)
            except (HandoffError, ReplicaDead, RuntimeError,
                    ValueError) as e:
                accepted, error = False, e
            if accepted:
                src = (handle.prefill_replica_id if handle is not None
                       else None)
                hid = payload["request"]["request_id"]
                self.handoffs_completed += 1
                self.handoff_log.append((hid, src, target))
                del self.handoff_log[:-LOG_LIMIT]
                self.recorder.record(
                    "handoff_inject", request_id=hid,
                    trace_id=payload["request"].get("trace_id"),
                    replica_id=target, iteration=self._iteration,
                    src=src)
                # the waterfall's wire stage, as a fleet-level
                # histogram: steps from export to accepted injection
                get_registry().histogram("fleet/wire_rtt").observe(
                    self._iteration - ent.get("exported_at",
                                              self._iteration))
                if handle is not None:
                    handle.replica_id = target
                    handle.handoffs += 1
                    handle.weights_version = getattr(
                        self._replicas[target], "weights_version", 0)
                continue
            if error is None:
                retry.append(ent)       # starvation: retry next step
                continue
            self._count_if_digest_reject(error)
            ent["attempts"] += 1
            self.handoff_retries += 1
            get_registry().counter("fleet/handoff_retries").inc()
            hid = payload["request"]["request_id"]
            if ent["attempts"] > self.scfg.handoff_max_retries:
                self.handoffs_dropped += 1
                get_registry().counter("fleet/handoffs_dropped").inc()
                self.recorder.record(
                    "handoff_dropped", request_id=hid,
                    trace_id=payload["request"].get("trace_id"),
                    iteration=self._iteration,
                    attempts=ent["attempts"], error=str(error))
                log_dist(f"fleet: handoff for {hid!r} dropped after "
                         f"{ent['attempts']} failed injections "
                         f"({error}) — re-prefilling through failover",
                         ranks=[0])
                if handle is not None and not handle.done:
                    self._failover(handle)
                continue
            ent["not_before"] = self._iteration + \
                self.scfg.handoff_retry_delay_steps(ent["attempts"])
            retry.append(ent)
        self._handoff_backlog = retry

    def _count_if_digest_reject(self, e) -> None:
        """Count an integrity-gate rejection. Covers BOTH paths a
        digest mismatch surfaces on: a local ``verify_handoff`` raise
        (``HandoffError.kind == "digest"``) and a REMOTE worker's
        refusal, which crosses the wire as a typed error reply and
        re-raises here as RuntimeError carrying the stable message
        token."""
        if getattr(e, "kind", None) == "digest" \
                or "handoff digest mismatch" in str(e):
            self.handoffs_rejected_corrupt += 1
            get_registry().counter(
                "fleet/handoffs_rejected_corrupt").inc()

    def _record_handoff_export(self, payload: dict, src_rid: int):
        self.recorder.record(
            "handoff_export",
            request_id=payload["request"]["request_id"],
            trace_id=payload["request"].get("trace_id"),
            replica_id=src_rid, iteration=self._iteration,
            prefill_len=int(payload["prefill_len"]))

    def _inject(self, rep, payload, handle) -> bool:
        blob = payload.get("_truncated")
        if blob is not None:
            # chaos-corrupted in transit: decoding raises the named
            # HandoffError exactly as a real torn wire transfer would
            payload = deserialize_handoff(blob)
        # the pre-injection integrity gate: a payload whose bits
        # changed since export (wire, staging, at rest) raises the
        # named HandoffError(kind="digest") — a flipped bit NEVER
        # enters a KV pool (remote targets re-verify on their side too)
        verify_handoff(payload)
        if rep.backend == "inprocess":
            live = rep.inject_handoff(
                payload, on_token=(self._on_token_cb(handle)
                                   if handle is not None else None))
            if live is None:
                return False
            if handle is not None:
                handle._inner = live
            return True
        return rep.inject_handoff(payload)

    # -- failure containment ----------------------------------------------
    def _health_sweep(self):
        """Cadenced probe (every ``health_every_steps``): a hard death
        (process exit, kill) fails over immediately; a wedged-but-alive
        process replica (live pid, dead /healthz) accumulates misses and
        fails over after ``max_missed_health`` consecutive ones."""
        for rid in list(self._alive()):
            rep = self._replicas[rid]
            state = rep.probe_health()
            if state == "ok":
                rep.missed_health = 0
                continue
            if state == "dead":
                self._fail_replica(rid)
                continue
            rep.missed_health += 1
            if rep.missed_health >= self.fcfg.max_missed_health:
                rep.alive = False
                self._fail_replica(rid)

    def _fail_replica(self, rid: int):
        """Dead-replica containment — the fleet-level mirror of
        ``engine.recover()``: forget its router affinity, requeue every
        request it owned through the router with generated tokens
        RETAINED (the continuation re-prefills prompt + partial output
        elsewhere — token-exact under greedy sampling, the PR-10 resume
        guarantee), and reap the corpse."""
        rep = self._replicas[rid]
        rep.alive = False
        self._failed.add(rid)
        self.dead_replicas += 1
        self.router.forget_replica(rid)
        if self._aggregator is not None:
            self._aggregator.mark_dead(rid)
        self.recorder.record("replica_dead", replica_id=rid,
                             iteration=self._iteration)
        # hand the death to the supervision policy FIRST — restart after
        # backoff, or permanent retirement on a crash loop — so the
        # failovers below can park on the pending restart when this was
        # the last live replica instead of declaring total loss
        lid = self._lineage.pop(rid, None)
        if self._supervised and lid is not None:
            verdict = self.supervisor.on_death(lid, self._iteration)
            if verdict == "retired":
                self._note_crash_loop_retirement(lid, rep.role)
        # reap the corpse BEFORE failing its work over: kill() drains
        # the worker's partial-metrics line and closes the pipe fds, and
        # a total-loss RuntimeError out of the failover below must not
        # leave a zombie (or lose the partial snapshot)
        try:
            rep.kill()
        except Exception:   # ds-tpu: lint-ok[PY001] — reaping a corpse
            # must never take the fleet down with it
            pass
        victims = [h for h in self._handles.values()
                   if h.replica_id == rid and not h.done]
        for handle in victims:
            self._failover(handle)
        self._prune_dead()
        log_dist(f"fleet: replica {rid} dead — {len(victims)} requests "
                 "requeued through the router", ranks=[0])

    def _prune_dead(self):
        """Trim the corpse history to ``DEAD_REPLICAS_KEPT``: the most
        recent dead replicas stay in ``self._replicas`` (snapshots read
        their metrics and partial snapshots), everything older is
        dropped from the replica map, the failed set, the lineage map,
        and the aggregator."""
        dead = [rid for rid, rep in sorted(self._replicas.items())
                if not rep.alive]
        for rid in dead[:max(0, len(dead) - DEAD_REPLICAS_KEPT)]:
            rep = self._replicas.pop(rid, None)
            # the pruned corpse's protocol-error count rolls into the
            # carried total so snapshot()'s counter never goes DOWN
            self._protocol_errors_pruned += getattr(
                rep, "protocol_errors", 0)
            self._stale_fence_pruned[0] += getattr(
                rep, "stale_epoch_replies", 0)
            self._stale_fence_pruned[1] += getattr(
                rep, "duplicate_replies", 0)
            self._failed.discard(rid)
            self._lineage.pop(rid, None)
            if self._aggregator is not None:
                self._aggregator.forget(rid)

    def _failover(self, handle: FleetRequest):
        """Re-dispatch one orphaned request: continuation = original
        prompt + retained tokens, budget = what is still owed."""
        handle.failovers += 1
        handle.preemptions += 1
        self.failovers += 1
        handle._inner = None
        self.recorder.record("failover", request_id=handle.request_id,
                             trace_id=handle.trace_id,
                             replica_id=handle.replica_id,
                             iteration=self._iteration,
                             tokens_retained=len(handle.tokens))
        remaining = handle.remaining_budget()
        if remaining <= 0:          # owed nothing more: call it finished
            self._finalize(handle, "finished")
            return
        eligible = self._dispatchable(self._alive(self._submit_roles()))
        if not eligible:
            if self._can_wait_for_capacity():
                self._park(handle)
                return
            raise RuntimeError(
                "fleet: no live replica left to fail requests over to")
        target = self.router.route(
            handle.effective_prompt(), self._stats(eligible),
            step=self._iteration, request_id=handle.request_id)
        self.dispatch_log.append((handle.request_id, target))
        del self.dispatch_log[:-LOG_LIMIT]
        self._dispatch(handle, target, handle.effective_prompt(),
                       remaining)

    # -- closed-loop autoscaling ------------------------------------------
    def _autoscale_tick(self):
        """Publish fleet totals as the gauges the autoscaler reads, then
        ACT on its recommendation: spawn replicas toward
        ``target_replicas`` on sustained backlog, retire one (drained
        via the preemption/slot-cap path) on sustained idleness."""
        alive = self._alive()
        stats = self._stats(alive)
        reg = self._scale_registry
        reg.gauge("serving/queue_depth").set(
            sum(s.queue_depth for s in stats))
        reg.gauge("serving/active_slots").set(
            sum(s.active_slots for s in stats))
        reg.gauge("serving/slot_cap").set(
            sum(s.slot_cap for s in stats))
        decision = self._scaler.observe()
        self.last_scale_decision = decision
        if decision["action"] == "scale_up":
            target = min(decision["target_replicas"],
                         self.fcfg.max_replicas)
            while len(self._alive()) < target:
                rep = self._spawn_replica()
                log_dist(f"fleet: scale-up -> spawned replica "
                         f"{rep.replica_id} ({decision['reason']})",
                         ranks=[0])
        elif decision["action"] == "scale_down":
            if len(alive) > self.fcfg.min_replicas:
                rid = self._pick_retirable(alive)
                if rid is not None:
                    self._retire_replica(rid)

    def _pick_retirable(self, alive):
        """Highest-id replica whose removal keeps the fleet serviceable.
        Disaggregated fleets are role-aware: only a role with >= 2 live
        members may shrink (losing the last decode — or prefill —
        replica bricks the fleet regardless of the total count), decode
        capacity drains before prefill (autoscale spawns rejoin as
        decode). None = nothing is safely retirable."""
        if not self.fcfg.disaggregate:
            return max(alive)
        by_role = {}
        for rid in alive:
            by_role.setdefault(self._replicas[rid].role, []).append(rid)
        for role in ("decode", "full", "prefill"):
            rids = by_role.get(role, [])
            if len(rids) > 1:
                return max(rids)
        return None

    def pick_disposable_replica(self) -> int:
        """The chaos/retire victim selector the kill hooks share: the
        highest-id live replica whose death the fleet can absorb
        (role-aware under disaggregation); falls back to the highest id
        when nothing is safely disposable — the caller asked for a
        kill, so a bricking kill is honored loudly rather than
        silently skipped."""
        alive = self._alive()
        rid = self._pick_retirable(alive)
        return rid if rid is not None else max(alive)

    def _retire_replica(self, rid: int):
        """Graceful scale-down: drain the replica through the PR-10
        preemption/slot-cap path (active requests preempted with tokens
        retained), re-dispatch everything it still owns through the
        router, then stop it."""
        rep = self._replicas[rid]
        if rep.backend == "inprocess":
            rep.engine.set_slot_cap(1)      # preemption-path drain
        victims = [h for h in self._handles.values()
                   if h.replica_id == rid and not h.done]
        rep.alive = False                   # no more routing to it
        self._failed.add(rid)               # failover already handled here
        # a deliberate drain is not a crash: the supervisor must neither
        # respawn this lineage nor count it toward a crash loop
        self.supervisor.deregister(self._lineage.pop(rid, None))
        self.router.forget_replica(rid)
        if self._aggregator is not None:
            self._aggregator.mark_dead(rid)
        self.recorder.record("replica_retired", replica_id=rid,
                             iteration=self._iteration)
        for handle in victims:
            self._failover(handle)
        rep.stop()
        self.replicas_retired += 1
        self._prune_dead()
        log_dist(f"fleet: scale-down -> retired replica {rid} "
                 f"({len(victims)} requests re-dispatched)", ranks=[0])

    # -- federation: HTTP front-end + rolling updates ----------------------
    def attach_frontend(self, frontend):
        """Wire a ``FleetFrontend``: its mailbox drains into ``submit``
        at the top of every ``advance()`` (dispatch thread only — the
        HTTP handler threads never touch the fleet)."""
        self._frontend = frontend
        return frontend

    def start_rolling_update(self, *, checkpoint: Optional[str] = None,
                             module=None, params=None,
                             spec_update: Optional[dict] = None,
                             verify: Optional[bool] = None):
        """Begin a zero-downtime rolling weight update (federation/
        rolling.py): manifest-verify the target, then drain -> swap ->
        rejoin one replica per fleet step until the whole fleet serves
        the new weights. Progress rides ``advance()``; the returned
        ``RollingUpdate`` exposes ``done``/``snapshot()``."""
        from .federation.rolling import RollingUpdate, RollingUpdateError
        if self.rolling is not None and not self.rolling.done:
            raise RollingUpdateError(
                "a rolling update is already in progress "
                f"(v{self.rolling.version}, "
                f"{len(self.rolling.swapped)}/{len(self.rolling.order)} "
                "swapped)")
        fed = self.fedcfg
        if verify is None:
            verify = fed.rolling_verify if fed is not None else True
        drain_cap = fed.rolling_drain_slot_cap if fed is not None else 1
        self.rolling = RollingUpdate(
            self, checkpoint=checkpoint, module=module, params=params,
            spec_update=spec_update, verify=verify,
            drain_slot_cap=drain_cap)
        return self.rolling

    # -- telemetry ---------------------------------------------------------
    def per_request_breakdown(self, include_requests: bool = True) -> dict:
        """The per-request latency waterfall (observability/fleet.py):
        queue -> prefill -> handoff -> wire -> decode stage steps per
        traced request plus per-stage p50/p95 — stage sums telescope
        exactly to each request's end-to-end fleet steps. Derived from
        the flight recorder, so it covers the last-N completed
        requests."""
        return per_request_breakdown(self.recorder.events,
                                     include_requests=include_requests)

    def slo_sample(self) -> dict:
        """The merged sample the SLO watch judges (observability/
        slo.py), built from the fleet's own books on the step clock —
        every value is deterministic given the same request trace. An
        absent key (no completed requests yet, no remote peers) reads
        as "ok" for its rule."""
        sample = {}
        bd = self.per_request_breakdown(include_requests=True)
        # TTFT in fleet steps = submit->first_token = queue + prefill
        waits = [row["queue"] + row["prefill"]
                 for row in (bd.get("requests") or {}).values()]
        if waits:
            sample["ttft_p95_steps"] = float(percentile(waits, 95))
        if self.requests_submitted:
            sample["shed_rate"] = (self.requests_shed
                                   / self.requests_submitted)
        if self._replicas:
            sample["replica_up_fraction"] = (len(self._alive())
                                             / len(self._replicas))
        attempts = self.handoffs_completed + self.handoff_retries
        if attempts:
            sample["corrupt_handoff_rate"] = (
                self.handoffs_rejected_corrupt / attempts)
        # dispatch->reply RTT pooled across every remote peer's
        # sliding window (the wire accountant's histograms)
        rtts = []
        for name, hist in get_registry()._hists.items():
            if name.startswith("wire/rtt_ms/"):
                rtts.extend(hist.window)
        if rtts:
            sample["wire_rtt_p95_ms"] = float(percentile(rtts, 95))
        return sample

    def snapshot(self) -> dict:
        """The fleet section of /statusz: per-replica stats + serving
        snapshots, router policy/decisions, handoff + failover + scaling
        counters, the aggregated telemetry view, the flight-recorder
        timeline, and the per-request waterfall. Host state only."""
        replicas = {}
        for rid, rep in sorted(self._replicas.items()):
            entry = {"role": rep.role, "alive": rep.alive,
                     **rep.stats().to_dict()}
            if rep.backend == "inprocess":
                # a dead engine's host-side metrics stay readable: the
                # work it served before dying must not vanish from the
                # per-replica breakdown (or the kill-run bench block)
                entry["serving"] = rep.engine.metrics.snapshot()
            entry["telemetry_port"] = rep.telemetry_port
            entry["lineage"] = self._lineage.get(rid)
            pm = getattr(rep, "last_partial_metrics", None)
            if pm is not None:
                # the worker's SIGTERM snapshot: what a supervised
                # teardown managed to say on its way down
                entry["partial_metrics"] = pm
            replicas[str(rid)] = entry
        out = {
            "iteration": self._iteration,
            "backend": self.fcfg.backend,
            "disaggregate": self.fcfg.disaggregate,
            "degraded_mode": self.degraded,
            "degraded_entered": self.degraded_entered,
            "replicas": replicas,
            "router": self.router.stats(),
            "handoffs_in_transit": len(self._handoff_backlog),
            "handoffs_completed": self.handoffs_completed,
            "handoff_retries": self.handoff_retries,
            "handoffs_dropped": self.handoffs_dropped,
            "failovers": self.failovers,
            "dead_replicas": self.dead_replicas,
            "replicas_spawned": self.replicas_spawned,
            "replicas_retired": self.replicas_retired,
            "replica_restarts": self.replica_restarts,
            "requests_parked": len(self._orphans),
            "worker_protocol_errors": self._protocol_errors_pruned + sum(
                getattr(rep, "protocol_errors", 0)
                for rep in self._replicas.values()),
            "handoffs_rejected_corrupt": self.handoffs_rejected_corrupt,
            "stale_epoch_replies": self._stale_fence_pruned[0] + sum(
                getattr(rep, "stale_epoch_replies", 0)
                for rep in self._replicas.values()),
            "duplicate_replies": self._stale_fence_pruned[1] + sum(
                getattr(rep, "duplicate_replies", 0)
                for rep in self._replicas.values()),
            "supervision": self.supervisor.snapshot(),
            "requests_submitted": self.requests_submitted,
            "requests_finished": self.requests_finished,
            "requests_shed": self.requests_shed,
            "remote_replicas": sum(
                1 for rep in self._replicas.values()
                if rep.backend == "remote" and rep.alive),
            "weights_version": self.weights_version,
            "rolling_updates": self.rolling_updates,
            "rolling_swaps": self.rolling_swaps,
            "rolling": (self.rolling.snapshot()
                        if self.rolling is not None else None),
            "draining": sorted(self._draining),
            "autoscale": self.last_scale_decision,
            "flight_recorder": self.recorder.snapshot(),
            "per_request_breakdown": self.per_request_breakdown(
                include_requests=False),
        }
        if self._aggregator is not None:
            out["telemetry"] = self._aggregator.snapshot()
        if self.slo_watch is not None:
            # rides every snapshot AND the crash path (the exit/crash
            # dumps call snapshot()), so open incidents survive a wreck
            out["slo"] = self.slo_watch.snapshot()
        if self._frontend is not None:
            out["frontend"] = self._frontend.snapshot()
        return out

    def metrics_snapshot(self) -> dict:
        """The router-level /statusz payload: the process registry plus
        the fleet section (observability/export.py renders it). The
        aggregator's per-replica up/staleness gauges and merged totals
        fold into the registry view, so the router's /metrics carries
        ``ds_tpu_fleet_replica_*`` and ``ds_tpu_fleet_merged_*``
        series — the fleet-wide scrape surface."""
        from ...observability.metrics import get_registry
        reg = get_registry().snapshot()
        if self._aggregator is not None:
            reg.setdefault("gauges", {}).update(self._aggregator.gauges())
        return {"registry": reg, "fleet": self.snapshot()}

    # -- fleet-wide trace stitching ----------------------------------------
    def trace_dumps(self):
        """Collect the per-lane Chrome-trace dumps: the router
        process's own active tracer (which, on the in-process backend,
        also holds every replica's spans — one process, one stream)
        plus each process replica's ``trace_dump`` (workers record when
        ``serving.fleet.replica_trace`` is on)."""
        from ...observability.trace import active_tracer, chrome_trace_events
        dumps = []
        tracer = active_tracer()
        if tracer is not None and tracer.events:
            dumps.append(("router", chrome_trace_events(tracer.events)))
        for rid, rep in sorted(self._replicas.items()):
            events = rep.trace_dump()
            if events:
                dumps.append((f"replica{rid}:{rep.role}", events))
        return dumps

    def stitched_trace(self) -> dict:
        """ONE Chrome trace for the whole fleet: one process lane per
        replica (plus the router), request spans joined across lanes by
        their ``args.trace_id``. Load it in chrome://tracing or
        Perfetto; ``breakdown_from_trace`` rebuilds the per-request
        waterfall from it."""
        from ...observability.fleet import stitch_chrome_traces
        return stitch_chrome_traces(self.trace_dumps())

    def write_stitched_trace(self, path: str) -> str:
        from ...observability.fleet import write_stitched_trace
        return write_stitched_trace(self.trace_dumps(), path)

    def start_telemetry(self, port: int = 0, host: str = "127.0.0.1"):
        """Router-level /metrics + /healthz + /statusz (the fleet
        section rides /statusz); per-replica endpoints are separate
        (``serving.fleet.replica_telemetry``)."""
        if self.telemetry is not None:
            return self.telemetry
        from ...observability.export import TelemetryServer
        self.telemetry = TelemetryServer(self.metrics_snapshot, host=host,
                                         port=port).start()
        log_dist(f"fleet telemetry: http://{host}:{self.telemetry.port}"
                 "/statusz", ranks=[0])
        return self.telemetry

    def close(self):
        if self._frontend is not None:
            f, self._frontend = self._frontend, None
            f.stop()
        if self.telemetry is not None:
            t, self.telemetry = self.telemetry, None
            t.stop()
        for rep in self._replicas.values():
            try:
                rep.stop()
            except Exception:   # ds-tpu: lint-ok[PY001] — teardown must
                # reach every replica even when one refuses to die
                pass
