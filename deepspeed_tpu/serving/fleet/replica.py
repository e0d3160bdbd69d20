"""Replica wrappers: one supervised ServingEngine, local or subprocess.

Two backends behind one narrow surface (``submit`` / ``advance`` /
``stats`` / ``healthy`` / handoff export+inject / ``stop``):

- ``LocalReplica`` — an in-process ``ServingEngine`` driven in lockstep
  on the fleet clock. The deterministic/CI path: stats are host ints
  read synchronously, tokens stream through ``on_token`` callbacks, and
  a replayed trace reproduces every dispatch bit-exactly.
- ``ProcessReplica`` — one worker subprocess (``fleet/worker.py``) per
  replica over a line-JSON pipe protocol, each with its own telemetry
  endpoint (``/metrics`` + ``/healthz`` on its own port — the PR-8
  plane, per process). Exchanges are synchronous request/response, so
  dispatch order stays deterministic; wall-clock effects enter only
  through process scheduling, which the protocol never consults.

Failure matrix (docs/serving.md "Multi-replica fleet"):

- a DETECTED dead replica (missed health checks, worker process exit,
  ``kill()``) is contained — the manager requeues its in-flight
  requests through the router, the fleet-level mirror of
  ``engine.recover()`` — and, under supervision
  (``serving.fleet.supervision``), a fresh incarnation respawns after
  exponential backoff;
- a pipe PROTOCOL failure (malformed or truncated frame, reply
  timeout) is a named ``WorkerProtocolError`` carrying the replica id:
  the pipe is desynchronized, so the replica is declared dead and the
  same containment + supervision path runs — raw decode errors never
  propagate into the fleet loop;
- an in-process ``ReplicaCrash`` out of ``advance()`` is recoverable
  under supervision: the crashed engine is discarded wholesale (its
  donated device buffers are untrustworthy), its requests fail over
  with tokens retained, and a FRESH engine respawns after backoff —
  reusing the process-global jit cache, so a restart never recompiles.
  With supervision disabled it stays fatal-by-design (partial fleet
  snapshot + nonzero exit), the pre-supervision PR-12 contract.
"""

import base64
import json
import os
import select
import subprocess
import sys
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from ...utils.logging import log_dist
from .handoff import deserialize_handoff, serialize_handoff

PROTOCOL_SENTINEL = "@fleet "


class ReplicaCrash(RuntimeError):
    """An in-process replica died mid-advance (chaos injection or a real
    engine fault). Under supervision the manager contains it — failover
    with tokens retained, then a fresh engine after backoff; with
    supervision disabled it is fatal (partial snapshot + nonzero
    exit)."""


class ReplicaDead(RuntimeError):
    """A process replica stopped answering the pipe protocol."""


class WorkerProtocolError(ReplicaDead):
    """The worker pipe protocol broke: a malformed or truncated frame,
    or a reply timeout. Subclasses ``ReplicaDead`` on purpose — a
    desynchronized pipe cannot be resynchronized, so every containment
    site treats it as a death and supervision takes over; the named
    type and ``replica_id``/``kind`` keep the failure attributable
    instead of a raw ``JSONDecodeError`` in the fleet loop."""

    def __init__(self, replica_id: int, kind: str, detail: str):
        self.replica_id = int(replica_id)
        self.kind = kind            # "timeout" | "malformed" | "truncated"
        super().__init__(f"replica {replica_id} worker protocol error "
                         f"({kind}): {detail}")


@dataclass
class ReplicaStats:
    """One replica's dispatch-relevant state, snapshotted on the fleet
    step clock — the same host ints its ``/metrics`` plane exports
    (queue-depth / active-slot gauges, per-class TTFT), read without the
    scrape race so routing replays bit-exactly."""
    replica_id: int
    alive: bool = True
    role: str = "full"
    iteration: int = 0
    queue_depth: int = 0
    active_slots: int = 0
    num_slots: int = 0
    slot_cap: int = 0
    free_slots: int = 0
    class_ttft_p95: Dict[str, float] = field(default_factory=dict)
    # Federation: the router-side view of a REMOTE peer's load, stamped
    # by the manager from its FleetTelemetryAggregator snapshot (scraped
    # off-step, read on-step — deterministic for a given scrape history).
    # None for local replicas and never serialized: the worker's own
    # stats reply has the authoritative synchronous numbers.
    scraped_load: Optional[float] = None

    def to_dict(self) -> dict:
        return {"replica_id": self.replica_id, "alive": self.alive,
                "role": self.role, "iteration": self.iteration,
                "queue_depth": self.queue_depth,
                "active_slots": self.active_slots,
                "num_slots": self.num_slots, "slot_cap": self.slot_cap,
                "free_slots": self.free_slots,
                "class_ttft_p95": dict(self.class_ttft_p95)}


def engine_stats(engine, replica_id: int, role: str,
                 alive: bool = True) -> ReplicaStats:
    """Build a ``ReplicaStats`` snapshot from a live engine's host
    state (shared by LocalReplica and the worker's stats reply)."""
    active = sum(r is not None for r in engine._slot_req)
    return ReplicaStats(
        replica_id=replica_id, alive=alive, role=role,
        iteration=engine.iteration,
        queue_depth=engine.scheduler.depth,
        active_slots=active,
        num_slots=engine.config.num_slots,
        slot_cap=engine.slot_cap,
        free_slots=engine.num_free_slots,
        class_ttft_p95={
            name: p95 for name in list(engine.metrics.per_class)
            if (p95 := engine.metrics.class_ttft_p95(name)) is not None})


class LocalReplica:
    """One in-process engine under fleet supervision."""

    backend = "inprocess"

    def __init__(self, replica_id: int, role: str, module, params, config,
                 *, telemetry: bool = False):
        from ..engine import ServingEngine
        self.replica_id = replica_id
        self.role = role
        self._config = config
        self._telemetry = telemetry
        self.engine = ServingEngine(module, params, config)
        if role == "prefill":
            self.engine.set_prefill_role(True)
        self.alive = True
        self.missed_health = 0
        self.weights_version = 0   # bumped by rolling updates
        self.fail_at: Optional[int] = None   # chaos: raise ReplicaCrash
                                             # once the clock passes this
        if telemetry:
            self.engine.start_telemetry(port=0)

    @property
    def telemetry_port(self) -> Optional[int]:
        t = self.engine.telemetry
        return t.port if t is not None else None

    def submit(self, prompt, max_new_tokens, request_id, priority=0,
               on_token=None, trace_id=None):
        return self.engine.submit(prompt, max_new_tokens,
                                  request_id=request_id, on_token=on_token,
                                  priority=priority, trace_id=trace_id)

    def advance(self):
        if self.fail_at is not None and \
                self.engine.iteration >= self.fail_at:
            self.alive = False
            raise ReplicaCrash(
                f"replica {self.replica_id} crashed at iteration "
                f"{self.engine.iteration} (injected)")
        self.engine.advance()

    def stats(self) -> ReplicaStats:
        return engine_stats(self.engine, self.replica_id, self.role,
                            self.alive)

    def healthy(self) -> bool:
        return self.alive

    def probe_health(self) -> str:
        """Health-sweep probe: an in-process replica is either alive or
        hard-dead (``kill()``) — there is no transient-miss state to
        count, so ``max_missed_health`` only governs scrape-probed
        process replicas."""
        return "ok" if self.alive else "dead"

    @property
    def busy(self) -> bool:
        return self.alive and self.engine.busy

    def trace_dump(self):
        """In-process replicas record into the ROUTER's tracer (one
        process, one span stream) — there is no per-replica dump; the
        stitcher gives the whole in-process fleet one lane."""
        return None

    def metrics_sample(self):
        """Direct host-dict snapshot for the telemetry aggregator (the
        in-process analog of a /metrics scrape). Keys are normalized to
        the SAME ``serving_*`` names a worker's scraped /metrics parses
        to, so `ds_tpu_fleet_merged_*` series keep one name space
        whichever backend serves them. Stays readable after death —
        the work a dead replica served must not vanish."""
        from ...observability.export import prometheus_name
        return {prometheus_name(f"serving/{k}", prefix=""): v
                for k, v in self.engine.metrics.snapshot().items()
                if isinstance(v, (int, float))}

    # -- handoff -----------------------------------------------------------
    def take_handoff_ready(self) -> List:
        return self.engine.take_handoff_ready()

    def export_handoff(self, slot, req) -> dict:
        return self.engine.export_handoff(slot, req)

    def inject_handoff(self, payload, request=None, on_token=None):
        return self.engine.inject_handoff(payload, request=request,
                                          on_token=on_token)

    # -- rolling updates ---------------------------------------------------
    def set_slot_cap(self, n: int):
        """The PR 10 drain lever, surfaced on the replica interface so
        rolling updates squeeze every backend the same way."""
        self.engine.set_slot_cap(int(n))

    def swap_weights(self, module, params):
        """Rolling update: replace the engine wholesale with one built
        from the new weights (same serving config, same role). Only
        legal on a DRAINED replica — the manager guarantees zero
        in-flight requests before calling."""
        from ..engine import ServingEngine
        had_telemetry = self.engine.telemetry is not None or self._telemetry
        self.engine.close()
        self.engine = ServingEngine(module, params, self._config)
        if self.role == "prefill":
            self.engine.set_prefill_role(True)
        if had_telemetry:
            self.engine.start_telemetry(port=0)
        self.weights_version += 1

    # -- lifecycle ---------------------------------------------------------
    def kill(self):
        """Simulated hard death (the failover test's hook): the manager
        sees ``healthy() == False`` on its next sweep and requeues."""
        self.alive = False
        self.engine.close()

    def stop(self):
        self.alive = False
        self.engine.close()


class ProcessReplica:
    """One worker subprocess speaking the fleet/worker.py line protocol.

    Every exchange is synchronous (send one op line, read its reply), so
    cross-replica dispatch ORDER is exactly the manager's call order.
    Worker stdout multiplexes engine logs and protocol lines; protocol
    lines carry the ``@fleet `` sentinel and everything else is passed
    through to this process's stdout untouched.
    """

    backend = "process"

    def __init__(self, replica_id: int, role: str, spec: dict, *,
                 reply_timeout_s: float = 120.0):
        self.replica_id = replica_id
        self.role = role
        self.alive = True
        self.missed_health = 0
        self.reply_timeout_s = reply_timeout_s
        self.telemetry_port: Optional[int] = None
        self.telemetry_host = "127.0.0.1"   # children bind loopback;
                                            # RemoteReplica overrides with
                                            # the host it dialed (bugfix:
                                            # scrape URLs were localhost-
                                            # only by assumption)
        self.weights_version = 0            # bumped by rolling updates
        self.protocol_errors = 0   # malformed/truncated frames + reply
                                   # timeouts observed on this pipe
        self.last_partial_metrics: Optional[dict] = None
                                   # the worker's SIGTERM snapshot (the
                                   # PR-4 emergency-save analog), drained
                                   # at kill time
        self._scrape = None   # cached MetricsScrapeClient (staleness
                              # stamps accumulate across probes)
        self._last_stats: Optional[ReplicaStats] = None
        self._inflight = 0    # submits since the last advance reply —
                              # folded into queue_depth so a same-step
                              # burst spreads instead of piling onto one
                              # stale snapshot
        # one process per chip: this parent has imported the fleet and
        # so holds whatever device JAX gave it. Workers get the parent's
        # platform explicitly; under a TPU parent they could only fail
        # or hang reaching the chip — or, worse, serve from CPU silently.
        import jax
        platform = jax.default_backend()
        if platform == "tpu":
            raise ValueError(
                "fleet backend 'process' cannot start under a parent whose "
                "JAX platform is tpu: the parent holds the chip, so worker "
                "subprocesses cannot reach it. Use FleetConfig(backend="
                "'inprocess') — one device per replica in this process.")
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = platform
        # binary pipes + an explicit byte buffer: select() watches the
        # raw fd, so a buffering text wrapper could strand a complete
        # reply line in userspace while select blocks on a drained fd
        self._buf = b""
        self._proc = subprocess.Popen(
            self._worker_argv(),
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.dirname(os.path.abspath(__file__))))))
        self._send({"op": "init", "replica_id": replica_id, "role": role,
                    **spec})
        ready = self._read_reply()
        self.telemetry_port = ready.get("telemetry_port")
        log_dist(f"fleet: replica {replica_id} worker pid "
                 f"{self._proc.pid} ready (role={role}, telemetry port "
                 f"{self.telemetry_port})", ranks=[0])

    @staticmethod
    def _worker_argv():
        """The worker subprocess command line — overridable so
        protocol/lifecycle tests can drive a stub worker without
        building an engine."""
        return [sys.executable, "-m", "deepspeed_tpu.serving.fleet.worker"]

    def _protocol_error(self, kind: str, detail: str):
        """Declare the pipe desynchronized: count it, mark the replica
        dead, raise the NAMED error supervision restarts on."""
        self.alive = False
        self.protocol_errors += 1
        from ...observability.metrics import get_registry
        get_registry().counter("fleet/worker_protocol_errors").inc()
        raise WorkerProtocolError(self.replica_id, kind, detail)

    # -- protocol plumbing -------------------------------------------------
    def _send(self, msg: dict):
        if self._proc.stdin is None or self._proc.poll() is not None:
            self.alive = False
            raise ReplicaDead(f"replica {self.replica_id} worker is gone")
        try:
            self._proc.stdin.write((json.dumps(msg) + "\n").encode("utf-8"))
            self._proc.stdin.flush()
        except (BrokenPipeError, OSError, ValueError) as e:
            # ValueError: write on a pipe a teardown branch already
            # closed — same verdict as a broken pipe
            self.alive = False
            raise ReplicaDead(
                f"replica {self.replica_id} pipe closed: {e}") from e

    def _read_line(self) -> bytes:
        """Next complete stdout line, buffered byte-wise (select on the
        raw fd + os.read — never a buffering reader that could strand a
        complete line in userspace while select blocks)."""
        fd = self._proc.stdout.fileno()
        while b"\n" not in self._buf:
            ready, _, _ = select.select([fd], [], [], self.reply_timeout_s)
            if not ready:
                self._protocol_error(
                    "timeout", f"worker silent past "
                    f"{self.reply_timeout_s}s (pid {self._proc.pid} "
                    "may be wedged)")
            chunk = os.read(fd, 1 << 16)
            if not chunk:                     # EOF — the worker died
                if self._buf:
                    # bytes stranded without a newline: the worker died
                    # MID-frame — a truncated frame, not a clean exit
                    self._protocol_error(
                        "truncated", f"worker exited mid-frame with "
                        f"{len(self._buf)} unterminated bytes "
                        f"(rc={self._proc.poll()})")
                self.alive = False
                raise ReplicaDead(
                    f"replica {self.replica_id} worker exited "
                    f"(rc={self._proc.poll()})")
            self._buf += chunk
        line, self._buf = self._buf.split(b"\n", 1)
        return line

    def _read_reply(self) -> dict:
        while True:
            line = self._read_line().decode("utf-8", "replace")
            if line.startswith(PROTOCOL_SENTINEL):
                try:
                    reply = json.loads(line[len(PROTOCOL_SENTINEL):])
                except ValueError:
                    self._protocol_error(
                        "malformed",
                        f"undecodable protocol frame: {line[:120]!r}")
                if reply.get("op") == "partial_metrics":
                    # out-of-band: the worker's SIGTERM handler shipped
                    # its partial snapshot — stash it and keep waiting
                    # for the actual reply
                    self.last_partial_metrics = reply
                    continue
                if reply.get("op") == "error":
                    raise RuntimeError(
                        f"replica {self.replica_id} worker error: "
                        f"{reply.get('detail')}")
                return reply
            sys.stdout.write(f"[replica {self.replica_id}] {line}\n")

    # -- the replica surface ----------------------------------------------
    def submit(self, prompt, max_new_tokens, request_id, priority=0,
               on_token=None, trace_id=None):
        """Forward one submission; token streaming arrives as events in
        later ``advance()`` replies (``on_token`` is ignored here — the
        manager applies events to its fleet handles). ``trace_id``
        crosses the pipe so the worker's spans join the fleet trace."""
        self._send({"op": "submit", "id": request_id,
                    "prompt": np.asarray(prompt, np.int32).tolist(),
                    "max_new_tokens": int(max_new_tokens),
                    "priority": int(priority),
                    "trace_id": trace_id})
        self._inflight += 1
        return self._read_reply()

    def advance(self) -> dict:
        """One lockstep engine iteration; the reply carries the step's
        token events, finished requests, staged handoff ids, and a fresh
        stats snapshot."""
        self._send({"op": "advance"})
        reply = self._read_reply()
        self._inflight = 0
        try:
            self._last_stats = ReplicaStats(
                replica_id=self.replica_id, alive=True, role=self.role,
                **reply["stats"])
        except (KeyError, TypeError) as e:
            # a structurally wrong advance reply is a protocol break,
            # not a crash in the fleet loop
            self._protocol_error(
                "malformed", f"advance reply missing/bad stats: {e}")
        return reply

    def stats(self) -> ReplicaStats:
        if self._last_stats is None or not self.alive:
            return ReplicaStats(replica_id=self.replica_id,
                                alive=self.alive, role=self.role,
                                queue_depth=self._inflight)
        s = self._last_stats
        if self._inflight:
            s = ReplicaStats(**{**s.to_dict()})
            s.queue_depth += self._inflight
        return s

    def healthy(self) -> bool:
        if not self.alive or self._proc.poll() is not None:
            self.alive = False
            return False
        return True

    @property
    def scrape_client(self):
        """Cached scrape client over this worker's telemetry endpoint
        (one client per replica so its ``last_success_unix`` staleness
        stamp accumulates across health sweeps and aggregator polls);
        None without a telemetry port."""
        if self.telemetry_port is None:
            return None
        if self._scrape is None:
            from ...observability.export import MetricsScrapeClient
            self._scrape = MetricsScrapeClient(
                f"http://{self.telemetry_host}:{self.telemetry_port}")
        return self._scrape

    def probe_health(self) -> str:
        """Health-sweep probe: a dead process (exit/kill/pipe loss) is
        ``"dead"`` immediately; a live worker whose telemetry endpoint
        stops answering ``/healthz`` is a ``"miss"`` — the sweep counts
        those against ``max_missed_health`` (a wedged worker can sit on
        a live pid forever). Without a telemetry port the pid is the
        only signal and a live one reads ``"ok"``."""
        if not self.healthy():
            return "dead"
        probe = self.scrape_client
        if probe is not None:
            return "ok" if probe.healthz() else "miss"
        return "ok"

    def trace_dump(self):
        """Pull the worker's recorded span stream (Chrome-trace event
        dicts) for stitching; [] when the worker records no spans or
        has died (a dead lane is simply absent from the stitched
        trace)."""
        try:
            self._send({"op": "trace_dump"})
            return self._read_reply().get("events") or []
        except (ReplicaDead, RuntimeError):
            return []

    def metrics_sample(self):
        """Aggregator source: parsed /metrics scrape, or None when the
        endpoint is unreachable/absent."""
        probe = self.scrape_client
        return probe.gauges() if probe is not None else None

    @property
    def busy(self) -> bool:
        s = self.stats()
        return self.alive and bool(s.queue_depth or s.active_slots)

    # -- handoff (payloads cross the pipe as base64 npz blobs) -------------
    def export_handoff_by_id(self, request_id) -> dict:
        self._send({"op": "export", "id": request_id})
        reply = self._read_reply()
        return deserialize_handoff(base64.b64decode(reply["blob"]))

    def inject_handoff(self, payload, request=None) -> bool:
        blob = base64.b64encode(serialize_handoff(payload)).decode("ascii")
        self._send({"op": "inject", "blob": blob})
        return bool(self._read_reply().get("accepted"))

    # -- rolling updates ---------------------------------------------------
    def set_slot_cap(self, n: int):
        self._send({"op": "slot_cap", "n": int(n)})
        self._read_reply()

    def swap_weights_spec(self, spec_update: dict):
        """Rolling update over the wire: the worker rebuilds its engine
        from its init spec merged with ``spec_update`` (new checkpoint
        or model seed). Returns the worker's fresh telemetry port (the
        old endpoint died with the old engine)."""
        self._send({"op": "swap", "spec": dict(spec_update)})
        reply = self._read_reply()
        self.telemetry_port = reply.get("telemetry_port")
        self._scrape = None          # the endpoint moved with the port
        self._last_stats = None
        self.weights_version += 1
        return self.telemetry_port

    # -- lifecycle ---------------------------------------------------------
    def _close_pipes(self):
        """Release both pipe fds — EVERY teardown branch must land here
        or repeated spawn/stop cycles leak two fds per replica."""
        for f in (self._proc.stdin, self._proc.stdout):
            try:
                if f is not None:
                    f.close()
            except OSError:
                pass

    def _drain_partial(self):
        """Best-effort read of whatever the worker flushed on its way
        down — the SIGTERM handler's ``partial_metrics`` line in
        particular. Never blocks past a beat; called after the process
        is already dead or dying."""
        if self._proc.stdout is None:
            return
        fd = self._proc.stdout.fileno()
        try:
            while True:
                ready, _, _ = select.select([fd], [], [], 0.2)
                if not ready:
                    break
                chunk = os.read(fd, 1 << 16)
                if not chunk:
                    break
                self._buf += chunk
        except OSError:
            pass
        while b"\n" in self._buf:
            line, self._buf = self._buf.split(b"\n", 1)
            text = line.decode("utf-8", "replace")
            if not text.startswith(PROTOCOL_SENTINEL):
                continue
            try:
                reply = json.loads(text[len(PROTOCOL_SENTINEL):])
            except ValueError:
                continue
            if reply.get("op") == "partial_metrics":
                self.last_partial_metrics = reply

    def _reap(self, grace_s: float = 10.0):
        """Wait the child out so no zombie survives; escalate to
        SIGKILL when the grace window runs dry."""
        try:
            self._proc.wait(timeout=grace_s)
        except subprocess.TimeoutExpired:
            self._proc.kill()
            try:
                self._proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass

    def kill(self):
        self.alive = False
        if self._proc.poll() is None:
            # SIGTERM first: the worker's PR-4-style handler gets one
            # beat to ship its partial metrics snapshot up the pipe
            self._proc.terminate()
            self._reap(grace_s=5)
        self._drain_partial()
        self._close_pipes()

    def stop(self):
        if self.alive and self._proc.poll() is None:
            try:
                self._send({"op": "stop"})
                self._proc.wait(timeout=30)
            except (ReplicaDead, subprocess.TimeoutExpired):
                self._proc.kill()
                self._reap()
        elif self._proc.poll() is None:
            # declared dead (protocol error) but the pid survives — a
            # wedged worker must not outlive its fleet
            self._proc.kill()
            self._reap()
        self.alive = False
        self._drain_partial()
        self._close_pipes()
