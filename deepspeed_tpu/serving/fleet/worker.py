"""Fleet worker: one ServingEngine subprocess on a line-JSON protocol.

``python -m deepspeed_tpu.serving.fleet.worker`` reads an ``init`` spec
line on stdin (serving config dict + model spec + role + optional
telemetry port), builds its engine, then serves ops until ``stop``:

    {"op": "submit", "id", "prompt", "max_new_tokens", "priority"}
    {"op": "advance"}                 -> events/finished/handoffs/stats
    {"op": "export", "id"}            -> base64 handoff blob
    {"op": "inject", "blob": b64}     -> accepted true/false
    {"op": "slot_cap", "n": N}        -> admission cap (rolling drain)
    {"op": "swap", "spec": {...}}     -> rebuild engine (rolling update)
    {"op": "stop"}

Replies go to stdout prefixed with the ``@fleet `` sentinel so they
multiplex cleanly with engine logging (the parent passes everything
else through). Every op is answered before the next is read — the
synchronous exchange is what keeps multi-process dispatch replayable.

Each worker is its own process and device world: ``JAX_PLATFORMS`` /
``XLA_FLAGS`` from the environment pick its backend and virtual device
subset, and ``telemetry_port`` lights up the per-replica PR-8
``/metrics`` + ``/healthz`` + ``/statusz`` endpoint the router-side
scrape client (observability/export.py) reads.
"""

import base64
import json
import os
import signal
import sys
import time

import numpy as np

from .handoff import deserialize_handoff, serialize_handoff
from .replica import PROTOCOL_SENTINEL, engine_stats


def _reply(msg: dict):
    # default=float: metrics snapshots carry numpy scalars
    sys.stdout.write(PROTOCOL_SENTINEL + json.dumps(msg, default=float)
                     + "\n")
    sys.stdout.flush()


def _build_engine(spec: dict):
    from ..config import ServingConfig
    from ..engine import ServingEngine
    model_spec = dict(spec.get("model") or {})
    seed = model_spec.pop("seed", 0)
    from ...models.gpt import GPT, GPTConfig, build_demo_model
    if spec.get("checkpoint"):
        from ...runtime.checkpointing import load_module_params
        params = load_module_params(spec["checkpoint"])
        module = GPT(GPTConfig(**model_spec))
    else:
        module, params = build_demo_model(seed=seed, **model_spec)
    serving = dict(spec.get("serving") or {})
    serving.pop("fleet", None)      # a replica IS the fleet's leaf
    return ServingEngine(module, params, ServingConfig(**serving))


class _Worker:
    # ``_reply`` is an instance METHOD (defaulting to the stdout pipe
    # dialect) so the federation socket worker can subclass and answer
    # over a FrameConnection instead — one op surface, two transports.
    def _reply(self, msg: dict):
        _reply(msg)

    def __init__(self, spec: dict):
        self.replica_id = spec.get("replica_id", 0)
        self.role = spec.get("role", "full")
        self._spec = dict(spec)
        if spec.get("trace"):
            # fleet-wide tracing: this worker's spans (queue wait,
            # admit, prefill chunks, handoff inject, decode residency —
            # each tagged with its request's trace_id) record into a
            # process-local tracer the parent pulls via ``trace_dump``
            # and stitches into one fleet Chrome trace
            from ...observability.trace import Tracer, activate
            activate(Tracer())
        self.engine = _build_engine(spec)
        if self.role == "prefill":
            self.engine.set_prefill_role(True)
        telemetry_port = self._start_telemetry(spec)
        self._handles = {}           # id -> Request
        self._reported = set()       # ids whose completion already went out
        self._admit_reported = set() # ids whose first admission went out
        self._events = []            # [[id, token, engine iteration]]
        self._staged = {}            # id -> (slot, req) awaiting export
        # deterministic chaos hooks (the fleet scenario pack's vehicle):
        # {"hang_at_advance": N, "hang_s": S} wedges op_advance at engine
        # iteration N — the parent's reply timeout must contain it
        chaos = dict(spec.get("chaos") or {})
        self._hang_at = chaos.get("hang_at_advance")
        self._hang_s = float(chaos.get("hang_s", 600.0))
        # PR-4 preemption parity (runtime/resilience/preemption.py): a
        # supervised teardown (SIGTERM from the parent's kill path or
        # the orchestrator) ships this worker's partial metrics snapshot
        # up the pipe before the default termination runs — a killed
        # replica's work must not vanish without a trace
        try:
            signal.signal(signal.SIGTERM, self._on_sigterm)
        except ValueError:
            # federation tests host a socket worker on a non-main
            # thread, where installing handlers is forbidden; the
            # engine is still torn down by the stop op
            pass
        self._reply({"op": "ready", "replica_id": self.replica_id,
                     "telemetry_port": telemetry_port})

    def _start_telemetry(self, spec):
        port = spec.get("telemetry_port")
        if port is None:
            return None
        # bugfix ride-along: remote workers must bind their scrape
        # endpoint on the federation listen interface, not the
        # 127.0.0.1 the in-process spawn path assumed — the router's
        # scrape client dials the host it dialed the worker on
        host = spec.get("telemetry_host") or "127.0.0.1"
        return self.engine.start_telemetry(port=port, host=host).port

    def _on_sigterm(self, signum, frame):
        try:
            self._reply({"op": "partial_metrics",
                    "replica_id": self.replica_id,
                    "reason": f"signal {signum}",
                    "iteration": self.engine.iteration,
                    "metrics": self.engine.metrics.snapshot()})
        finally:
            # chain to the default action so termination semantics are
            # exactly what the parent expects (the PreemptionHandler
            # re-deliver pattern)
            signal.signal(signum, signal.SIG_DFL)
            os.kill(os.getpid(), signum)

    def _on_token(self, req, token):
        self._events.append([req.request_id, int(token),
                             self.engine.iteration])

    def _completions(self):
        done = []
        for rid, req in list(self._handles.items()):
            if req.done and rid not in self._reported:
                self._reported.add(rid)
                done.append({
                    "id": rid, "status": req.status,
                    "shed_reason": req.shed_reason,
                    "submitted_iteration": req.submitted_iteration,
                    "first_token_iteration": req.first_token_iteration,
                    "finished_iteration": req.finished_iteration,
                    "preemptions": req.preemptions,
                })
        return done

    def op_submit(self, msg):
        req = self.engine.submit(
            np.asarray(msg["prompt"], np.int32), msg["max_new_tokens"],
            request_id=msg["id"], priority=msg.get("priority", 0),
            on_token=self._on_token, trace_id=msg.get("trace_id"))
        self._handles[msg["id"]] = req
        self._reply({"op": "submitted", "id": msg["id"], "status": req.status})

    def _admissions(self):
        """Ids admitted since the last advance reply (first admission
        only — a preempt/resume cycle is not a fresh queue->admit
        transition): the parent stamps its fleet-clock admit mark for
        the per-request waterfall from these."""
        out = []
        for rid, req in self._handles.items():
            if (req.admitted_iteration is not None
                    and rid not in self._admit_reported):
                self._admit_reported.add(rid)
                out.append(rid)
        return sorted(out, key=str)

    def op_advance(self, msg):
        if self._hang_at is not None \
                and self.engine.iteration >= self._hang_at:
            time.sleep(self._hang_s)   # chaos: a wedged worker — the
                                       # parent's reply timeout fires
        self.engine.advance()
        for slot, req in self.engine.take_handoff_ready():
            self._staged[req.request_id] = (slot, req)
        events, self._events = self._events, []
        stats = {k: v for k, v in engine_stats(
            self.engine, self.replica_id, self.role).to_dict().items()
            if k not in ("replica_id", "alive", "role")}
        self._reply({"op": "advanced", "iteration": self.engine.iteration,
                "events": events, "finished": self._completions(),
                "admitted": self._admissions(),
                "handoff_ready": sorted(self._staged, key=str),
                "stats": stats})

    def _export_blob(self, msg) -> bytes:
        """Pop the staged handoff and serialize it — shared by the pipe
        dialect (base64 in the JSON reply) and the federation socket
        (raw blob frame)."""
        slot, req = self._staged.pop(msg["id"])
        payload = self.engine.export_handoff(slot, req)
        self._handles.pop(msg["id"], None)   # completion lands elsewhere
        return serialize_handoff(payload)

    def op_export(self, msg):
        self._reply({"op": "payload", "id": msg["id"],
                "blob": base64.b64encode(
                    self._export_blob(msg)).decode("ascii")})

    def _inject_payload(self, payload):
        rid = payload["request"]["request_id"]
        live = self.engine.inject_handoff(payload,
                                          on_token=self._on_token)
        if live is not None:
            self._handles[rid] = live
            self._admit_reported.add(rid)   # injection IS the admission
        self._reply({"op": "injected", "id": rid,
                "accepted": live is not None})

    def op_inject(self, msg):
        self._inject_payload(
            deserialize_handoff(base64.b64decode(msg["blob"])))

    def op_slot_cap(self, msg):
        """Rolling-update drain lever: the parent squeezes this
        replica's admission cap over the wire (the PR 10 slot-cap path)
        so in-flight requests finish while nothing new is admitted."""
        self.engine.set_slot_cap(int(msg["n"]))
        self._reply({"op": "slot_capped", "n": int(msg["n"]),
                     "iteration": self.engine.iteration})

    def op_swap(self, msg):
        """Rolling weight update: rebuild the engine from a new spec
        (checkpoint or model seed). Refused while requests are in
        flight — the parent drains first; a swap must never drop work."""
        if self._handles and not all(r.done for r in self._handles.values()):
            self._reply({"op": "error",
                         "detail": "swap refused: requests in flight"})
            return
        spec = dict(self._spec)
        spec.update(msg.get("spec") or {})
        self.engine.close()
        self._spec = spec
        self.engine = _build_engine(spec)
        if self.role == "prefill":
            self.engine.set_prefill_role(True)
        telemetry_port = self._start_telemetry(spec)
        self._handles.clear()
        self._reported.clear()
        self._admit_reported.clear()
        self._events = []
        self._staged.clear()
        self._reply({"op": "swapped", "replica_id": self.replica_id,
                     "telemetry_port": telemetry_port,
                     "iteration": self.engine.iteration})

    def op_trace_dump(self, msg):
        """Ship this worker's recorded span stream as Chrome-trace
        event dicts (JSON-able) for fleet-level stitching."""
        from ...observability.trace import active_tracer, chrome_trace_events
        tracer = active_tracer()
        events = chrome_trace_events(tracer.events) if tracer else []
        self._reply({"op": "trace", "replica_id": self.replica_id,
                "events": events})

    def serve(self):
        for line in sys.stdin:
            line = line.strip()
            if not line:
                continue
            msg = json.loads(line)
            op = msg.get("op")
            if op == "stop":
                break
            handler = getattr(self, f"op_{op}", None)
            if handler is None:
                self._reply({"op": "error", "detail": f"unknown op {op!r}"})
                continue
            try:
                handler(msg)
            except Exception as e:   # ds-tpu: lint-ok[PY001] — the
                # protocol boundary: an op failure must reach the parent
                # as a typed error reply, never kill the pipe silently
                self._reply({"op": "error", "detail": f"{op}: {e}"})
        self.engine.close()
        self._reply({"op": "bye"})


def main():
    from ...utils.host_env import configure_compile_cache
    configure_compile_cache()
    first = sys.stdin.readline()
    if not first:
        return 2
    spec = json.loads(first)
    if spec.get("op") != "init":
        _reply({"op": "error", "detail": "first line must be the init spec"})
        return 2
    _Worker(spec).serve()
    return 0


if __name__ == "__main__":
    sys.exit(main())
