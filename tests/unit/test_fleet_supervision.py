"""Self-healing serving fleet (serving/fleet/supervision.py + manager).

Acceptance surface of the supervision PR:

- restart-then-token-exact-continuation on BOTH backends: a dead or
  crashed replica's requests fail over with tokens retained (bit-equal
  to an uncontended single-engine ``generate()`` under greedy), a fresh
  incarnation respawns after exponential backoff, and new traffic lands
  on it — with the restarted in-process engine reusing the
  process-global jit cache (compile-once probes intact);
- in-process ``ReplicaCrash`` is recoverable under supervision (and
  still fatal with ``supervision.enabled: false`` —
  test_serving_fleet.py keeps that contract);
- crash-loop retirement: a lineage that keeps dying inside
  ``crash_window_steps`` is permanently retired and the fleet keeps
  serving on the survivors;
- degraded disaggregation: an empty prefill pool routes submissions to
  decode replicas (their own chunked prefill), bit-equal to a healthy
  disaggregated fleet, exiting automatically when a prefill replica
  returns;
- handoff hardening: truncated payloads raise the NAMED
  ``HandoffError``, injection failures retry with bounded backoff then
  re-prefill through failover, and a re-sent payload after an
  ambiguous failure is deduplicated (never double-injected);
- worker pipe protocol errors surface as ``WorkerProtocolError``
  (replica id attached) and trigger supervision instead of propagating
  raw; ``ProcessReplica`` teardown reaps the child and closes both
  pipe fds on every branch (fd count stays flat across spawn/stop
  cycles);
- router health: a replica whose aggregated telemetry is stale/down
  receives no new dispatches until it reads healthy again.

Unique vocab sizes per engine-building test (repo convention): jit
caches are process-global, so distinct shapes keep compile-once probes
honest across tests.
"""

import os
import signal
import sys
import time

import numpy as np
import pytest
import jax.numpy as jnp

from deepspeed_tpu.inference.generation import generate
from deepspeed_tpu.models.gpt import GPT, GPTConfig
from deepspeed_tpu.serving import PagingConfig, ServingConfig
from deepspeed_tpu.serving.fleet.config import FleetConfig
from deepspeed_tpu.serving.fleet.handoff import HandoffError, \
    deserialize_handoff, serialize_handoff
from deepspeed_tpu.serving.fleet.manager import ServingFleet
from deepspeed_tpu.serving.fleet.replica import (ProcessReplica,
                                                 ReplicaDead,
                                                 WorkerProtocolError)
from deepspeed_tpu.serving.fleet.supervision import (ReplicaSupervisor,
                                                     SupervisionConfig)


def _model(vocab, seed=0):
    cfg = GPTConfig(vocab_size=vocab, max_seq_len=128, d_model=32,
                    n_layers=2, n_heads=2, dtype=jnp.float32)
    m = GPT(cfg)
    import jax
    params = m.init(jax.random.PRNGKey(seed),
                    jnp.ones((1, 8), jnp.int32))["params"]
    return m, params


def _cfg(fleet, num_slots=2, **kw):
    return ServingConfig(num_slots=num_slots, max_len=128,
                         paging=PagingConfig(page_len=16),
                         fleet=fleet, **kw)


def _prompts(seed, n, vocab, lo=5, hi=30):
    r = np.random.RandomState(seed)
    return [r.randint(1, vocab, size=int(r.randint(lo, hi)))
            for _ in range(n)]


def _assert_token_exact(m, params, prompt, handle, max_new):
    ref = np.asarray(generate(m, params, np.asarray(prompt)[None],
                              max_new_tokens=max_new, temperature=0.0,
                              max_len=128))[0, len(prompt):]
    np.testing.assert_array_equal(
        np.asarray(handle.tokens), ref,
        err_msg=f"request {handle.request_id} (handoffs={handle.handoffs},"
                f" failovers={handle.failovers})")


# ---------------------------------------------------------------------------
# policy + config units (no engine, no jax compute)
# ---------------------------------------------------------------------------

class TestSupervisionConfig:
    def test_defaults_enabled_and_validation(self):
        cfg = SupervisionConfig().validate()
        assert cfg.enabled and cfg.max_restarts == 3
        with pytest.raises(ValueError, match="max_restarts"):
            SupervisionConfig(max_restarts=-1).validate()
        with pytest.raises(ValueError, match="crash_window_steps"):
            SupervisionConfig(crash_window_steps=0).validate()
        with pytest.raises(ValueError, match="backoff_base_steps"):
            SupervisionConfig(backoff_base_steps=0).validate()
        with pytest.raises(ValueError, match="backoff_max_steps"):
            SupervisionConfig(backoff_base_steps=8,
                              backoff_max_steps=4).validate()
        with pytest.raises(ValueError, match="handoff_max_retries"):
            SupervisionConfig(handoff_max_retries=-1).validate()
        with pytest.raises(ValueError, match="handoff_backoff_steps"):
            SupervisionConfig(handoff_backoff_steps=0).validate()
        with pytest.raises(ValueError, match="worker_reply_timeout_s"):
            FleetConfig(worker_reply_timeout_s=0).validate()

    def test_backoff_schedule_is_exponential_and_capped(self):
        cfg = SupervisionConfig(backoff_base_steps=2, backoff_max_steps=16)
        assert [cfg.restart_delay_steps(n) for n in range(5)] == \
            [2, 4, 8, 16, 16]
        assert [cfg.handoff_retry_delay_steps(n) for n in (1, 2, 3)] == \
            [1, 2, 4]

    def test_block_plumbing_through_serving_config(self):
        cfg = ServingConfig(
            num_slots=2, max_len=128,
            fleet={"replicas": 2,
                   "supervision": {"max_restarts": 1,
                                   "backoff_base_steps": 4}}).validate()
        assert cfg.fleet.supervision.max_restarts == 1
        assert cfg.fleet.supervision.backoff_base_steps == 4
        # absent sub-block = defaults, supervision ON
        assert FleetConfig().validate().supervision.enabled
        off = FleetConfig(
            supervision={"enabled": False}).validate().supervision
        assert not off.enabled


class TestReplicaSupervisor:
    def _sup(self, **kw):
        return ReplicaSupervisor(SupervisionConfig(**kw).validate())

    def test_restart_verdict_and_backoff_clock(self):
        s = self._sup(backoff_base_steps=2)
        lid = s.register("full")
        assert s.on_death(lid, step=10) == "restart"
        assert not s.take_due(11) and s.pending()
        assert s.take_due(12) == [(lid, "full")]
        assert not s.pending()          # taken = no longer due
        # second death: the backoff doubled
        assert s.on_death(lid, step=20) == "restart"
        assert not s.take_due(23) and s.take_due(24) == [(lid, "full")]

    def test_crash_loop_retires_within_window(self):
        s = self._sup(max_restarts=2, crash_window_steps=100)
        lid = s.register("decode")
        assert s.on_death(lid, 10) == "restart"
        assert s.on_death(lid, 20) == "restart"
        assert s.on_death(lid, 30) == "retired"
        assert s.retired_total == 1 and not s.pending()
        # a retired lineage stays retired
        assert s.on_death(lid, 40) == "retired"

    def test_old_crashes_age_out_of_the_window(self):
        s = self._sup(max_restarts=2, crash_window_steps=50,
                      backoff_base_steps=2)
        lid = s.register("full")
        assert s.on_death(lid, 0) == "restart"
        assert s.on_death(lid, 10) == "restart"
        assert s._lineages[lid]["due"] == 10 + 4   # 2 in-window crashes
        # step 100: BOTH prior crashes aged out — still a restart, and
        # the backoff RESETS to the base delay (an isolated crash is
        # not a loop; lifetime restart count must not escalate it)
        assert s.on_death(lid, 100) == "restart"
        assert s._lineages[lid]["due"] == 100 + 2

    def test_deregister_cancels_pending_restart(self):
        s = self._sup(backoff_base_steps=1)
        lid = s.register("full")
        s.on_death(lid, 0)
        s.deregister(lid)
        assert not s.pending() and not s.take_due(100)
        s.deregister(None)              # tolerated (no lineage)

    def test_pending_filters_by_role(self):
        s = self._sup()
        a, b = s.register("prefill"), s.register("decode")
        s.on_death(b, 0)
        assert s.pending(("decode", "full")) and not s.pending(("prefill",))
        assert s.snapshot()["lineages"][str(b)]["restart_due_step"] is not None
        assert a is not None


class TestNamedErrors:
    def test_worker_protocol_error_carries_replica_id(self):
        e = WorkerProtocolError(3, "timeout", "silent past 5s")
        assert isinstance(e, ReplicaDead)
        assert e.replica_id == 3 and e.kind == "timeout"
        assert "replica 3" in str(e) and "timeout" in str(e)

    def test_truncated_handoff_blob_raises_named_error(self):
        payload = {
            "version": 2, "page_len": 16, "kv_quant": None,
            "prefill_len": 8, "n_pages_filled": 1,
            "kv": [{"k": np.zeros((2, 2), np.float32)}],
            "state": {"last_token": 1, "remaining": 4},
            "request": {"request_id": "r", "trace_id": None,
                        "prompt": np.arange(8, dtype=np.int32),
                        "generated": [1], "max_new_tokens": 5,
                        "priority": 0},
        }
        blob = serialize_handoff(payload)
        # round-trip is fine ...
        assert deserialize_handoff(blob)["prefill_len"] == 8
        # ... every truncation raises the NAMED error (a ValueError, so
        # pre-existing catch sites still work)
        for cut in (0, 8, len(blob) // 2, len(blob) - 3):
            with pytest.raises(HandoffError):
                deserialize_handoff(blob[:cut])
        assert issubclass(HandoffError, ValueError)


# ---------------------------------------------------------------------------
# ProcessReplica lifecycle: fd hygiene + protocol errors (stub worker,
# no engine, no jax)
# ---------------------------------------------------------------------------

_STUB_WORKER = r'''
import json, sys, time
SENT = "@fleet "
def reply(m):
    sys.stdout.write(SENT + json.dumps(m) + "\n"); sys.stdout.flush()
spec = json.loads(sys.stdin.readline())
reply({"op": "ready", "replica_id": spec.get("replica_id"),
       "telemetry_port": None})
for line in sys.stdin:
    msg = json.loads(line)
    op = msg.get("op")
    if op == "stop":
        break
    if op == "hang":
        reply({"op": "ack"}); time.sleep(600)
    elif op == "garbage":
        sys.stdout.write(SENT + "this is not json\n"); sys.stdout.flush()
    else:
        reply({"op": "echo", "got": op})
reply({"op": "bye"})
'''


class _StubReplica(ProcessReplica):
    @staticmethod
    def _worker_argv():
        return [sys.executable, "-c", _STUB_WORKER]


def _open_fds():
    return len(os.listdir("/proc/self/fd"))


class TestProcessReplicaLifecycle:
    def test_spawn_stop_cycles_hold_fd_count_flat(self):
        """Every teardown branch (graceful stop AND the kill path) must
        reap the child and close both pipe fds — the leak was the
        timeout branch keeping stdout/stdin open."""
        _StubReplica(0, "full", {}).stop()      # warm caches/imports
        base = _open_fds()
        for i in range(6):
            rep = _StubReplica(i, "full", {})
            if i % 2:
                rep.stop()
            else:
                rep.kill()
            assert rep._proc.poll() is not None     # reaped, no zombie
            assert rep._proc.stdout.closed and rep._proc.stdin.closed
        assert _open_fds() == base

    def test_kill_reaps_a_stop_refusing_worker(self):
        """A worker that ignores ``stop`` (wedged in a hang) is killed,
        reaped, and its fds closed — repeatedly, without leaking."""
        rep = _StubReplica(0, "full", {}, reply_timeout_s=2)
        rep._send({"op": "hang"})
        rep._read_reply()               # ack — now it sleeps forever
        base_pid = rep._proc.pid
        rep.kill()
        assert rep._proc.poll() is not None
        assert rep._proc.stdout.closed
        assert base_pid > 0

    def test_reply_timeout_is_a_named_protocol_error(self):
        rep = _StubReplica(7, "full", {}, reply_timeout_s=0.5)
        rep._send({"op": "hang"})
        rep._read_reply()               # the ack
        rep._send({"op": "nothing"})    # hung: no reply is coming
        with pytest.raises(WorkerProtocolError) as ei:
            rep._read_reply()
        assert ei.value.replica_id == 7 and ei.value.kind == "timeout"
        assert not rep.alive and rep.protocol_errors == 1
        rep.stop()                      # dead-marked + live pid: reaped
        assert rep._proc.poll() is not None

    def test_malformed_frame_is_a_named_protocol_error(self):
        rep = _StubReplica(9, "full", {}, reply_timeout_s=5)
        rep._send({"op": "garbage"})
        with pytest.raises(WorkerProtocolError) as ei:
            rep._read_reply()
        assert ei.value.kind == "malformed" and ei.value.replica_id == 9
        rep.stop()


# ---------------------------------------------------------------------------
# router health integration (light: no decode dispatch, no compiles)
# ---------------------------------------------------------------------------

def test_stale_replica_receives_no_dispatches_until_healthy():
    """The scrape-driven-routing robustness half: a replica whose
    aggregated telemetry is down/stale is skipped by BOTH router
    policies until it reads healthy again — and telemetry alone never
    bricks dispatch (all-stale falls back to all-alive)."""
    m, p = _model(vocab=1511)
    fleet = ServingFleet(m, p, _cfg(FleetConfig(replicas=2),
                                    num_slots=2))
    agg = fleet._aggregator
    assert agg is not None
    now = time.time()
    agg.replicas[0].update(up=True, last_success_unix=now)
    agg.replicas[1].update(up=False, scrapes_failed=1)
    for i in range(6):
        fleet.submit(_prompts(i, 1, 1511)[0], max_new_tokens=4,
                     request_id=f"a{i}")
    assert all(t == 0 for _, t in fleet.dispatch_log[-6:])
    # healthy again: load-aware routing resumes (replica 0 is deep)
    agg.replicas[1].update(up=True, last_success_unix=time.time(),
                           scrapes_failed=0)
    for i in range(4):
        fleet.submit(_prompts(50 + i, 1, 1511)[0], max_new_tokens=4,
                     request_id=f"b{i}")
    assert any(t == 1 for _, t in fleet.dispatch_log[-4:])
    # stale EVERYWHERE must not brick dispatch
    stale = now - 10_000
    agg.replicas[0].update(last_success_unix=stale)
    agg.replicas[1].update(last_success_unix=stale)
    fleet.submit(_prompts(99, 1, 1511)[0], max_new_tokens=4,
                 request_id="c0")
    assert len(fleet.dispatch_log) == 11
    fleet.close()


# ---------------------------------------------------------------------------
# supervised recovery, end to end — slow lane (engines + compiles)
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestSupervisedRecovery:
    def test_crash_restart_token_exact_inprocess(self):
        """An injected in-process ReplicaCrash is contained: requests
        fail over token-exactly, a fresh engine respawns after backoff
        REUSING the process-global jit cache (zero extra decode
        compiles), and post-restart traffic is token-exact too."""
        from deepspeed_tpu.serving.paging.manager import _paged_decode_jit
        m, p = _model(vocab=1523)
        fleet = ServingFleet(m, p, _cfg(
            FleetConfig(replicas=2,
                        supervision={"backoff_base_steps": 2}),
            num_slots=2))
        decode_before = _paged_decode_jit._cache_size()
        prompts = _prompts(3, 6, 1523)
        handles = [fleet.submit(pr, max_new_tokens=8, request_id=i)
                   for i, pr in enumerate(prompts)]
        for step in range(500):
            if not fleet.busy:
                break
            if step == 3:
                fleet._replicas[1].fail_at = 0   # ReplicaCrash next step
            fleet.advance()
        assert all(h.status == "finished" for h in handles)
        assert fleet.dead_replicas == 1 and fleet.replica_restarts == 1
        assert len(fleet._alive()) == 2
        for pr, h in zip(prompts, handles):
            _assert_token_exact(m, p, pr, h, 8)
        # the respawned engine serves fresh traffic, same programs
        post = fleet.submit(prompts[0], max_new_tokens=8,
                            request_id="post")
        fleet.run(max_iterations=300)
        assert post.status == "finished"
        _assert_token_exact(m, p, prompts[0], post, 8)
        assert _paged_decode_jit._cache_size() == decode_before + 1
        snap = fleet.snapshot()
        assert snap["replica_restarts"] == 1
        assert snap["supervision"]["restarts_scheduled"] == 1
        fleet.close()

    def test_all_dead_parks_work_until_restart(self):
        """Total loss with restarts pending does NOT raise: the backlog
        parks, the fleet stalls on its backoff clock, and everything
        completes token-exactly on the respawned replicas."""
        m, p = _model(vocab=1531)
        fleet = ServingFleet(m, p, _cfg(
            FleetConfig(replicas=2,
                        supervision={"backoff_base_steps": 1}),
            num_slots=2))
        h = fleet.submit(np.arange(1, 9), max_new_tokens=6,
                         request_id="x")
        fleet.kill_replica(0)
        fleet.kill_replica(1)
        fleet.run(max_iterations=400)
        assert h.status == "finished"
        _assert_token_exact(m, p, np.arange(1, 9), h, 6)
        assert fleet.replica_restarts == 2
        fleet.close()

    def test_crash_loop_retires_and_fleet_keeps_serving(self):
        """A lineage that dies on every incarnation is permanently
        retired after max_restarts inside the window; the fleet serves
        the whole workload on the survivor (fleet/replicas_retired)."""
        from deepspeed_tpu.observability.metrics import get_registry
        m, p = _model(vocab=1543)
        retired_before = get_registry().counter(
            "fleet/replicas_retired").value
        fleet = ServingFleet(m, p, _cfg(
            FleetConfig(replicas=2,
                        supervision={"max_restarts": 2,
                                     "crash_window_steps": 64,
                                     "backoff_base_steps": 1}),
            num_slots=2))
        victim = fleet._lineage[1]
        prompts = _prompts(11, 6, 1543)
        handles = [fleet.submit(pr, max_new_tokens=6, request_id=i)
                   for i, pr in enumerate(prompts)]
        for _ in range(400):
            if not fleet.busy:
                break
            for rid, rep in list(fleet._replicas.items()):
                if rep.alive and fleet._lineage.get(rid) == victim:
                    rep.fail_at = 0
            fleet.advance()
        assert all(h.status == "finished" for h in handles)
        for pr, h in zip(prompts, handles):
            _assert_token_exact(m, p, pr, h, 6)
        assert fleet.replicas_retired == 1
        assert fleet.replica_restarts == 2      # then the loop tripped
        assert fleet._alive() and all(
            fleet._lineage[rid] != victim for rid in fleet._alive())
        assert get_registry().counter("fleet/replicas_retired").value \
            == retired_before + 1
        assert not fleet.supervisor.pending()
        fleet.close()

    def test_degraded_prefill_parity_vs_healthy_fleet(self):
        """Prefill-pool wipe: the degraded fleet (decode replicas doing
        their own chunked prefill) produces token streams BIT-EQUAL to
        a healthy disaggregated fleet serving the same workload, enters
        and exits degraded mode on the advertised edges, and serves
        NEW work submitted during the outage."""
        m, p = _model(vocab=1549)

        def build():
            return ServingFleet(m, p, _cfg(
                FleetConfig(replicas=3, disaggregate=True,
                            prefill_replicas=1,
                            supervision={"backoff_base_steps": 8}),
                num_slots=2))

        prompts = _prompts(13, 5, 1549)
        healthy = build()
        ref_handles = [healthy.submit(pr, max_new_tokens=6, request_id=i)
                       for i, pr in enumerate(prompts)]
        healthy.run(max_iterations=500)
        assert all(h.status == "finished" for h in ref_handles)
        assert not healthy.degraded_entered
        healthy.close()

        fleet = build()
        handles = [fleet.submit(pr, max_new_tokens=6, request_id=i)
                   for i, pr in enumerate(prompts)]
        entered = exited = False
        mid = None
        for step in range(600):
            if not fleet.busy and exited:
                break
            if step == 2:
                fleet.kill_replica(0)       # the whole prefill pool
            if fleet.degraded and mid is None:
                mid = fleet.submit(prompts[0], max_new_tokens=6,
                                   request_id="mid")
            fleet.advance()
            entered |= fleet.degraded
            exited |= (entered and not fleet.degraded)
        fleet.run(max_iterations=400)
        assert entered and exited and mid is not None
        assert all(h.status == "finished" for h in handles)
        assert mid.status == "finished"
        # parity vs the healthy fleet (and, transitively, generate())
        assert [h.tokens for h in handles] == \
            [h.tokens for h in ref_handles]
        _assert_token_exact(m, p, prompts[0], mid, 6)
        assert fleet.degraded_entered == 1
        assert fleet.snapshot()["degraded_mode"] is False
        fleet.close()

    def test_handoff_idempotence_under_ambiguous_failure(self):
        """First injection SUCCEEDS but the manager is told it failed
        (ambiguous: reply lost mid-inject). The retried payload must be
        deduplicated by the receiving engine — one live request, one
        token stream, token-exact."""
        from deepspeed_tpu.observability.metrics import get_registry
        m, p = _model(vocab=1553)
        fleet = ServingFleet(m, p, _cfg(
            FleetConfig(replicas=2, disaggregate=True,
                        prefill_replicas=1,
                        supervision={"handoff_max_retries": 3,
                                     "handoff_backoff_steps": 1}),
            num_slots=2))
        dedup_before = get_registry().counter(
            "serving/handoff_dedup").value
        real_inject = fleet._inject
        state = {"ambiguous": 1}

        def flaky_inject(rep, payload, handle):
            ok = real_inject(rep, payload, handle)
            if ok and state["ambiguous"]:
                state["ambiguous"] -= 1
                return False            # the reply "never arrived"
            return ok
        fleet._inject = flaky_inject
        prompts = _prompts(17, 3, 1553)
        handles = [fleet.submit(pr, max_new_tokens=6, request_id=i)
                   for i, pr in enumerate(prompts)]
        fleet.run(max_iterations=500)
        assert all(h.status == "finished" for h in handles)
        for pr, h in zip(prompts, handles):
            _assert_token_exact(m, p, pr, h, 6)
        assert state["ambiguous"] == 0      # the failure really fired
        assert get_registry().counter("serving/handoff_dedup").value \
            == dedup_before + 1
        assert fleet.handoffs_dropped == 0
        fleet.close()

    def test_inject_handoff_dedupes_at_the_engine(self):
        """Engine-level guard: injecting the same payload twice returns
        the SAME live request and allocates no second slot."""
        from deepspeed_tpu.serving.engine import ServingEngine
        m, p = _model(vocab=1559)
        cfg = _cfg(None, num_slots=2)
        pre = ServingEngine(m, p, cfg)
        pre.set_prefill_role(True)
        prompt = np.arange(1, 20, dtype=np.int32)
        pre.submit(prompt, 6, request_id="h0")
        payload = None
        for _ in range(200):
            pre.advance()
            ready = pre.take_handoff_ready()
            if ready:
                slot, req = ready[0]
                payload = pre.export_handoff(slot, req)
                break
        assert payload is not None
        blob = serialize_handoff(payload)
        dec = ServingEngine(m, p, cfg)
        first = dec.inject_handoff(deserialize_handoff(blob))
        assert first is not None
        again = dec.inject_handoff(deserialize_handoff(blob))
        assert again is first               # deduped, not re-injected
        assert sum(r is not None for r in dec._slot_req) == 1
        # the guard holds even after the request FINISHES and leaves
        # the slot/queue scans: a late retry must not run it twice
        dec.run(max_iterations=300)
        assert first.done
        late = dec.inject_handoff(deserialize_handoff(blob))
        assert late is first
        assert sum(r is not None for r in dec._slot_req) == 0
        pre.close()
        dec.close()

    def test_real_engine_fault_contained_like_a_crash(self):
        """Supervision contains ANY engine fault out of advance(), not
        just the ReplicaCrash chaos hook: a raising engine is one
        replica's death — failover + restart, fleet keeps serving."""
        m, p = _model(vocab=1571)
        fleet = ServingFleet(m, p, _cfg(
            FleetConfig(replicas=2,
                        supervision={"backoff_base_steps": 2}),
            num_slots=2))
        prompts = _prompts(23, 4, 1571)
        handles = [fleet.submit(pr, max_new_tokens=6, request_id=i)
                   for i, pr in enumerate(prompts)]
        victim = fleet._replicas[1].engine
        orig = victim.advance
        fired = {"n": 0}

        def raising_advance():
            fired["n"] += 1
            raise ValueError("synthetic XLA fault")   # NOT ReplicaCrash
        victim.advance = raising_advance
        fleet.run(max_iterations=500)
        assert fired["n"] == 1 and orig is not None
        assert all(h.status == "finished" for h in handles)
        for pr, h in zip(prompts, handles):
            _assert_token_exact(m, p, pr, h, 6)
        assert fleet.dead_replicas == 1 and fleet.replica_restarts == 1
        fleet.close()

    def test_dead_replica_history_is_bounded(self, monkeypatch):
        """A supervised fleet restarts without bound: the corpse map,
        failed set, lineage map, and aggregator entries must not grow
        with every incarnation (bounded to DEAD_REPLICAS_KEPT)."""
        from deepspeed_tpu.serving.fleet import manager as manager_mod
        monkeypatch.setattr(manager_mod, "DEAD_REPLICAS_KEPT", 2)
        m, p = _model(vocab=1579)
        fleet = ServingFleet(m, p, _cfg(
            FleetConfig(replicas=2,
                        supervision={"max_restarts": 10,
                                     "crash_window_steps": 4,
                                     "backoff_base_steps": 1}),
            num_slots=2))
        victim = fleet._lineage[1]
        h = fleet.submit(np.arange(1, 9), max_new_tokens=40,
                         request_id="long")
        crashes = 0
        for _ in range(120):
            if crashes >= 6 and not fleet.busy:
                break
            for rid, rep in list(fleet._replicas.items()):
                if rep.alive and fleet._lineage.get(rid) == victim \
                        and crashes < 6:
                    rep.fail_at = 0
                    crashes += 1
            fleet.advance()
        assert crashes == 6        # six incarnations died ...
        dead = [rid for rid, rep in fleet._replicas.items()
                if not rep.alive]
        assert len(dead) <= 2      # ... but only the recent corpses stay
        assert len(fleet._failed) <= 2
        assert len(fleet._aggregator.replicas) <= len(fleet._replicas)
        fleet.run(max_iterations=400)
        assert h.status == "finished"
        _assert_token_exact(m, p, np.arange(1, 9), h, 40)
        fleet.close()


@pytest.mark.slow
class TestProcessBackendRecovery:
    MODEL = {"vocab_size": 1567, "max_seq_len": 128, "d_model": 32,
             "n_layers": 2, "n_heads": 2, "seed": 0}

    def _spec(self, cfg):
        import dataclasses
        return {"serving": dataclasses.asdict(
                    dataclasses.replace(cfg, fleet=None)),
                "model": self.MODEL}

    def test_worker_kill_restart_token_exact(self):
        """The process-backend half of restart-then-continuation: a
        SIGKILLed worker's requests finish on the survivor token-exact,
        supervision respawns a fresh worker, and new traffic lands on
        the restarted fleet token-exact."""
        from deepspeed_tpu.models.gpt import build_demo_model
        cfg = _cfg(FleetConfig(replicas=2, backend="process",
                               supervision={"backoff_base_steps": 1}),
                   num_slots=2)
        fleet = ServingFleet(None, None, cfg, spec=self._spec(cfg))
        prompts = _prompts(19, 5, 1567)
        handles = [fleet.submit(pr, max_new_tokens=5, request_id=i)
                   for i, pr in enumerate(prompts)]
        for step in range(500):
            if not fleet.busy:
                break
            if step == 3:
                fleet._replicas[1]._proc.kill()
            fleet.advance()
        assert all(h.status == "finished" for h in handles)
        assert fleet.dead_replicas == 1 and fleet.replica_restarts >= 1
        m, p = build_demo_model(**self.MODEL)
        for pr, h in zip(prompts, handles):
            _assert_token_exact(m, p, pr, h, 5)
        post = fleet.submit(prompts[0], max_new_tokens=5,
                            request_id="post")
        fleet.run(max_iterations=400)
        assert post.status == "finished"
        _assert_token_exact(m, p, prompts[0], post, 5)
        fleet.close()

    def test_worker_sigterm_emits_partial_metrics(self):
        """The PR-4 parity satellite: a SIGTERMed worker ships its
        partial metrics snapshot up the pipe before dying, and the
        fleet surfaces it in the per-replica snapshot entry."""
        cfg = _cfg(FleetConfig(replicas=1,
                               supervision={"enabled": False},
                               backend="process"), num_slots=2)
        fleet = ServingFleet(None, None, cfg, spec=self._spec(cfg))
        h = fleet.submit(np.arange(1, 12), max_new_tokens=4,
                         request_id="t")
        for _ in range(3):
            fleet.advance()
        rep = fleet._replicas[0]
        os.kill(rep._proc.pid, signal.SIGTERM)
        rep._proc.wait(timeout=30)
        with pytest.raises(RuntimeError):
            for _ in range(10):             # death detected, total loss
                fleet.advance()
        assert rep.last_partial_metrics is not None
        pm = rep.last_partial_metrics
        assert pm["replica_id"] == 0 and "metrics" in pm
        assert fleet.snapshot()["replicas"]["0"]["partial_metrics"] == pm
        assert h.request_id == "t"
        fleet.close()
