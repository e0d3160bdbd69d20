"""Serving QoS (deepspeed_tpu/serving/qos.py + engine integration).

Acceptance surface of the overload-resilience PR:

- priority preemption-to-queue with token-exact resumption vs an
  uncontended ``generate()`` reference (slot-starved AND page-starved);
- deterministic SLO-aware shedding: the same overload trace produces
  the same shed set bit-for-bit, protected classes never shed, and the
  high-priority class's p95 TTFT stays inside its SLO target under a
  ~3x-overload burst scenario;
- fault containment: an injected RESOURCE_EXHAUSTED during prefill and an
  injected hung decode dispatch both leave the engine serving the
  remaining requests (no process death), with the events visible in the
  metrics snapshot / statusz payload;
- requeue-and-re-prefill recovery (``engine.recover``) restores every
  queued + active request after an engine restart;
- elasticity: the autoscaler recommends from the registry gauges and
  scale-down drains slots via the preemption path;
- the TS002/zero-finding lint gate over every touched subsystem.
"""

import os
import sys
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.gpt import GPT, GPTConfig
from deepspeed_tpu.inference.generation import generate
from deepspeed_tpu.serving import (PagingConfig, QosConfig, ServingConfig)
from deepspeed_tpu.serving.engine import ServingEngine
from deepspeed_tpu.serving.qos import (LEVEL_DEGRADE, LEVEL_HEALTHY,
                                       LEVEL_REFUSE, LEVEL_SHED,
                                       QosController)
import deepspeed_tpu.serving.paging.manager as manager_mod

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _model(vocab=97, max_seq_len=128, d_model=32, n_layers=2, n_heads=2,
           seed=0):
    cfg = GPTConfig(vocab_size=vocab, max_seq_len=max_seq_len,
                    d_model=d_model, n_layers=n_layers, n_heads=n_heads,
                    dtype=jnp.float32)
    m = GPT(cfg)
    params = m.init(jax.random.PRNGKey(seed),
                    jnp.ones((1, 8), jnp.int32))["params"]
    return m, params


def _qos(**kw):
    classes = kw.pop("classes", [
        {"name": "interactive", "priority": 2, "ttft_slo_steps": 32,
         "preempt_after_steps": 1, "sheddable": False},
        {"name": "standard", "priority": 1, "ttft_slo_steps": 128},
        {"name": "batch", "priority": 0},
    ])
    return QosConfig(classes=classes, **kw)


def _assert_token_exact(m, params, req, max_len=128):
    ref = np.asarray(generate(m, params, np.asarray(req.prompt)[None],
                              max_new_tokens=req.max_new_tokens,
                              temperature=0.0, max_len=max_len)
                     )[0, len(req.prompt):]
    np.testing.assert_array_equal(
        np.asarray(req.output_tokens), ref,
        err_msg=f"request {req.request_id} (preemptions={req.preemptions})")


# ---------------------------------------------------------------------------
# config + controller (no jax needed beyond import)
# ---------------------------------------------------------------------------

class TestQosConfig:
    def test_defaults_and_validation(self):
        q = QosConfig().validate()
        assert {c.name for c in q.classes} == {"interactive", "standard",
                                               "batch"}
        with pytest.raises(ValueError, match="distinct"):
            QosConfig(classes=[{"name": "a", "priority": 1},
                               {"name": "b", "priority": 1}]).validate()
        with pytest.raises(ValueError, match="at least one"):
            QosConfig(classes=[]).validate()
        with pytest.raises(ValueError, match="ladder_patience"):
            QosConfig(ladder_patience_steps=0).validate()
        with pytest.raises(ValueError, match="watchdog_timeout_s"):
            QosConfig(watchdog_timeout_s=0).validate()
        with pytest.raises(ValueError, match="min_free_page_frac"):
            QosConfig(min_free_page_frac=1.5).validate()

    def test_class_for_mapping(self):
        q = _qos()
        assert q.class_for(2).name == "interactive"
        assert q.class_for(1).name == "standard"
        assert q.class_for(0).name == "batch"
        # off-grid priorities: nearest class at-or-below, else the lowest
        assert q.class_for(7).name == "interactive"
        assert q.class_for(-3).name == "batch"
        assert q.lowest_sheddable().name == "batch"

    def test_serving_config_block_plumbing(self):
        from deepspeed_tpu.runtime.config import DeepSpeedConfig
        c = DeepSpeedConfig.from_dict({"serving": {
            "num_slots": 2, "max_len": 64,
            "qos": {"enabled": True,
                    "shed_queue_depth": 5,
                    "classes": [{"name": "hi", "priority": 1,
                                 "sheddable": False},
                                {"name": "lo", "priority": 0}]}}})
        assert c.serving.qos_enabled
        assert c.serving.qos.shed_queue_depth == 5
        assert c.serving.qos.class_for(1).name == "hi"
        # absent block keeps the pre-QoS engine config
        assert not ServingConfig().qos_enabled

    def test_ladder_deterministic_escalation_and_recovery(self):
        q = QosConfig(shed_queue_depth=4, ladder_patience_steps=2,
                      recover_patience_steps=3)
        ctl = QosController(q)
        levels = []
        depths = [5, 5, 5, 5, 5, 5, 0, 0, 0, 0, 0, 0, 0, 0, 0]
        for it, d in enumerate(depths):
            levels.append(ctl.observe(iteration=it, queue_depth=d,
                                      ttft_p95_steps=None, free_frac=None))
        # patience=2: +1 level every 2 overloaded evals, capped at refuse;
        # recovery=3: -1 level every 3 healthy evals
        assert levels == [0, 1, 1, 2, 2, 3, 3, 3, 2, 2, 2, 1, 1, 1, 0]
        # the run is pure arithmetic on the step clock: replay == replay
        ctl2 = QosController(QosConfig(shed_queue_depth=4,
                                       ladder_patience_steps=2,
                                       recover_patience_steps=3))
        levels2 = [ctl2.observe(iteration=it, queue_depth=d,
                                ttft_p95_steps=None, free_frac=None)
                   for it, d in enumerate(depths)]
        assert levels2 == levels
        assert [c["to"] for c in ctl.level_changes[:3]] == \
            ["shed", "degrade", "refuse"]

    def test_admit_decisions(self):
        q = _qos(shed_queue_depth=4)
        ctl = QosController(q)
        inter, std, batch = (q.class_for(p) for p in (2, 1, 0))
        # healthy: everyone admits
        assert ctl.admit(batch, class_ttft_p95=None) == (True, None)
        # SLO-aware: a sheddable class already past its p95 target sheds
        ok, reason = ctl.admit(std, class_ttft_p95=500)
        assert not ok and reason == "slo"
        # protected classes never shed, even at refuse level
        ctl.level = LEVEL_REFUSE
        assert ctl.admit(inter, class_ttft_p95=10_000)[0]
        ok, reason = ctl.admit(std, class_ttft_p95=None)
        assert not ok and reason == "refuse"
        ctl.level = LEVEL_SHED
        ok, reason = ctl.admit(batch, class_ttft_p95=None)
        assert not ok and reason == "ladder"
        assert ctl.admit(std, class_ttft_p95=None)[0]  # only lowest sheds

    def test_chunk_budget_degradation(self):
        ctl = QosController(QosConfig(degraded_max_chunks_per_iter=1))
        assert ctl.max_chunks(4) == 4
        ctl.level = LEVEL_DEGRADE
        assert ctl.max_chunks(4) == 1
        ctl.level = LEVEL_HEALTHY
        assert ctl.max_chunks(4) == 4


# ---------------------------------------------------------------------------
# priority scheduler
# ---------------------------------------------------------------------------

class TestPriorityScheduler:
    def _sched(self, **kw):
        from deepspeed_tpu.serving.scheduler import FifoScheduler
        return FifoScheduler(ServingConfig(max_len=64, **kw))

    def _req(self, rid, priority=0, deadline=None):
        from deepspeed_tpu.serving.request import Request
        r = Request(np.ones(3, np.int32), 4, rid, deadline_steps=deadline,
                    priority=priority)
        r.submitted_iteration = 0
        return r

    def test_priority_order_fifo_within_class(self):
        s = self._sched()
        for rid, prio in [("a0", 0), ("b2", 2), ("c0", 0), ("d1", 1),
                          ("e2", 2)]:
            s.add(self._req(rid, prio))
        order = [s.next_request().request_id for _ in range(5)]
        assert order == ["b2", "e2", "d1", "a0", "c0"]

    def test_requeue_goes_to_class_front(self):
        s = self._sched()
        s.add(self._req("a", 1))
        s.add(self._req("b", 1))
        pre = self._req("v", 1)
        s.requeue(pre)
        assert s.peek() is pre          # front of its class
        s.add(self._req("hi", 2))
        assert s.peek().request_id == "hi"   # higher class still wins

    def test_shed_queued_and_expire_exemptions(self):
        s = self._sched()
        lo, hi = self._req("lo", 0, deadline=1), self._req("hi", 2,
                                                           deadline=1)
        resumable = self._req("res", 0, deadline=1)
        resumable.tokens.append(7)      # preempted-with-progress
        for r in (lo, hi, resumable):
            s.add(r)
        shed = s.shed_queued(lambda r: r.priority == 0 and not r.tokens)
        assert [r.request_id for r in shed] == ["lo"]
        # expire never claims a token-bearing (resumable) request
        expired = s.expire(iteration=100)
        assert [r.request_id for r in expired] == ["hi"]
        assert s.peek() is resumable


# ---------------------------------------------------------------------------
# priority preemption -> requeue -> resume (the tentpole acceptance)
# ---------------------------------------------------------------------------

class TestPreemption:
    @pytest.mark.slow
    def test_preempt_requeue_resume_token_exact(self):
        """2 slots saturated by low-priority requests; a late interactive
        request preempts one back to the queue. EVERY request — the
        preempted-then-resumed one included — must match its uncontended
        generate() reference exactly, and the interactive TTFT must beat
        waiting for a natural slot release."""
        m, params = _model(vocab=61)
        eng = ServingEngine(m, params, ServingConfig(
            num_slots=2, max_len=128, qos=_qos()))
        r = np.random.RandomState(0)
        lows = [eng.submit(r.randint(1, 61, size=6), max_new_tokens=20,
                           request_id=f"low{i}", priority=0)
                for i in range(2)]
        for _ in range(3):
            eng.advance()
        hi = eng.submit(r.randint(1, 61, size=5), max_new_tokens=4,
                        request_id="hi", priority=2)
        eng.run()

        assert hi.status == "finished"
        assert sum(q.preemptions for q in lows) == 1
        victim = next(q for q in lows if q.preemptions)
        assert victim.resumptions == 1 and victim.status == "finished"
        # preemption must beat head-of-line blocking: the 20-token heads
        # would otherwise hold both slots for ~17 more iterations
        assert (hi.first_token_iteration - hi.submitted_iteration) <= 4
        for req in [hi] + lows:
            _assert_token_exact(m, params, req)
        snap = eng.metrics.snapshot()
        assert snap["requests_preempted"] == 1
        assert snap["requests_resumed"] == 1
        assert snap["class/batch/preempted"] == 1
        assert snap["class/batch/resumed"] == 1

    @pytest.mark.slow
    def test_preempt_resume_token_exact_paged(self):
        """Same contract for a page-starved head: pages released at
        preemption, resumption re-prefills prompt + partial output
        (prefix-cache hits make it cheap), outputs stay token-exact."""
        m, params = _model(vocab=61)
        paging = PagingConfig(page_len=16, num_pages=2 * (128 // 16) + 1)
        eng = ServingEngine(m, params, ServingConfig(
            num_slots=3, max_len=128, paging=paging,
            qos=_qos()))
        r = np.random.RandomState(3)
        # two requests whose budgets together exhaust the 2-row pool
        lows = [eng.submit(r.randint(1, 61, size=40), max_new_tokens=80,
                           request_id=f"pl{i}", priority=0)
                for i in range(2)]
        for _ in range(8):
            eng.advance()
        hi = eng.submit(r.randint(1, 61, size=8), max_new_tokens=4,
                        request_id="phi", priority=2)
        eng.run()
        assert hi.status == "finished"
        assert eng.metrics.requests_preempted >= 1
        assert eng.metrics.requests_resumed >= 1
        for req in [hi] + lows:
            assert req.status == "finished"
            _assert_token_exact(m, params, req)

    def test_no_preemption_without_qos_or_risk(self):
        """Without a qos block (or before preempt_after_steps elapses)
        nothing is ever preempted — the pre-QoS engine is untouched."""
        m, params = _model(vocab=61)
        eng = ServingEngine(m, params, ServingConfig(
            num_slots=1, max_len=128))
        r = np.random.RandomState(1)
        a = eng.submit(r.randint(1, 61, size=4), max_new_tokens=10,
                       priority=0)
        b = eng.submit(r.randint(1, 61, size=4), max_new_tokens=3,
                       priority=9)
        eng.run()
        assert a.preemptions == 0 and b.status == "finished"
        assert eng.metrics.requests_preempted == 0


# ---------------------------------------------------------------------------
# SLO-aware shedding under overload (deterministic)
# ---------------------------------------------------------------------------

class TestOverloadShedding:
    def _overload_run(self, m, params):
        """~3x overload: bursts of 8 arriving every ~8 steps against 4
        slots serving ~16-token outputs — offered load far beyond
        capacity, the ladder must shed batch while interactive holds."""
        if REPO_ROOT not in sys.path:
            sys.path.insert(0, REPO_ROOT)
        from benchmarks.serving.load_harness import make_qos_trace, replay
        qos = _qos(shed_queue_depth=8, ladder_patience_steps=4,
                   classes=[
                       {"name": "interactive", "priority": 2,
                        "ttft_slo_steps": 32, "preempt_after_steps": 4,
                        "sheddable": False},
                       {"name": "standard", "priority": 1,
                        "ttft_slo_steps": 128},
                       {"name": "batch", "priority": 0},
                   ])
        eng = ServingEngine(m, params, ServingConfig(
            num_slots=4, max_len=128, qos=qos))
        trace = make_qos_trace("burst", seed=0, num_requests=40,
                               vocab_size=61, prompt_len_range=(4, 32),
                               output_len_range=(4, 16),
                               mean_interarrival=1.0)
        handles = replay(eng, trace)
        return eng, trace, handles

    def test_3x_overload_sheds_deterministically_and_holds_slo(self):
        m, params = _model(vocab=61)
        runs = []
        for _ in range(2):
            eng, trace, handles = self._overload_run(m, params)
            shed_ids = sorted(h.request_id for h in handles
                              if h.status == "shed")
            stamps = [(h.request_id, h.status, h.first_token_iteration)
                      for h in handles]
            runs.append((shed_ids, stamps, eng.metrics.snapshot()))
        (shed_a, stamps_a, snap_a), (shed_b, stamps_b, snap_b) = runs
        # same trace -> same shed set, same step-clock stamps, bit-exact
        assert shed_a == shed_b and shed_a
        assert stamps_a == stamps_b
        # the ladder actually engaged and batch bore the shedding
        assert snap_a["requests_shed"] == len(shed_a)
        assert snap_a["class/batch/shed"] > 0
        # protected interactive: never shed, p95 TTFT inside its SLO
        assert snap_a.get("class/interactive/shed", 0) == 0
        assert snap_a["class/interactive/ttft_steps_p95"] <= 32
        # shed is an explicit status with a reason, not a TTL expiry
        assert sum(v for k, v in snap_a.items()
                   if k.startswith("shed/")) == len(shed_a)
        assert snap_a["requests_timed_out"] == 0

    def test_queue_ttl_still_sheds_without_qos(self):
        """The pre-QoS deadline TTL path is untouched: no qos block, a
        deadline still times out deterministically."""
        m, params = _model(vocab=61)
        eng = ServingEngine(m, params, ServingConfig(
            num_slots=1, max_len=128))
        r = np.random.RandomState(5)
        head = eng.submit(r.randint(1, 61, size=4), max_new_tokens=12)
        late = eng.submit(r.randint(1, 61, size=4), max_new_tokens=4,
                          deadline_steps=3)
        eng.run()
        assert head.status == "finished" and late.status == "timeout"


# ---------------------------------------------------------------------------
# fault containment: OOM shed, hung-decode watchdog, recovery
# ---------------------------------------------------------------------------

class _FaultyChunks:
    """Stands where the manager's module global ``_chunk_prefill_jit``
    does (the manager compiles a width ahead, then runs the executable):
    every executable it hands out calls ``before()`` first."""

    def __init__(self, before):
        self.before, self.program = before, manager_mod._chunk_prefill_jit

    def compile_ahead(self, *args, **kwargs):
        run = self.program.compile_ahead(*args, **kwargs)

        def faulty(*call_args):
            self.before()
            return run(*call_args)
        return faulty


class TestFaultContainment:
    def test_oom_on_admit_sheds_and_keeps_serving(self, monkeypatch):
        """An injected RESOURCE_EXHAUSTED during prefill sheds exactly that
        request (status shed, reason oom, forensics captured) and the
        engine finishes everyone else token-exactly — no process death."""
        m, params = _model(vocab=61)
        eng = ServingEngine(m, params, ServingConfig(
            num_slots=2, max_len=128, qos=_qos()))
        r = np.random.RandomState(1)
        reqs = [eng.submit(r.randint(1, 61, size=5), max_new_tokens=4,
                           request_id=i, priority=1) for i in range(3)]
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] == 2:
                raise RuntimeError(
                    "RESOURCE_EXHAUSTED: Out of memory while trying to "
                    "allocate 9437184 bytes.")
        monkeypatch.setattr(manager_mod, "_chunk_prefill_jit",
                            _FaultyChunks(flaky))
        eng.run()

        statuses = [q.status for q in reqs]
        assert statuses.count("shed") == 1
        shed = next(q for q in reqs if q.status == "shed")
        assert shed.shed_reason == "oom"
        assert eng.last_oom_forensics is not None
        assert "RESOURCE_EXHAUSTED" in eng.last_oom_forensics["reason"]
        for q in reqs:
            if q.status == "finished":
                _assert_token_exact(m, params, q)
        snap = eng.metrics.snapshot()
        assert snap["shed/oom"] == 1 and snap["recoveries"] == 1
        kinds = [f["kind"] for f in snap["faults"]]
        assert "oom" in kinds and "recovery" in kinds
        # a non-OOM error still propagates (no blanket swallowing)
        def boom():
            raise RuntimeError("boom")
        monkeypatch.setattr(manager_mod, "_chunk_prefill_jit",
                            _FaultyChunks(boom))
        eng._paged.chunk_programs.clear()    # compile through the patch
        eng.submit(r.randint(1, 61, size=4), max_new_tokens=2, priority=1)
        with pytest.raises(RuntimeError, match="boom"):
            eng.run()

    @pytest.mark.slow
    def test_watchdog_fires_recovers_and_stays_token_exact(self,
                                                           monkeypatch):
        """An injected hung decode dispatch trips the watchdog; the next
        advance() runs requeue-and-re-prefill recovery and every request
        still finishes token-exactly (no process death)."""
        m, params = _model(vocab=61)
        eng = ServingEngine(m, params, ServingConfig(
            num_slots=2, max_len=128,
            qos=_qos(watchdog_timeout_s=0.15)))
        r = np.random.RandomState(2)
        reqs = [eng.submit(r.randint(1, 61, size=5), max_new_tokens=6,
                           request_id=f"w{i}", priority=1)
                for i in range(3)]
        orig = manager_mod._paged_decode_jit
        calls = {"n": 0}
        escalations = []
        # the stall spans two watchdog windows, so the hard-abort
        # escalation may also fire — capture it instead of os._exit so
        # the soft recovery path under test can still run to completion
        eng.on_watchdog_fatal = escalations.append

        def stalled(*a, **kw):
            calls["n"] += 1
            if calls["n"] == 2:
                time.sleep(0.5)     # well past the 0.15s watchdog budget
            return orig(*a, **kw)
        monkeypatch.setattr(manager_mod, "_paged_decode_jit", stalled)
        try:
            eng.run()
        finally:
            eng.close()
        snap = eng.metrics.snapshot()
        kinds = [f["kind"] for f in snap["faults"]]
        assert "watchdog" in kinds
        assert snap["recoveries"] >= 1
        for q in reqs:
            assert q.status == "finished"
            _assert_token_exact(m, params, q)

    def test_watchdog_escalates_when_flag_never_consumed(self):
        """A TRULY hung dispatch never reaches the next advance(), so the
        soft flag alone cannot recover it: one full extra watchdog window
        with the flag unconsumed runs the fatal escalation hook (default
        os._exit(70) — the serve CLI hangs its partial-snapshot emitter
        here). A consumed flag (the dispatch was merely slow) must NOT
        escalate."""
        m, params = _model(vocab=61)
        eng = ServingEngine(m, params, ServingConfig(
            num_slots=1, max_len=128,
            qos=_qos(watchdog_timeout_s=0.1)))
        fatals = []
        eng.on_watchdog_fatal = fatals.append
        eng._on_watchdog_fire("stuck report")        # flag never consumed
        time.sleep(0.3)
        assert fatals == ["stuck report"]
        # consumed-flag case: the engine loop picked it up in time
        eng._on_watchdog_fire("slow report")
        eng._watchdog_report = None                  # advance() consumed it
        time.sleep(0.3)
        assert fatals == ["stuck report"]            # no second escalation
        eng.close()

    def test_watchdog_disarms_on_healthy_steps(self):
        """A generous timeout never fires across a healthy run (the
        arm/disarm bracket really disarms between dispatches)."""
        m, params = _model(vocab=61)
        eng = ServingEngine(m, params, ServingConfig(
            num_slots=2, max_len=128,
            qos=_qos(watchdog_timeout_s=30.0)))
        r = np.random.RandomState(4)
        reqs = [eng.submit(r.randint(1, 61, size=4), max_new_tokens=3,
                           priority=1) for _ in range(3)]
        eng.run()
        assert eng._watchdog is not None and not eng._watchdog.fired
        assert all(q.status == "finished" for q in reqs)
        assert eng.metrics.faults == []
        eng.close()
        assert eng._watchdog is None    # close() tears the thread down

    @pytest.mark.slow
    def test_recover_requeues_queued_and_active(self):
        """engine.recover() — the engine-restart path: every active
        request is requeued with tokens retained, queued requests stay
        queued, and the rerun finishes everyone token-exactly."""
        m, params = _model(vocab=61)
        eng = ServingEngine(m, params, ServingConfig(
            num_slots=2, max_len=128, qos=_qos()))
        r = np.random.RandomState(6)
        reqs = [eng.submit(r.randint(1, 61, size=5), max_new_tokens=8,
                           request_id=f"r{i}", priority=i % 2)
                for i in range(4)]
        for _ in range(3):
            eng.advance()
        active_before = [q for q in reqs if q.status == "running"]
        assert active_before                    # someone was mid-flight
        eng.recover("simulated engine restart")
        assert all(q.status == "preempted" for q in active_before)
        assert eng.num_free_slots == 2          # device state rebuilt
        eng.run()
        for q in reqs:
            assert q.status == "finished"
            _assert_token_exact(m, params, q)
        snap = eng.metrics.snapshot()
        assert snap["recoveries"] == 1
        assert snap["requests_resumed"] == len(active_before)


# ---------------------------------------------------------------------------
# elasticity: autoscaler + slot-cap drain
# ---------------------------------------------------------------------------

class TestElasticity:
    @pytest.mark.slow
    def test_set_slot_cap_drains_via_preemption(self):
        m, params = _model(vocab=61)
        eng = ServingEngine(m, params, ServingConfig(
            num_slots=3, max_len=128, qos=_qos()))
        r = np.random.RandomState(7)
        reqs = [eng.submit(r.randint(1, 61, size=5), max_new_tokens=10,
                           request_id=f"s{i}", priority=1)
                for i in range(3)]
        for _ in range(4):                      # one prefill chunk each
            eng.advance()
        assert sum(q.status == "running" for q in reqs) == 3
        eng.set_slot_cap(1)                     # drain, don't drop
        drained = [q for q in reqs if q.status == "preempted"]
        assert len(drained) == 2
        assert all(q.tokens for q in drained)   # progress retained
        eng.run()
        for q in reqs:
            assert q.status == "finished"
            _assert_token_exact(m, params, q)
        assert eng.slot_cap == 1
        assert eng.metrics.snapshot()["slot_cap"] == 1

    def test_autoscaler_recommends_and_applies(self):
        from deepspeed_tpu.elasticity import (ServingAutoscaleConfig,
                                              ServingAutoscaler)
        m, params = _model(vocab=61)
        eng = ServingEngine(m, params, ServingConfig(
            num_slots=4, max_len=128, qos=_qos()))
        eng.set_slot_cap(2)
        scaler = ServingAutoscaler(
            eng, ServingAutoscaleConfig(patience=2, min_slots=1))
        r = np.random.RandomState(8)
        reqs = [eng.submit(r.randint(1, 61, size=5), max_new_tokens=12,
                           priority=1) for _ in range(8)]
        decisions = []
        while eng.busy:
            eng.advance()
            decisions.append(scaler.observe())
        ups = [d for d in decisions if d["action"] == "scale_up"]
        assert ups, "saturation never produced a scale-up recommendation"
        assert ups[0]["target_slots"] > 2
        applied = scaler.apply(ups[0])
        assert applied["applied_slot_cap"] == ups[0]["target_slots"]
        assert eng.slot_cap == applied["applied_slot_cap"]
        eng.run()
        assert all(q.status == "finished" for q in reqs)
        # drained-idle path: empty queue + idle slots recommends down
        for _ in range(4):
            eng.advance()
            d = scaler.observe()
        assert d["action"] in ("scale_down", "hold")
        from deepspeed_tpu.observability.metrics import get_registry
        assert get_registry().gauge("elasticity/slot_cap_target").value \
            is not None

    def test_autoscaler_replica_hint_when_maxed(self):
        from deepspeed_tpu.elasticity import (ServingAutoscaleConfig,
                                              ServingAutoscaler)
        from deepspeed_tpu.observability.metrics import get_registry
        reg = get_registry()
        scaler = ServingAutoscaler(
            None, ServingAutoscaleConfig(patience=1), registry=reg)
        reg.gauge("serving/queue_depth").set(40)
        reg.gauge("serving/active_slots").set(8)
        reg.gauge("serving/slot_cap").set(8)
        d = scaler.observe()
        assert d["action"] == "scale_up" and d["target_replicas"] >= 2

    def test_config_validation(self):
        from deepspeed_tpu.elasticity import ServingAutoscaleConfig
        with pytest.raises(ValueError, match="min_slots"):
            ServingAutoscaleConfig(min_slots=0).validate()
        with pytest.raises(ValueError, match="patience"):
            ServingAutoscaleConfig(patience=0).validate()
        with pytest.raises(ValueError, match="occupancy_low"):
            ServingAutoscaleConfig(occupancy_low=2.0).validate()


# ---------------------------------------------------------------------------
# telemetry surface: per-class metrics in /statusz + the snapshot
# ---------------------------------------------------------------------------

class TestQosTelemetry:
    def test_class_breakdown_and_qos_reach_statusz(self):
        from deepspeed_tpu.observability.export import build_statusz
        m, params = _model(vocab=61)
        eng = ServingEngine(m, params, ServingConfig(
            num_slots=2, max_len=128, qos=_qos()))
        r = np.random.RandomState(9)
        for i in range(3):
            eng.submit(r.randint(1, 61, size=4), max_new_tokens=3,
                       priority=i % 3)
        eng.run()
        statusz = build_statusz(eng.metrics_snapshot())
        serving = statusz["serving"]
        assert any(k.startswith("class/interactive/") for k in serving)
        assert any(k.startswith("class/batch/") for k in serving)
        assert serving["requests_shed"] == 0
        assert statusz["qos"]["level_name"] == "healthy"
        # registry counters exist for the fleet scrape path
        from deepspeed_tpu.observability.metrics import get_registry
        snap = get_registry().snapshot()
        assert "serving/qos_level" in snap["gauges"]


def test_serving_and_elasticity_subsystems_lint_clean():
    """The CI zero-finding gate over every subsystem this PR touches:
    serving (incl. qos + paging), elasticity, the serve CLI, and the
    bench harness — no baseline, no new suppressions."""
    from deepspeed_tpu.analysis.cli import main as lint_main
    assert lint_main([
        os.path.join(REPO_ROOT, "deepspeed_tpu", "serving"),
        os.path.join(REPO_ROOT, "deepspeed_tpu", "elasticity"),
        os.path.join(REPO_ROOT, "benchmarks", "serving"),
        os.path.join(REPO_ROOT, "bin", "ds_tpu_serve"),
        "-q"]) == 0
