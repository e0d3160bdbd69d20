"""What the server and the trainer record about their own time (ISSUE
24): the always-on histograms and counters in the process registry
reconcile with the traffic, with and without an active ``Tracer``; the
spans nest the way interval arithmetic on them assumes; and the new
sites cost no span object while tracing is off."""

import time

import numpy as np
import pytest

from deepspeed_tpu.observability import (Tracer, activate, active_tracer,
                                         deactivate, get_registry)
from deepspeed_tpu.observability import trace as trace_mod
from deepspeed_tpu.serving import ServingConfig
from deepspeed_tpu.serving.engine import ServingEngine
from deepspeed_tpu.serving.paging import PagingConfig

from .test_observability import make_batch, make_engine
from .test_serving_paging import _model

COUNTERS = ("serving/decode_slots_busy", "serving/decode_slots_offered",
            "serving/prefill_tokens_reused",
            "serving/prefill_tokens_computed")
HISTOGRAMS = ("serving/queue_wait_ms", "serving/prefill_wait_ms",
              "serving/advance_readback_ms", "serving/advance_host_ms",
              "train/host_to_dispatch_ms")
RETROACTIVE = ("serving/queue_wait", "serving/decode_residency")
# open from one call into a later one, so they nest in no advance()
# (tests/unit/test_iteration_log.py holds them to where they do lie)
BETWEEN_CALLS = ("serving/caller", "serving/gc")
SLOTS = 2


@pytest.fixture(autouse=True)
def _clean_tracer():
    yield
    deactivate()


class Since:
    """The registry's growth since this object was made: the process
    registry is shared by every test of the worker, so all is read as
    deltas."""

    def __init__(self):
        reg = get_registry()
        self.counters = {n: reg.counter(n).value for n in COUNTERS}
        self.counts = {n: reg.histogram(n).count for n in HISTOGRAMS}

    def counter(self, name):
        return get_registry().counter(name).value - self.counters[name]

    def samples(self, name):
        h = get_registry().histogram(name)
        new = h.count - self.counts[name]
        assert new <= h.window.maxlen
        return list(h.window)[len(h.window) - new:]


def _paged_server():
    m, params = _model()
    return ServingEngine(m, params, ServingConfig(
        num_slots=SLOTS, max_len=128, seed=0,
        paging=PagingConfig(page_len=16, prefill_chunk=16)))


def _prompts():
    """Five prompts behind one 48-token system prompt: the first two are
    admitted together into the two free slots (nothing published yet),
    the later ones reuse the published pages."""
    r = np.random.RandomState(11)
    sys_p = r.randint(1, 97, size=48).astype(np.int32)
    return [np.concatenate([sys_p, r.randint(1, 97, size=int(n))
                            .astype(np.int32)])
            for n in r.randint(2, 10, size=5)]


def _serve(eng, prompts):
    reqs = [eng.submit(p, max_new_tokens=4) for p in prompts]
    worked = 0
    while eng.busy:
        eng.advance()
        worked += 1
    assert all(r.done for r in reqs)
    return reqs, worked


def _inside(inner, outers):
    t0, t1 = inner[1], inner[1] + inner[2]
    return any(o[1] <= t0 and t1 <= o[1] + o[2] for o in outers)


@pytest.mark.parametrize("traced", [False, True], ids=["off", "traced"])
def test_server_accounts_for_its_time_and_the_counts_reconcile(traced):
    eng = _paged_server()
    prompts = _prompts()
    tracer = Tracer()
    if traced:
        activate(tracer)
    since = Since()
    reqs, worked = _serve(eng, prompts)
    eng.advance()                       # idle: records nothing
    deactivate()

    # prefix reuse: every admitted prompt token was reused or computed
    reused = since.counter("serving/prefill_tokens_reused")
    computed = since.counter("serving/prefill_tokens_computed")
    assert reused + computed == sum(len(p) for p in prompts)
    assert reused >= 3 * 48             # the three later requests hit
    assert (reused, computed) == (eng.metrics.prefill_tokens_reused,
                                  eng.metrics.prefill_tokens_computed)

    # occupancy: decoding slots of the slots every decode dispatch offers
    offered = since.counter("serving/decode_slots_offered")
    busy = since.counter("serving/decode_slots_busy")
    assert offered == eng.metrics.decode_iterations * SLOTS
    assert 0 < busy <= offered
    # a slot still waiting for its prefill chunks is not decoding: the
    # first request alone runs four chunks before anything decodes
    assert busy < offered

    # one host and one read-back sample per advance() that had work
    host = since.samples("serving/advance_host_ms")
    readback = since.samples("serving/advance_readback_ms")
    assert len(host) == len(readback) == worked
    assert min(host) > 0 and min(readback) >= 0 and max(readback) > 0

    # one queue wait and one prefill wait per admission
    queue_wait = since.samples("serving/queue_wait_ms")
    prefill_wait = since.samples("serving/prefill_wait_ms")
    assert len(queue_wait) == len(prefill_wait) == len(prompts)
    assert eng.metrics.requests_admitted == len(prompts)
    # the first two were admitted together; the second waited through
    # the first one's chunks, one an iteration, holding its slot
    assert reqs[0].admitted_iteration == reqs[1].admitted_iteration
    assert prefill_wait[1] > prefill_wait[0] >= 0
    assert prefill_wait[1] == pytest.approx(
        (reqs[1].first_chunk_at_ns - reqs[1].admitted_at_ns) / 1e6)
    # ... while it had hardly queued at all
    assert queue_wait[1] < prefill_wait[1]

    if not traced:
        assert not tracer.events
        return

    events = list(tracer.events)
    by_name = {}
    for e in events:
        by_name.setdefault(e[0], []).append(e)
    advances = by_name["serving/advance"]
    assert len(advances) == worked + 1          # the idle one is spanned
    live = [e for e in events if e[0].startswith("serving/")
            and e[0] != "serving/advance"
            and e[0] not in RETROACTIVE + BETWEEN_CALLS]
    assert {"serving/admission", "serving/prefill_chunk",
            "serving/decode_iter", "serving/harvest", "serving/readback",
            "serving/page_table_copy"} <= \
        {e[0] for e in live}
    outside = [e[0] for e in live if not _inside(e, advances)]
    assert not outside, outside
    assert all(_inside(e, by_name["serving/harvest"])
               for e in by_name["serving/readback"])
    assert len(by_name["serving/admission"]) == len(advances)
    assert sum(e[4]["admitted"] for e in by_name["serving/admission"]) \
        == len(prompts)
    assert [e[4]["iteration"] for e in advances] == sorted(
        e[4]["iteration"] for e in advances)
    # one computation, two consumers: the retroactive span is the
    # histogram's sample
    assert [e[2] / 1e6 for e in by_name["serving/queue_wait"]] == \
        pytest.approx(queue_wait)
    # the read-back spans lie inside the clock reads that time them
    spanned = sum(e[2] for e in by_name["serving/readback"]) / 1e6
    assert 0 < spanned <= sum(readback)


@pytest.mark.parametrize("traced", [False, True], ids=["off", "traced"])
def test_trainer_times_entry_to_dispatch_and_brackets_the_step(traced):
    eng = make_engine()
    batch = make_batch(16)
    eng.train_batch(batch)              # compiles
    tracer = Tracer()
    if traced:
        activate(tracer)
    since = Since()
    for _ in range(3):
        eng.train_batch(batch)
    deactivate()
    eng.destroy()
    waits = since.samples("train/host_to_dispatch_ms")
    assert len(waits) == 3 and min(waits) > 0
    if not traced:
        assert not tracer.events
        return
    names = [e[0] for e in tracer.events]
    assert names == ["data", "train/prepare", "fwd_bwd_step",
                     "train/finish"] * 3
    for i in range(0, len(names), 4):
        data, prepare, step, finish = list(tracer.events)[i:i + 4]
        assert data[1] + data[2] <= prepare[1]
        assert prepare[1] + prepare[2] <= step[1]
        assert step[1] + step[2] <= finish[1]
        # entry -> program enqueued covers data, prepare and the dispatch
        assert waits[i // 4] >= (data[2] + prepare[2] + step[2]) / 1e6


def test_with_no_tracer_the_new_sites_allocate_no_span(monkeypatch):
    """Tracing off: ``advance()`` and ``train_batch()`` get the shared
    no-op from every ``span()`` call, never a span object, and a whole
    idle ``advance()`` with its two sites and no clock read stays within
    the disabled-path budget."""
    assert active_tracer() is None
    made = []
    real = trace_mod._Span.__init__

    def counting(self, *a, **kw):
        made.append(a)
        real(self, *a, **kw)
    monkeypatch.setattr(trace_mod._Span, "__init__", counting)
    eng = _paged_server()
    _serve(eng, _prompts()[:2])
    trainer = make_engine()
    trainer.train_batch(make_batch(16))
    trainer.destroy()
    assert not made
    n = 2_000
    t0 = time.perf_counter()
    for _ in range(n):
        eng.advance()
    per_call = (time.perf_counter() - t0) / n
    # an idle advance() is a few dozen attribute reads; the budget is
    # ~10x what it takes here and far under one decode iteration
    assert per_call < 200e-6, f"{per_call * 1e6:.1f}us per idle advance()"
    activate(Tracer())
    eng.advance()
    assert [a[1] for a in made] == ["serving/advance", "serving/admission"]
