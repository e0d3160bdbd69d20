"""Continuous-batching serving engine (deepspeed_tpu/serving/).

The acceptance test drives 33 requests with mixed prompt/output lengths
through 4 slots (slots << requests) and requires every request's tokens
to EXACTLY match a per-request whole-batch generate() reference, with
jit-cache-size assertions proving decode compiles once and prefill at
most once per chunk width.
"""

import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.gpt import GPT, GPTConfig
from deepspeed_tpu.inference.generation import generate, init_cache
from deepspeed_tpu.serving import (PagingConfig, ServingConfig,
                                   SpeculationConfig)
from deepspeed_tpu.serving.engine import ServingEngine
from deepspeed_tpu.serving.paging.manager import (PagedKVManager,
                                                  _chunk_prefill_jit,
                                                  _paged_decode_jit)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _model(vocab=97, max_seq_len=128, d_model=32, n_layers=2, n_heads=2,
           scan_layers=True, seed=0, **kw):
    cfg = GPTConfig(vocab_size=vocab, max_seq_len=max_seq_len,
                    d_model=d_model, n_layers=n_layers, n_heads=n_heads,
                    dtype=jnp.float32, scan_layers=scan_layers, **kw)
    m = GPT(cfg)
    params = m.init(jax.random.PRNGKey(seed),
                    jnp.ones((1, 8), jnp.int32))["params"]
    return m, params


def _mixed_workload(n, vocab, seed=0, prompt_range=(3, 24), out_range=(1, 8)):
    r = np.random.RandomState(seed)
    prompts = [r.randint(1, vocab, size=r.randint(*prompt_range)
                         ).astype(np.int32) for _ in range(n)]
    outs = [int(r.randint(*out_range)) for _ in range(n)]
    return prompts, outs


# ---------------------------------------------------------------------------
# config
# ---------------------------------------------------------------------------

class TestServingConfig:
    @pytest.mark.parametrize("max_len, spec_tokens, cache_len", [
        (48, 0, 128), (100, 0, 128), (128, 0, 128), (1000, 0, 1024),
        (2048, 0, 2048), (128, 4, 256)])
    def test_absent_paging_block_validates_and_pages(self, max_len,
                                                     spec_tokens, cache_len):
        """No ``paging`` block is the default block: the slot capacity is
        a 128 multiple, so the default page always tiles it, and the
        default pool holds a full-length request in every slot."""
        spec = (SpeculationConfig(max_spec_tokens=spec_tokens)
                if spec_tokens else None)
        cfg = ServingConfig(num_slots=3, max_len=max_len,
                            speculation=spec).validate()
        assert cfg.paging == PagingConfig()
        assert cfg.cache_len == cache_len
        assert cfg.cache_len % cfg.paging.page_len == 0
        assert cfg.paging.chunk_tokens == cfg.paging.page_len == 128
        assert cfg.paging.pool_pages(3, cache_len) == 3 * cache_len // 128 + 1
        assert ServingConfig(max_len=max_len, paging=None).paging == cfg.paging

    def test_validation(self):
        with pytest.raises(ValueError, match="num_slots"):
            ServingConfig(num_slots=0).validate()
        with pytest.raises(ValueError, match="page_len"):
            ServingConfig(max_len=128, paging={"page_len": 48}).validate()
        with pytest.raises(ValueError, match="pipeline_depth"):
            ServingConfig(pipeline_depth=-1).validate()
        with pytest.raises(ValueError, match="max_queue"):
            ServingConfig(max_queue=0).validate()
        ServingConfig(max_queue=None).validate()   # unbounded stays legal

    def test_deepspeed_config_block(self):
        from deepspeed_tpu.runtime.config import DeepSpeedConfig
        c = DeepSpeedConfig.from_dict(
            {"serving": {"num_slots": 4, "max_len": 256,
                         "eos_token_id": 2}})
        assert isinstance(c.serving, ServingConfig)
        assert c.serving.num_slots == 4
        assert c.serving.eos_token_id == 2
        assert DeepSpeedConfig.from_dict({}).serving is None


# ---------------------------------------------------------------------------
# cache tree helpers
# ---------------------------------------------------------------------------

class TestCacheHelpers:
    @pytest.mark.parametrize("scan_layers", [
        pytest.param(True, marks=pytest.mark.slow),
        False,
    ])
    def test_set_index_reaches_every_unit(self, scan_layers):
        from deepspeed_tpu.inference.cache import (cache_max_len,
                                                   set_cache_index)
        m, params = _model(scan_layers=scan_layers)
        cache = init_cache(m, params, 3, 128)
        assert cache_max_len(cache) == 128

        lens = jnp.asarray([5, 0, 7], jnp.int32)
        cache = set_cache_index(cache, lens)

        # every cache_index leaf now carries the per-row vector
        def collect(node, out):
            if isinstance(node, dict):
                if "cache_index" in node:
                    out.append(np.asarray(node["cache_index"]))
                for v in node.values():
                    if isinstance(v, dict):
                        collect(v, out)
            return out
        from flax.core import unfreeze
        idxs = collect(unfreeze(cache), [])
        assert idxs
        for a in idxs:
            np.testing.assert_array_equal(a.reshape(-1, 3)[-1], [5, 0, 7])


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------

class TestScheduler:
    def test_fifo_and_queue_cap(self):
        from deepspeed_tpu.serving.scheduler import FifoScheduler
        from deepspeed_tpu.serving.request import Request
        sched = FifoScheduler(ServingConfig(max_queue=2, max_len=64))
        a = Request(np.ones(3, np.int32), 4, "a")
        b = Request(np.ones(3, np.int32), 4, "b")
        sched.add(a)
        sched.add(b)
        with pytest.raises(RuntimeError, match="queue full"):
            sched.add(Request(np.ones(3, np.int32), 4, "c"))
        assert sched.next_request() is a
        assert sched.next_request() is b
        assert sched.next_request() is None

    def test_budget_validation(self):
        from deepspeed_tpu.serving.scheduler import FifoScheduler
        sched = FifoScheduler(ServingConfig(max_len=64))
        sched.validate_request(32, 32)                  # exactly fits
        with pytest.raises(ValueError, match="per-slot budget"):
            sched.validate_request(33, 32)
        with pytest.raises(ValueError, match="empty prompt"):
            sched.validate_request(0, 4)


# ---------------------------------------------------------------------------
# the acceptance integration test
# ---------------------------------------------------------------------------

class TestContinuousBatchingParity:
    @pytest.mark.slow
    def test_33_requests_through_4_slots_match_generate(self):
        """33 mixed-length requests, 4 slots: every request's streamed
        tokens exactly match its whole-batch generate() reference;
        decode compiled once, prefill once (every prompt here is one
        chunk of one page)."""
        # vocab 101 is unique to this test so the jit-cache deltas below
        # cannot be absorbed by entries from other tests' shapes
        m, params = _model(vocab=101)
        prompts, outs = _mixed_workload(33, 101, seed=0)

        streamed = {}

        def on_token(req, tok):
            streamed.setdefault(req.request_id, []).append(tok)

        eng = ServingEngine(m, params,
                            ServingConfig(num_slots=4, max_len=128, seed=0))
        decode_before = _paged_decode_jit._cache_size()
        prefill_before = _chunk_prefill_jit.record.compiles
        reqs = [eng.submit(p, max_new_tokens=o, on_token=on_token)
                for p, o in zip(prompts, outs)]
        eng.run()

        assert _paged_decode_jit._cache_size() == decode_before + 1
        assert _chunk_prefill_jit.record.compiles == prefill_before + 1

        for req, p, o in zip(reqs, prompts, outs):
            assert req.done
            ref = np.asarray(generate(m, params, p[None], max_new_tokens=o,
                                      temperature=0.0, max_len=128)
                             )[0, len(p):]
            np.testing.assert_array_equal(np.asarray(req.output_tokens), ref,
                                          err_msg=f"request {req.request_id}")
            # streamed tokens arrived in order and match the final result
            assert streamed[req.request_id] == req.output_tokens

        # slots really were recycled: far more admissions than slots, and
        # the queue actually backed up behind the pool
        snap = eng.metrics.snapshot()
        assert snap["requests_admitted"] == 33 > eng.config.num_slots
        assert snap["requests_finished"] == 33
        assert snap["queue_depth_max"] > 0
        assert snap["tokens_generated"] == sum(outs)
        assert not eng.busy and eng.num_free_slots == 4

    @pytest.mark.parametrize("prompt_len", [1, 128, 129, 300])
    def test_every_prompt_length_has_a_compiled_home(self, prompt_len):
        """One token, one page exactly, one page plus one, three pages:
        through the DEFAULT config (no paging block) each equals
        generate() token for token, from one decode program and the
        prefill program at one page (admitted alone a prompt goes in the
        widest chunks its pages fill, and none of these fills four): the
        chunks hold every page of it once."""
        m, params = _model(vocab=83, max_seq_len=512)
        prompt = np.random.RandomState(prompt_len).randint(
            1, 83, size=prompt_len).astype(np.int32)
        eng = ServingEngine(m, params, ServingConfig(num_slots=2,
                                                     max_len=512))
        assert isinstance(eng._paged, PagedKVManager)
        req = eng.submit(prompt, max_new_tokens=4)
        eng.run()
        ref = np.asarray(generate(m, params, prompt[None], max_new_tokens=4,
                                  temperature=0.0, max_len=512)
                         )[0, prompt_len:]
        np.testing.assert_array_equal(np.asarray(req.output_tokens), ref)
        snap = eng.metrics.snapshot()
        assert snap["prefill_chunk_pages"] == snap["prefill_chunks"] \
            == -(-prompt_len // 128)

    def test_the_registry_holds_the_two_paged_programs_and_no_others(self):
        from deepspeed_tpu.observability.programs import get_program_registry
        m, params = _model(vocab=83, max_seq_len=512)
        eng = ServingEngine(m, params, ServingConfig(num_slots=2,
                                                     max_len=512))
        eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=2)
        eng.run()
        serving = {name for name in get_program_registry().table()
                   if name.startswith("serving/")}
        assert {"serving/paged_decode", "serving/chunk_prefill"} <= serving
        assert serving <= {"serving/paged_decode", "serving/chunk_prefill",
                           "serving/spec_verify_iter"}
        table = eng.metrics_snapshot()["programs"]
        assert table["serving/paged_decode"]["calls"] >= 1
        assert table["serving/chunk_prefill"]["calls"] >= 1

    @pytest.mark.parametrize("arch", [
        pytest.param("gptj", marks=pytest.mark.slow),
        pytest.param("bloom", marks=pytest.mark.slow),
    ])
    def test_rotary_and_alibi_variants(self, arch):
        """Per-slot positions must be exact for rotary (position enters
        q/k) and ALiBi (relative bias computed in-kernel per slot)."""
        variants = {
            "gptj": dict(rotary=True, learned_pos=False,
                         parallel_residual=True, shared_parallel_ln=True,
                         attn_use_bias=False, rotary_dim=8),
            "bloom": dict(alibi=True, learned_pos=False, embed_ln=True),
        }
        m, params = _model(vocab=89, **variants[arch])
        prompts, outs = _mixed_workload(8, 89, seed=1, out_range=(2, 6))
        eng = ServingEngine(m, params,
                            ServingConfig(num_slots=2, max_len=128))
        reqs = [eng.submit(p, max_new_tokens=o)
                for p, o in zip(prompts, outs)]
        eng.run()
        for req, p, o in zip(reqs, prompts, outs):
            ref = np.asarray(generate(m, params, p[None], max_new_tokens=o,
                                      temperature=0.0, max_len=128)
                             )[0, len(p):]
            np.testing.assert_array_equal(np.asarray(req.output_tokens), ref,
                                          err_msg=f"{arch} {req.request_id}")

    @pytest.mark.slow
    def test_eos_completes_slot_early(self):
        """A slot must free on EOS, its stream ending with the EOS token,
        matching the generate() eos semantics truncated at the first hit."""
        m, params = _model(vocab=61)
        prompts, _ = _mixed_workload(6, 61, seed=2)
        # pick an eos that actually occurs: the first greedily generated
        # token of request 0
        probe = np.asarray(generate(m, params, prompts[0][None],
                                    max_new_tokens=1, temperature=0.0,
                                    max_len=128))
        eos = int(probe[0, len(prompts[0])])
        eng = ServingEngine(m, params,
                            ServingConfig(num_slots=2, max_len=128,
                                          eos_token_id=eos))
        reqs = [eng.submit(p, max_new_tokens=8) for p in prompts]
        eng.run()
        hit_early = 0
        for req, p in zip(reqs, prompts):
            ref = np.asarray(generate(m, params, p[None], max_new_tokens=8,
                                      temperature=0.0, eos_token_id=eos,
                                      max_len=128))[0, len(p):]
            got = req.output_tokens
            if eos in got:
                assert got[-1] == eos            # stream STOPS at eos
                assert eos not in got[:-1]
                hit_early += len(got) < 8
            np.testing.assert_array_equal(got, ref[:len(got)])
        assert hit_early > 0   # request 0's first token IS eos by design


# ---------------------------------------------------------------------------
# engine plumbing
# ---------------------------------------------------------------------------

class TestEnginePlumbing:
    def test_submit_validation_and_init_guards(self):
        m, params = _model()
        eng = ServingEngine(m, params, ServingConfig(num_slots=1,
                                                     max_len=64))
        with pytest.raises(ValueError, match="per-slot budget"):
            eng.submit(np.ones(60, np.int32), max_new_tokens=8)
        with pytest.raises(ValueError, match="max_seq_len"):
            # model max_seq_len=128 < requested slot budget
            ServingEngine(m, params, ServingConfig(num_slots=1,
                                                   max_len=256))
        with pytest.raises(ValueError, match="config= or as keyword"):
            ServingEngine(m, params, ServingConfig(), num_slots=2)

    @pytest.mark.slow
    def test_inference_engine_serve_bridge(self):
        import deepspeed_tpu
        m, params = _model(vocab=53)
        eng = deepspeed_tpu.init_inference(m, params=params,
                                           dtype=jnp.float32)
        srv = eng.serve({"num_slots": 2, "max_len": 64})
        req = srv.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=3)
        srv.run()
        ref = np.asarray(eng.generate(np.arange(1, 6, dtype=np.int32)[None],
                                      max_new_tokens=3, max_len=64))
        np.testing.assert_array_equal(req.output_tokens, ref[0, 5:])

    def test_from_config_and_metrics_monitor_flush(self):
        class FakeMonitor:
            enabled = True

            def __init__(self):
                self.events = []

            def write_events(self, events):
                self.events.extend(events)

        m, params = _model(vocab=53)
        mon = FakeMonitor()
        srv = ServingEngine.from_config(
            m, params, {"serving": {"num_slots": 2, "max_len": 64,
                                    "metrics_interval": 1}}, monitor=mon)
        for p in (np.arange(1, 5, dtype=np.int32),
                  np.arange(1, 9, dtype=np.int32)):
            srv.submit(p, max_new_tokens=3)
        srv.run()
        labels = {label for label, _, _ in mon.events}
        assert "serving/queue_depth" in labels
        assert "serving/slot_occupancy" in labels
        snap = srv.metrics.snapshot()
        assert snap["tokens_generated"] == 6
        assert snap["requests_finished"] == 2
        assert snap["ttft_steps_p50"] is not None
        assert 0 < snap["slot_occupancy_mean"] <= 1

    @pytest.mark.slow
    def test_interleaved_submit_and_advance(self):
        """submit() during service (the online pattern): later arrivals
        join the running batch and still match their references."""
        m, params = _model(vocab=71)
        prompts, outs = _mixed_workload(6, 71, seed=3, out_range=(3, 6))
        eng = ServingEngine(m, params,
                            ServingConfig(num_slots=2, max_len=128))
        first = [eng.submit(p, max_new_tokens=o)
                 for p, o in zip(prompts[:2], outs[:2])]
        for _ in range(2):
            eng.advance()
        late = [eng.submit(p, max_new_tokens=o)
                for p, o in zip(prompts[2:], outs[2:])]
        eng.run()
        for req, p, o in zip(first + late, prompts, outs):
            ref = np.asarray(generate(m, params, p[None], max_new_tokens=o,
                                      temperature=0.0, max_len=128)
                             )[0, len(p):]
            np.testing.assert_array_equal(np.asarray(req.output_tokens), ref)


# ---------------------------------------------------------------------------
# bench harness + lint gate
# ---------------------------------------------------------------------------

class TestBenchHarness:
    @pytest.mark.slow
    def test_trace_is_deterministic_and_replay_reproduces_steps(self,
                                                                tmp_path):
        import sys
        if REPO_ROOT not in sys.path:
            sys.path.insert(0, REPO_ROOT)
        from benchmarks.serving.load_harness import make_trace, replay
        t1 = make_trace(7, 12, prompt_len_range=(3, 10),
                        output_len_range=(2, 5), vocab_size=59)
        t2 = make_trace(7, 12, prompt_len_range=(3, 10),
                        output_len_range=(2, 5), vocab_size=59)
        assert t1 == t2                                # seeded trace
        arrivals = [t["arrival_step"] for t in t1]
        assert arrivals == sorted(arrivals)

        m, params = _model(vocab=59)

        def run_once():
            eng = ServingEngine(m, params,
                                ServingConfig(num_slots=2, max_len=128,
                                              seed=0))
            handles = replay(eng, make_trace(
                7, 12, prompt_len_range=(3, 10), output_len_range=(2, 5),
                vocab_size=59))
            return ([h.output_tokens for h in handles],
                    [(h.admitted_iteration, h.first_token_iteration,
                      h.finished_iteration) for h in handles])
        tokens_a, steps_a = run_once()
        tokens_b, steps_b = run_once()
        assert tokens_a == tokens_b
        assert steps_a == steps_b      # step-clock metrics reproduce exactly

    @pytest.mark.slow
    def test_replay_admits_same_step_burst_together(self):
        """An idle gap followed by a burst of same-step arrivals must be
        admitted as a burst (filling the slots), not serialized one
        request per idle wake-up."""
        import sys
        if REPO_ROOT not in sys.path:
            sys.path.insert(0, REPO_ROOT)
        from benchmarks.serving.load_harness import replay
        m, params = _model(vocab=59)
        eng = ServingEngine(m, params,
                            ServingConfig(num_slots=3, max_len=128, seed=0))
        r = np.random.RandomState(0)
        trace = [{"id": i, "arrival_step": 50,
                  "prompt": r.randint(1, 59, size=5).tolist(),
                  "max_new_tokens": 3} for i in range(3)]
        handles = replay(eng, trace)
        admits = [h.admitted_iteration for h in handles]
        assert len(set(admits)) == 1, admits   # all admitted together
        assert all(h.done for h in handles)


def test_serving_subsystem_lints_clean():
    """The satellite CI gate: deepspeed_tpu/serving/ ships with ZERO lint
    findings — no baseline file, no suppressions needed."""
    from deepspeed_tpu.analysis.cli import main as lint_main
    assert lint_main([os.path.join(REPO_ROOT, "deepspeed_tpu", "serving"),
                      "-q"]) == 0


# ---------------------------------------------------------------------------
# robustness: queue deadlines (TTL), cancel, timeout/rejection counters
# ---------------------------------------------------------------------------

class TestServingRobustness:
    def test_queued_request_times_out_on_deadline(self):
        """1 slot, a long-running head request, a queued request with a
        tight deadline: the queued one completes with `timeout` status
        instead of waiting forever, and never consumes a slot."""
        m, params = _model(vocab=61)
        eng = ServingEngine(m, params,
                            ServingConfig(num_slots=1, max_len=128))
        r = np.random.RandomState(0)
        head = eng.submit(r.randint(1, 61, size=4), max_new_tokens=12)
        late = eng.submit(r.randint(1, 61, size=4), max_new_tokens=4,
                          deadline_steps=3)
        eng.run()
        assert head.status == "finished"
        assert len(head.output_tokens) == 12
        assert late.status == "timeout"
        assert late.done and late.output_tokens == []
        assert late.finished_iteration is not None
        snap = eng.metrics.snapshot()
        assert snap["requests_timed_out"] == 1
        assert snap["requests_finished"] == 1

    def test_deadline_from_config_default(self):
        m, params = _model(vocab=61)
        eng = ServingEngine(m, params,
                            ServingConfig(num_slots=1, max_len=128,
                                          default_deadline_steps=2))
        r = np.random.RandomState(1)
        head = eng.submit(r.randint(1, 61, size=4), max_new_tokens=10)
        late = eng.submit(r.randint(1, 61, size=4), max_new_tokens=4)
        assert late.deadline_steps == 2        # inherited from the config
        eng.run()
        assert head.status == "finished"       # admitted before expiry
        assert late.status == "timeout"

    def test_cancel_queued_and_active(self):
        """cancel() frees a queued entry without touching slots, and an
        active cancel releases the slot immediately for the next queued
        request (which must still decode correctly)."""
        from deepspeed_tpu.inference.generation import generate as gen
        m, params = _model(vocab=61)
        eng = ServingEngine(m, params,
                            ServingConfig(num_slots=1, max_len=128))
        r = np.random.RandomState(2)
        active = eng.submit(r.randint(1, 61, size=5), max_new_tokens=20,
                            request_id="active")
        queued = eng.submit(r.randint(1, 61, size=5), max_new_tokens=3,
                            request_id="queued")
        tail_prompt = r.randint(1, 61, size=5)
        tail = eng.submit(tail_prompt, max_new_tokens=4, request_id="tail")
        eng.advance()                           # admit `active`, 1 decode
        assert eng.cancel("queued") is True
        assert queued.status == "cancelled" and queued.done
        assert eng.cancel("active") is True     # frees the only slot
        assert active.status == "cancelled" and active.slot is None
        assert eng.cancel("nope") is False      # unknown id
        assert eng.cancel("active") is False    # already terminal
        eng.run()
        assert tail.status == "finished"
        ref = np.asarray(gen(m, params, tail_prompt[None], max_new_tokens=4,
                             temperature=0.0, max_len=128))[0, 5:]
        np.testing.assert_array_equal(np.asarray(tail.output_tokens), ref)
        snap = eng.metrics.snapshot()
        assert snap["requests_cancelled"] == 2
        assert snap["requests_finished"] == 1
        # cancelled requests must not have streamed tokens post-cancel
        assert len(active.output_tokens) <= 2   # admit token + <=1 decode

    def test_rejection_counters(self):
        m, params = _model(vocab=61)
        eng = ServingEngine(m, params,
                            ServingConfig(num_slots=1, max_len=32,
                                          max_queue=1))
        r = np.random.RandomState(3)
        with pytest.raises(ValueError, match="per-slot budget"):
            eng.submit(r.randint(1, 61, size=30), max_new_tokens=10)
        eng.submit(r.randint(1, 61, size=4), max_new_tokens=2)
        with pytest.raises(RuntimeError, match="queue full"):
            eng.submit(r.randint(1, 61, size=4), max_new_tokens=2)
        assert eng.metrics.snapshot()["requests_rejected"] == 2
        eng.run()
