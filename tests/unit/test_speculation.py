"""Token-exact self-speculative decoding (serving/speculation.py).

The parity contract: the speculative engine's output is BITWISE
IDENTICAL to the non-speculative engine (and to the whole-batch
generate() reference) on both cache layouts — speculation may only
compress iterations, never change a token. The compile-once probe
asserts speculation adds exactly ONE compiled program per engine mode,
and the allocator invariant is checked after every advance on the paged
rollback tests.

Stub proposers make the accept/reject edges deterministic: the ORACLE
proposes the request's true greedy continuation (full acceptance — the
multi-token accounting surface), the ADVERSARY proposes provably-wrong
tokens (full rejection — every dispatch must still emit exactly the one
token a plain decode would).
"""

import os
import subprocess
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.gpt import GPT, GPTConfig
from deepspeed_tpu.inference.generation import generate
from deepspeed_tpu.serving import (PagingConfig, QosConfig, ServingConfig,
                                   SpeculationConfig)
from deepspeed_tpu.serving.engine import ServingEngine
from deepspeed_tpu.serving.speculation import NgramProposer, _spec_verify_jit

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _model(vocab, max_seq_len=128, d_model=32, n_layers=2, n_heads=2,
           seed=0):
    cfg = GPTConfig(vocab_size=vocab, max_seq_len=max_seq_len,
                    d_model=d_model, n_layers=n_layers, n_heads=n_heads,
                    dtype=jnp.float32)
    m = GPT(cfg)
    params = m.init(jax.random.PRNGKey(seed),
                    jnp.ones((1, 8), jnp.int32))["params"]
    return m, params


def _spec(**kw):
    return SpeculationConfig(**kw)


def _motif_prompt(r, vocab, motif_len, n):
    motif = r.randint(1, vocab, size=motif_len).astype(np.int32)
    return np.tile(motif, -(-n // motif_len))[:n]


def _ref(m, params, prompt, max_new, max_len=128):
    return np.asarray(generate(m, params, np.asarray(prompt)[None],
                               max_new_tokens=max_new, temperature=0.0,
                               max_len=max_len))[0, len(prompt):]


def _assert_token_exact(m, params, req, max_len=128):
    ref = _ref(m, params, req.prompt, req.max_new_tokens, max_len)
    np.testing.assert_array_equal(
        np.asarray(req.output_tokens), ref,
        err_msg=f"request {req.request_id}")


class _OracleProposer:
    """Proposes each request's TRUE greedy continuation (full
    acceptance): the upper edge of the acceptance rule, deterministic
    because the engine's emitted prefix is itself greedy-exact."""

    def __init__(self, refs):
        # refs: list of full (prompt + greedy continuation) int arrays
        self.refs = [np.asarray(f, np.int32) for f in refs]

    def propose(self, seq, k):
        n = len(seq)
        for full in self.refs:
            if n <= len(full) and (full[:n] == seq).all():
                return full[n:n + k].astype(np.int32)
        return np.zeros((0,), np.int32)


class _AdversaryProposer(_OracleProposer):
    """Proposes provably-WRONG tokens (the true continuation + 1 mod
    vocab): every proposal rejects at position 0, so every speculative
    dispatch must fall back to emitting exactly one correct token."""

    def __init__(self, refs, vocab):
        super().__init__(refs)
        self.vocab = vocab

    def propose(self, seq, k):
        true = super().propose(seq, k)
        return ((true + 1) % self.vocab).astype(np.int32)


# ---------------------------------------------------------------------------
# config plumbing (no jax compute)
# ---------------------------------------------------------------------------

class TestSpeculationConfig:
    def test_defaults_and_validation(self):
        c = _spec().validate(0.0)
        assert c.enabled and c.max_spec_tokens == 4
        assert c.ngram_max == 3 and c.ngram_min == 1
        with pytest.raises(ValueError, match="max_spec_tokens"):
            _spec(max_spec_tokens=0).validate(0.0)
        with pytest.raises(ValueError, match="ngram_min"):
            _spec(ngram_min=0).validate(0.0)
        with pytest.raises(ValueError, match="ngram_max"):
            _spec(ngram_max=1, ngram_min=2).validate(0.0)

    def test_greedy_only(self):
        with pytest.raises(ValueError, match="greedy"):
            ServingConfig(temperature=0.7,
                          speculation=_spec()).validate()
        # a disabled block under sampling stays legal
        ServingConfig(temperature=0.7,
                      speculation=_spec(enabled=False)).validate()

    def test_dict_coercion_and_spec_enabled(self):
        cfg = ServingConfig(speculation={"max_spec_tokens": 2})
        assert isinstance(cfg.speculation, SpeculationConfig)
        assert cfg.speculation.max_spec_tokens == 2
        assert cfg.spec_enabled
        assert not ServingConfig().spec_enabled
        assert not ServingConfig(
            speculation={"enabled": False}).spec_enabled

    def test_cache_len_headroom(self):
        # the verify step writes K+1 candidate entries at the frontier:
        # cache_len pads max_len by max_spec_tokens (then rounds to 128)
        base = ServingConfig(num_slots=2, max_len=128)
        spec = ServingConfig(num_slots=2, max_len=128, speculation=_spec())
        assert base.cache_len == 128
        assert spec.cache_len == 256
        assert ServingConfig(num_slots=2, max_len=120,
                             speculation=_spec()).cache_len == 128


# ---------------------------------------------------------------------------
# host-side n-gram proposer (pure numpy)
# ---------------------------------------------------------------------------

class TestNgramProposer:
    def test_tiled_motif_proposes_continuation(self):
        p = NgramProposer(_spec(ngram_max=3, ngram_min=1))
        seq = np.tile([7, 8, 9, 10], 4).astype(np.int32)   # ... 9 10 | ?
        got = p.propose(seq, 4)
        np.testing.assert_array_equal(got, [7, 8, 9, 10])

    def test_last_occurrence_wins(self):
        # suffix [5]: occurs earlier twice with different continuations
        # — recent context (the LAST earlier occurrence) wins
        p = NgramProposer(_spec(ngram_max=1, ngram_min=1))
        seq = np.asarray([5, 1, 5, 2, 5], np.int32)
        np.testing.assert_array_equal(p.propose(seq, 1), [2])

    def test_longest_ngram_first(self):
        # bigram [3 4] matches -> continuation 9; the unigram [4]
        # match (-> 5) must NOT preempt it
        p = NgramProposer(_spec(ngram_max=2, ngram_min=1))
        seq = np.asarray([3, 4, 9, 4, 5, 3, 4], np.int32)
        np.testing.assert_array_equal(p.propose(seq, 1), [9])

    def test_empty_cases(self):
        p = NgramProposer(_spec())
        assert p.propose(np.asarray([1, 2, 3, 4], np.int32), 0).size == 0
        assert p.propose(np.asarray([1], np.int32), 4).size == 0
        # no repeated n-gram anywhere -> nothing to propose
        assert p.propose(np.asarray([1, 2, 3, 4, 5], np.int32), 4).size == 0

    def test_proposals_capped_at_k(self):
        p = NgramProposer(_spec())
        seq = np.tile([7, 8, 9, 10], 4).astype(np.int32)
        np.testing.assert_array_equal(p.propose(seq, 3), [7, 8, 9])
        # a match too close to the tail truncates instead of wrapping
        tail = np.tile([7, 8], 8).astype(np.int32)
        assert p.propose(tail, 3).shape[0] <= 3


# ---------------------------------------------------------------------------
# QoS: speculation is the FIRST degradation rung
# ---------------------------------------------------------------------------

class TestQosSpeculationRung:
    def _controller(self):
        from deepspeed_tpu.serving.qos import QosController
        return QosController(QosConfig(shed_queue_depth=4,
                                       ladder_patience_steps=3))

    def test_shed_before_requests_and_replays_bit_exact(self):
        from deepspeed_tpu.serving.qos import LEVEL_HEALTHY
        depths = [0, 5, 5, 0, 5, 5, 5, 5, 0]
        trails = []
        for _ in range(2):                       # bit-exact replay
            c = self._controller()
            trail = []
            for it, d in enumerate(depths):
                c.observe(iteration=it, queue_depth=d, ttft_p95_steps=None,
                          free_frac=None)
                trail.append((c.max_spec_tokens(4), c.level,
                              c.snapshot()["speculation_shed"]))
            trails.append(trail)
        assert trails[0] == trails[1]
        trail = trails[0]
        # the FIRST overloaded iteration sheds speculation while the
        # ladder is still healthy — strictly before any request sheds
        assert trail[1] == (0, LEVEL_HEALTHY, True)
        assert trail[2] == (0, LEVEL_HEALTHY, True)
        assert trail[3][0] == 4 and trail[3][2] is False  # instant return
        # request shedding needs patience_steps consecutive overloads
        assert trail[5][1] == LEVEL_HEALTHY       # streak 2: still healthy
        assert trail[6][1] > LEVEL_HEALTHY        # streak 3: ladder moves
        assert trail[6][0] == 0                   # and spec stays shed


# ---------------------------------------------------------------------------
# engine parity + compile-once + accounting (the tentpole acceptance)
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestSpecEngineDefaultPaging:
    def test_token_exact_and_compile_once(self):
        """Mixed repetitive + uniform workload through 3 slots: every
        output bitwise-equal to generate(), real speculation happened,
        and TWO same-geometry engines compile the verify program ONCE."""
        vocab = 89
        m, params = _model(vocab)
        before = _spec_verify_jit._cache_size()
        r = np.random.RandomState(0)
        snaps = []
        for _ in range(2):
            eng = ServingEngine(m, params, ServingConfig(
                num_slots=3, max_len=128,
                speculation=_spec()))
            reqs = []
            for i in range(7):
                n = int(r.randint(6, 30))
                prompt = (_motif_prompt(r, vocab, 3, n) if i % 2 == 0
                          else r.randint(1, vocab, size=n).astype(np.int32))
                reqs.append(eng.submit(prompt, int(r.randint(4, 16)),
                                       request_id=i))
            eng.run()
            for req in reqs:
                assert req.status == "finished"
                _assert_token_exact(m, params, req)
            snaps.append(eng.metrics.snapshot())
            eng.close()
        # ONE new compiled program across both engines — compile-once
        assert _spec_verify_jit._cache_size() == before + 1
        for snap in snaps:
            assert snap["spec_proposed_tokens"] > 0
            assert 0.0 <= snap["spec_acceptance_rate"] <= 1.0
            assert (snap["spec_accepted_tokens"]
                    + snap["spec_rejected_tokens"]
                    == snap["spec_proposed_tokens"])

    def test_multi_token_accounting(self):
        """An oracle-proposed k-token step bumps token counters by k+1
        while the step clock ticks ONCE per dispatch. The engine
        pipelines dispatch->harvest, so the first decode dispatch rides
        alongside the un-harvested prefill (no emitted tokens yet -> no
        proposal) and the tail dispatch has budget 0: 13 tokens land in
        4 decode iterations (plain, spec e=5, spec e=5, plain), and TTFT
        (iteration-denominated) matches the spec-off engine."""
        vocab = 83
        m, params = _model(vocab)
        r = np.random.RandomState(1)
        prompt = r.randint(1, vocab, size=9).astype(np.int32)
        max_new = 13
        full = np.concatenate([prompt, _ref(m, params, prompt, max_new)])

        def run(speculate):
            eng = ServingEngine(m, params, ServingConfig(
                num_slots=1, max_len=128,
                speculation=_spec() if speculate else None))
            if speculate:
                eng._spec = _OracleProposer([full])
            h = eng.submit(prompt, max_new, request_id="a")
            eng.run()
            snap = eng.metrics.snapshot()
            eng.close()
            return h, snap

        h_on, on = run(True)
        h_off, off = run(False)
        np.testing.assert_array_equal(h_on.output_tokens, h_off.output_tokens)
        assert on["tokens_generated"] == off["tokens_generated"] == max_new
        # budget math: plain decode (prefill not yet harvested), two
        # K=4 full-acceptance steps (e=5 each), plain tail (budget 0)
        assert on["decode_iterations"] == 4
        assert off["decode_iterations"] == max_new
        assert on["spec_proposed_tokens"] == on["spec_accepted_tokens"] == 8
        assert on["spec_acceptance_rate"] == 1.0
        assert on["tokens_per_decode_iteration"] == pytest.approx(13 / 4)
        # TTFT stays iteration-denominated and admission-driven:
        # speculation must not move it
        ttft = (h_on.first_token_iteration - h_on.submitted_iteration)
        assert ttft == (h_off.first_token_iteration
                        - h_off.submitted_iteration)

    def test_full_rejection_emits_plain_decode(self):
        """The adversary rejects every proposal at position 0: outputs
        stay exact and every dispatch emits exactly one token — the
        step count degrades to the plain engine's, never below."""
        vocab = 79
        m, params = _model(vocab)
        r = np.random.RandomState(2)
        prompts = [r.randint(1, vocab, size=int(r.randint(5, 20)))
                   .astype(np.int32) for _ in range(3)]
        outs = [int(r.randint(3, 10)) for _ in range(3)]
        refs = [np.concatenate([p, _ref(m, params, p, o)])
                for p, o in zip(prompts, outs)]
        eng = ServingEngine(m, params, ServingConfig(
            num_slots=2, max_len=128,
            speculation=_spec()))
        eng._spec = _AdversaryProposer(refs, vocab)
        reqs = [eng.submit(p, o, request_id=i)
                for i, (p, o) in enumerate(zip(prompts, outs))]
        eng.run()
        for req in reqs:
            assert req.status == "finished"
            _assert_token_exact(m, params, req)
        snap = eng.metrics.snapshot()
        assert snap["spec_proposed_tokens"] > 0
        assert snap["spec_accepted_tokens"] == 0
        assert snap["spec_acceptance_rate"] == 0.0
        eng.close()


# ---------------------------------------------------------------------------
# paged rollback edges (allocator invariants after every advance)
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestSpecEnginePaged:
    def _run_checked(self, eng):
        """Advance to completion, asserting the page-allocator invariant
        after EVERY iteration — a leaked/double-freed page from a
        speculative rollback fails here, not at teardown."""
        while eng.busy:
            eng.advance()
            eng._paged.allocator.check()
        eng.metrics.flush()

    def test_token_exact_compile_once_and_rollback(self):
        """Motif + uniform workload on the paged engine: outputs exact,
        allocator green after every advance, verify program compiled
        once across two same-geometry engines."""
        vocab = 73
        m, params = _model(vocab)
        before = _spec_verify_jit._cache_size()
        r = np.random.RandomState(3)
        for _ in range(2):
            eng = ServingEngine(m, params, ServingConfig(
                num_slots=3, max_len=128,
                paging=PagingConfig(page_len=16),
                speculation=_spec()))
            reqs = []
            for i in range(6):
                n = int(r.randint(6, 30))
                prompt = (_motif_prompt(r, vocab, 3, n) if i % 2 == 0
                          else r.randint(1, vocab, size=n).astype(np.int32))
                reqs.append(eng.submit(prompt, int(r.randint(4, 16)),
                                       request_id=i))
            self._run_checked(eng)
            for req in reqs:
                assert req.status == "finished"
                _assert_token_exact(m, params, req)
            assert eng.metrics.snapshot()["spec_proposed_tokens"] > 0
            eng.close()
        assert _spec_verify_jit._cache_size() == before + 1

    def test_accept_and_reject_straddle_page_boundary(self):
        """Frontiers engineered to cross a page edge mid-verify-window,
        under both full acceptance (oracle) and full rejection
        (adversary): the accepted prefix advances across the boundary,
        the rejected tail rolls back across it, and the allocator stays
        green throughout."""
        vocab = 71
        page_len = 16
        m, params = _model(vocab)
        r = np.random.RandomState(4)
        # prompt lengths land the first verify windows around the 16/32
        # page edges: 14+1 tokens ends at 15 (straddle), 15 at 16, ...
        cases = [(14, 12), (15, 12), (16, 12), (30, 12)]
        refs = []
        prompts = []
        for n, o in cases:
            p = r.randint(1, vocab, size=n).astype(np.int32)
            prompts.append(p)
            refs.append(np.concatenate([p, _ref(m, params, p, o)]))
        for proposer in (_OracleProposer(refs),
                         _AdversaryProposer(refs, vocab)):
            eng = ServingEngine(m, params, ServingConfig(
                num_slots=2, max_len=128,
                paging=PagingConfig(page_len=page_len),
                speculation=_spec()))
            eng._spec = proposer
            reqs = [eng.submit(p, o, request_id=i)
                    for i, (p, (_, o)) in enumerate(zip(prompts, cases))]
            self._run_checked(eng)
            for req in reqs:
                assert req.status == "finished"
                _assert_token_exact(m, params, req)
            eng.close()

    def test_speculation_with_chunked_prefill(self):
        """Chunked prefill and speculation compose: long motif prompts
        prefill in page-sized chunks, then speculate — outputs exact,
        allocator green."""
        vocab = 67
        m, params = _model(vocab)
        r = np.random.RandomState(5)
        eng = ServingEngine(m, params, ServingConfig(
            num_slots=2, max_len=128,
            paging=PagingConfig(page_len=16, prefill_chunk=16),
            speculation=_spec()))
        prompts = [_motif_prompt(r, vocab, 4, 40),
                   r.randint(1, vocab, size=37).astype(np.int32)]
        reqs = [eng.submit(p, 12, request_id=i)
                for i, p in enumerate(prompts)]
        self._run_checked(eng)
        for req in reqs:
            assert req.status == "finished"
            _assert_token_exact(m, params, req)
        assert eng.metrics.prefill_chunks > 0
        eng.close()

    def test_mid_speculation_handoff(self):
        """A slot exported MID-SPECULATION (tokens already emitted by
        accepted verify steps) hands off token-exactly: the importer
        continues from the transferred pages — garbage past the
        frontier in the last page never surfaces."""
        vocab = 61
        m, params = _model(vocab)
        r = np.random.RandomState(6)
        prompt = _motif_prompt(r, vocab, 3, 20)
        max_new = 24
        cfg = ServingConfig(num_slots=2, max_len=128,
                            paging=PagingConfig(page_len=16),
                            speculation=_spec())
        a = ServingEngine(m, params, cfg)
        h = a.submit(prompt, max_new, request_id="mid")
        for _ in range(6):
            if not a.busy:
                break
            a.advance()
        while a._pending:              # drain: tokens must be frontier-true
            a._harvest_one()
        assert not h.done and len(h.tokens) > 1   # genuinely mid-flight
        spec_on_a = a.metrics.snapshot().get("spec_proposed_tokens", 0)
        slot = next(s for s, req in enumerate(a._slot_req) if req is h)
        payload = a.export_handoff(slot, h)
        a.close()

        b = ServingEngine(m, params, cfg)
        h2 = b.inject_handoff(payload)
        assert h2 is not None
        while b.busy:
            b.advance()
            b._paged.allocator.check()
        b.metrics.flush()
        assert h2.status == "finished"
        _assert_token_exact(m, params, h2)
        assert spec_on_a > 0           # the export really was mid-spec
        assert b.metrics.snapshot()["spec_proposed_tokens"] > 0
        b.close()


# ---------------------------------------------------------------------------
# QoS integration: shed-speculation-first, preemption, bit-exact replay
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestSpecQosIntegration:
    def _qos(self):
        return QosConfig(classes=[
            {"name": "interactive", "priority": 2, "ttft_slo_steps": 32,
             "preempt_after_steps": 1, "sheddable": False},
            {"name": "standard", "priority": 1, "ttft_slo_steps": 128},
            {"name": "batch", "priority": 0},
        ], shed_queue_depth=3, ladder_patience_steps=2)

    def test_speculation_with_preemption_resume(self):
        """Preempt/resume composes with speculation: the resumed
        request re-prefills prompt + partial output and keeps
        speculating — every output exact."""
        vocab = 59
        m, params = _model(vocab)
        eng = ServingEngine(m, params, ServingConfig(
            num_slots=2, max_len=128,
            speculation=_spec(), qos=self._qos()))
        r = np.random.RandomState(7)
        lows = [eng.submit(_motif_prompt(r, vocab, 3, 8), 20,
                           request_id=f"low{i}", priority=0)
                for i in range(2)]
        for _ in range(3):
            eng.advance()
        hi = eng.submit(_motif_prompt(r, vocab, 3, 6), 4,
                        request_id="hi", priority=2)
        eng.run()
        assert hi.status == "finished"
        assert sum(q.preemptions for q in lows) >= 1
        for req in [hi] + lows:
            assert req.status == "finished"
            _assert_token_exact(m, params, req)
        eng.close()

    def test_overload_replay_is_bit_exact(self):
        """The same overloaded trace twice: identical outputs, identical
        spec counters, identical ladder transitions — the deterministic
        shed-speculation-before-requests sequence replays exactly."""
        vocab = 53
        m, params = _model(vocab)
        runs = []
        for _ in range(2):
            eng = ServingEngine(m, params, ServingConfig(
                num_slots=1, max_len=128,
                speculation=_spec(), qos=self._qos()))
            r = np.random.RandomState(8)
            reqs = [eng.submit(_motif_prompt(r, vocab, 3,
                                             int(r.randint(5, 12))),
                               int(r.randint(3, 9)), request_id=i,
                               priority=int(r.choice([0, 1])))
                    for i in range(7)]
            eng.run()
            snap = eng.metrics.snapshot()
            runs.append({
                "outputs": [list(q.output_tokens) for q in reqs],
                "statuses": [q.status for q in reqs],
                "spec": {k: snap.get(k) for k in
                         ("spec_proposed_tokens", "spec_accepted_tokens",
                          "spec_rejected_tokens")},
                "level_changes": eng._qos.level_changes,
                "shed": sorted(str(q.request_id) for q in reqs
                               if q.status == "shed"),
            })
            eng.close()
        assert runs[0] == runs[1]


# ---------------------------------------------------------------------------
# CLI + lint gates
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_cli_serve_speculate_smoke():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "bin", "ds_tpu_serve"),
         "--synthetic", "4", "--speculate", "--max-spec-tokens", "3",
         "--num-slots", "2", "--max-len", "128", "--quiet"],
        capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "spec:" in r.stdout
    assert '"spec_proposed_tokens"' in r.stdout


def test_speculation_module_lints_clean():
    from deepspeed_tpu.analysis.cli import main as lint_main
    assert lint_main([os.path.join(REPO_ROOT, "deepspeed_tpu", "serving",
                                   "speculation.py"), "-q"]) == 0
