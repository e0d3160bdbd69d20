"""Paged KV cache for the serving engine (deepspeed_tpu/serving/paging/).

The acceptance test reruns the PR-3 parity suite shape — many mixed
requests through a slot pool — against a page pool whose HBM budget
equals TWO full-length contiguous rows, and requires the paged engine to
hold >= 10x that many requests concurrently while every request's tokens
EXACTLY match its per-request generate() reference. jit-cache probes
prove paged decode compiles once and chunk prefill at most once per
chunk-width bucket.
"""

import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.gpt import GPT, GPTConfig
from deepspeed_tpu.inference.generation import generate, init_cache
from deepspeed_tpu.serving import ServingConfig
from deepspeed_tpu.serving.engine import ServingEngine
from deepspeed_tpu.serving.paging import (NULL_PAGE, PageAllocator,
                                          PagingConfig, PrefixCache)
from deepspeed_tpu.serving.paging.manager import (_chunk_prefill_jit,
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


def _generate_ref(m, params, prompt, out, max_len=128):
    return np.asarray(generate(m, params, prompt[None], max_new_tokens=out,
                               temperature=0.0, max_len=max_len)
                      )[0, len(prompt):]


def _kv_bytes(tree):
    return sum(int(leaf.size) * leaf.dtype.itemsize
               for leaf in jax.tree.leaves(tree)
               if getattr(leaf, "ndim", 0) >= 4)


# ---------------------------------------------------------------------------
# config
# ---------------------------------------------------------------------------

class TestPagingConfig:
    def test_defaults_and_derived(self):
        p = PagingConfig()
        assert p.page_len == 128
        assert p.chunk_tokens == 128                 # prefill_chunk default
        # a full-length request in every slot, plus the null page
        assert p.pool_pages(num_slots=4, cache_len=1024) == 4 * 8 + 1
        assert PagingConfig(num_pages=33).pool_pages(4, 1024) == 33

    def test_validation(self):
        with pytest.raises(ValueError, match="page_len"):
            PagingConfig(page_len=0).validate(128)
        with pytest.raises(ValueError, match="must divide"):
            PagingConfig(page_len=96).validate(128)
        with pytest.raises(ValueError, match="prefill_chunk"):
            PagingConfig(page_len=16, prefill_chunk=24).validate(128)
        with pytest.raises(ValueError, match="max_chunks_per_iter"):
            PagingConfig(page_len=16, max_chunks_per_iter=0).validate(128)
        with pytest.raises(ValueError, match="num_pages"):
            # 128/16 = 8 pages for one full row, +1 null => 9 minimum
            PagingConfig(page_len=16, num_pages=8).validate(128)
        PagingConfig(page_len=16, num_pages=9).validate(128)

    def test_serving_config_lifts_a_dict_and_defaults_an_absent_block(self):
        cfg = ServingConfig(num_slots=2, max_len=128,
                            paging={"page_len": 16})
        assert isinstance(cfg.paging, PagingConfig)
        assert cfg.validate().paging.page_len == 16
        assert ServingConfig(num_slots=2).paging == PagingConfig()
        # no key names another layout: the retired switch is unknown
        with pytest.raises(TypeError, match="enabled"):
            ServingConfig(num_slots=2, paging={"enabled": False})

    def test_deepspeed_config_nested_block(self):
        from deepspeed_tpu.runtime.config import (DeepSpeedConfig,
                                                  DeepSpeedConfigError)
        c = DeepSpeedConfig.from_dict(
            {"serving": {"num_slots": 4, "max_len": 256,
                         "paging": {"page_len": 128,
                                    "prefill_chunk": 256}}})
        assert isinstance(c.serving.paging, PagingConfig)
        assert c.serving.paging.chunk_tokens == 256
        # bad paging arithmetic fails at config PARSE, not engine build
        with pytest.raises(DeepSpeedConfigError, match="page_len"):
            DeepSpeedConfig.from_dict(
                {"serving": {"num_slots": 4, "max_len": 256,
                             "paging": {"page_len": 96}}})


# ---------------------------------------------------------------------------
# page allocator: alloc/free/refcount invariants
# ---------------------------------------------------------------------------

class TestPageAllocator:
    def test_alloc_free_roundtrip(self):
        a = PageAllocator(9)                  # 8 usable + null
        assert a.usable_pages == 8 and a.free_pages == 8
        pages = a.alloc(3)
        assert len(pages) == 3 and NULL_PAGE not in pages
        assert a.pages_in_use == 3
        assert all(a.refcount(p) == 1 for p in pages)
        freed = a.release(pages)
        assert sorted(freed) == sorted(pages)
        assert a.free_pages == 8
        a.check()

    def test_alloc_all_or_nothing(self):
        a = PageAllocator(5)                  # 4 usable
        assert a.alloc(5) is None             # over capacity: no grant
        assert a.free_pages == 4              # ...and nothing leaked
        assert a.alloc(4) is not None
        assert a.alloc(1) is None
        a.check()

    def test_shared_page_lifecycle(self):
        a = PageAllocator(4)
        (page,) = a.alloc(1)
        a.retain([page])                      # second holder (prefix reader)
        assert a.refcount(page) == 2
        assert a.release([page]) == []        # first release: still held
        assert a.free_pages == 2
        assert a.release([page]) == [page]    # last holder frees
        assert a.free_pages == 3
        a.check()

    def test_misuse_raises(self):
        a = PageAllocator(4)
        (page,) = a.alloc(1)
        a.release([page])
        with pytest.raises(ValueError, match="release of unallocated"):
            a.release([page])                 # double free
        with pytest.raises(ValueError, match="retain of unallocated"):
            a.retain([page])
        with pytest.raises(ValueError, match="cannot allocate"):
            a.alloc(-1)
        a.check()

    def test_invariant_under_random_exercise(self):
        r = np.random.RandomState(0)
        a = PageAllocator(17)
        live = []
        for _ in range(300):
            op = r.randint(3)
            if op == 0:
                got = a.alloc(int(r.randint(1, 4)))
                if got is not None:
                    live.append(got)
            elif op == 1 and live:
                run = live[r.randint(len(live))]
                a.retain(run)
                live.append(list(run))
            elif op == 2 and live:
                a.release(live.pop(r.randint(len(live))))
            a.check()                         # invariant holds at every step
        for run in live:
            a.release(run)
        a.check()
        assert a.free_pages == 16


# ---------------------------------------------------------------------------
# prefix tree: hit / miss / eviction
# ---------------------------------------------------------------------------

class TestPrefixCache:
    def _cache(self, pages=17, page_len=4):
        a = PageAllocator(pages)
        return a, PrefixCache(page_len, a)

    def test_miss_insert_hit(self):
        a, c = self._cache()
        toks = list(range(100, 112))          # 3 full pages of 4
        assert c.match(toks) == []
        pages = a.alloc(3)
        assert c.insert(toks, pages) == 3
        assert all(a.refcount(p) == 2 for p in pages)   # tree + request
        # full prompt matches at most its first 2 pages: the page holding
        # the LAST prompt token is never shared (its logits seed sampling)
        assert c.match(toks) == pages[:2]
        # a longer prompt sharing the prefix matches all 3 cached pages
        assert c.match(toks + [1, 2, 3, 4, 5]) == pages
        # diverging tail: only the common page run matches
        assert c.match(toks[:4] + [9] * 8) == pages[:1]
        c.note_admitted(2)
        c.note_admitted(0)
        st = c.stats()
        assert st["prefix_lookups"] == 2 and st["prefix_hits"] == 1
        assert st["prefix_pages_reused"] == 2

    def test_insert_dedup_existing_nodes_win(self):
        a, c = self._cache()
        toks = list(range(8))
        first = a.alloc(2)
        assert c.insert(toks, first) == 2
        dup = a.alloc(2)
        assert c.insert(toks, dup) == 0       # duplicate run: no new nodes
        assert c.match(toks + [1] * 4) == first
        assert a.refcount(dup[0]) == 1        # loser's copy stays private
        a.check()

    def test_evict_leaf_lru(self):
        a, c = self._cache(pages=5, page_len=4)
        old = a.alloc(2)
        c.insert(list(range(8)), old)
        a.release(old)                        # request done; tree holds them
        new = a.alloc(2)
        c.insert(list(range(50, 58)), new)
        a.release(new)
        assert a.free_pages == 0
        # need 1 free page: the least-recently-used LEAF goes first —
        # that's old's tail page, not its root (children pin parents)
        assert c.evict(1) == 1
        assert a.refcount(old[1]) == 0 and a.refcount(old[0]) == 1
        assert c.match(list(range(8)) + [1] * 4) == old[:1]
        st = c.stats()
        assert st["prefix_pages_evicted"] == 1 and st["prefix_nodes"] == 3
        a.check()

    def test_evict_under_live_reader_is_safe(self):
        a, c = self._cache(pages=3, page_len=4)
        run = a.alloc(2)
        c.insert(list(range(8)), run)
        # a live request still references the run (admission retained it)
        a.retain(run)
        a.release(run)                        # original request finished
        # pinned leaves are not eviction candidates: dropping them frees
        # nothing now and would destroy a hittable prefix for zero gain
        assert c.evict(2) == 0
        assert c.stats()["prefix_nodes"] == 2
        assert a.free_pages == 0              # nothing freed under the reader
        assert c.match(list(range(8)) + [0] * 4) == run   # still hittable
        a.release(run)                        # reader finishes
        assert c.evict(2) == 2                # now evictable -> both freed
        assert c.stats()["prefix_nodes"] == 0 and a.free_pages == 2
        a.check()


# ---------------------------------------------------------------------------
# chunked prefill: decode advances between chunks
# ---------------------------------------------------------------------------

class TestChunkedPrefill:
    @pytest.mark.slow
    def test_long_prompt_interleaves_with_decode(self):
        """A 100-token prompt prefills in page chunks; the running decode
        batch advances between every pair of chunks (never stalls more
        than max_chunks_per_iter=1 chunk per decode dispatch)."""
        m, params = _model()
        r = np.random.RandomState(3)
        eng = ServingEngine(m, params, ServingConfig(
            num_slots=3, max_len=128, seed=0,
            paging=PagingConfig(page_len=16, prefill_chunk=16)))
        short = [eng.submit(r.randint(1, 97, size=5).astype(np.int32),
                            max_new_tokens=24) for _ in range(2)]
        for _ in range(3):
            eng.advance()                     # shorts admitted + decoding
        long_p = r.randint(1, 97, size=100).astype(np.int32)
        lreq = eng.submit(long_p, max_new_tokens=4)
        eng.advance()                         # admits long + its 1st chunk
        assert eng._prefill_tasks             # 6 chunks still pending
        decode_during_chunks = []
        while eng._prefill_tasks:             # the 7-chunk prefill window
            eng.advance()
            decode_during_chunks.append(
                int(eng.metrics.decode_iterations))
        eng.run()
        # every chunk iteration also dispatched a decode: strict +1 steps
        assert len(decode_during_chunks) >= 6          # ceil(100/16) - 1
        assert decode_during_chunks == list(range(
            decode_during_chunks[0],
            decode_during_chunks[0] + len(decode_during_chunks)))
        assert eng.metrics.prefill_chunks >= 7
        np.testing.assert_array_equal(
            np.asarray(lreq.output_tokens), _generate_ref(m, params, long_p, 4))
        for s in short:
            assert s.done and len(s.output_tokens) == 24

    @pytest.mark.slow
    def test_chunk_budget_per_iteration(self):
        """max_chunks_per_iter bounds prefill work between decodes."""
        m, params = _model()
        r = np.random.RandomState(5)
        eng = ServingEngine(m, params, ServingConfig(
            num_slots=2, max_len=128, seed=0,
            paging=PagingConfig(page_len=16, prefill_chunk=16,
                                max_chunks_per_iter=4)))
        long_p = r.randint(1, 97, size=90).astype(np.int32)
        req = eng.submit(long_p, max_new_tokens=3)
        eng.advance()                         # admit + first 4 chunks
        assert eng.metrics.prefill_chunks == 4
        eng.advance()                         # remaining 2 chunks
        assert eng.metrics.prefill_chunks == 6
        eng.run()
        np.testing.assert_array_equal(
            np.asarray(req.output_tokens), _generate_ref(m, params, long_p, 3))


# ---------------------------------------------------------------------------
# prefix sharing end-to-end: copy-free reuse, exact tokens
# ---------------------------------------------------------------------------

class TestPrefixSharingEndToEnd:
    @pytest.mark.slow
    def test_shared_system_prompt_skips_recompute(self):
        m, params = _model()
        r = np.random.RandomState(11)
        sys_p = r.randint(1, 97, size=48).astype(np.int32)
        prompts = [np.concatenate([sys_p, r.randint(1, 97, size=int(n))
                                   .astype(np.int32)])
                   for n in r.randint(2, 10, size=6)]
        eng = ServingEngine(m, params, ServingConfig(
            num_slots=2, max_len=128, seed=0,
            paging=PagingConfig(page_len=16, prefill_chunk=16)))
        reqs = [eng.submit(p, max_new_tokens=4) for p in prompts]
        eng.run()
        for req, p in zip(reqs, prompts):
            np.testing.assert_array_equal(
                np.asarray(req.output_tokens), _generate_ref(m, params, p, 4))
        st = eng._paged.stats()
        # the first two admit together (both slots free, nothing published
        # yet); every later request hits the cached 48-token system prompt
        assert st["prefix_hits"] >= 4
        assert st["prefix_tokens_reused"] >= 4 * 48 // 16 * 16
        snap = eng.metrics.snapshot()
        # the prefill-FLOPs ledger: reused + computed == submitted prompt
        # tokens (chunk padding is not counted as computed prompt tokens)
        total_prompt = sum(len(p) for p in prompts)
        assert (snap["prefill_tokens_reused"]
                + snap["prefill_tokens_computed"]) == total_prompt
        assert snap["prefill_recompute_skipped_frac"] > 0.3

    def test_starved_admit_pins_matched_prefix(self):
        """A page-starved admission that prefix-matches must pin the
        matched run BEFORE eviction: an unpinned match could be evicted,
        freed, and re-allocated as the same request's private pages —
        one physical page aliased twice in its slot's table."""
        m, params = _model()
        r = np.random.RandomState(7)
        eng = ServingEngine(m, params, ServingConfig(
            num_slots=2, max_len=128, seed=0,
            paging=PagingConfig(page_len=16, prefill_chunk=16,
                                num_pages=9)))
        pm, a = eng._paged, eng._paged.allocator
        sys_p = r.randint(1, 97, size=32).astype(np.int32)
        first = eng.submit(
            np.concatenate([sys_p, r.randint(1, 97, size=4)
                            .astype(np.int32)]), max_new_tokens=4)
        eng.run()
        assert first.done and pm.stats()["prefix_nodes"] == 2
        cached = pm.prefix.match(
            np.concatenate([sys_p, sys_p]))   # the 2 cached pages
        assert len(cached) == 2
        # 8 usable pages: 2 held by the tree. A live request pins 5 more
        # (host-side admission is all the allocator state needs), leaving
        # 1 free.
        assert pm.try_admit(
            1, r.randint(1, 97, size=64).astype(np.int32), 16) is not None
        assert a.free_pages == 1
        # This request matches both cached pages and needs 2 MORE
        # (32+28 prompt + 4 new = 4 pages) — the evict path runs while
        # the matched run itself is the only leaf in the tree.
        big = np.concatenate([sys_p,
                              r.randint(1, 97, size=28).astype(np.int32)])
        assert pm.try_admit(0, big, 4) is None      # starved, clean refusal
        assert pm.stats()["prefix_nodes"] == 2      # match NOT wiped/freed
        assert all(a.refcount(p) == 1 for p in cached)    # pin undone
        assert pm.prefix.match(np.concatenate([sys_p, sys_p])) == cached
        a.check()

    def test_pool_starvation_evicts_prefix_then_admits(self):
        """A page-starved queue head waits, the prefix cache evicts, and
        admission resumes — FIFO order preserved, tokens exact."""
        m, params = _model()
        r = np.random.RandomState(13)
        # tiny pool: 1 full-length row equivalent (8 usable pages of 16)
        eng = ServingEngine(m, params, ServingConfig(
            num_slots=2, max_len=128, seed=0,
            paging=PagingConfig(page_len=16, prefill_chunk=16,
                                num_pages=9)))
        a = eng._paged.allocator
        first = eng.submit(r.randint(1, 97, size=40).astype(np.int32),
                           max_new_tokens=4)         # 3 pages, publishes 2
        eng.run()
        assert first.done and eng._paged.stats()["prefix_nodes"] == 2
        big_p = r.randint(1, 97, size=100).astype(np.int32)
        big = eng.submit(big_p, max_new_tokens=8)    # needs 7 of 8 pages
        eng.run()
        assert big.done
        np.testing.assert_array_equal(
            np.asarray(big.output_tokens), _generate_ref(m, params, big_p, 8))
        assert eng._paged.stats()["prefix_pages_evicted"] >= 1
        a.check()


# ---------------------------------------------------------------------------
# the acceptance test: 10x density at equal HBM, token-exact
# ---------------------------------------------------------------------------

class TestPagedDensityAcceptance:
    @pytest.mark.slow
    def test_10x_concurrency_at_2_row_hbm_budget(self):
        """Pool = 2 full-length rows of HBM; 40 mixed requests, 32 slots.
        Full-length contiguous rows would cap concurrency at 2 — the
        paged engine must hold >= 10x that many at once, every request
        token-exactly matching generate(), decode compiled ONCE and chunk
        prefill once per chunk-width bucket."""
        # vocab 103 is unique to this test: the jit-cache deltas below
        # cannot be absorbed by entries from other tests' shapes
        m, params = _model(vocab=103, max_seq_len=256)
        r = np.random.RandomState(0)
        prompts = [r.randint(1, 103, size=int(n)).astype(np.int32)
                   for n in r.randint(3, 13, size=40)]
        outs = [int(o) for o in r.randint(1, 5, size=40)]

        rows_budget = 2
        cfg = ServingConfig(
            num_slots=32, max_len=256, seed=0,
            paging=PagingConfig(page_len=16, prefill_chunk=16,
                                max_chunks_per_iter=4,
                                num_pages=rows_budget * (256 // 16) + 1))
        eng = ServingEngine(m, params, cfg)

        # equal-HBM check, CPU-backend byte accounting: the page pool
        # weighs exactly rows_budget contiguous full-length rows plus the
        # one reserved null page
        pool_bytes = eng._paged.pool_bytes()
        row_bytes = _kv_bytes(init_cache(m, params, rows_budget, 256))
        assert pool_bytes == row_bytes * (rows_budget * 16 + 1) \
            // (rows_budget * 16)
        assert eng._paged.stats()["full_length_rows_equivalent"] == 2

        decode_before = _paged_decode_jit._cache_size()
        chunk_before = _chunk_prefill_jit.record.compiles
        reqs = [eng.submit(p, max_new_tokens=o)
                for p, o in zip(prompts, outs)]
        eng.run()

        for req, p, o in zip(reqs, prompts, outs):
            assert req.done
            np.testing.assert_array_equal(
                np.asarray(req.output_tokens),
                _generate_ref(m, params, p, o, max_len=256),
                err_msg=f"request {req.request_id}")

        snap = eng.metrics.snapshot()
        assert snap["requests_finished"] == 40
        # the density claim: >= 10x the concurrency the same HBM spent on
        # full-length contiguous rows could hold
        assert snap["concurrent_requests_peak"] >= 10 * rows_budget
        # compile-once: ONE paged decode program; chunk prefill one per
        # chunk-width bucket (every prompt here pads to one 16-wide chunk)
        assert _paged_decode_jit._cache_size() == decode_before + 1
        assert _chunk_prefill_jit.record.compiles == chunk_before + 1
        eng._paged.allocator.check()
        assert eng._paged.allocator.pages_in_use == \
            eng._paged.stats()["prefix_nodes"]   # only the tree holds pages

    @pytest.mark.parametrize("arch", [
        pytest.param("gptj", marks=pytest.mark.slow),
        pytest.param("bloom", marks=pytest.mark.slow),
    ])
    def test_rotary_and_alibi_variants_paged(self, arch):
        variants = {
            "gptj": dict(rotary=True, learned_pos=False,
                         parallel_residual=True, shared_parallel_ln=True,
                         attn_use_bias=False, rotary_dim=8),
            "bloom": dict(alibi=True, learned_pos=False, embed_ln=True),
        }
        m, params = _model(vocab=89, **variants[arch])
        r = np.random.RandomState(7)
        prompts = [r.randint(1, 89, size=int(n)).astype(np.int32)
                   for n in r.randint(3, 40, size=6)]
        eng = ServingEngine(m, params, ServingConfig(
            num_slots=2, max_len=128, seed=0,
            paging=PagingConfig(page_len=16, prefill_chunk=32)))
        reqs = [eng.submit(p, max_new_tokens=5) for p in prompts]
        eng.run()
        for req, p in zip(reqs, prompts):
            np.testing.assert_array_equal(
                np.asarray(req.output_tokens),
                _generate_ref(m, params, p, 5), err_msg=arch)

    @pytest.mark.slow
    def test_unstacked_layers_paged(self):
        m, params = _model(vocab=91, scan_layers=False)
        r = np.random.RandomState(9)
        prompts = [r.randint(1, 91, size=int(n)).astype(np.int32)
                   for n in r.randint(3, 30, size=4)]
        eng = ServingEngine(m, params, ServingConfig(
            num_slots=2, max_len=128, seed=0,
            paging=PagingConfig(page_len=16, prefill_chunk=32)))
        reqs = [eng.submit(p, max_new_tokens=4) for p in prompts]
        eng.run()
        for req, p in zip(reqs, prompts):
            np.testing.assert_array_equal(
                np.asarray(req.output_tokens), _generate_ref(m, params, p, 4))


# ---------------------------------------------------------------------------
# the decode program leaves the pool where it is: read in place by layer,
# appended in place, and the output pool is the donated input
# ---------------------------------------------------------------------------

def _walk_eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _walk_eqns(inner)


def _scan_streams(eqn):
    """Shapes of what a ``scan`` slices per step (xs) and stacks (ys)."""
    skip = eqn.params["num_consts"] + eqn.params["num_carry"]
    xs = [v.aval.shape for v in eqn.invars[skip:]]
    ys = [v.aval.shape for v in eqn.outvars[eqn.params["num_carry"]:]]
    return xs, ys


PAGED_PAGE = 128            # the cell's page: offset 127 -> 0 of the next


def _filled_pool(m, params, num_pages, page_len, seed):
    """A page pool of ``m`` whose K/V pages hold seeded noise."""
    from deepspeed_tpu.inference.cache import init_page_pool
    pool = init_page_pool(m, params, num_pages, page_len)
    leaves, tree = jax.tree.flatten(pool)
    r = np.random.RandomState(seed)
    leaves = [jnp.asarray(r.randn(*x.shape).astype(np.float32)) * 0.3
              if x.ndim >= 4 else x for x in leaves]
    return jax.tree.unflatten(tree, leaves)


class _PagedScene:
    """Four slots over a hand-made pool: slots 0 and 1 share a full
    prefix page, slot 0 is two tokens from its page's end, slot 2 rides
    the batch inactive, slot 3 is active with nothing in the pool."""
    SHARED = 6
    TABLE = np.array([[6, 1, 2], [6, 4, 5], [7, 0, 0], [8, 0, 0]], np.int32)
    LENGTHS = np.array([2 * PAGED_PAGE - 2, PAGED_PAGE + 5, 3, 0], np.int32)
    ACTIVE = np.array([True, True, False, True])

    def __init__(self, scan_layers, kv_int8):
        self.m, self.params = _model(vocab=101, max_seq_len=3 * PAGED_PAGE,
                                     scan_layers=scan_layers)
        self.pool = _filled_pool(self.m, self.params, 9, PAGED_PAGE, seed=3)
        if kv_int8:
            self.pool = self._quantized(self.pool)

    @staticmethod
    def _quantized(pool):
        """The filled pool as ``quantize_page_pool`` lays an int8 pool
        out: int8 pages beside their scale planes."""
        from deepspeed_tpu.inference.cache import _map_units, _quantize_kv

        def fill(unit):
            out = dict(unit)
            for name, sname in (("cached_key", "key_scale"),
                                ("cached_value", "value_scale")):
                out[name], out[sname] = _quantize_kv(unit[name])
            return out

        return _map_units(pool, fill)

    def state(self):
        return {"lengths": jnp.asarray(self.LENGTHS),
                "last_token": jnp.asarray([5, 17, 29, 41], jnp.int32),
                "active": jnp.asarray(self.ACTIVE),
                "remaining": jnp.full((4,), 99, jnp.int32)}

    def args(self, use_kernel, it=0, pool=None, state=None):
        """Positional arguments of ``_paged_decode_iter_impl``."""
        return (self.m, self.params, self.pool if pool is None else pool,
                jnp.asarray(self.TABLE), state or self.state(),
                jax.random.PRNGKey(0), jnp.int32(it), -1, 1.0, 0, 1.0, None,
                True, False, False, use_kernel, jnp.float32)

    def run(self, use_kernel, steps=4):
        from deepspeed_tpu.serving.paging.manager import \
            _paged_decode_iter_impl
        step = jax.jit(_paged_decode_iter_impl,
                       static_argnums=(0, 11, 12, 13, 14, 15, 16))
        pool, state, toks = self.pool, self.state(), []
        for it in range(steps):
            # (pool, state, tokens, done, an expert layer's counts)
            pool, state, tok, _, _ = step(*self.args(use_kernel, it, pool,
                                                     state))
            toks.append(np.asarray(tok))
        return pool, state, np.stack(toks)


def _pages(leaf):
    """A pool leaf with the page axis first (4-D and stacked 5-D)."""
    x = np.asarray(leaf)
    return x if x.ndim == 4 else np.moveaxis(x, 1, 0)


class TestPoolStaysInPlace:
    @pytest.mark.parametrize("kv_int8", [False, True], ids=["fp", "int8"])
    @pytest.mark.parametrize("scan_layers", [True, False],
                             ids=["scanned", "unscanned"])
    def test_kernel_path_steps_across_a_page_boundary(self, scan_layers,
                                                      kv_int8):
        """Four steps on the kernel path (interpret mode), slot 0 going
        from offset 126 over 127 to 0 and 1 of its next page: the tokens
        and every page but the null page are the gather path's."""
        scene = _PagedScene(scan_layers, kv_int8)
        pool_k, state_k, toks_k = scene.run(use_kernel=True)
        pool_g, state_g, toks_g = scene.run(use_kernel=False)
        np.testing.assert_array_equal(toks_k, toks_g)
        assert (toks_k[:, 2] == -1).all() and (toks_k[:, [0, 1, 3]] >= 0).all()
        np.testing.assert_array_equal(
            np.asarray(state_k["lengths"]), scene.LENGTHS + 4 * scene.ACTIVE)
        for (path, a), b, before in zip(
                jax.tree_util.tree_flatten_with_path(pool_k)[0],
                jax.tree.leaves(pool_g), jax.tree.leaves(scene.pool)):
            if a.ndim < 4:
                continue
            a, b, before = _pages(a), _pages(b), _pages(before)
            exact = a.dtype == np.int8
            # layer 0's K/V is the same arithmetic on both paths; deeper
            # layers sit behind two softmax implementations
            np.testing.assert_allclose(
                a[1:].astype(np.float32), b[1:].astype(np.float32),
                atol=1 if exact else 2e-5, rtol=0 if exact else 2e-5,
                err_msg=str(path))
            # the shared prefix page, and every page no slot appends
            # to, is bit-unchanged
            for page in (scene.SHARED, 3, 5, 7):
                np.testing.assert_array_equal(a[page], before[page],
                                              err_msg=f"{path} {page}")
            # what was appended: slot 0 filled its page and began the
            # next, slot 3 wrote its first four tokens
            assert (a[1][..., -2:] != before[1][..., -2:]).any()
            assert (a[2][..., :2] != before[2][..., :2]).any()
            np.testing.assert_array_equal(a[2][..., 2:], before[2][..., 2:])
            assert (a[8][..., :4] != before[8][..., :4]).any()

    @pytest.mark.parametrize("kv_int8", [False, True], ids=["fp", "int8"])
    @pytest.mark.parametrize("stacked", [True, False],
                             ids=["stacked", "4d"])
    @pytest.mark.parametrize("pages", [
        [3, 0, 5, 0], [0, 0, 0, 0], [3, 1, 5, 6], [0, 3, 0, 5],
        [0, 0, 0, 6]],
        ids=["rows-0-and-2", "no-live-row", "every-row-live",
             "live-rows-not-a-prefix", "last-row-alone"])
    def test_append_is_the_scatter_it_replaced(self, pages, stacked, kv_int8):
        """``scatter_token_pages`` against the indexed scatter the parent
        ran (``kv.at[:, pages, :, :, offsets].set``): bit-identical on
        every page but the null page, scale planes included — and the
        null page, which rows that hold no token are routed to (two of
        them at once in three of the cases), is what it was before the
        call: the append makes no trip for them."""
        from deepspeed_tpu.inference.cache import (_quantize_kv,
                                                   scatter_token_pages)
        r = np.random.RandomState(11)
        lead = (3,) if stacked else ()
        kv = lambda: jnp.asarray(
            r.randn(*lead, 7, 2, 8, 16).astype(np.float32))
        unit = {"cached_key": kv(), "cached_value": kv(),
                "cache_index": jnp.zeros(lead + (4,), jnp.int32)}
        if kv_int8:
            for name, sname in (("cached_key", "key_scale"),
                                ("cached_value", "value_scale")):
                unit[name], unit[sname] = _quantize_kv(unit[name])
        tok = {"k": jnp.asarray(r.randn(*lead, 4, 2, 8, 1), jnp.float32),
               "v": jnp.asarray(r.randn(*lead, 4, 2, 8, 1), jnp.float32)}
        pages = jnp.asarray(pages, jnp.int32)
        offsets = jnp.asarray([15, 4, 0, 9], jnp.int32)
        got = jax.jit(scatter_token_pages)(
            {"attn": unit}, {"attn": tok}, pages, offsets)["attn"]

        def parent(dst, val):
            if stacked:
                return dst.at[:, pages, :, :, offsets].set(
                    val[..., 0].transpose(1, 0, 2, 3))
            return dst.at[pages, :, :, offsets].set(val[..., 0])

        for name, sname, leaf in (("cached_key", "key_scale", tok["k"]),
                                  ("cached_value", "value_scale", tok["v"])):
            planes = {name: leaf}
            if kv_int8:
                # compiled, as the append's is: eager division rounds a
                # scale one ulp apart in one row of these
                planes[name], planes[sname] = jax.jit(_quantize_kv)(leaf)
            for plane, val in planes.items():
                want = parent(unit[plane], val)
                assert got[plane].dtype == unit[plane].dtype
                np.testing.assert_array_equal(_pages(got[plane])[1:],
                                              _pages(want)[1:])
                np.testing.assert_array_equal(_pages(got[plane])[0],
                                              _pages(unit[plane])[0])
                live = np.asarray(pages) != 0
                assert live.any() == (_pages(got[plane])[1:]
                                      != _pages(unit[plane])[1:]).any()

    def test_a_pool_too_large_for_one_number_a_token_is_refused(self):
        """The append reads one int32 a trip: a token's place in the
        pool and the row it comes from. Pages x page_len x rows past
        2**31 is refused where the program is traced."""
        from deepspeed_tpu.inference.cache import scatter_token_pages
        sds = jax.ShapeDtypeStruct

        def trace(num_pages, rows):
            unit = {"cached_key": sds((num_pages, 2, 8, 128), jnp.bfloat16),
                    "cached_value": sds((num_pages, 2, 8, 128), jnp.bfloat16),
                    "cache_index": sds((rows,), jnp.int32)}
            tok = {"k": sds((rows, 2, 8, 1), jnp.bfloat16),
                   "v": sds((rows, 2, 8, 1), jnp.bfloat16)}
            return jax.eval_shape(scatter_token_pages, {"attn": unit},
                                  {"attn": tok}, sds((rows,), jnp.int32),
                                  sds((rows,), jnp.int32))

        trace(65535, 256)                              # 2**31 - 32768
        with pytest.raises(ValueError, match="one int32"):
            trace(65536, 256)

    @pytest.mark.parametrize("kv_int8", [False, True], ids=["fp", "int8"])
    def test_no_scan_streams_the_pool_and_every_leaf_is_aliased(self,
                                                                kv_int8):
        """Structure of the kernel-path program of a scanned model: the
        layer scan neither slices (xs) nor restacks (ys) anything of the
        pool's per-layer shape — only the small per-layer state and the
        step's one-token K/V — and every donated pool leaf is an output
        buffer of the lowered program."""
        from deepspeed_tpu.serving.paging.manager import \
            _paged_decode_iter_impl
        scene = _PagedScene(scan_layers=True, kv_int8=kv_int8)
        static = (0, 11, 12, 13, 14, 15, 16)
        per_layer = {x.shape[1:] for x in jax.tree.leaves(scene.pool)
                     if x.ndim == 5}
        assert (9, 2, 16, PAGED_PAGE) in per_layer
        seen = 0
        for use_kernel, streams_pool in ((True, False), (False, True)):
            jaxpr = jax.make_jaxpr(_paged_decode_iter_impl,
                                   static_argnums=static)(
                *scene.args(use_kernel))
            layer_scans = [e for e in _walk_eqns(jaxpr.jaxpr)
                           if e.primitive.name == "scan"
                           and e.params["length"] == 2]
            assert layer_scans
            for eqn in layer_scans:
                xs, ys = _scan_streams(eqn)
                seen += 1
                # K/V-shaped streams longer than the step's one token:
                # the gather path streams its gathered view by design
                # (which shows that this looks in the right place)
                kv = [sh for sh in xs + ys if len(sh) == 5 and sh[-1] > 1]
                assert bool(kv) == streams_pool, (use_kernel, kv)
                assert not any(sh[1:] in per_layer for sh in xs + ys)
        assert seen >= 2
        lowered = jax.jit(_paged_decode_iter_impl, static_argnums=static,
                          donate_argnums=(2, 4)).lower(*scene.args(True))
        main = lowered.as_text().split("func.func public @main(", 1)[1]
        args = main.split(") -> ", 1)[0].split("%arg")[1:]
        for leaf in jax.tree.leaves(scene.pool):
            if leaf.ndim < 4:
                continue
            dims = "x".join(map(str, leaf.shape))
            mine = [a for a in args if f"tensor<{dims}x" in a]
            assert mine and all("tf.aliasing_output" in a for a in mine), \
                (dims, mine)

    @pytest.mark.parametrize("program", ["train", "generate"])
    def test_without_a_paged_view_the_layer_scan_is_what_it_was(self,
                                                               program):
        """The shared scan of ``GPT.__call__``: ``value_and_grad`` of a
        training loss streams ``params`` alone, ``generate()`` streams
        ``params`` and restacks ``cache`` — no pool collection, no layer
        index, nothing the parent's trace did not carry."""
        m, params = _model(vocab=64, max_seq_len=128)
        stacked = sorted(x.shape for x in jax.tree.leaves(params["h"]))
        ids = jnp.ones((2, 8), jnp.int32)
        if program == "train":
            def loss(p):
                logits = m.apply({"params": p}, ids)
                return jnp.mean(jax.nn.log_softmax(logits)[..., 0])
            jaxpr = jax.make_jaxpr(jax.value_and_grad(loss))(params)
            cache = []
        else:
            jaxpr = jax.make_jaxpr(lambda p: generate(
                m, p, ids, max_new_tokens=4, temperature=0.0, max_len=128))(
                    params)
            cache = sorted(x.shape for x in jax.tree.leaves(
                init_cache(m, params, 2, 128)["h"]))
        scans = [e for e in _walk_eqns(jaxpr.jaxpr)
                 if e.primitive.name == "scan" and e.params["length"] == 2]
        assert scans
        forward = 0
        for eqn in scans:
            assert not any(
                v.aval.ndim >= 5 for v in eqn.invars[:eqn.params[
                    "num_consts"]]), "a stacked pool crossed the scan"
            xs, ys = _scan_streams(eqn)
            ints = [v.aval for v in eqn.invars
                    if jnp.issubdtype(v.aval.dtype, jnp.integer)]
            if sorted(xs) == sorted(stacked + cache):
                forward += 1
                # (under value_and_grad the ys are the residuals)
                assert program == "train" or sorted(ys) == cache
                # cache_index is the one integer a decode scan streams
                assert len(ints) == (1 if cache else 0), ints
        # train: the forward scan; generate: prefill and the decode step
        assert forward == (1 if program == "train" else 2)


# ---------------------------------------------------------------------------
# the kernel is handed length 0 for every row that does not decode
# ---------------------------------------------------------------------------

def _watch_kernel_lengths(monkeypatch):
    """Every ``lengths`` the paged-attention kernel is called with from
    here on, in call order (one entry a layer a dispatch): programs
    traced under the patch report through a debug callback."""
    import importlib
    pa_mod = importlib.import_module(
        "deepspeed_tpu.ops.pallas.paged_attention")
    seen, inner = [], pa_mod.paged_attention

    def watched(q, k_pages, v_pages, page_table, lengths, *rest, **kw):
        jax.debug.callback(lambda x: seen.append(np.asarray(x)), lengths,
                           ordered=True)
        return inner(q, k_pages, v_pages, page_table, lengths, *rest, **kw)

    monkeypatch.setattr(pa_mod, "paged_attention", watched)
    return seen


class _IdleRowsScene:
    """Four slots over a hand-made pool, two of them decoding. ``stale``:
    the other two were released and keep their lengths and their page
    table rows, whose pages have since been poisoned. ``fresh``: the
    same two rows as a server that never used them has them."""
    PAGE, LAYERS = 16, 2
    TABLE = np.array([[1, 2, 0, 0], [9, 10, 11, 0], [3, 0, 0, 0],
                      [10, 9, 0, 0]], np.int32)
    LENGTHS = np.array([20, 40, 5, 30], np.int32)
    ACTIVE = np.array([True, False, True, False])
    POISONED = (9, 10, 11)

    def __init__(self, family):
        import flax.core.meta as flax_meta
        if family == "olmoe":
            from deepspeed_tpu.models.olmoe import OLMoE, OLMoEConfig
            self.m = OLMoE(OLMoEConfig(
                vocab_size=103, hidden_size=32, intermediate_size=16,
                num_hidden_layers=self.LAYERS, num_attention_heads=2,
                num_experts=4, num_experts_per_tok=2,
                max_position_embeddings=64, dtype=jnp.float32))
        else:
            self.m = GPT(GPTConfig(
                vocab_size=103, max_seq_len=64, d_model=32,
                n_layers=self.LAYERS, n_heads=2, dtype=jnp.float32))
        self.params = flax_meta.unbox(self.m.init(
            jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32)))["params"]
        self.pool = _filled_pool(self.m, self.params, 12, self.PAGE, seed=7)

    def _pool(self, stale):
        """Pages 9-11: NaN where they were a released slot's, zeros
        where nobody ever wrote them."""
        bad = jnp.asarray(self.POISONED)
        fill = jnp.nan if stale else 0.0
        return jax.tree.map(
            lambda x: (x.at[:, bad].set(fill) if x.ndim == 5
                       else x.at[bad].set(fill)) if x.ndim >= 4 else x,
            self.pool)

    def run(self, stale, steps=3):
        from deepspeed_tpu.serving.paging import manager
        idle = ~self.ACTIVE
        table, lengths = self.TABLE.copy(), self.LENGTHS.copy()
        if not stale:
            table[idle], lengths[idle] = 0, 0
        state = {"lengths": jnp.asarray(lengths),
                 "last_token": jnp.asarray([5, 17, 29, 41], jnp.int32),
                 "active": jnp.asarray(self.ACTIVE),
                 "remaining": jnp.full((4,), 99, jnp.int32)}
        step = jax.jit(manager._paged_decode_iter_impl,
                       static_argnums=(0, 11, 12, 13, 14, 15, 16))
        pool, toks, counts = self._pool(stale), [], []
        for it in range(steps):
            pool, state, tok, _, cnt = step(
                self.m, self.params, pool, jnp.asarray(table), state,
                jax.random.PRNGKey(0), jnp.int32(it), -1, 1.0, 0, 1.0, None,
                True, False, False, True, jnp.float32)
            toks.append(np.asarray(tok))
            counts.append(None if cnt is None else np.asarray(cnt))
        jax.effects_barrier()
        return pool, state, np.stack(toks), counts


class TestIdleRowsAreNotWalked:
    @pytest.mark.parametrize("family", ["gpt", "olmoe"])
    def test_released_slots_with_stale_lengths_and_poisoned_pages(
            self, family, monkeypatch):
        """The live rows get, token for token and logit for logit, what a
        server that never used the other two slots gives them (an expert
        layer's counts too); the kernel is told 0 for the released rows,
        and the append makes no trip for them: the null page their
        writes are routed to is what it was before the first step."""
        from deepspeed_tpu.serving.paging import manager
        lengths_seen = _watch_kernel_lengths(monkeypatch)
        logits_seen, sample = [], manager._sample_impl

        def watched(logits, *rest):
            jax.debug.callback(
                lambda x: logits_seen.append(np.asarray(x, np.float32)),
                logits, ordered=True)
            return sample(logits, *rest)
        monkeypatch.setattr(manager, "_sample_impl", watched)

        scene = _IdleRowsScene(family)
        live, steps = scene.ACTIVE, 3
        pool_s, state_s, toks_s, counts_s = scene.run(stale=True)
        stale_logits, stale_lengths = list(logits_seen), list(lengths_seen)
        del logits_seen[:], lengths_seen[:]
        pool_f, state_f, toks_f, counts_f = scene.run(stale=False)

        np.testing.assert_array_equal(toks_s, toks_f)
        assert (toks_s[:, live] >= 0).all() and (toks_s[:, ~live] == -1).all()
        assert len(stale_logits) == len(logits_seen) == steps
        for a, b in zip(stale_logits, logits_seen):
            np.testing.assert_allclose(a[live], b[live], atol=1e-6, rtol=0)
        for a, b in zip(counts_s, counts_f):
            assert (a is None) == (family == "gpt")
            if a is not None:
                np.testing.assert_array_equal(a, b)
                assert a.sum(1).tolist() == [2 * live.sum()] * scene.LAYERS
        # one call a layer a step, and every one of them masked
        assert len(stale_lengths) == steps * scene.LAYERS
        for i, got in enumerate(stale_lengths):
            want = np.where(live, scene.LENGTHS + i // scene.LAYERS, 0)
            np.testing.assert_array_equal(got, want)
        # a released row keeps its length (admission overwrites it)
        np.testing.assert_array_equal(np.asarray(state_s["lengths"]),
                                      scene.LENGTHS + steps * live)
        # its K/V of this step goes nowhere: the null page it is routed
        # to is not written, in either run
        for leaf, fresh, before in zip(jax.tree.leaves(pool_s),
                                       jax.tree.leaves(pool_f),
                                       jax.tree.leaves(scene.pool)):
            if leaf.ndim >= 4:
                pages = _pages(leaf)
                np.testing.assert_array_equal(pages[0], _pages(before)[0])
                np.testing.assert_array_equal(_pages(fresh)[0],
                                              _pages(before)[0])
                assert np.isnan(pages[list(scene.POISONED)]).all()
                np.testing.assert_allclose(pages[1:9], _pages(fresh)[1:9],
                                           atol=1e-6, rtol=0)

    def _engine(self, vocab, monkeypatch, **serving):
        from deepspeed_tpu.observability import metrics as registry_mod
        reg = registry_mod.MetricsRegistry()
        monkeypatch.setattr(registry_mod, "_DEFAULT_REGISTRY", reg)
        m, params = _model(vocab=vocab)
        paging = dict(page_len=16, prefill_chunk=16, kernel="on",
                      enable_prefix_cache=False)
        paging.update(serving.pop("paging", {}))
        eng = ServingEngine(m, params, ServingConfig(
            max_len=128, seed=0,
            paging=PagingConfig(**paging), **serving))
        assert eng._paged.use_kernel == (paging["kernel"] == "on")
        count = lambda name: reg.counter("serving/" + name).value
        return m, params, eng, count

    def test_a_slot_waiting_for_its_prefill_chunks_is_masked(self,
                                                             monkeypatch):
        """Slot 0 served a request and was released: it keeps length 32.
        The next request it is given has three chunks of prompt; while
        they run, the decode dispatches (for the request beside it) hand
        the kernel 0 for slot 0, then the prompt's 40, 41, ... — and its
        tokens, the first one after the last chunk too, are
        ``generate()``'s."""
        lengths_seen = _watch_kernel_lengths(monkeypatch)
        m, params, eng, count = self._engine(163, monkeypatch, num_slots=2)
        r = np.random.RandomState(11)
        first = eng.submit(r.randint(1, 163, size=30).astype(np.int32),
                           max_new_tokens=3)
        eng.run()
        assert first.done and eng._slot_req == [None, None]
        assert int(np.asarray(eng._state["lengths"])[0]) == 32
        # two tokens from decode dispatches, and a third dispatch went out
        # before the host had read that the request was done
        assert count("paged_rows_walked") == 2
        assert count("decode_slots_busy") == 3
        del lengths_seen[:]

        short_p = r.randint(1, 163, size=5).astype(np.int32)
        long_p = r.randint(1, 163, size=40).astype(np.int32)
        short = eng.submit(short_p, max_new_tokens=12)     # slot 1
        long = eng.submit(long_p, max_new_tokens=4)        # slot 0, stale
        eng.advance()
        assert eng._slot_req == [long, short] and eng._prefill_tasks
        eng.run()
        jax.effects_barrier()
        per_dispatch = np.stack(lengths_seen[::2])         # layer 0's calls
        slot0 = per_dispatch[:, 0].tolist()
        waiting = slot0.index(40)
        assert waiting >= 3 and slot0[:waiting] == [0] * waiting
        assert slot0[waiting:waiting + 3] == [40, 41, 42]
        assert per_dispatch[:waiting, 1].tolist() == list(range(5, 5 + waiting))
        for req, prompt, n in ((short, short_p, 12), (long, long_p, 4)):
            np.testing.assert_array_equal(
                np.asarray(req.output_tokens),
                _generate_ref(m, params, prompt, n))
        walked, busy, offered = (count("paged_rows_walked"),
                                 count("decode_slots_busy"),
                                 count("decode_slots_offered"))
        assert 0 < walked <= busy < offered
        # every token but a request's first comes from a decode dispatch
        assert walked == (3 - 1) + (12 - 1) + (4 - 1)

    def test_a_full_batch_walks_every_row_it_is_offered(self, monkeypatch):
        """Two slots, two requests admitted and prefilled in the same
        iteration, as many tokens each, read back before the next
        dispatch: no dispatch has a row to mask."""
        m, params, eng, count = self._engine(
            167, monkeypatch, num_slots=2, pipeline_depth=0,
            paging=dict(max_chunks_per_iter=2))
        r = np.random.RandomState(13)
        reqs = [eng.submit(r.randint(1, 167, size=9).astype(np.int32),
                           max_new_tokens=6) for _ in range(2)]
        eng.run()
        assert all(q.done and len(q.output_tokens) == 6 for q in reqs)
        assert count("paged_rows_walked") == count("decode_slots_busy") \
            == count("decode_slots_offered") == 2 * (6 - 1)

    def test_the_gather_path_counts_no_walked_rows(self, monkeypatch):
        """``kernel='off'``: no kernel is handed a length, and the
        gathered view's cost does not follow lengths."""
        m, params, eng, count = self._engine(
            173, monkeypatch, num_slots=2, paging=dict(kernel="off"))
        req = eng.submit(np.arange(1, 8, dtype=np.int32), max_new_tokens=4)
        eng.run()
        assert req.done and not eng._paged.use_kernel
        assert count("decode_slots_busy") == 4
        assert count("paged_rows_walked") == 0


def _slot_tokens_kv(eng, req):
    """What the pool holds for ``req``'s slot, up to the length the
    device has it at: per attention unit and leaf, ``[..., h, d|1, n]``
    with the pages laid end to end, and ``n``."""
    slot = eng._slot_req.index(req)
    n = int(np.asarray(eng._state["lengths"])[slot])
    units, _ = eng._paged.export_slot(slot, n)
    out = []
    for unit in units:
        for name, leaf in sorted(unit.items()):
            x = np.moveaxis(leaf, -4, -2)               # [..., h, d, pages, p]
            out.append(x.reshape(x.shape[:-2] + (-1,))[..., :n])
    return out, n


class TestTheAppendWritesTheRowsThatDecode:
    """A server with 1, then 3, of its 4 slots decoding — the others
    never used, released with a stale length, or in the middle of their
    prefill chunks — gives every request the tokens and the pages a
    server that holds that request alone gives it, and never writes the
    null page."""
    VOCAB, MAX_NEW = 181, 40

    def _engine(self, m, params, path):
        from deepspeed_tpu.serving import SpeculationConfig
        return ServingEngine(m, params, ServingConfig(
            num_slots=4, max_len=128, seed=0,
            paging=PagingConfig(
                page_len=16, prefill_chunk=16, enable_prefix_cache=False,
                kernel="on" if path == "kernel" else "off"),
            speculation=(SpeculationConfig() if path == "speculative"
                         else None)))

    def _advance_until(self, eng, ready):
        for _ in range(200):
            if ready():
                return
            eng.advance()
        raise AssertionError("the server never got there")

    @pytest.mark.parametrize("path", ["kernel", "gather", "speculative"])
    def test_tokens_and_pages_are_a_lone_requests(self, path):
        m, params = _model(vocab=self.VOCAB)
        r = np.random.RandomState(23)
        motif = r.randint(1, self.VOCAB, size=4).astype(np.int32)
        prompts = {
            "first": np.tile(motif, 3)[:9],            # speculation has
            "long": np.tile(motif[::-1], 13)[:50],     # something to propose
            "longer": r.randint(1, self.VOCAB, size=37).astype(np.int32),
            "brief": r.randint(1, self.VOCAB, size=5).astype(np.int32)}
        decoding = lambda eng: int(np.asarray(eng._state["active"]).sum())
        tokens = lambda req: len(req.output_tokens)

        eng = self._engine(m, params, path)
        assert eng._paged.use_kernel == (path == "kernel")
        first = eng.submit(prompts["first"], max_new_tokens=self.MAX_NEW)
        brief = eng.submit(prompts["brief"], max_new_tokens=2)
        self._advance_until(eng, lambda: brief.done and tokens(first) >= 6)
        # one row decodes; one was released and keeps its length; two
        # were never used
        assert decoding(eng) == 1 and eng._slot_req.count(None) == 3
        assert int(np.asarray(eng._state["lengths"])[
            eng._slot_req.index(first) ^ 1]) > 0
        long = eng.submit(prompts["long"], max_new_tokens=self.MAX_NEW)
        longer = eng.submit(prompts["longer"], max_new_tokens=self.MAX_NEW)
        eng.advance()
        eng.advance()
        # still one: the two new ones wait for their chunks
        assert decoding(eng) == 1 and len(eng._prefill_tasks) == 2
        self._advance_until(
            eng, lambda: min(tokens(long), tokens(longer)) >= 5)
        assert decoding(eng) == 3 and not first.done
        together = {name: _slot_tokens_kv(eng, req) for name, req in
                    (("first", first), ("long", long), ("longer", longer))}
        eng.run()
        for leaf in jax.tree.leaves(eng._paged.pool):
            if leaf.ndim >= 4:
                assert not _pages(leaf)[NULL_PAGE].any()
        if path == "speculative":
            assert eng.metrics.snapshot()["spec_proposed_tokens"] > 0

        for name, req in (("first", first), ("long", long),
                          ("longer", longer)):
            np.testing.assert_array_equal(
                np.asarray(req.output_tokens),
                _generate_ref(m, params, prompts[name], self.MAX_NEW))
            alone = self._engine(m, params, path)
            lone = alone.submit(prompts[name], max_new_tokens=self.MAX_NEW)
            kv, n = together[name]
            self._advance_until(alone, lambda: lone in alone._slot_req and int(
                np.asarray(alone._state["lengths"])[
                    alone._slot_req.index(lone)]) >= n)
            assert not lone.done
            kv_alone, n_alone = _slot_tokens_kv(alone, lone)
            assert n > len(prompts[name]) and n_alone >= n
            # to the bit; a verify window places a token where its
            # proposals' fate put it, and a matmul's rounding follows
            for a, b in zip(kv, kv_alone):
                np.testing.assert_allclose(
                    a, b[..., :n], rtol=0, err_msg=name,
                    atol=1e-5 if path == "speculative" else 0)
            alone.run()
            np.testing.assert_array_equal(np.asarray(lone.output_tokens),
                                          np.asarray(req.output_tokens))


# ---------------------------------------------------------------------------
# trace spans + lint gate
# ---------------------------------------------------------------------------

def test_paged_trace_spans():
    """Chunked admits show up in ds_tpu_trace: serving/prefill_chunk and
    serving/page_table_copy spans interleave with serving/decode_iter."""
    from deepspeed_tpu.observability.trace import Tracer, activate, deactivate
    m, params = _model()
    r = np.random.RandomState(21)
    eng = ServingEngine(m, params, ServingConfig(
        num_slots=2, max_len=128, seed=0,
        paging=PagingConfig(page_len=16, prefill_chunk=16)))
    t = Tracer()
    activate(t)
    try:
        req = eng.submit(r.randint(1, 97, size=50).astype(np.int32),
                         max_new_tokens=3)
        eng.run()
    finally:
        deactivate()
    assert req.done
    names = [e[0] for e in t.events]
    assert names.count("serving/prefill_chunk") >= 4       # ceil(50/16)
    assert "serving/page_table_copy" in names
    assert "serving/decode_iter" in names
    # interleaving is visible in the span stream: a decode dispatch lands
    # between the first and last prefill chunk
    first_chunk = names.index("serving/prefill_chunk")
    last_chunk = len(names) - 1 - names[::-1].index("serving/prefill_chunk")
    assert any(n == "serving/decode_iter"
               for n in names[first_chunk:last_chunk])


def test_serving_paging_lints_clean():
    """The satellite CI gate: serving/paging/ ships with ZERO lint
    findings — no baseline file, no suppressions (TS002-clean: no new
    per-step host syncs)."""
    from deepspeed_tpu.analysis.cli import main as lint_main
    assert lint_main([os.path.join(REPO_ROOT, "deepspeed_tpu", "serving",
                                   "paging"), "-q"]) == 0
