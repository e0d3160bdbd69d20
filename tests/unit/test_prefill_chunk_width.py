"""The width of the one prefill chunk program an iteration, when it is
the server's to choose (``serving.paging.prefill_chunk`` left at None):

(a) the choice as a pure function of the counts the host holds
(``serving/paging/config.py chunk_pages``); (b) the tiny GPT-2, OLMoE and
LFM2 models of ``tests/unit/``: a long prompt admitted alone (wide
chunks) and among decoding rows (one page a chunk) gives the reference's
rows of logits, and the same rows either way; an int8 page pool takes a
run of pages with its scale planes; (c) LFM2, whose pages carry the
convolution state at their ends: prefix hits that restore from the first,
a middle and the last page of a wide chunk, and a request preempted
between two wide chunks and resumed; (d) ``InferenceEngine.serve()``
compiles every width before the first request, and a wide dispatch then
compiles nothing; (e) ``serving/prefill_chunk_pages`` counts the pages
dispatched, and the span says how many.

Pages are 16 tokens here (the gathered-pages path the CPU takes): a
prompt of 101 tokens is 7 pages, cut alone into chunks of 4, 1, 1 and 1.
The tolerance is ``tests/unit/test_lfm2.py``'s and ``test_olmoe.py``'s:
2e-4 of a logit against the float32 reference, 2e-5 between two runs of
the program."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from benchmarks.chip import model as bench_model
from benchmarks.chip import reference
from benchmarks.chip.families import gpt2, lfm2, olmoe
from benchmarks.chip.tools.lfm2_check import DispatchLog
from deepspeed_tpu.observability import metrics as registry_mod
from deepspeed_tpu.observability import trace as trace_mod
from deepspeed_tpu.serving import ServingConfig
from deepspeed_tpu.serving.engine import ServingEngine
from deepspeed_tpu.serving.paging import PagingConfig, manager
from deepspeed_tpu.serving.paging.config import CHUNK_PAGES, chunk_pages
from deepspeed_tpu.serving.qos import (LEVEL_DEGRADE, LEVEL_SHED, QosConfig)
from tests.unit.test_lfm2 import CONFIG as LFM2_CONFIG
from tests.unit.test_olmoe import CONFIG as OLMOE_CONFIG

GPT2_CONFIG = {"n_embd": 64, "n_layer": 2, "n_head": 4, "vocab_size": 512,
               "n_positions": 512, "layer_norm_epsilon": 1e-5,
               "compute_dtype": "float32", "param_dtype": "float32"}
FAMILIES = {"gpt2": (gpt2, GPT2_CONFIG), "olmoe": (olmoe, OLMOE_CONFIG),
            "lfm2": (lfm2, LFM2_CONFIG)}
PAGE = 16
TOL = 2e-4
SEED = 2 ** 31 + 35


# -- (a) the choice -----------------------------------------------------------

@pytest.mark.parametrize("waiting, decoding, pages_left, degraded, want", [
    # the waiting outnumber the decoding: the widest the pages left fill
    (1, 0, 15, False, 4), (13, 3, 8, False, 4), (2, 1, 4, False, 4),
    (1, 0, 4, False, 4), (5, 4, 5, False, 4), (9, 0, 1, False, 1),
    # never wider than the pages left, its last, partly filled one counted
    (16, 0, 7, False, 4), (16, 0, 3, False, 1), (16, 0, 2, False, 1),
    (16, 0, 1, False, 1),
    # one page while the decoding are at least as many as the waiting
    (1, 1, 15, False, 1), (3, 3, 15, False, 1), (1, 2, 15, False, 1),
    (9, 23, 19, False, 1), (0, 0, 4, False, 1),
    # one page at the level where the ladder shrinks the chunk budget
    (13, 3, 8, True, 1), (1, 0, 15, True, 1),
])
def test_the_width_is_a_function_of_the_hosts_counts(
        waiting, decoding, pages_left, degraded, want):
    got = chunk_pages(waiting, decoding, pages_left, degraded)
    assert got == want and got in CHUNK_PAGES and got <= pages_left


def test_the_widths_are_a_small_fixed_set_widest_first():
    assert CHUNK_PAGES[-1] == 1 and len(CHUNK_PAGES) <= 3
    assert list(CHUNK_PAGES) == sorted(CHUNK_PAGES, reverse=True)


@pytest.mark.parametrize("level, pages", [(LEVEL_SHED, 4),
                                          (LEVEL_DEGRADE, 1)])
def test_the_engine_hands_the_choice_the_ladders_level(level, pages):
    """A prompt of four pages admitted alone: one chunk of four pages,
    and four of one page once the ladder stands where it shrinks the
    chunk budget (shedding alone does not narrow it)."""
    module = gpt2.build(GPT2_CONFIG, False)
    params = bench_model.seeded_params(module, SEED)
    eng = ServingEngine(module, params, ServingConfig(
        num_slots=2, max_len=128, paging=PagingConfig(page_len=PAGE),
        qos=QosConfig(ladder_patience_steps=10 ** 6,
                      recover_patience_steps=10 ** 6)))
    eng._qos.level = level
    eng.submit(_ids(4 * PAGE, 1), max_new_tokens=2, priority=2)
    eng.run()
    assert eng._qos.level == level
    assert eng.metrics.prefill_chunk_pages == 4
    assert eng.metrics.prefill_chunks == 4 // pages


def test_a_fixed_prefill_chunk_stays_the_width_it_was():
    """An explicit ``prefill_chunk`` is not the server's to change: full
    chunks, then a tail cut to the pages left, whatever waits."""
    module = gpt2.build(GPT2_CONFIG, False)
    params = bench_model.seeded_params(module, SEED)
    eng = ServingEngine(module, params, ServingConfig(
        num_slots=2, max_len=256,
        paging=PagingConfig(page_len=PAGE, prefill_chunk=3 * PAGE)))
    eng.compile_chunk_programs()
    assert eng._paged.chunk_programs == {}    # nothing to choose from
    eng.submit(_ids(7 * PAGE + 5, 2), max_new_tokens=2)
    eng.run()
    assert (eng.metrics.prefill_chunks, eng.metrics.prefill_chunk_pages) \
        == (3, 8)                             # 3 + 3 + 2 pages
    # the same road with its widths: each compiled when first met
    assert sorted(eng._paged.chunk_programs) == [2 * PAGE, 3 * PAGE]


# -- through ServingEngine, logits watched ------------------------------------

def _ids(length, seed, vocab=512):
    return np.random.default_rng(seed).integers(
        1, vocab, size=length, dtype=np.int32)


@pytest.fixture(scope="module")
def seen():
    """The logits every paged serving program of this file samples from,
    in dispatch order (``tests/unit/test_olmoe.py`` has the reasons)."""
    logits_seen = []
    sample = manager._sample_impl

    def watched(logits, *rest):
        jax.debug.callback(
            lambda x: logits_seen.append(np.asarray(x, np.float32)), logits,
            ordered=True)
        return sample(logits, *rest)
    patch = pytest.MonkeyPatch()
    patch.setattr(manager, "_sample_impl", watched)
    yield logits_seen
    patch.undo()


class Served(DispatchLog):
    """A server built by ``serve()`` with the default ``prefill_chunk``
    (every width compiled ahead), its dispatches logged: ``rows(handle)``
    are the logits a request's tokens were sampled from, and ``widths``
    the pages of every chunk program in dispatch order."""

    def __init__(self, name, seen, slots=3, **options):
        family, config = FAMILIES[name]
        self.family, self.config = family, config
        self.sizes = family.sizes(config, False)
        module = family.build(config, False)
        self.params = bench_model.seeded_params(module, SEED)
        serving = {"num_slots": slots, "max_len": 256,
                   "paging": {"page_len": PAGE, "num_pages": 16 * slots + 1},
                   **options}
        with reference.highest():
            srv = ds.init_inference(module, params=self.params,
                                    dtype=jnp.float32).serve(serving)
        super().__init__(srv, seen)
        self.widths = []
        chunk = srv._dispatch_chunk

        def widths_noted(slot, req, prompt, max_new, start, width, is_last):
            self.widths.append(width // PAGE)
            return chunk(slot, req, prompt, max_new, start, width, is_last)
        srv._dispatch_chunk = widths_noted

    def wanted(self, prompt, handle):
        """The reference's rows at the positions that predict each
        served token: one full forward pass over prompt + output."""
        ids = np.concatenate([prompt, np.asarray(handle.output_tokens)])
        kept = {"near_ties": "kept"} if self.family is lfm2 else {}
        with reference.highest():
            want = np.asarray(self.family.reference_logits(
                self.params, jnp.asarray(ids[None]), self.sizes,
                self.config, **kept))[0]
        return want[len(prompt) - 1:len(ids) - 1]

    def gap(self, prompt, handle):
        return float(np.max(np.abs(self.rows(handle)
                                   - self.wanted(prompt, handle))))


def _alone_then_among(served, prompt):
    """``prompt`` admitted into an empty server, then again while two
    rows decode: (handle, widths) of each."""
    srv = served.srv
    with reference.highest():
        alone = srv.submit(prompt, max_new_tokens=6)
        srv.run()
        first = list(served.widths)
        short = [srv.submit(_ids(9, 40 + i), max_new_tokens=60)
                 for i in range(2)]
        while not all(h.output_tokens for h in short):
            srv.advance()
        del served.widths[:]
        among = srv.submit(prompt, max_new_tokens=6)
        srv.run()
    assert alone.status == among.status == "finished"
    return (alone, first), (among, list(served.widths))


@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_a_long_prompt_reads_the_same_rows_in_wide_chunks_and_in_pages(
        name, seen):
    """7 pages alone: a chunk of 4 pages and three of 1 (the last one
    partly filled); among two decoding rows: seven chunks of one page.
    Both give the reference's rows, and each other's."""
    served = Served(name, seen, **{"paging": {
        "page_len": PAGE, "num_pages": 49, "enable_prefix_cache": False}})
    prompt = _ids(6 * PAGE + 5, 5)
    (alone, wide), (among, narrow) = _alone_then_among(served, prompt)
    assert wide == [4, 1, 1, 1] and narrow == [1] * 7
    rows = served.rows(alone), served.rows(among)
    assert rows[0].shape == rows[1].shape == (6, served.sizes["vocab_size"])
    assert served.gap(prompt, alone) < TOL
    assert served.gap(prompt, among) < TOL
    assert list(alone.output_tokens) == list(among.output_tokens)
    assert float(np.max(np.abs(rows[0] - rows[1]))) < 2e-5
    served.srv.close()


def test_an_int8_page_pool_takes_a_run_of_pages_with_its_scale_planes(seen):
    """``scatter_chunk_pages`` quantizes a run of four pages with their
    scale planes as it does one. A chunk attends its own tokens' K/V
    before they are rounded, so the two ways of cutting a prompt differ
    by the pool's rounding (1e-3 of a logit here, of a standard
    deviation of 0.16) and no more: each way's rows stay that close to
    the float32 reference's on its own tokens."""
    served = Served("gpt2", seen, quantize={"kv": "int8"}, **{"paging": {
        "page_len": PAGE, "num_pages": 49, "enable_prefix_cache": False}})
    assert served.srv._paged.kv_quant == "int8"
    prompt = _ids(8 * PAGE, 6)
    (alone, wide), (among, narrow) = _alone_then_among(served, prompt)
    assert wide == [4, 4] and narrow == [1] * 8
    assert TOL < served.gap(prompt, alone) < 1e-2
    assert TOL < served.gap(prompt, among) < 1e-2
    first = served.rows(alone)[0], served.rows(among)[0]
    assert float(np.max(np.abs(first[0] - first[1]))) < 1e-2
    served.srv.close()


# -- (c) LFM2: the state stored with every page of a wide chunk ---------------

@pytest.fixture
def registry(monkeypatch):
    reg = registry_mod.MetricsRegistry()
    monkeypatch.setattr(registry_mod, "_DEFAULT_REGISTRY", reg)
    return reg


def test_prefix_hits_restore_from_any_page_of_a_wide_chunk(seen, registry):
    """A fills pages 0-3 in one chunk of four, which stores the state at
    the end of each. Three later requests share its first page, its
    first two and all four, then go their own way: each starts its own
    chunk from the state stored with the first, a middle and the last
    page of A's wide chunk, and reads the reference's rows."""
    served = Served("lfm2", seen, slots=4)
    srv = served.srv
    count = lambda name: registry.counter("serving/" + name).value
    a = _ids(4 * PAGE + 7, 21)
    with reference.highest():
        ha = srv.submit(a, max_new_tokens=4)
        srv.run()
    assert served.widths == [4, 1]
    assert count("state_snapshots_stored") == 4       # one a whole page
    assert (count("state_resets"), count("state_snapshots_restored")) \
        == (1, 0)
    hits = [np.concatenate([a[:n * PAGE], _ids(PAGE + 9, 30 + n)])
            for n in (1, 2, 4)]
    with reference.highest():
        handles = [srv.submit(p, max_new_tokens=4) for p in hits]
        srv.run()
    assert count("state_snapshots_restored") == 3
    assert count("prefill_tokens_reused") == (1 + 2 + 4) * PAGE
    assert served.gap(a, ha) < TOL
    for prompt, handle in zip(hits, handles):
        assert handle.status == "finished"
        assert served.gap(prompt, handle) < TOL
    srv.close()


def test_a_request_preempted_between_two_wide_chunks_resumes(seen):
    """Ten pages in slot 1 with nothing decoding: after its first chunk
    of four pages the slot cap falls to one, the request goes back to
    the queue and is prefilled again from its first page in slot 0 (a
    prefill cut short publishes no page), in wide chunks again."""
    served = Served("lfm2", seen, slots=2)
    srv = served.srv
    quick, long = _ids(5, 51), _ids(9 * PAGE + 3, 52)
    with reference.highest():
        hq = srv.submit(quick, max_new_tokens=1)
        hl = srv.submit(long, max_new_tokens=5)
        while not (hq.done and 4 in served.widths):
            srv.advance()
        assert srv._prefill_tasks and srv._prefill_tasks[0][1] is hl
        before = len(served.widths)
        srv.set_slot_cap(1)
        assert hl.status == "preempted" and not srv._prefill_tasks
        srv.run()
    assert hl.status == "finished" and srv.metrics.requests_preempted == 1
    assert served.widths[before:] == [4, 4, 1, 1]
    assert served.gap(long, hl) < TOL
    srv.close()


# -- (d) every width exists before the first request --------------------------

class Compiles:
    """Backend compiles of the process while ``on``: what
    ``benchmarks/chip/phases.py CompileLog`` counts, cache hits too."""

    def __init__(self):
        self.on, self.names = False, []
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, seconds, **kw):
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.names.append(kw.get("fun_name", "?"))


def test_serve_compiles_every_width_and_a_wide_dispatch_compiles_nothing():
    log = Compiles()
    module = gpt2.build(GPT2_CONFIG, False)
    params = bench_model.seeded_params(module, SEED)
    srv = ds.init_inference(module, params=params, dtype=jnp.float32).serve(
        {"num_slots": 2, "max_len": 256, "paging": {"page_len": PAGE}})
    assert sorted(srv._paged.chunk_programs) == sorted(
        w * PAGE for w in CHUNK_PAGES)
    # what the harness's warm-up runs: a page and a little, then decode
    srv.submit(_ids(PAGE + 2, 61), max_new_tokens=3)
    srv.run()
    chunks, pages = srv.metrics.prefill_chunks, srv.metrics.prefill_chunk_pages
    log.on = True
    handle = srv.submit(_ids(7 * PAGE, 62), max_new_tokens=3)
    srv.run()
    log.on = False
    assert handle.status == "finished"
    assert (srv.metrics.prefill_chunks - chunks,
            srv.metrics.prefill_chunk_pages - pages) == (4, 7)   # 4 + 3 x 1
    assert log.names == []
    srv.close()


def test_a_servers_widths_stop_at_its_slots_pages():
    """A slot of two pages is never asked for a chunk of four: that
    width is not compiled."""
    module = gpt2.build(GPT2_CONFIG, False)
    params = bench_model.seeded_params(module, SEED)
    srv = ds.init_inference(module, params=params, dtype=jnp.float32).serve(
        {"num_slots": 2, "max_len": 128, "paging": {"page_len": 64}})
    assert sorted(srv._paged.chunk_programs) == [64]
    srv.close()


def test_an_engine_built_directly_compiles_a_width_when_it_meets_it():
    """``ServingEngine(...)`` itself compiles nothing ahead: a width's
    first dispatch compiles it by the call ``serve()`` makes ahead and
    keeps it in the same table, which is all a dispatch ever runs."""
    # a vocabulary of its own: no other test's programs fit these shapes
    module = gpt2.build(dict(GPT2_CONFIG, vocab_size=499), False)
    params = bench_model.seeded_params(module, SEED)
    eng = ServingEngine(module, params, ServingConfig(
        num_slots=2, max_len=256, paging=PagingConfig(page_len=PAGE)))
    record = manager._chunk_prefill_jit.record
    assert eng._paged.chunk_programs == {}
    before, through_jit = record.compiles, \
        manager._chunk_prefill_jit._cache_size()
    eng.submit(_ids(7 * PAGE, 63, vocab=499), max_new_tokens=2)
    eng.run()
    assert record.compiles == before + 2
    assert sorted(eng._paged.chunk_programs) == sorted(
        w * PAGE for w in CHUNK_PAGES)        # what serve() asks for ahead
    # nothing went round the table: the jit itself was never called
    assert manager._chunk_prefill_jit._cache_size() == through_jit
    assert eng.metrics.prefill_chunk_pages == 7
    # ... and asking for the widths now, as serve() does, compiles nothing
    eng.compile_chunk_programs()
    assert record.compiles == before + 2


# -- (e) the counter and the span ---------------------------------------------

def test_the_counter_counts_the_pages_dispatched_and_the_span_says_how_many(
        registry):
    module = gpt2.build(GPT2_CONFIG, False)
    params = bench_model.seeded_params(module, SEED)
    eng = ServingEngine(module, params, ServingConfig(
        num_slots=2, max_len=256, paging=PagingConfig(page_len=PAGE)))
    tracer = trace_mod.Tracer(annotate_device=False)
    trace_mod.activate(tracer)
    try:
        eng.submit(_ids(5 * PAGE + 1, 71), max_new_tokens=2)   # 4 + 1 + 1
        eng.run()
    finally:
        trace_mod.deactivate()
    count = lambda name: registry.counter("serving/" + name).value
    assert count("prefill_chunk_pages") == 6 and count("prefill_chunks") == 3
    assert count("prefill_tokens_computed") == 5 * PAGE + 1
    snap = eng.metrics.snapshot()
    assert (snap["prefill_chunks"], snap["prefill_chunk_pages"]) == (3, 6)
    args = [e[4] for e in tracer.events if e[0] == "serving/prefill_chunk"]
    assert [a["pages"] for a in args] == [4, 1, 1]
    assert [a["tokens"] for a in args] == [4 * PAGE, PAGE, 1]
