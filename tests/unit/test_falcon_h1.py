"""Falcon-H1 through the program's normal paths against the plain float32
reference of ``benchmarks/chip/families/falcon_h1.py`` (its recurrence one
token at a time), on the CPU at a small size (hidden 64, 3 layers; a mixer
of 8 heads x 16 x 8 in 2 groups, 4 taps, chunks of 16; 4 query heads on 2
K/V heads of 8, so a head is not hidden / heads; MLP width 96; vocabulary
256; every muP multiplier away from 1), seeded weights, logits and never
tokens:

(a) the full forward pass; (b) prefill in chunks of one and of several
pages, then decoding through the paged pool and the slots' states; (c) a
chunk that starts from a restored snapshot: a prefix hit at the branch
and at the leaf, a hit shortened by a missing snapshot, an evicted
snapshot; (d) batch invariance, idle rows, a slot reused after release,
a request preempted and resumed; (e) the refusals; (f) the controls, each
of which must fail the tolerance the sound program passes.

The tolerance. Program and reference both compute in float32 on the CPU
(``reference.highest()``) and differ in the order of their sums: the
program's chunked scan multiplies a chunk's decays as matrices and
carries the state between chunks where the reference steps the
recurrence a token at a time, attention is a blocked softmax against one
softmax. Such differences read 1e-6 to 3e-6 of a standard deviation of
the reference's logits here. ``TOL`` = 3e-5 sigma leaves a decade for
another backend's summation order and is a twentieth of the smallest
control (a matrix state kept in bf16 reads 6e-4; every other control
reads 1e-2 and up: ``CONTROL_FACTOR``).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from benchmarks.chip import model as bench_model
from benchmarks.chip import reference
from benchmarks.chip.families import falcon_h1 as family
from benchmarks.chip.tools.falcon_h1_check import other_groups
from benchmarks.chip.tools.lfm2_check import DispatchLog
from deepspeed_tpu.inference import cache as cache_mod
from deepspeed_tpu.models.layers import Mamba2Mixer
from deepspeed_tpu.observability import metrics as registry_mod
from deepspeed_tpu.ops.pallas import ssm_update as update_mod
from deepspeed_tpu.serving.paging import manager

PAGE = 16
CONFIG = {
    "hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "mamba_d_ssm": 64, "mamba_n_heads": 8, "mamba_d_head": 8,
    "mamba_d_state": 16, "mamba_n_groups": 2, "mamba_d_conv": 4,
    "mamba_chunk_size": PAGE, "max_position_embeddings": 512,
    "vocab_size": 256,
    "embedding_multiplier": 5.66, "lm_head_multiplier": 0.25,
    "attention_in_multiplier": 0.9, "attention_out_multiplier": 0.6,
    "key_multiplier": 0.35, "ssm_in_multiplier": 0.25,
    "ssm_out_multiplier": 0.5,
    "ssm_multipliers": [0.35, 0.25, 0.18, 0.5, 0.35],
    "mlp_multipliers": [0.18, 0.4],
    "mamba_conv_bias": True, "mamba_proj_bias": False,
    "mamba_rms_norm": True, "mamba_norm_before_gate": False,
    "attention_bias": False, "mlp_bias": False, "projectors_bias": False,
    "rms_norm_eps": 1e-5, "rope_theta": 1e11,
    "compute_dtype": "float32", "param_dtype": "float32",
    "ssm_state_dtype": "float32"}
SIZES = family.sizes(CONFIG, False)
TOL = 3e-5            # sigmas of the reference's logits: the docstring
CONTROL_FACTOR = 10   # every control reads at least this many TOL
SEED = 2 ** 31 + 44


@pytest.fixture(scope="module")
def model():
    """The module and its seeded weights; the convolution's bias and the
    gated norm's scale are drawn (at their initial zeros and ones a
    dropped bias or a norm on the wrong side of the gate reads less)."""
    module = family.build(CONFIG, False)
    params = jax.tree.map(lambda a: a,
                          bench_model.seeded_params(module, SEED))
    key = jax.random.PRNGKey(1)
    for i in range(CONFIG["num_hidden_layers"]):
        mixer = params[f"layers_{i}"]["mixer"]
        key, a, b = jax.random.split(key, 3)
        mixer["conv_b"] = 0.5 * jax.random.normal(a, mixer["conv_b"].shape)
        mixer["norm"] = jax.random.uniform(b, mixer["norm"].shape,
                                           minval=0.5, maxval=1.5)
        # the published initialisation: a state that neither dies in a
        # token nor blows up in ten
        decay = np.exp(-np.exp(np.asarray(mixer["A_log"]))
                       * np.log1p(np.exp(np.asarray(mixer["dt_bias"]))))
        assert 0.2 < decay.min() and decay.max() < 1.0
        assert np.all(np.asarray(mixer["D"]) == 1.0)
    return module, params


def _ids(rows, length, seed=3):
    return np.random.default_rng(seed).integers(
        1, SIZES["vocab_size"], size=(rows, length), dtype=np.int32)


def _reference(params, ids, sizes=SIZES, config=CONFIG):
    with reference.highest():
        return np.asarray(family.reference_logits(
            params, jnp.asarray(ids), sizes, config))


def _gap(got, want):
    """The largest difference, in standard deviations of ``want``."""
    want = np.asarray(want)
    return float(np.max(np.abs(np.asarray(got) - want)) / want.std())


# -- (a) the forward pass -----------------------------------------------------

def test_forward_logits_are_the_references(model):
    module, params = model
    ids = _ids(3, 70)
    with reference.highest():
        got = module.apply({"params": params}, jnp.asarray(ids))
    want = _reference(params, ids)
    assert want.shape == (3, 70, SIZES["vocab_size"])
    assert _gap(got, want) < TOL


@pytest.mark.parametrize("chunk", [1, 8, 128])
def test_the_chunked_scan_does_not_depend_on_its_chunk(model, chunk):
    """The chunked form at a chunk of one position (the recurrence
    itself), of half a page and of more than the sequence."""
    module, params = model
    ids = jnp.asarray(_ids(2, 50))
    other = type(module)(dataclasses.replace(module.config,
                                             mamba_chunk_size=chunk))
    with reference.highest():
        assert _gap(other.apply({"params": params}, ids),
                    module.apply({"params": params}, ids)) < TOL


def _mixer():
    return Mamba2Mixer(d_model=32, d_ssm=32, n_heads=4, d_head=8, d_state=16,
                       n_groups=2, chunk=8, mup=(0.4, 0.3, 0.2, 0.5, 0.4),
                       in_multiplier=0.5, dtype=jnp.float32)


def test_the_mixers_three_forms_agree():
    """A whole sequence from zero state; the same in two chunks, the
    second from the first's carried states, the first right-padded with
    positions outside ``token_mask``; the same one token at a time
    against a slot's state, an idle row beside it keeping its own."""
    mixer = _mixer()
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 21, 32))
    variables = mixer.init(jax.random.PRNGKey(1), x)
    whole = mixer.apply(variables, x)

    def step(cache, part, mask=None):
        out, new = mixer.apply({**variables, "cache": cache}, part,
                               decode=True, token_mask=mask,
                               mutable=["cache", "kv_token"])
        return out, new["cache"], new.get("kv_token")
    zeros = {"conv_state": jnp.zeros((1, 3, 96)),
             "ssm_state": jnp.zeros((1, 4, 16, 8))}
    padded = jnp.concatenate([x[:, :13], jnp.ones((1, 3, 32))], axis=1)
    first, cache, tok = step(zeros, padded, jnp.arange(16)[None] < 13)
    # the padding wrote nothing: the state is the one after position 12
    _, want, _ = step(zeros, x[:, :13])
    for k in want:
        np.testing.assert_allclose(cache[k], want[k], atol=1e-6)
    # the states at the chunk's ends, one every 8 positions
    assert tok["chunk_states"]["ssm_state"].shape == (1, 2, 4, 16, 8)
    _, at8, _ = step(zeros, x[:, :8])
    np.testing.assert_allclose(tok["chunk_states"]["ssm_state"][:, 0],
                               at8["ssm_state"], atol=1e-6)
    np.testing.assert_allclose(tok["chunk_states"]["conv_state"][:, 0],
                               at8["conv_state"], atol=1e-6)
    second, cache, _ = step(cache, x[:, 13:])
    np.testing.assert_allclose(
        jnp.concatenate([first[:, :13], second], axis=1), whole, atol=2e-6)

    two = jax.tree.map(lambda a: jnp.concatenate([a, a + 1.0]), zeros)
    outs = []
    for t in range(21):
        tokens = jnp.concatenate([x[:, t:t + 1], x[:, t:t + 1]])
        out, two, _ = step(two, tokens, jnp.array([[True], [False]]))
        outs.append(out[:1])
    np.testing.assert_allclose(jnp.concatenate(outs, axis=1), whole,
                               atol=2e-6)
    for k in zeros:                      # the idle row kept its state
        np.testing.assert_array_equal(two[k][1], zeros[k][0] + 1.0)


def test_the_update_kernel_is_the_recurrence_and_skips_idle_rows():
    """``ops/pallas/ssm_update.py``'s kernel, interpreted, against its
    plain form at a state Mosaic's tiling takes (16 heads in 2 groups,
    16 x 128 a head): rows 1 and 4 do not decode."""
    rows, heads, n, p, groups = 5, 16, 16, 128, 2
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    state = jax.random.normal(k[0], (rows, heads, n, p))
    dx = jax.random.normal(k[1], (rows, heads, p))
    decay = jax.random.uniform(k[2], (rows, heads))
    b = jax.random.normal(k[3], (rows, groups, n))
    c = jax.random.normal(k[4], (rows, groups, n))
    active = jnp.array([True, False, True, True, False])
    want_y, want = update_mod._update_xla(state, dx, decay, b, c, active)
    y, new = update_mod.ssm_update(state, dx, decay, b, c, active,
                                   interpret=True)
    np.testing.assert_allclose(new, want, atol=1e-6)
    np.testing.assert_allclose(y, want_y * active[:, None, None], atol=1e-5)
    np.testing.assert_array_equal(new[1], state[1])
    y, new = update_mod.ssm_update(state, dx, decay, b, c,
                                   jnp.zeros((rows,), bool), interpret=True)
    np.testing.assert_array_equal(new, state)
    assert float(jnp.abs(y).max()) == 0.0


# -- (b), (c), (d) through ServingEngine --------------------------------------

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
    """A ``ServingEngine`` (paged pool, pages of 16, the prefix cache on,
    greedy) whose dispatches are logged in order (``benchmarks/chip/tools/
    lfm2_check.py DispatchLog``): ``rows(handle)`` are the float32 logits
    each of a request's tokens was sampled from."""

    def __init__(self, module, params, seen, slots=3, kernel="auto",
                 snapshots=8, prefix=True, chunk=PAGE):
        super().__init__(ds.init_inference(
            module, params=params, dtype=jnp.float32).serve(
            {"num_slots": slots, "max_len": 256,
             "paging": {"page_len": PAGE, "prefill_chunk": chunk,
                        "kernel": kernel, "state_snapshots": snapshots,
                        "enable_prefix_cache": prefix}}),
            seen)

    def run(self, *prompts, new_tokens=8):
        with reference.highest():
            handles = [self.srv.submit(p, max_new_tokens=new_tokens)
                       for p in prompts]
            self.srv.run()
        assert all(h.status == "finished" for h in handles)
        return handles

    def close(self):
        self.srv.close()


def _teacher_forced(params, prompt, handle, **wrong):
    """The reference's logits at the positions that predict each served
    token: one full forward pass over prompt + output."""
    ids = np.concatenate([prompt, np.asarray(handle.output_tokens)])
    want = _reference(params, ids[None], **wrong)[0]
    return want[len(prompt) - 1:len(ids) - 1]


@pytest.mark.parametrize("kernel,chunk", [("auto", PAGE), ("auto", 4 * PAGE),
                                          ("on", PAGE)],
                         ids=["one-page-chunks", "four-page-chunks",
                              "paged-kernel"])
def test_chunked_prefill_then_paged_decode_gives_the_references_logits(
        model, seen, kernel, chunk):
    """A prompt of 75 tokens is prefilled in five chunks of one page, or
    in a chunk of four pages and a last one of one (padded by 5 rows:
    they write no state), then 9 tokens are decoded through the page
    pool and the slot's two states with two idle slots beside it — by
    the gathered-pages path the CPU takes by default, and by the
    paged-attention kernel (interpreted here) that the chip runs."""
    module, params = model
    prompt = _ids(1, 75, seed=5)[0]
    served = Served(module, params, seen, kernel=kernel, chunk=chunk)
    handle, = served.run(prompt, new_tokens=10)
    got = served.rows(handle)
    served.close()
    want = _teacher_forced(params, prompt, handle)
    assert got.shape == want.shape == (10, SIZES["vocab_size"])
    assert _gap(got, want) < TOL


def test_a_requests_logits_do_not_depend_on_its_company(model, seen):
    """Batch invariance: the same request alone (two idle slots beside
    it for every decode step) and among two others of other lengths."""
    module, params = model
    a, b, c = _ids(1, 40, 7)[0], _ids(1, 11, 8)[0], _ids(1, 66, 9)[0]
    served = Served(module, params, seen, prefix=False)
    h1, = served.run(a)
    alone = served.rows(h1)
    served.close()
    served = Served(module, params, seen, prefix=False)
    h2, _, _ = served.run(a, b, c)
    among = served.rows(h2)
    served.close()
    assert list(h1.output_tokens) == list(h2.output_tokens)
    assert _gap(alone, among) < TOL
    assert _gap(among, _teacher_forced(params, a, h2)) < TOL


def test_a_reused_slot_does_not_leak_its_last_requests_state(model, seen):
    """One slot, two requests one after the other, no prefix cache: the
    second starts from zeros, not from what the first left in the slot's
    states."""
    module, params = model
    first, second = _ids(1, 50, 11)[0], _ids(1, 23, 12)[0]
    served = Served(module, params, seen, slots=1, prefix=False)
    served.run(first)
    unit = served.srv._paged.pool["layers_0"]["mixer"]
    assert float(jnp.abs(unit["ssm_state"]).max()) > 0
    assert float(jnp.abs(unit["conv_state"]).max()) > 0
    handle, = served.run(second)
    got = served.rows(handle)
    served.close()
    assert _gap(got, _teacher_forced(params, second, handle)) < TOL


@pytest.fixture
def registry(monkeypatch):
    reg = registry_mod.MetricsRegistry()
    monkeypatch.setattr(registry_mod, "_DEFAULT_REGISTRY", reg)
    return reg


def _count(registry, name):
    return registry.counter("serving/" + name).value


def _system_and(*bodies, pages=3):
    """Prompts that open with one system prompt of ``pages`` whole pages
    and go on with bodies of their own."""
    system = _ids(1, pages * PAGE, 21)[0]
    return [np.concatenate([system, _ids(1, n, 30 + n)[0]]) for n in bodies]


def test_requests_that_share_a_system_prompt_hit_at_its_last_page(
        model, seen, registry):
    """The trap a rule of leaves falls in. A opens with three pages of
    system prompt and goes on for 37 tokens: its leaf is page 4's end.
    B parts ways with A at page 2's end, which is nobody's leaf: its
    match of three pages finds no snapshot (``state_restore_missed``),
    it computes its whole prompt and takes the snapshot the match wanted
    as it passes. C then starts from it: one chunk, the reference's
    logits."""
    module, params = model
    a, b, c = _system_and(37, 9, 20)
    served = Served(module, params, seen)
    mgr = served.srv._paged
    count = lambda name: _count(registry, name)
    served.run(a)
    assert (count("state_resets"), count("state_snapshots_taken")) == (1, 1)
    assert mgr.snapshots.has(mgr.prefix.match(a)[-1])            # A's leaf
    assert len(mgr.prefix.match(b)) == 3
    hb, = served.run(b)
    assert (count("state_restore_missed"), count("state_resets"),
            count("state_snapshots_restored")) == (1, 2, 0)
    assert count("prefill_tokens_reused") == 0     # the hit was shortened
    # the branch's snapshot is kept under the prefix cache's page
    assert mgr.snapshots.has(mgr.prefix.match(c)[-1])
    assert _gap(served.rows(hb), _teacher_forced(params, b, hb)) < TOL
    chunks = served.srv.metrics.prefill_chunks
    hc, = served.run(c)
    assert served.srv.metrics.prefill_chunks - chunks == 2   # 20 tokens
    assert (count("state_restore_missed"),
            count("state_snapshots_restored")) == (1, 1)
    assert count("prefill_tokens_reused") == 3 * PAGE
    assert registry.gauge("serving/state_snapshots_in_use").value \
        == mgr.snapshots.in_use == 3   # A's leaf, the branch (B's leaf
                                       # too: one page end), C's leaf
    assert registry.gauge("serving/state_bytes").value == mgr.state_bytes()
    got = served.rows(hc)
    served.close()
    assert _gap(got, _teacher_forced(params, c, hc)) < TOL


def test_a_repeated_prompt_hits_at_its_leaf_until_it_is_evicted(
        model, seen, registry):
    """A's own prompt again starts from the snapshot at its last whole
    page. With a pool of two entries, two other requests' leaves then
    evict A's (least recently used first), and A's next repeat is
    shortened to nothing and computed whole: the reference's logits
    every time."""
    module, params = model
    a, = _system_and(37)
    served = Served(module, params, seen, snapshots=2)
    count = lambda name: _count(registry, name)
    served.run(a)
    h, = served.run(a)
    assert count("state_snapshots_restored") == 1
    assert count("prefill_tokens_reused") == 5 * PAGE
    assert _gap(served.rows(h), _teacher_forced(params, a, h)) < TOL
    served.run(_ids(1, 40, 51)[0])
    served.run(_ids(1, 40, 52)[0])
    assert count("state_snapshots_evicted") == 1
    h, = served.run(a)
    assert (count("state_snapshots_restored"),
            count("state_restore_missed")) == (1, 1)
    got = served.rows(h)
    served.close()
    assert _gap(got, _teacher_forced(params, a, h)) < TOL


def test_a_page_that_is_freed_takes_its_snapshot_with_it(model, seen):
    """The prefix cache evicts a published page to make room: the page
    goes back to the free list and its entry with it, so the page's next
    owner cannot be started from the old tokens' state."""
    module, params = model
    served = Served(module, params, seen, slots=1)
    mgr = served.srv._paged
    served.run(_ids(1, 40, 61)[0])
    page, = [p for p in range(mgr.num_pages) if mgr.snapshots.has(p)]
    mgr.prefix.evict(mgr.allocator.usable_pages)      # everything it can
    assert mgr.allocator.refcount(page) == 0
    assert not mgr.snapshots.has(page) and mgr.snapshots.in_use == 0
    assert mgr.snapshots.evicted == 1
    served.close()


def test_a_request_preempted_in_its_decode_resumes_where_it_was(model, seen):
    """Two slots; after a few tokens the slot cap falls to one and the
    request in slot 1 goes back to the queue with its tokens kept. It is
    prefilled again over prompt + kept output, from the snapshot at its
    own prompt's leaf, and every token's logits, before and after, are
    the reference's."""
    module, params = model
    a, b = _ids(1, 36, 31)[0], _ids(1, 70, 32)[0]
    served = Served(module, params, seen, slots=2)
    srv = served.srv
    with reference.highest():
        ha = srv.submit(a, max_new_tokens=6)
        hb = srv.submit(b, max_new_tokens=12)
        while len(hb.output_tokens) < 4:
            srv.advance()
        srv.set_slot_cap(1)
        assert hb.status == "preempted" and 4 <= len(hb.output_tokens) < 12
        srv.run()
    assert ha.status == hb.status == "finished"
    assert srv.metrics.requests_preempted >= 1
    assert srv.metrics.prefill_tokens_reused == 4 * PAGE     # b's leaf
    for prompt, handle in ((a, ha), (b, hb)):
        got = served.rows(handle)
        assert _gap(got, _teacher_forced(params, prompt, handle)) < TOL
    served.close()


# -- (e) what refuses such a model --------------------------------------------

def test_handoff_and_speculation_refuse_a_model_with_a_mixers_state(model):
    module, params = model
    eng = ds.init_inference(module, params=params, dtype=jnp.float32)
    options = {"num_slots": 2, "max_len": 64, "paging": {"page_len": PAGE}}
    with pytest.raises(NotImplementedError,
                       match="FalconH1 keeps a state-space mixer's state"):
        eng.serve(dict(options, speculation={"max_spec_tokens": 2}))
    srv = eng.serve(options)
    srv.submit(_ids(1, 20)[0], max_new_tokens=2)
    srv.advance()
    with pytest.raises(NotImplementedError,
                       match="handoff.*does not carry recurrent state"):
        srv._paged.export_slot(0, 20)
    with pytest.raises(NotImplementedError, match="import_slot"):
        srv._paged.import_slot(1, [], 0, 1)
    srv.close()


def test_ragged_generate_refuses_it_and_equal_rows_are_the_references(model):
    module, params = model
    eng = ds.init_inference(module, params=params, dtype=jnp.float32)
    ids = _ids(2, 12)
    with pytest.raises(NotImplementedError, match="recurrent"):
        eng.generate(jnp.asarray(ids), max_new_tokens=2,
                     prompt_lengths=[12, 7])
    with reference.highest():
        out = np.asarray(eng.generate(jnp.asarray(ids), max_new_tokens=4))
    want = _reference(params, out[:, :-1])
    np.testing.assert_array_equal(out[:, 12:], want[:, 11:].argmax(-1))


# -- (f) the controls ---------------------------------------------------------

def _other_groups(params):
    """The weights of a model whose heads read B and C of the *other*
    group (``tools/falcon_h1_check.py other_groups``)."""
    return other_groups(params, SIZES)


WRONG_REFERENCES = {
    "ssm_out_multiplier dropped": {"ssm_out_multiplier": 1.0},
    "key_multiplier dropped": {"key_multiplier": 1.0},
    "mlp_multipliers[1] dropped": {
        "mlp_multipliers": [CONFIG["mlp_multipliers"][0], 1.0]},
    "an ssm_multiplier dropped": {
        "ssm_multipliers": CONFIG["ssm_multipliers"][:3] + [1.0]
        + CONFIG["ssm_multipliers"][4:]},
    "the norm before the gate": {"mamba_norm_before_gate": True},
    "the convolution's bias dropped": {"mamba_conv_bias": False},
}


@pytest.mark.parametrize("name", sorted(WRONG_REFERENCES))
def test_a_wrong_model_fails_the_tolerance(model, name):
    """The program as it is against a reference that is wrong in one
    published detail."""
    module, params = model
    ids = _ids(2, 60)
    with reference.highest():
        got = module.apply({"params": params}, jnp.asarray(ids))
    wrong = _reference(params, ids,
                       config=dict(CONFIG, **WRONG_REFERENCES[name]))
    assert _gap(got, wrong) > CONTROL_FACTOR * TOL, name


def test_b_and_c_of_the_wrong_group_fail_the_tolerance(model):
    module, params = model
    ids = _ids(2, 60)
    with reference.highest():
        got = module.apply({"params": params}, jnp.asarray(ids))
    assert _gap(got, _reference(_other_groups(params), ids)) \
        > CONTROL_FACTOR * TOL
    # the harness is sound: swapped twice is the model
    assert _gap(got, _reference(_other_groups(_other_groups(params)),
                                ids)) < TOL


def _hit_after(served, params, break_pool):
    """A, then B that parts at the system prompt's end (and takes the
    branch's snapshot), then ``break_pool`` on the pool, then C, which
    hits: the gap of C's logits to the reference's."""
    a, b, c = _system_and(37, 9, 20)
    served.run(a)
    served.run(b)
    mgr = served.srv._paged
    mgr.pool = break_pool(mgr)
    h, = served.run(c)
    assert served.srv.metrics.prefill_tokens_reused == 3 * PAGE
    gap = _gap(served.rows(h), _teacher_forced(params, c, h))
    served.close()
    return gap


def _snapshots_changed(mgr, change):
    return cache_mod._walk_state(mgr.pool, lambda unit: dict(
        unit, snapshots=jax.tree.map(change, unit["snapshots"])))


def test_the_hit_harness_is_sound(model, seen):
    module, params = model
    assert _hit_after(Served(module, params, seen), params,
                      lambda mgr: mgr.pool) < TOL


def test_a_prefix_hit_from_zero_state_fails_the_tolerance(model, seen):
    """The shared pages' K/V are there; the snapshots are zeroed."""
    module, params = model
    gap = _hit_after(Served(module, params, seen), params,
                     lambda mgr: _snapshots_changed(mgr, jnp.zeros_like))
    assert gap > CONTROL_FACTOR * TOL


def test_the_snapshot_of_the_wrong_page_fails_the_tolerance(model, seen):
    """The two entries change places: the hit starts from the state at
    the end of A's last whole page, not of the system prompt's."""
    module, params = model

    def shifted(mgr):
        assert mgr.snapshots.in_use == 2          # A's leaf and the branch
        return _snapshots_changed(
            mgr, lambda leaf: leaf.at[1:3].set(leaf[2:0:-1]))
    gap = _hit_after(Served(module, params, seen), params, shifted)
    assert gap > CONTROL_FACTOR * TOL


def test_a_state_dropped_between_two_chunks_fails_the_tolerance(
        model, seen, monkeypatch):
    """A chunk that takes zeros where it should take its slot's state."""
    module, params = model
    view = manager.chunk_state_view
    monkeypatch.setattr(
        manager, "chunk_state_view",
        lambda cache, pool, prev, fresh, slot, restore:
            view(cache, pool, prev, True, slot, restore))
    jax.clear_caches()
    prompt = _ids(1, 75, seed=5)[0]
    served = Served(module, params, seen)
    handle, = served.run(prompt)
    gap = _gap(served.rows(handle), _teacher_forced(params, prompt, handle))
    served.close()
    jax.clear_caches()
    assert gap > CONTROL_FACTOR * TOL


def test_a_matrix_state_kept_in_bf16_fails_the_tolerance(model, seen):
    """The configuration says float32 for the mixer's state: the same
    program with the slots' matrix states (and the snapshots) in bf16
    rounds the state at every token."""
    module, params = model
    rounded = type(module)(dataclasses.replace(
        module.config, ssm_state_dtype=jnp.bfloat16))
    prompt = _ids(1, 75, seed=5)[0]
    served = Served(rounded, params, seen)
    unit = served.srv._paged.pool["layers_0"]["mixer"]
    assert unit["ssm_state"].dtype == unit["snapshots"]["ssm_state"].dtype \
        == jnp.bfloat16
    handle, = served.run(prompt, new_tokens=10)
    gap = _gap(served.rows(handle), _teacher_forced(params, prompt, handle))
    served.close()
    assert gap > CONTROL_FACTOR * TOL
