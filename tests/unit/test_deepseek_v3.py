"""DeepSeek-V3 (at Kanana-2's shape) through the program's normal paths
against the plain float32 reference of
``benchmarks/chip/families/deepseek_v3.py``, on the CPU at a small size
(hidden 64; three layers, the first dense; 4 heads on a latent 32 + 8
wide, nope 16, values 16; 8 routed experts top-3 of width 32 scaled by
2.448 beside two shared experts; dense width 96; vocabulary 512), seeded
weights with a seeded non-zero ``expert_bias``, logits and never tokens:

(a) the full forward pass, and the attention module's two forms
(expanded | absorbed) on one input; (b) prefill in chunks of one page and
of the server's choice (four pages), then decoding through the paged
LATENT pool step by step as ``ServingEngine`` does it, by the gathered
row and by the latent kernel (interpreted here); (c) a prefix hit on
latent pages against a cold admission; (d) the latent kernel against its
dense twin; (e) the router: chosen by the biased scores, weighed by the
unbiased, normalised, scaled, the shared experts counted once; (f) the
controls of ``tools/kanana_check.py``, each of which must fail the
tolerance the sound program passes; (g) what is refused by name; (h)
LFM2's router at a scaling factor of 1 traces what the parent traced.

The tolerance is ``test_lfm2.py``'s and for its reasons: program and
reference both compute in float32 on the CPU (``reference.highest()``)
and differ in the order of their sums — absorbed against expanded
attention (``(q W^K) . c`` for ``q . (W^K c)``), the kernel's blocked
softmax against one softmax, rows sorted by expert against every expert
for every token. Such differences read 0 to 2e-6 of a logit here (the
logits' standard deviation is 0.16). ``TOL`` = 2e-4 leaves two decades
and is under a fiftieth of the smallest control (``CONTROL_FACTOR``) but
one, the bias in the weights at its seeded spread of 0.01, which states
its own factor. bf16 activations in this float32 configuration read
thirty times ``TOL`` and more (the last test of (a)).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from benchmarks.chip import model as bench_model
from benchmarks.chip import reference
from benchmarks.chip.families import deepseek_v3 as family
from benchmarks.chip.tools import kanana_check
from benchmarks.chip.tools.lfm2_check import DispatchLog
from deepspeed_tpu.models.deepseek_v3 import DeepseekV3Config
from deepspeed_tpu.models.layers import LatentAttention
from deepspeed_tpu.moe.sharded_moe import topk_routing
from deepspeed_tpu.observability import metrics as registry_mod
from deepspeed_tpu.ops.pallas.latent_attention import latent_attention
from deepspeed_tpu.serving.paging import manager

CONFIG = {"hidden_size": 64, "intermediate_size": 96,
          "moe_intermediate_size": 32, "num_hidden_layers": 3,
          "num_attention_heads": 4, "kv_lora_rank": 32,
          "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
          "first_k_dense_replace": 1, "n_routed_experts": 8,
          "n_shared_experts": 2, "num_experts_per_tok": 3,
          "max_position_embeddings": 1024, "vocab_size": 512,
          "q_lora_rank": None, "n_group": 1, "topk_group": 1,
          "norm_topk_prob": True, "routed_scaling_factor": 2.448,
          "scoring_func": "sigmoid", "rms_norm_eps": 1e-6,
          "rope_theta": 1000000, "rope_scaling": None,
          "rope_interleave": True,
          "compute_dtype": "float32", "param_dtype": "float32"}
SIZES = family.sizes(CONFIG, False)
TOL = 2e-4            # of a logit, absolute: the module's docstring
CONTROL_FACTOR = 50   # every control reads at least this many TOL
SEED = 2 ** 31 + 37
PAGE = 128


@pytest.fixture(scope="module")
def model():
    """The module and its seeded weights; the latent norm's scale is
    drawn too (at its initial ones a norm left out of the cache would
    differ by a factor a softmax barely sees)."""
    module = family.build(CONFIG, False)
    params = jax.tree.map(lambda a: a,
                          bench_model.seeded_params(module, SEED))
    key = jax.random.PRNGKey(1)
    for i in range(CONFIG["num_hidden_layers"]):
        key, sub = jax.random.split(key)
        params[f"layers_{i}"]["attn"]["kv_a_norm"]["scale"] = \
            jax.random.uniform(sub, (32,), minval=0.5, maxval=1.5)
        if i >= CONFIG["first_k_dense_replace"]:
            seeded = params[f"layers_{i}"]["moe"]["expert_bias"]
            assert seeded.shape == (8,) and float(jnp.abs(seeded).min()) > 0
    return module, params


def _ids(rows, length, seed=3):
    return np.random.default_rng(seed).integers(
        1, SIZES["vocab_size"], size=(rows, length), dtype=np.int32)


def _reference(params, ids, sizes=SIZES, config=CONFIG, **how):
    with reference.highest():
        return np.asarray(family.reference_logits(
            params, jnp.asarray(ids), sizes, config, near_ties="kept",
            **how))


def _gap(got, want):
    return float(np.max(np.abs(np.asarray(got) - np.asarray(want))))


# -- (a) the forward pass -----------------------------------------------------

def test_forward_logits_are_the_references(model):
    module, params = model
    ids = _ids(3, 300)                  # over one block of the reference
    with reference.highest():
        got = module.apply({"params": params}, jnp.asarray(ids))
    want = _reference(params, ids)
    assert want.shape == (3, 300, SIZES["vocab_size"])
    assert 0.05 < float(want.std()) < 1.0        # TOL is of such logits
    assert _gap(got, want) < TOL


def test_the_reference_in_blocks_of_queries_is_the_reference_in_one(
        model, monkeypatch):
    _, params = model
    ids = _ids(1, 300)
    want = _reference(params, ids)
    monkeypatch.setattr(family, "QUERY_BLOCK", 512)
    assert _gap(_reference(params, ids), want) < 2e-6


@pytest.mark.parametrize("interleave", [True, False],
                         ids=["interleaved", "halves"])
def test_absorbed_attention_is_expanded_attention(interleave):
    """One module, one input: the whole sequence with keys and values
    expanded for every position, against a chunk from an empty cache in
    the absorbed form (the key up-projection folded into the query, the
    value up-projection after the softmax), and against the same fed a
    token at a time."""
    attn = LatentAttention(
        n_heads=4, d_model=64, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, rotary_base=1e6,
        rope_interleave=interleave, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 64))
    params = attn.init(jax.random.PRNGKey(1), x)["params"]
    params["kv_a_norm"]["scale"] = jax.random.uniform(
        jax.random.PRNGKey(2), (32,), minval=0.5, maxval=1.5)
    with reference.highest():
        expanded = attn.apply({"params": params}, x,
                              positions=jnp.arange(40))
        cache = {"cached_key": jnp.zeros((2, 1, 40, 48)),
                 "cache_index": jnp.zeros((), jnp.int32)}
        absorbed, mut = attn.apply(
            {"params": params, "cache": cache}, x, decode=True,
            positions=jnp.arange(40), mutable=["cache", "kv_token"])
        steps = []
        for t in range(40):
            out, m = attn.apply(
                {"params": params, "cache": cache}, x[:, t:t + 1],
                decode=True, positions=jnp.arange(t, t + 1),
                mutable=["cache", "kv_token"])
            cache = m["cache"]
            steps.append(out)
    assert _gap(absorbed, expanded) < 2e-5
    assert _gap(jnp.concatenate(steps, 1), expanded) < 2e-5
    # what a step publishes is what the cache keeps: one vector a token,
    # rank + rope wide, and no values beside it
    assert mut["kv_token"]["k"].shape == (2, 1, 40, 40)
    assert set(mut["cache"]) == {"cached_key", "cache_index"}
    np.testing.assert_array_equal(np.asarray(mut["cache"]["cached_key"]
                                             [..., :40]),
                                  np.asarray(mut["kv_token"]["k"]))


def test_bf16_activations_in_this_float32_configuration_fail_the_tolerance(
        model):
    """The nearest precision below the configuration's: the same module
    with bf16 activations reads far over ``TOL``."""
    module, params = model
    low = family.build(CONFIG, False, dtype=jnp.bfloat16)
    ids = _ids(2, 48)
    got = low.apply({"params": params}, jnp.asarray(ids))
    assert _gap(got.astype(jnp.float32), _reference(params, ids)) > 30 * TOL


def test_float32_activations_over_bf16_weights_are_the_references(model):
    """The cell's arithmetic: weights rounded to bf16, every activation
    float32, a product of the two from the activation's three bf16 terms
    (``dot_exact_weights``, the experts' rows likewise), the latent
    up-projection among them."""
    import flax.core.meta as flax_meta
    module, params = model
    cell = family.build(CONFIG, False, dtype=jnp.float32,
                        param_dtype=jnp.bfloat16)
    dtypes = jax.eval_shape(lambda r: flax_meta.unbox(cell.init(
        r, jnp.ones((1, 8), jnp.int32)))["params"], jax.random.PRNGKey(0))
    assert dtypes["layers_1"]["attn"]["kv_b_proj"].dtype == jnp.bfloat16
    assert dtypes["layers_1"]["moe"]["shared"]["w1"]["kernel"].dtype \
        == jnp.bfloat16
    assert dtypes["layers_1"]["moe"]["router"].dtype == jnp.float32
    cast = jax.tree.map(lambda a, d: a.astype(d.dtype), params, dtypes)
    ids = _ids(2, 70)
    got = cell.apply({"params": cast}, jnp.asarray(ids))
    assert got.dtype == jnp.float32
    assert _gap(got, _reference(cast, ids)) < TOL


# -- (b), (c) through ServingEngine -------------------------------------------

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
    """A ``ServingEngine`` (paged latent pool, chunked prefill, the prefix
    cache on, greedy) whose dispatches are logged in order:
    ``rows(handle)`` are the float32 logits each of a request's tokens
    was sampled from."""

    def __init__(self, module, params, seen, slots=3, kernel="auto",
                 chunk=PAGE, max_len=1024, dtype=jnp.float32, **options):
        super().__init__(ds.init_inference(
            module, params=params, dtype=dtype).serve(dict(
                {"num_slots": slots, "max_len": max_len,
                 "paging": {"page_len": PAGE,
                            "num_pages": slots * max_len // PAGE + 1,
                            "prefill_chunk": chunk, "kernel": kernel}},
                **options)), seen)

    def run(self, *prompts, new_tokens=8):
        with reference.highest():
            handles = [self.srv.submit(p, max_new_tokens=new_tokens)
                       for p in prompts]
            self.srv.run()
        assert all(h.status == "finished" for h in handles)
        return handles

    def close(self):
        self.srv.close()


def _teacher_forced(params, prompt, handle, **how):
    return kanana_check.reference_rows(
        family, params, prompt, np.asarray(handle.output_tokens), SIZES,
        CONFIG, **how)


@pytest.fixture
def registry(monkeypatch):
    reg = registry_mod.MetricsRegistry()
    monkeypatch.setattr(registry_mod, "_DEFAULT_REGISTRY", reg)
    return reg


@pytest.mark.parametrize("kernel", ["auto", "on"],
                         ids=["gathered-row", "latent-kernel"])
@pytest.mark.parametrize("chunk,pages", [(PAGE, 6), (None, 3)],
                         ids=["a-page-a-chunk", "four-pages-a-chunk"])
def test_chunked_prefill_then_paged_decode_gives_the_references_logits(
        model, seen, registry, kernel, chunk, pages):
    """A prompt of 700 tokens is prefilled a page at a time (six chunks,
    the last padded) or as the server chooses (a chunk of four pages,
    then two of one), then 9 tokens are decoded through the latent page
    pool with two idle slots beside it — by the gathered row the CPU
    takes by default, and by the latent kernel (interpreted here) that
    the chip runs."""
    module, params = model
    prompt = _ids(1, 700, seed=5)[0]
    served = Served(module, params, seen, kernel=kernel, chunk=chunk)
    pool = served.srv._paged.pool
    unit = pool["layers_1"]["attn"]
    assert set(unit) == {"cached_key", "cache_index"}       # no values
    assert unit["cached_key"].shape == (25, 1, 40, PAGE)
    handle, = served.run(prompt, new_tokens=10)
    got = served.rows(handle)
    served.close()
    count = lambda name: registry.counter("serving/" + name).value
    assert count("prefill_chunks") == pages
    assert count("prefill_chunk_pages") == 6
    # nine decode dispatches attended 700, 701, .. pooled tokens
    assert count("latent_tokens_walked") == sum(range(700, 709))
    # each of the 700 + 9 live rows took the shared experts once in each
    # of the two expert layers, and three routed experts
    assert registry.counter("moe/shared_expert_rows").value == 2 * 709
    assert registry.counter("moe/assignments").value == 3 * 2 * 709
    want = _teacher_forced(params, prompt, handle)
    assert got.shape == want.shape == (10, SIZES["vocab_size"])
    assert _gap(got, want) < TOL


def _prefix_pair():
    """A: two whole pages and a tail. B: A's two pages, then its own."""
    a = _ids(1, 2 * PAGE + 37, 21)[0]
    b = np.concatenate([a[:2 * PAGE], _ids(1, 45, 22)[0]])
    return a, b


def test_a_prefix_hit_on_latent_pages_is_a_cold_admission(model, seen,
                                                          registry):
    """B shares two whole latent pages with the earlier A: its one
    prefill chunk starts at position 256 over A's pages, and its logits
    are those of B admitted cold, and the reference's."""
    module, params = model
    a, b = _prefix_pair()
    served = Served(module, params, seen)
    served.run(a)
    assert len(served.srv._paged.prefix.match(b)) == 2
    chunks = served.srv.metrics.prefill_chunks
    hit, = served.run(b)
    got = served.rows(hit)
    assert served.srv.metrics.prefill_chunks - chunks == 1
    assert registry.counter("serving/prefill_tokens_reused").value \
        == 2 * PAGE
    served.close()
    served = Served(module, params, seen)     # nothing published: cold
    cold, = served.run(b)
    alone = served.rows(cold)
    served.close()
    assert list(hit.output_tokens) == list(cold.output_tokens)
    assert _gap(got, alone) < 2e-5
    assert _gap(got, _teacher_forced(params, b, hit)) < TOL


# -- (d) the kernel against its dense twin ------------------------------------

@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 3e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("stacked", [False, True], ids=["one-layer",
                                                        "stacked"])
def test_the_latent_kernel_is_its_dense_twin(dtype, tol, stacked):
    """Rows of 0, 1, 130 and 300 pooled tokens over pages of 128 in a
    shuffled pool, blocks of two pages; what lies past a row's length is
    poisoned with NaN and must not be read into the sum."""
    rng = np.random.default_rng(0)
    heads, d, vw, pages, max_pages = 4, 40, 32, 9, 3
    lengths = np.asarray([0, 1, 130, 300], np.int32)
    table = np.asarray([[0, 0, 0], [5, 0, 0], [2, 7, 0], [1, 3, 8]],
                       np.int32)
    pool = rng.standard_normal((2, pages, 1, d, PAGE)).astype(np.float32)
    for row, n in enumerate(lengths):
        for j in range(max_pages):
            lo = max(0, min(PAGE, n - j * PAGE))
            if table[row, j]:
                pool[:, table[row, j], 0, :, lo:] = np.nan
    pool[:, 0] = np.nan                       # the null page
    q = jnp.asarray(rng.standard_normal((4, heads, d)), dtype)
    new = jnp.asarray(rng.standard_normal((4, d)), dtype)
    pool = jnp.asarray(pool, dtype)
    args = (q, pool if stacked else pool[1], jnp.asarray(table),
            jnp.asarray(lengths), new)
    kw = dict(value_width=vw, softmax_scale=24 ** -0.5,
              layer=1 if stacked else None, block_tokens=2 * PAGE)
    with reference.highest():
        kernel = latent_attention(*args, impl="kernel", **kw)
        dense = latent_attention(*args, impl="dense", **kw)
    assert kernel.shape == (4, heads, vw) and kernel.dtype == dtype
    assert bool(jnp.isfinite(kernel.astype(jnp.float32)).all())
    assert _gap(kernel.astype(jnp.float32), dense.astype(jnp.float32)) < tol
    # a row of length 0 attends its own token only
    np.testing.assert_allclose(
        np.asarray(kernel[0].astype(jnp.float32)),
        np.broadcast_to(np.asarray(new[0, :vw].astype(jnp.float32)),
                        (heads, vw)), atol=tol)


def test_the_latent_kernel_refuses_a_pool_of_another_shape():
    q, new = jnp.zeros((2, 4, 40)), jnp.zeros((2, 40))
    table, lengths = jnp.zeros((2, 2), jnp.int32), jnp.zeros(2, jnp.int32)
    kw = dict(value_width=32, softmax_scale=1.0)
    with pytest.raises(ValueError, match="latent pool"):
        latent_attention(q, jnp.zeros((5, 2, 40, PAGE)), table, lengths,
                         new, **kw)
    with pytest.raises(ValueError, match="`layer`"):
        latent_attention(q, jnp.zeros((2, 5, 1, 40, PAGE)), table, lengths,
                         new, **kw)


# -- (e) the router -----------------------------------------------------------

def test_the_router_chooses_by_biased_scores_weighs_unbiased_and_scales():
    rng = np.random.default_rng(4)
    logits = rng.standard_normal((50, 16)).astype(np.float32)
    bias = (rng.standard_normal(16) * 0.5).astype(np.float32)
    scores, weights, chosen = topk_routing(
        jnp.asarray(logits), 6, True, score="sigmoid",
        bias=jnp.asarray(bias), scale=2.448, norm_eps=1e-20)
    s = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)))
    want = np.argsort(-(s + bias), axis=-1)[:, :6]
    np.testing.assert_array_equal(np.asarray(chosen), want)
    assert (want != np.argsort(-s, axis=-1)[:, :6]).any()   # the bias chose
    w = np.take_along_axis(s, want, -1)
    w = w / w.sum(-1, keepdims=True) * 2.448
    np.testing.assert_allclose(np.asarray(weights), w, rtol=2e-6)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 2.448, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(scores), s, rtol=2e-6)
    # a softmax router scales too, and at 1 nothing is multiplied
    _, soft, _ = topk_routing(jnp.asarray(logits), 6, True, scale=3.0)
    np.testing.assert_allclose(np.asarray(soft).sum(-1), 3.0, rtol=1e-6)
    one = str(jax.make_jaxpr(lambda x: topk_routing(
        x, 6, True, score="sigmoid", bias=jnp.asarray(bias)))(logits))
    scaled = str(jax.make_jaxpr(lambda x: topk_routing(
        x, 6, True, score="sigmoid", bias=jnp.asarray(bias),
        scale=2.448))(logits))
    assert scaled.count(" mul ") == one.count(" mul ") + 1


def test_the_routers_counts_are_the_expert_layers_and_the_live_rows(model):
    """Counts come back ``[L_moe, E]`` for the two expert layers only,
    rows outside ``token_mask`` are routed nowhere, and the module says
    how many rows its counts mean took the shared experts."""
    module, params = model
    ids = _ids(2, 20)
    mask = jnp.asarray([[True] * 20, [True] * 7 + [False] * 13])
    with reference.highest():
        _, aux = module.apply({"params": params}, jnp.asarray(ids),
                              token_mask=mask, return_router=True)
    counts = np.asarray(aux["counts"])
    assert counts.shape == (2, 8)
    assert counts.sum(-1).tolist() == [27 * 3, 27 * 3]
    assert module.shared_expert_rows(counts) == 2 * 27


# -- (f) the controls ---------------------------------------------------------

@pytest.fixture(scope="module")
def sound(model, seen):
    """Two requests served as configured: their prompts, handles and the
    rows they were sampled from."""
    module, params = model
    prompts = [_ids(1, 200, 41)[0], _ids(1, 90, 42)[0]]
    served = Served(module, params, seen)
    handles = served.run(*prompts, new_tokens=9)
    rows = [served.rows(h) for h in handles]
    served.close()
    return list(zip(prompts, handles, rows))


def test_the_control_harness_is_sound(model, sound):
    _, params = model
    for prompt, handle, rows in sound:
        assert _gap(rows, _teacher_forced(params, prompt, handle)) < TOL


@pytest.mark.parametrize("name", sorted(kanana_check.WRONG))
def test_a_wrong_model_fails_the_tolerance(model, sound, name):
    """Each wrong model of ``tools/kanana_check.py``: the rows served as
    configured against a reference that is wrong in that one detail."""
    _, params = model
    # the seeded bias is a twentieth of the scores' spread: in the
    # weights it moves a logit by a sixth of what the others do
    factor = 5 if name == "bias_in_the_weights" else CONTROL_FACTOR
    for prompt, handle, rows in sound:
        wrong = _teacher_forced(params, prompt, handle, wrong=name)
        assert _gap(rows, wrong) > factor * TOL, name


@pytest.mark.parametrize("kind", kanana_check.LOWERED)
def test_the_reference_with_lowered_weights_fails_the_tolerance(
        model, sound, kind):
    _, params = model
    prompt, handle, rows = sound[0]
    low = _teacher_forced(params, prompt, handle,
                          lower=kanana_check.lowered(kind))
    assert _gap(rows, low) > CONTROL_FACTOR * TOL


def test_a_prefix_hit_on_another_documents_pages_fails_the_tolerance(
        model, seen):
    module, params = model
    a, b = _prefix_pair()
    other = np.append(_ids(1, 2 * PAGE, 23)[0], np.int32(1))
    served = Served(module, params, seen)
    served.run(a)
    served.run(other, new_tokens=1)
    prefix = served.srv._paged.prefix
    kanana_check.swap_pages(served.srv, prefix.match(b), prefix.match(other))
    hit, = served.run(b)
    got = served.rows(hit)
    served.close()
    assert _gap(got, _teacher_forced(params, b, hit)) > CONTROL_FACTOR * TOL


def test_the_reference_leaves_its_own_near_ties_unjudged(model, monkeypatch):
    """A position where the third and fourth of the biased scores lie
    under ``NEAR_TIE`` apart comes back as zeros; every other row as
    computed."""
    _, params = model
    ids = _ids(1, 40, 51)
    kept = _reference(params, ids)
    with reference.highest():
        judged = np.asarray(family.reference_logits(
            params, jnp.asarray(ids), SIZES, CONFIG))
    np.testing.assert_array_equal(judged, kept)      # none at this seed
    # widen the rule until it catches some: those rows, and only those
    monkeypatch.setattr(family, "NEAR_TIE", 2e-2)
    with reference.highest():
        judged = np.asarray(family.reference_logits(
            params, jnp.asarray(ids), SIZES, CONFIG))
    zeroed = (judged == 0).all(-1)[0]
    assert 0 < zeroed.sum() < 40
    np.testing.assert_array_equal(judged[0][~zeroed], kept[0][~zeroed])


# -- (g) what is refused by name ----------------------------------------------

@pytest.mark.parametrize("option,value", [
    ("q_lora_rank", 1536), ("n_group", 8), ("topk_group", 4),
    ("rope_scaling", {"type": "yarn", "factor": 4.0}),
    ("scoring_func", "softmax")])
def test_a_configuration_that_is_not_built_is_refused_by_name(option, value):
    with pytest.raises(ValueError, match=option):
        DeepseekV3Config(**{option: value})


def _options(**more):
    return dict({"num_slots": 2, "max_len": 256,
                 "paging": {"page_len": PAGE, "num_pages": 5}}, **more)


@pytest.mark.parametrize("what,options", [
    ("serving.speculation", _options(speculation={"max_spec_tokens": 2})),
    ("quantize_page_pool", _options(quantize={"kv": "int8"}))],
    ids=["speculation", "int8-pages"])
def test_a_server_option_not_built_for_a_latent_pool_is_refused(
        model, what, options):
    module, params = model
    eng = ds.init_inference(module, params=params, dtype=jnp.float32)
    with pytest.raises(ValueError, match=f"{what}.*latent page pool"):
        eng.serve(options)


def test_a_page_handoff_refuses_a_latent_pool(model):
    module, params = model
    srv = ds.init_inference(module, params=params,
                            dtype=jnp.float32).serve(_options())
    srv.submit(_ids(1, 20)[0], max_new_tokens=2)
    srv.advance()
    with pytest.raises(ValueError, match="export_pages.*latent page pool"):
        srv._paged.export_slot(0, 20)
    with pytest.raises(ValueError, match="import_pages.*latent page pool"):
        srv._paged.import_slot(1, [], 0, 1)
    srv.close()


def test_generate_refuses_a_latent_cache(model):
    module, params = model
    eng = ds.init_inference(module, params=params, dtype=jnp.float32)
    with pytest.raises(ValueError, match=r"generate\(\) is not built"):
        eng.generate(jnp.asarray(_ids(2, 12)), max_new_tokens=3,
                     prompt_lengths=jnp.asarray([12, 7]))


def test_tensor_parallel_serving_refuses_latent_attention(model):
    module, params = model
    eng = ds.init_inference(module, params=params, dtype=jnp.float32,
                            mp_size=2)
    with pytest.raises(ValueError, match="mp_size > 1"):
        srv = eng.serve(_options())
        srv.submit(_ids(1, 20)[0], max_new_tokens=2)
        srv.run()


# -- (h) LFM2's router --------------------------------------------------------

def _parents_topk_routing(logits, k, renormalize=False, *, score="softmax",
                          bias=None, scale=1.0, norm_eps=1e-6):
    """``topk_routing`` as it stood before it took a scaling factor."""
    assert scale == 1.0 and norm_eps == 1e-6
    if score == "softmax":
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        weights, experts = jax.lax.top_k(probs, k)
        if renormalize:
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        return probs, weights, experts.astype(jnp.int32)
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, experts = jax.lax.top_k(
        scores if bias is None else scores + bias.astype(jnp.float32), k)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if renormalize:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-6)
    return scores, weights, experts.astype(jnp.int32)


def test_lfm2_passes_its_scaling_factor_and_at_one_traces_the_parents_program(
        monkeypatch):
    from benchmarks.chip.families import lfm2 as lfm2_family
    from deepspeed_tpu.moe import layer as moe_layer
    from .test_lfm2 import CONFIG as LFM2_CONFIG
    module = lfm2_family.build(LFM2_CONFIG, False)
    params = bench_model.seeded_params(module, SEED)
    ids = jnp.asarray(_ids(2, 24))
    forward = lambda m: str(jax.make_jaxpr(
        lambda p: m.apply({"params": p}, ids))(params))
    ours = forward(module)
    monkeypatch.setattr(moe_layer, "topk_routing", _parents_topk_routing)
    assert forward(module) == ours
    monkeypatch.undo()
    # and a factor other than 1 is the reference's
    config = dict(LFM2_CONFIG, routed_scaling_factor=1.7)
    scaled = lfm2_family.build(config, False)
    assert forward(scaled) != ours
    with reference.highest():
        got = scaled.apply({"params": params}, ids)
        want = lfm2_family.reference_logits(
            params, ids, lfm2_family.sizes(config, False), config,
            near_ties="kept")
    assert _gap(got, want) < TOL
