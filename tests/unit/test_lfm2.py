"""LFM2-MoE through the program's normal paths against the plain float32
reference of ``benchmarks/chip/families/lfm2.py``, on the CPU at a small
size (hidden 64; layers conv, conv, attention, conv, conv, conv,
attention, conv with 2 dense; 4 query heads on 2 K/V heads; 8 experts
top-3, expert width 32, dense width 96, vocabulary 512), seeded weights
with a seeded non-zero expert bias, logits and never tokens:

(a) the full forward pass; (b) prefill in chunks of one page, then
decoding through the paged cache step by step, as ``ServingEngine`` does
it; (c) the loss ``engine.train_batch`` is given, and its gradients; (d)
batch invariance: a request alone and among others with idle slots beside
it, and a slot reused by a second request after the first; (e) a prefix
hit: a request that shares two whole pages with an earlier one starts
from the convolution state stored with the second; (f) a request
preempted in the middle of its decode and resumed; (g) the controls —
nine wrong models and two broken programs, each of which must fail the
tolerance the sound program passes.

The tolerance. Program and reference both compute in float32 on the CPU
(``reference.highest()``) and differ in the order of their sums: the
program sorts rows by expert and multiplies group by group where the
reference computes every expert for every token, attention is the
kernel's blocked softmax against one softmax, and a chunk's convolution
takes its first two columns from the carried state where the reference
pads one long sequence with zeros (the same products in the same order:
bit-equal). Such differences read 0 to 2e-6 of a logit here (the logits'
standard deviation is 0.16). ``TOL`` = 2e-4 leaves two decades for another
backend's summation order and is under a fiftieth of the smallest
control (``CONTROL_FACTOR``). The bf16 case states its own limit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from benchmarks.chip import model as bench_model
from benchmarks.chip import reference
from benchmarks.chip.families import lfm2 as family
from benchmarks.chip.tools.lfm2_check import DispatchLog
from deepspeed_tpu.inference import cache as cache_mod
from deepspeed_tpu.models.layers import ShortConv
from deepspeed_tpu.models.lfm2 import lfm2_loss_fn
from deepspeed_tpu.moe.layer import EXPERT_BIAS_INIT_STD
from deepspeed_tpu.moe.sharded_moe import topk_routing
from deepspeed_tpu.observability import metrics as registry_mod
from deepspeed_tpu.serving.paging import manager

LAYERS = ["conv", "conv", "full_attention", "conv", "conv", "conv",
          "full_attention", "conv"]
CONFIG = {"hidden_size": 64, "intermediate_size": 96,
          "moe_intermediate_size": 32, "num_hidden_layers": 8,
          "layer_types": LAYERS, "num_attention_heads": 4,
          "num_key_value_heads": 2, "conv_L_cache": 3, "conv_bias": False,
          "num_dense_layers": 2, "num_experts": 8, "num_experts_per_tok": 3,
          "use_expert_bias": True, "norm_topk_prob": True,
          "routed_scaling_factor": 1, "norm_eps": 1e-5,
          "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
          "max_position_embeddings": 1024, "vocab_size": 512,
          "compute_dtype": "float32", "param_dtype": "float32"}
SIZES = family.sizes(CONFIG, False)
TOL = 2e-4            # of a logit, absolute: the module's docstring
CONTROL_FACTOR = 50   # every control reads at least this many TOL
SEED = 2 ** 31 + 33
PAGE = 128


@pytest.fixture(scope="module")
def model():
    """The module and its seeded weights: the expert bias is drawn (not
    zero), and so are the QK norms' scales (at their initial ones a norm
    a head and a norm over the projection differ by a factor only)."""
    module = family.build(CONFIG, False)
    params = jax.tree.map(lambda a: a,
                          bench_model.seeded_params(module, SEED))
    key = jax.random.PRNGKey(1)
    for i, kind in enumerate(LAYERS):
        if kind == "full_attention":
            for name in ("q_norm", "k_norm"):
                key, sub = jax.random.split(key)
                params[f"layers_{i}"]["attn"][name]["scale"] = \
                    jax.random.uniform(sub, (16,), minval=0.5, maxval=1.5)
        if i >= CONFIG["num_dense_layers"]:
            # seeded, not zero — and drawn ten times as wide here, so
            # that the controls on the bias read far over the tolerance
            seeded = params[f"layers_{i}"]["moe"]["expert_bias"]
            assert seeded.shape == (8,) and float(jnp.abs(seeded).min()) > 0
            params[f"layers_{i}"]["moe"]["expert_bias"] = seeded * 10.0
    return module, params


def _ids(rows, length, seed=3):
    return np.random.default_rng(seed).integers(
        1, SIZES["vocab_size"], size=(rows, length), dtype=np.int32)


def _reference(params, ids, sizes=SIZES, config=CONFIG):
    with reference.highest():
        return np.asarray(family.reference_logits(
            params, jnp.asarray(ids), sizes, config, near_ties="kept"))


def _gap(got, want):
    return float(np.max(np.abs(np.asarray(got) - np.asarray(want))))


# -- (a) the forward pass -----------------------------------------------------

def test_forward_logits_are_the_references(model):
    module, params = model
    ids = _ids(3, 70)
    with reference.highest():
        got = module.apply({"params": params}, jnp.asarray(ids))
    want = _reference(params, ids)
    assert want.shape == (3, 70, SIZES["vocab_size"])
    assert 0.05 < float(want.std()) < 1.0        # TOL is of such logits
    assert _gap(got, want) < TOL


def test_forward_in_bf16_stays_within_its_own_wider_limit(model):
    """bf16 activations and weights (the router, its bias and the
    convolution's three products stay float32) against the float32
    reference. Eight layers of 8-bit products, six of them with a router
    whose near-ties bf16 settles the other way (one expert of three is a
    third of a layer's output at this size), read on three draws of
    tokens: the median token's largest gap 0.020 to 0.030 of a logit (a
    sixth of the logits' standard deviation), the mean entry 0.007 to
    0.010, the worst token 0.11 to 0.17. The limits are twice the
    median's and the mean's readings; the worst token is held to the
    controls' scale only."""
    module, params = model
    low = family.build(CONFIG, False, dtype=jnp.bfloat16,
                       param_dtype=jnp.bfloat16)
    import flax.core.meta as flax_meta
    dtypes = jax.eval_shape(lambda r: flax_meta.unbox(low.init(
        r, jnp.ones((1, 8), jnp.int32)))["params"], jax.random.PRNGKey(0))
    assert dtypes["layers_2"]["moe"]["router"].dtype == jnp.float32
    assert dtypes["layers_2"]["moe"]["expert_bias"].dtype == jnp.float32
    cast = jax.tree.map(lambda a, d: a.astype(d.dtype), params, dtypes)
    ids = _ids(2, 48)
    got = low.apply({"params": cast}, jnp.asarray(ids))
    gaps = np.abs(np.asarray(got.astype(jnp.float32))
                  - _reference(params, ids))
    per_token = gaps.max(-1).ravel()
    assert TOL < np.quantile(per_token, 0.5) < 6e-2
    assert gaps.mean() < 2e-2 and per_token.max() < 0.4


def test_float32_activations_over_bf16_weights_are_the_references(model):
    """The cell's arithmetic: the weights rounded to bf16 (what the chip
    holds), every activation float32, a product of the two as the
    activation's three bf16 terms (``dot_exact_weights``; the experts'
    rows likewise, side by side in their groups). Against the reference
    on the same rounded weights it reads what float32 weights read — the
    limit is ``TOL`` — where bf16 activations (the test above) read a
    hundred times that and flip the router's near-ties."""
    module, params = model
    cell = family.build(CONFIG, False, dtype=jnp.float32,
                        param_dtype=jnp.bfloat16)
    import flax.core.meta as flax_meta
    dtypes = jax.eval_shape(lambda r: flax_meta.unbox(cell.init(
        r, jnp.ones((1, 8), jnp.int32)))["params"], jax.random.PRNGKey(0))
    assert dtypes["experts"]["w_gate"].dtype == jnp.bfloat16
    assert dtypes["layers_0"]["conv"]["w"].dtype == jnp.bfloat16
    cast = jax.tree.map(lambda a, d: a.astype(d.dtype), params, dtypes)
    ids = _ids(2, 70)
    got = cell.apply({"params": cast}, jnp.asarray(ids))
    assert got.dtype == jnp.float32
    assert _gap(got, _reference(cast, ids)) < TOL
    # no float32 copy of a weight is made: the program multiplies bf16
    program = str(jax.make_jaxpr(lambda p: cell.apply(
        {"params": p}, jnp.asarray(ids)))(cast))
    assert "ragged_dot" in program
    assert not any(f"f32[6,8,{a},{b}]" in program
                   for a, b in ((64, 32), (32, 64)))


def test_the_exact_weights_product_is_float32s_from_three_bf16_terms():
    from deepspeed_tpu.models.layers import dot_exact_weights, split_terms
    from deepspeed_tpu.moe.sharded_moe import grouped_matmul
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 33, 256)) * 3.0
    terms = split_terms(x)
    assert terms.shape == (3, 5, 33, 256) and terms.dtype == jnp.bfloat16
    rebuilt = terms.astype(jnp.float32).sum(0)
    assert _gap(rebuilt, x) <= float(jnp.abs(x).max()) * 2.0 ** -23
    w = jax.random.normal(jax.random.PRNGKey(1), (256, 96))
    low = w.astype(jnp.bfloat16)
    with reference.highest():
        want = jnp.dot(x, low.astype(jnp.float32))
        assert _gap(dot_exact_weights(x, low), want) < 1e-5 * 48
        # any other pair of types is the plain product
        assert _gap(dot_exact_weights(x, w), jnp.dot(x, w)) == 0.0
    assert dot_exact_weights(x.astype(jnp.bfloat16), low).dtype \
        == jnp.bfloat16
    rows = jax.random.normal(jax.random.PRNGKey(2), (300, 64))
    stack = jax.random.normal(jax.random.PRNGKey(3), (5, 64, 48)).astype(
        jnp.bfloat16)
    groups = jnp.asarray([100, 0, 50, 120, 10], jnp.int32)
    with reference.highest():
        want = jax.lax.ragged_dot(rows, stack.astype(jnp.float32), groups)
        got = grouped_matmul(rows, stack, groups)
        exact = str(jax.make_jaxpr(grouped_matmul)(rows, stack, groups))
    assert got.dtype == jnp.float32 and got.shape == (300, 48)
    assert _gap(got[:280], want[:280]) < 1e-5 * 24
    assert "reduce_precision" in exact and "bf16[900,64]" in exact
    # a model chooses this by tracing under the highest matmul precision
    # (models/lfm2.py, for a float32 dtype): outside it the same pair of
    # types is the one plain product it was before there was a choice
    for fn, args in ((grouped_matmul, (rows, stack, groups)),
                     (dot_exact_weights, (x, low))):
        assert "reduce_precision" not in str(jax.make_jaxpr(fn)(*args))
    assert _gap(dot_exact_weights(x, low),
                jnp.dot(x, low.astype(jnp.float32))) == 0.0


# -- the short convolution's three forms --------------------------------------

def test_the_convolutions_three_forms_agree_to_the_bit():
    """A whole sequence; the same in chunks that take the carry of the
    chunk before; and token by token against the carry: float32, bit for
    bit, outputs and states. A row's padding (``token_mask``) writes no
    state, and an idle row keeps its own."""
    conv = ShortConv(d_model=32, kernel=3, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 20, 32))
    params = conv.init(jax.random.PRNGKey(1), x)["params"]
    whole = conv.apply({"params": params}, x)

    def step(state, part, mask=None):
        out, mut = conv.apply({"params": params, "cache": state}, part,
                              decode=True, token_mask=mask,
                              mutable=["cache", "kv_token"])
        return out, mut["cache"], mut["kv_token"]["trail"]

    zero = {"conv_state": jnp.zeros((2, 2, 32))}
    outs, state = [], zero
    for lo, hi in ((0, 8), (8, 16), (16, 20)):
        out, state, trail = step(state, x[:, lo:hi])
        outs.append(out)
        assert trail.shape == (2, 2 + hi - lo, 32)
    np.testing.assert_array_equal(np.asarray(jnp.concatenate(outs, 1)),
                                  np.asarray(whole))
    chunked_state = state
    outs, state = [], zero
    for t in range(20):
        out, state, _ = step(state, x[:, t:t + 1])
        outs.append(out)
    np.testing.assert_array_equal(np.asarray(jnp.concatenate(outs, 1)),
                                  np.asarray(whole))
    np.testing.assert_array_equal(np.asarray(state["conv_state"]),
                                  np.asarray(chunked_state["conv_state"]))
    # the state is the last two columns of B * u: the trail's end
    _, _, trail = step(zero, x)
    np.testing.assert_array_equal(np.asarray(trail[:, -2:]),
                                  np.asarray(state["conv_state"]))
    # row 0 holds 5 tokens of 8 and row 1 none: the state after is the
    # one after 5 tokens, and row 1's own
    mask = jnp.asarray([[True] * 5 + [False] * 3, [False] * 8])
    _, padded, _ = step(chunked_state, x[:, :8], mask)
    _, from5, _ = step(chunked_state, x[:, :5])
    np.testing.assert_array_equal(np.asarray(padded["conv_state"][0]),
                                  np.asarray(from5["conv_state"][0]))
    np.testing.assert_array_equal(
        np.asarray(padded["conv_state"][1]),
        np.asarray(chunked_state["conv_state"][1]))
    assert _gap(padded["conv_state"][0], chunked_state["conv_state"][0]) > 0


# -- (b), (d), (e), (f) through ServingEngine ---------------------------------

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
    """A ``ServingEngine`` (paged pool, chunked prefill a page at a time
    — ``prefill_chunk`` is pinned, ``test_prefill_chunk_width.py`` has
    the widths the server chooses — the prefix cache on, greedy) whose
    dispatches are logged in order
    (``benchmarks/chip/tools/lfm2_check.py DispatchLog``): ``rows(handle)``
    are the float32 logits each of a request's tokens was sampled from,
    through preemption and resumption too."""

    def __init__(self, module, params, seen, slots=3, kernel="auto",
                 pages=None, prefix=True):
        super().__init__(ds.init_inference(
            module, params=params, dtype=jnp.float32).serve(
            {"num_slots": slots, "max_len": 512,
             "paging": {"page_len": PAGE,
                        "num_pages": pages or 4 * slots + 1,
                        "prefill_chunk": PAGE, "kernel": kernel,
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


@pytest.mark.parametrize("kernel", ["auto", "on"],
                         ids=["gathered-pages", "paged-kernel"])
def test_chunked_prefill_then_paged_decode_gives_the_references_logits(
        model, seen, kernel):
    """A prompt of 300 tokens is prefilled in three chunks of one page
    (the last one padded by 84 rows: they write no state), then 9 tokens
    are decoded through the page pool and the slot's convolution state
    with two idle slots beside it — by the gathered-pages path the CPU
    takes by default, and by the paged-attention kernel (interpreted
    here, its grid over the 2 K/V heads) that the chip runs."""
    module, params = model
    prompt = _ids(1, 300, seed=5)[0]
    served = Served(module, params, seen, kernel=kernel)
    handle, = served.run(prompt, new_tokens=10)
    got = served.rows(handle)
    served.close()
    want = _teacher_forced(params, prompt, handle)
    assert got.shape == want.shape == (10, SIZES["vocab_size"])
    assert _gap(got, want) < TOL


def test_a_requests_logits_do_not_depend_on_its_company(model, seen):
    """Batch invariance: the same request alone (two idle slots beside
    it) and among two others of other lengths."""
    module, params = model
    a, b, c = _ids(1, 150, 7)[0], _ids(1, 40, 8)[0], _ids(1, 260, 9)[0]
    served = Served(module, params, seen, prefix=False)
    h1, = served.run(a)
    alone = served.rows(h1)
    served.close()
    served = Served(module, params, seen, prefix=False)
    h2, _, _ = served.run(a, b, c)
    among = served.rows(h2)
    served.close()
    assert list(h1.output_tokens) == list(h2.output_tokens)
    assert _gap(alone, among) < 2e-5
    assert _gap(among, _teacher_forced(params, a, h2)) < TOL


def test_a_reused_slot_does_not_leak_its_last_requests_state(model, seen):
    """One slot, two requests one after the other, no prefix cache: the
    second starts from zeros, not from what the first left in the slot's
    convolution state."""
    module, params = model
    first, second = _ids(1, 200, 11)[0], _ids(1, 90, 12)[0]
    served = Served(module, params, seen, slots=1, prefix=False)
    served.run(first)
    state = served.srv._paged.pool["layers_0"]["conv"]["conv_state"]
    assert float(jnp.abs(state).max()) > 0        # the slot holds a state
    handle, = served.run(second)
    got = served.rows(handle)
    served.close()
    assert _gap(got, _teacher_forced(params, second, handle)) < TOL


@pytest.fixture
def registry(monkeypatch):
    reg = registry_mod.MetricsRegistry()
    monkeypatch.setattr(registry_mod, "_DEFAULT_REGISTRY", reg)
    return reg


def _prefix_pair():
    """A: two whole pages and a tail. B: A's two pages, then its own."""
    a = _ids(1, 2 * PAGE + 37, 21)[0]
    b = np.concatenate([a[:2 * PAGE], _ids(1, 45, 22)[0]])
    return a, b


def test_a_prefix_hit_starts_from_the_state_stored_with_its_last_page(
        model, seen, registry):
    """B shares two whole pages with the earlier A and diverges after
    them: its one prefill chunk starts at position 256 from the
    convolution state that A's second chunk stored with its second page,
    and its logits are the reference's on B's whole prompt."""
    module, params = model
    a, b = _prefix_pair()
    served = Served(module, params, seen)
    served.run(a)
    count = lambda name: registry.counter("serving/" + name).value
    assert (count("state_resets"), count("state_snapshots_restored")) \
        == (1, 0)
    assert count("state_snapshots_stored") == 2       # A's two whole pages
    mgr = served.srv._paged
    shared = mgr.prefix.match(b)
    assert len(shared) == 2
    # a published page carries the state at its end, in every conv layer
    for i, kind in enumerate(LAYERS):
        if kind == "conv":
            at_pages = mgr.pool[f"layers_{i}"]["conv"]["page_state"]
            assert at_pages.shape == (mgr.num_pages, 2, 64)
            assert float(jnp.abs(at_pages[np.asarray(shared)]).min(
                axis=(1, 2)).max()) > 0
    chunks_before = served.srv.metrics.prefill_chunks
    handle, = served.run(b)
    got = served.rows(handle)
    assert served.srv.metrics.prefill_chunks - chunks_before == 1
    assert count("prefill_tokens_reused") == 2 * PAGE
    assert (count("state_resets"), count("state_snapshots_restored")) \
        == (1, 1)
    served.close()
    assert _gap(got, _teacher_forced(params, b, handle)) < TOL


def test_a_request_preempted_in_its_decode_resumes_where_it_was(model, seen):
    """Two slots; after a few tokens the slot cap falls to one and the
    request in slot 1 goes back to the queue with its tokens kept
    (``set_slot_cap`` drains by the preemption path). It is prefilled
    again over prompt + kept output — from the prefix cache's pages and
    the state stored with them — and every token's logits, before and
    after, are the reference's."""
    module, params = model
    a, b = _ids(1, 140, 31)[0], _ids(1, 270, 32)[0]
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
    for prompt, handle in ((a, ha), (b, hb)):
        got = served.rows(handle)
        assert _gap(got, _teacher_forced(params, prompt, handle)) < TOL
    served.close()


def test_handoff_and_speculation_refuse_a_model_with_recurrent_state(model):
    module, params = model
    eng = ds.init_inference(module, params=params, dtype=jnp.float32)
    options = {"num_slots": 2, "max_len": 256,
               "paging": {"page_len": PAGE, "num_pages": 5}}
    with pytest.raises(NotImplementedError, match="recurrent state"):
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


# -- (c) the training loss and its gradients ----------------------------------

def test_loss_and_gradients_are_the_references(model):
    """The loss function ``ds.initialize`` is handed (next-token cross
    entropy) and its gradients, against ``jax.grad`` of the reference's
    loss. A gradient's entries are 1e-6 to 1e-2 here; the limit is
    relative to each leaf's largest entry. The expert bias chooses and
    never weighs: its gradient is zero on both sides."""
    module, params = model
    batch = {"input_ids": jnp.asarray(_ids(4, 33))}
    with reference.highest():
        got, grads = jax.value_and_grad(lambda p: lfm2_loss_fn(
            module, {"params": p}, batch, None, True))(params)
        want, want_grads = jax.value_and_grad(lambda p: family.reference_loss(
            p, batch["input_ids"], SIZES, CONFIG))(params)
    assert abs(float(got) - float(want)) < 1e-5
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), w in zip(flat, jax.tree.leaves(want_grads)):
        name = jax.tree_util.keystr(path)
        scale = float(jnp.max(jnp.abs(w)))
        if "expert_bias" in name:
            assert scale == 0.0 and float(jnp.max(jnp.abs(g))) == 0.0
            continue
        assert scale > 0.0, name
        assert _gap(g, w) < 1e-3 * scale, name


def test_it_trains_and_evaluates_through_ds_initialize():
    """The same module down ``ds.initialize`` -> ``train_batch`` /
    ``eval_batch``: the loss falls, and ``eval_batch`` is the loss
    function's value on the trained parameters."""
    module = family.build(CONFIG, False)
    batch = {"input_ids": _ids(8, 33)}
    engine, *_ = ds.initialize(
        model=module, loss_fn=family.train_loss(CONFIG),
        config={"train_batch_size": 8, "gradient_accumulation_steps": 1,
                "optimizer": {"type": "Adam", "params": {"lr": 3e-3}},
                "zero_optimization": {"stage": 1}, "steps_per_print": 1000},
        sample_batch={"input_ids": batch["input_ids"][:1]},
        rng=jax.random.PRNGKey(0))
    try:
        losses = [float(engine.train_batch(batch)) for _ in range(8)]
        assert losses[-1] < losses[0] - 0.2, losses
        assert abs(float(engine.eval_batch(batch)) - losses[-1]) < 0.5
    finally:
        engine.destroy()


# -- (g) the controls: each wrong model fails the tolerance -------------------

def _route_with(scores_of, weigh_biased=False):
    """``family._route`` with another score, or the bias in the weights."""
    def route(m, p, top_k, config):
        scores = scores_of(m @ p["router"])
        biased = scores + p["expert_bias"]
        _, chosen = jax.lax.top_k(biased, top_k)
        weight = jnp.take_along_axis(biased if weigh_biased else scores,
                                     chosen, axis=-1)
        weight = weight / (jnp.sum(weight, -1, keepdims=True) + 1e-6)
        return jnp.sum(jax.nn.one_hot(chosen, scores.shape[-1])
                       * weight[..., None], axis=-2)
    return route


def _conv_without(gate):
    """``family._conv`` with ``B *`` or ``C *`` left out."""
    def conv(n, p):
        b, c, u = jnp.split(n @ p["in_proj"]["kernel"], 3, axis=-1)
        bu = u if gate == "B" else b * u
        taps, s = p["w"].shape[0], n.shape[1]
        padded = jnp.pad(bu, ((0, 0), (taps - 1, 0), (0, 0)))
        z = sum(p["w"][j] * padded[:, j:j + s] for j in range(taps))
        return (z if gate == "C" else c * z) @ p["out_proj"]["kernel"]
    return conv


def _attention_with(kv_of_head="group", norm="head"):
    """``family._attention`` with query head i reading K/V head i % n_kv,
    or the QK norms over the whole projection."""
    def attention(n, p, n_head, n_kv, eps, theta):
        b, s, d = n.shape
        hd = d // n_head
        qkv = n @ p["qkv"]["kernel"]
        q, k = qkv[..., :d], qkv[..., d:d + n_kv * hd]
        v = qkv[..., d + n_kv * hd:].reshape(b, s, n_kv, hd)
        if norm == "projection":
            q = family._rms(q, jnp.tile(p["q_norm"]["scale"], n_head), eps)
            k = family._rms(k, jnp.tile(p["k_norm"]["scale"], n_kv), eps)
        q, k = q.reshape(b, s, n_head, hd), k.reshape(b, s, n_kv, hd)
        if norm == "head":
            q = family._rms(q, p["q_norm"]["scale"], eps)
            k = family._rms(k, p["k_norm"]["scale"], eps)
        q, k = family._rope(q, theta), family._rope(k, theta)
        spread = (lambda t: jnp.tile(t, (1, 1, n_head // n_kv, 1))) \
            if kv_of_head == "modulo" else \
            (lambda t: jnp.repeat(t, n_head // n_kv, axis=2))
        k, v = spread(k), spread(v)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
        scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores,
                           -jnp.inf)
        a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
        return a.reshape(b, s, d) @ p["out"]["kernel"]
    return attention


CONTROLS = {
    "softmax for sigmoid": dict(patch=("_route", _route_with(
        lambda x: jax.nn.softmax(x, -1)))),
    "the bias added to the weights": dict(patch=("_route", _route_with(
        jax.nn.sigmoid, weigh_biased=True))),
    "no normalisation": dict(config=dict(CONFIG, norm_topk_prob=False)),
    "top-(k-1)": dict(sizes=dict(SIZES, num_experts_per_tok=2)),
    "no expert bias in the choice": dict(
        config=dict(CONFIG, use_expert_bias=False)),
    "B * left out": dict(patch=("_conv", _conv_without("B"))),
    "C * left out": dict(patch=("_conv", _conv_without("C"))),
    "query head i reads K/V head i % n_kv": dict(
        patch=("_attention", _attention_with(kv_of_head="modulo"))),
    "QK norm over the whole projection": dict(
        patch=("_attention", _attention_with(norm="projection"))),
    "RoPE left out": dict(patch=("_rope", lambda x, theta: x)),
}


def test_the_control_harness_is_sound(model, monkeypatch):
    """The patched-in attention with nothing changed is the family's."""
    _, params = model
    ids = _ids(2, 40)
    want = _reference(params, ids)
    monkeypatch.setattr(family, "_attention", _attention_with())
    monkeypatch.setattr(family, "_route", _route_with(jax.nn.sigmoid))
    assert _gap(_reference(params, ids), want) < 1e-6


@pytest.mark.parametrize("name", sorted(CONTROLS))
def test_a_wrong_model_fails_the_tolerance(model, monkeypatch, name):
    """The program against a reference that is wrong in one published
    detail: the gap is ``CONTROL_FACTOR`` times the tolerance or more."""
    module, params = model
    ids = _ids(2, 70)
    with reference.highest():
        got = module.apply({"params": params}, jnp.asarray(ids))
    assert _gap(got, _reference(params, ids)) < TOL
    control = CONTROLS[name]
    if "patch" in control:
        monkeypatch.setattr(family, *control["patch"])
    wrong = _reference(params, ids, control.get("sizes", SIZES),
                       control.get("config", CONFIG))
    assert _gap(got, wrong) > CONTROL_FACTOR * TOL


def test_a_state_dropped_at_a_chunk_boundary_fails_the_tolerance(
        model, seen, monkeypatch):
    """A broken program: every prefill chunk starts from zeros. (One
    page more in the pool: the sound programs of this file are compiled
    and cached, and another pool shape is another program.)"""
    module, params = model
    sound = cache_mod.chunk_state_view
    monkeypatch.setattr(manager, "chunk_state_view",
                        lambda cache, pool, page, fresh, *rest: sound(
                            cache, pool, page, jnp.asarray(True), *rest))
    prompt = _ids(1, 300, seed=5)[0]
    served = Served(module, params, seen, pages=14)
    handle, = served.run(prompt, new_tokens=4)
    got = served.rows(handle)
    served.close()
    assert _gap(got, _teacher_forced(params, prompt, handle)) \
        > CONTROL_FACTOR * TOL


def test_a_prefix_hit_from_zero_state_fails_the_tolerance(model, seen):
    """A broken cache: the shared pages' K/V are there and the state
    stored with them is not (zeroed between the two requests)."""
    module, params = model
    a, b = _prefix_pair()
    served = Served(module, params, seen)
    served.run(a)
    mgr = served.srv._paged
    mgr.pool = cache_mod._walk_state(
        mgr.pool, lambda unit: dict(
            unit, page_state=jnp.zeros_like(unit["page_state"])))
    handle, = served.run(b)
    got = served.rows(handle)
    assert served.srv.metrics.prefill_tokens_reused == 2 * PAGE
    served.close()
    assert _gap(got, _teacher_forced(params, b, handle)) \
        > CONTROL_FACTOR * TOL


# -- the router by itself -----------------------------------------------------

def test_the_sigmoid_router_chooses_on_biased_scores_and_weighs_unbiased():
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0], [0.0, 0.0, 3.0, 0.1]])
    bias = jnp.asarray([-1.0, 0.0, 0.0, 0.9])
    scores, weights, chosen = topk_routing(logits, 2, True, score="sigmoid",
                                           bias=bias)
    s = np.asarray(jax.nn.sigmoid(logits))
    np.testing.assert_allclose(np.asarray(scores), s, rtol=1e-6)
    want = np.argsort(-(s + np.asarray(bias)), axis=-1)[:, :2]
    assert np.asarray(chosen).tolist() == want.tolist()
    assert np.asarray(chosen)[0].tolist() == [3, 1]     # not 0: the bias
    picked = np.take_along_axis(s, want, -1)
    np.testing.assert_allclose(
        np.asarray(weights),
        picked / (picked.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    # the softmax path is what it was
    probs, w, c = topk_routing(logits, 2)
    np.testing.assert_allclose(np.asarray(probs),
                               np.asarray(jax.nn.softmax(logits, -1)))
    assert np.asarray(c).tolist() == [[0, 1], [2, 3]]
    with pytest.raises(ValueError, match="unknown router score"):
        topk_routing(logits, 2, score="tanh")


def test_the_routers_counts_are_the_expert_layers_and_the_live_rows(model):
    module, params = model
    ids = jnp.asarray(_ids(4, 24))
    live = jnp.ones((4, 24), bool).at[1].set(False).at[3, 10:].set(False)
    full, router = module.apply({"params": params}, ids, return_router=True)
    part, masked = module.apply({"params": params}, ids, token_mask=live,
                                return_router=True)
    assert _gap(full[0], part[0]) == 0.0 and _gap(full[2], part[2]) == 0.0
    assert _gap(full[3, :10], part[3, :10]) == 0.0
    k, moe_layers = SIZES["num_experts_per_tok"], 6
    assert router["counts"].shape == (moe_layers, SIZES["num_experts"])
    assert np.asarray(router["counts"]).sum(1).tolist() \
        == [4 * 24 * k] * moe_layers
    assert np.asarray(masked["counts"]).sum(1).tolist() \
        == [(24 + 24 + 10) * k] * moe_layers


@pytest.mark.parametrize("name", ["the bias added to the weights",
                                  "no expert bias in the choice"])
def test_a_wrong_use_of_the_bias_fails_at_the_seeded_scale_too(
        model, monkeypatch, name):
    """The two controls on the bias with ``expert_bias`` as
    ``DroplessMoE`` seeds it (``EXPERT_BIAS_INIT_STD`` 0.01: the scale a
    configuration of this family runs), not the fixture's ten times
    that. Read on this draw: 0.13 and 0.17 of a logit against the sound
    program's 4.5e-7."""
    module, params = model
    params = dict(params)
    for i in range(CONFIG["num_dense_layers"], len(LAYERS)):
        moe = params[f"layers_{i}"]["moe"]
        params[f"layers_{i}"] = dict(params[f"layers_{i}"], moe=dict(
            moe, expert_bias=moe["expert_bias"] / 10.0))
    assert abs(float(jnp.std(jnp.stack(
        [params[f"layers_{i}"]["moe"]["expert_bias"]
         for i in range(CONFIG["num_dense_layers"], len(LAYERS))])))
        - EXPERT_BIAS_INIT_STD) < 0.5 * EXPERT_BIAS_INIT_STD
    ids = _ids(2, 70)
    with reference.highest():
        got = module.apply({"params": params}, jnp.asarray(ids))
    assert _gap(got, _reference(params, ids)) < TOL
    control = CONTROLS[name]
    if "patch" in control:
        monkeypatch.setattr(family, *control["patch"])
    wrong = _reference(params, ids, SIZES, control.get("config", CONFIG))
    assert _gap(got, wrong) > CONTROL_FACTOR * TOL


# -- the rows a comparison cannot judge (families/lfm2.py NEAR_TIE) ---------

def test_the_reference_flags_a_near_tie_of_the_chosen_and_the_left_out():
    """Scores handed in through an identity router: three tokens whose
    third and fourth of eight biased scores lie 5e-6, 1e-3 and 0 apart.
    The bias counts: the second token's *unbiased* scores are a tie."""
    logit = lambda s: np.log(s / (1 - s))
    base = np.array([0.9, 0.8, 0.7, 0.6, 0.3, 0.2, 0.1, 0.05])
    scores = np.stack([base, base, base])
    scores[0, 3] = 0.7 - 5e-6
    scores[1, 3] = 0.7                  # the bias sets these 1e-3 apart
    scores[2, 3] = 0.7
    bias = np.zeros((3, 8), np.float32)
    bias[1, 3] = -1e-3
    for row, want in enumerate([True, False, True]):
        p = {"router": jnp.eye(8, dtype=jnp.float32),
             "expert_bias": jnp.asarray(bias[row])}
        m = jnp.asarray(logit(scores[row]), jnp.float32)[None, None]
        weight = family._route(m, p, 3, CONFIG)
        near = family._near_tie(m, p, 3, CONFIG)
        assert bool(near[0, 0]) is want, row
        chosen = np.flatnonzero(np.asarray(weight[0, 0]))
        assert len(chosen) == 3 and set(chosen[:2]) == {0, 1}
        np.testing.assert_allclose(float(weight.sum()), 1.0, atol=1e-5)


def test_unjudged_rows_are_the_near_ties_and_what_the_convs_carry_them_into(
        model, monkeypatch):
    """``reference_logits`` as the benchmark calls it: every row is the
    plain reference's or zeros; with no near-tie none is zeroed (the
    seeded model at 40 positions holds none at 1e-5); every stretch of
    zeroed rows is at least the reach of a near-tie long; a wider limit
    zeroes a superset; and a comparison reads a zeroed row as a gap of 0."""
    _, params = model
    ids = _ids(2, 40)
    kept = _reference(params, ids)

    def judged(limit):
        monkeypatch.setattr(family, "NEAR_TIE", limit)
        with reference.highest():
            return np.asarray(family.reference_logits(
                params, jnp.asarray(ids), SIZES, CONFIG))

    assert family.NEAR_TIE == 1e-5
    np.testing.assert_array_equal(judged(1e-5), kept)
    reach = family._reach(SIZES)
    assert reach == 4
    before = np.zeros(ids.shape, bool)
    for limit in (2e-3, 1e-2, 1.0):
        got = judged(limit)
        zeroed = ~got.any(-1)
        np.testing.assert_array_equal(got[~zeroed], kept[~zeroed])
        assert (before <= zeroed).all()
        for row in zeroed:
            edges = np.flatnonzero(np.diff(np.r_[0, row, 0]))
            starts, ends = edges[::2], edges[1::2]
            assert all(e - s >= reach + 1 or e == len(row)
                       for s, e in zip(starts, ends))
        before = zeroed
    assert 0 < judged(2e-3).any(-1).sum() < ids.size   # some, not all
    assert zeroed.all()                                 # at 1.0: every row
    gap = got.max(-1) - np.take_along_axis(got, ids[..., None], -1)[..., 0]
    assert (gap == 0).all()


def test_a_flipped_near_tie_moves_rows_that_are_not_judged(model):
    """What a run of the cell meets about once in thirty: the program's
    rounding falls on the other side of a near-tie. Made here by hand — a
    model whose first expert layer has, at position 20, its third and
    fourth biased scores 2e-6 apart, and the same model with the fourth
    lifted 4e-6 (the other choice there, and nowhere else at 2e-6). The
    row at that position moves by whole tolerances; it is a row
    ``reference_logits`` does not judge; and no row before it moves."""
    _, params = model
    ids = _ids(1, 40, seed=11)
    at, layer = 20, CONFIG["num_dense_layers"]
    with reference.highest():
        f32 = lambda t: jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), t)
        x = f32(params["wte"])[ids]
        for i in range(layer + 1):
            p = f32(params[f"layers_{i}"])
            n = family._rms(x, p["operator_norm"]["scale"], 1e-5)
            h = x + (family._conv(n, p["conv"]) if LAYERS[i] == "conv"
                     else family._attention(n, p["attn"], 4, 2, 1e-5, 1e6))
            m = family._rms(h, p["ffn_norm"]["scale"], 1e-5)
            if i < layer:
                w = p["mlp"]
                x = h + (jax.nn.silu(m @ w["w1"]["kernel"])
                         * (m @ w["w3"]["kernel"])) @ w["w2"]["kernel"]
    moe = params[f"layers_{layer}"]["moe"]
    biased = np.asarray(jax.nn.sigmoid(m[0, at] @ moe["router"])
                        + moe["expert_bias"], np.float64)
    order = np.argsort(-biased)
    third, fourth = order[2], order[3]

    def with_bias(lift):
        bias = np.asarray(moe["expert_bias"], np.float64).copy()
        bias[fourth] += biased[third] - biased[fourth] + lift
        out = jax.tree.map(lambda a: a, params)
        out[f"layers_{layer}"]["moe"]["expert_bias"] = jnp.asarray(
            bias, jnp.float32)
        return out

    one, other = with_bias(-2e-6), with_bias(2e-6)
    a, b = _reference(one, ids), _reference(other, ids)
    moved = np.abs(a - b).max(-1)[0]
    assert moved[at] > 100 * TOL
    assert (moved[:at] == 0).all()
    with reference.highest():
        judged = np.asarray(family.reference_logits(
            one, jnp.asarray(ids), SIZES, CONFIG))
    unjudged = ~judged.any(-1)[0]
    reach = family._reach(SIZES)
    assert np.flatnonzero(unjudged).tolist() == list(range(at, at + reach + 1))
    # ... and what is judged is the plain reference's row
    np.testing.assert_array_equal(judged[0][~unjudged], a[0][~unjudged])
