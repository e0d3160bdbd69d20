"""OLMoE through the program's normal paths against the plain float32
reference of ``benchmarks/chip/families/olmoe.py``, on the CPU at a small
size (hidden 64, 2 layers, 4 heads, 8 experts top-3, expert width 32,
vocabulary 512), seeded weights, logits and never tokens:

(a) the full forward pass; (b) prefill in chunks, then decoding through
the paged cache step by step, as ``ServingEngine`` does it; (c) the loss
``engine.train_batch`` is given, and its gradients; (d) batch invariance:
a request alone and among others, with idle slots beside it; (e) the
controls — five wrong models, each of which must fail (a)'s tolerance.

The tolerance. Program and reference both compute in float32 on the CPU
(``reference.highest()``), and differ in the order of their sums: the
program sorts rows by expert and multiplies group by group, the reference
computes every expert for every token; attention is the kernel's blocked
softmax against one softmax. Such differences read 0 to 1e-6 of a logit
here (logits' standard deviation 0.16). ``TOL`` = 2e-4 leaves two decades
for another backend's summation order and is a thousand times under the
smallest control: renormalised weights move a logit by 0.27, a missing
third expert by 0.39. The bf16 case states its own limit.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from benchmarks.chip import model as bench_model
from benchmarks.chip import reference
from benchmarks.chip.families import olmoe as family
from deepspeed_tpu.models.olmoe import olmoe_loss_fn
from deepspeed_tpu.observability import metrics as registry_mod
from deepspeed_tpu.serving.paging import manager

CONFIG = {"hidden_size": 64, "intermediate_size": 32, "num_hidden_layers": 2,
          "num_attention_heads": 4, "num_experts": 8,
          "num_experts_per_tok": 3, "max_position_embeddings": 512,
          "vocab_size": 512, "norm_topk_prob": False, "rms_norm_eps": 1e-5,
          "rope_theta": 10000, "compute_dtype": "float32",
          "param_dtype": "float32"}
SIZES = family.sizes(CONFIG, False)
TOL = 2e-4          # of a logit, absolute: the module's docstring
SEED = 2 ** 31 + 28
PAGE = 128


@pytest.fixture(scope="module")
def model():
    module = family.build(CONFIG, False)
    return module, bench_model.seeded_params(module, SEED)


def _ids(rows, length, seed=3):
    return np.random.default_rng(seed).integers(
        1, SIZES["vocab_size"], size=(rows, length), dtype=np.int32)


def _reference(params, ids, sizes=SIZES, config=CONFIG):
    with reference.highest():
        return np.asarray(family.reference_logits(
            params, jnp.asarray(ids), sizes, config))


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
    """bf16 activations and weights (the router stays float32) against
    the float32 reference. A bf16 product carries 8 bits: two layers of
    such rounding move a token's logits by 1e-2 at most, a sixteenth of
    their standard deviation — the limit on nine tokens in ten is 2e-2,
    and on the mean 5e-3. The tenth is not rounding: where two gate
    probabilities nearly tie, bf16 matmuls pick another of the 8 experts
    than the reference does (a handful of 576 assignments here), and at
    top-3 of 8 one expert is a sixth of the layer's output: such a token
    reads 0.04 to 0.12. No fixed limit on the largest gap lets that in
    and keeps a wrong path out at this size, so the largest gap is held
    to the controls' scale only (under 0.3)."""
    module, params = model
    low = family.build(CONFIG, False, dtype=jnp.bfloat16,
                       param_dtype=jnp.bfloat16)
    import flax.core.meta as flax_meta
    dtypes = jax.eval_shape(lambda r: flax_meta.unbox(low.init(
        r, jnp.ones((1, 8), jnp.int32)))["params"], jax.random.PRNGKey(0))
    assert dtypes["h"]["mlp"]["router"].dtype == jnp.float32
    cast = jax.tree.map(lambda a, d: a.astype(d.dtype), params, dtypes)
    ids = _ids(2, 48)
    got = low.apply({"params": cast}, jnp.asarray(ids))
    gaps = np.abs(np.asarray(got.astype(jnp.float32))
                  - _reference(params, ids))
    per_token = gaps.max(-1).ravel()
    assert TOL < np.quantile(per_token, 0.9) < 2e-2
    assert gaps.mean() < 5e-3 and per_token.max() < 0.3


# -- (b), (d) through ServingEngine -------------------------------------------

@pytest.fixture(scope="module")
def seen():
    """The logits every paged serving program of this file samples from,
    in dispatch order: the programs' sampler is watched for as long as
    the file's tests run (a program is traced once and cached, so the
    watcher and its list are the file's, not a test's)."""
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


def _serve(module, params, prompts, new_tokens, seen, slots=3,
           kernel="auto"):
    """Serve ``prompts`` together through ``ServingEngine`` (paged pool,
    chunked prefill, greedy) and return, per request, the float32 logits
    that each of its tokens was sampled from, ``[new_tokens, V]``.
    Requests are admitted first come first served into slots 0, 1, ...;
    prefill runs one chunk of one page an iteration in that order
    (``prefill_chunk`` is pinned: the rows are found by counting
    chunks); a request's decode logits are its slot's row of the decode
    dispatches that follow its last chunk."""
    del seen[:]
    with reference.highest():
        srv = ds.init_inference(module, params=params,
                                dtype=jnp.float32).serve(
            {"num_slots": slots, "max_len": 512,
             "paging": {"page_len": PAGE, "num_pages": 4 * slots + 1,
                        "prefill_chunk": PAGE, "kernel": kernel,
                        "enable_prefix_cache": False}})
        handles = [srv.submit(p, max_new_tokens=new_tokens) for p in prompts]
        srv.run()
        srv.close()
    jax.effects_barrier()
    assert all(h.status == "finished" for h in handles)
    out, chunk_no = [], 0
    chunk_at = [i for i, x in enumerate(seen) if x.shape[0] == 1]
    for slot, (prompt, h) in enumerate(zip(prompts, handles)):
        chunk_no += -(-len(prompt) // PAGE)
        last = chunk_at[chunk_no - 1]
        decode = [x for x in seen[last + 1:] if x.shape[0] == slots]
        rows = [seen[last][0]] + [x[slot] for x in decode[:new_tokens - 1]]
        assert [int(r.argmax()) for r in rows] == list(h.output_tokens)
        out.append(np.stack(rows))
    return out, handles


def _teacher_forced(params, prompt, handle):
    """The reference's logits at the positions that predict each served
    token: one full forward pass over prompt + output."""
    ids = np.concatenate([prompt, np.asarray(handle.output_tokens)])
    want = _reference(params, ids[None])[0]
    return want[len(prompt) - 1:len(ids) - 1]


@pytest.mark.parametrize("kernel", ["auto", "on"],
                         ids=["gathered-pages", "paged-kernel"])
def test_chunked_prefill_then_paged_decode_gives_the_references_logits(
        model, seen, kernel):
    """A prompt of 300 tokens is prefilled in three chunks (the last one
    padded by 84 rows), then 9 tokens are decoded through the page pool
    with two idle slots beside it — by the gathered-pages path the CPU
    takes by default, and by the paged-attention kernel (interpreted
    here) that the chip runs."""
    module, params = model
    prompt = _ids(1, 300, seed=5)[0]
    (got,), (handle,) = _serve(module, params, [prompt], 10, seen,
                               kernel=kernel)
    want = _teacher_forced(params, prompt, handle)
    assert got.shape == want.shape == (10, SIZES["vocab_size"])
    assert _gap(got, want) < TOL


def test_a_requests_logits_do_not_depend_on_its_company(model, seen):
    """Batch invariance: the same request alone (two idle slots beside
    it) and among two others of other lengths. Its logits agree to
    rounding — far inside ``TOL`` — and both with the reference."""
    module, params = model
    a, b, c = _ids(1, 150, 7)[0], _ids(1, 40, 8)[0], _ids(1, 260, 9)[0]
    (alone,), (h1,) = _serve(module, params, [a], 8, seen)
    (among, _, _), (h2, _, _) = _serve(module, params, [a, b, c], 8, seen)
    assert list(h1.output_tokens) == list(h2.output_tokens)
    assert _gap(alone, among) < 2e-5
    assert _gap(among, _teacher_forced(params, a, h2)) < TOL


def test_idle_slots_and_padding_are_routed_to_no_expert(model, seen,
                                                        monkeypatch):
    """``moe/assignments`` = live tokens x k x layers: prompts of 150
    and 40 tokens are padded to 256 and 128 rows of prefill, and a third
    slot idles through every decode step."""
    module, params = model
    reg = registry_mod.MetricsRegistry()
    monkeypatch.setattr(registry_mod, "_DEFAULT_REGISTRY", reg)
    prompts = [_ids(1, 150, 7)[0], _ids(1, 40, 8)[0]]
    _, handles = _serve(module, params, prompts, 6, seen)
    # every prompt token and every generated token but the last is routed
    live = sum(len(p) + len(h.output_tokens) - 1
               for p, h in zip(prompts, handles))
    k, layers = SIZES["num_experts_per_tok"], SIZES["num_hidden_layers"]
    assert reg.counter("moe/assignments").value == live * k * layers
    calls = reg.counter("moe/expert_calls").value
    assert calls % layers == 0 and calls >= layers * (3 + 5)
    assert reg.counter("moe/experts_offered").value \
        == calls * SIZES["num_experts"]
    touched = reg.counter("moe/experts_touched").value
    assert 0 < touched <= reg.counter("moe/experts_offered").value
    # the largest group of a call is at least the mean group
    assert reg.counter("moe/load_max").value * SIZES["num_experts"] \
        >= live * k * layers


def test_a_masked_row_changes_no_other_rows_logits(model):
    """The model alone: rows masked out of a batch leave the others'
    logits as they were, and the router's counts ``[L, E]`` hold the live
    rows only."""
    module, params = model
    ids = jnp.asarray(_ids(4, 24))
    live = jnp.ones((4, 24), bool).at[1].set(False).at[3, 10:].set(False)
    full, router = module.apply({"params": params}, ids, return_router=True)
    part, masked = module.apply({"params": params}, ids, token_mask=live,
                                return_router=True)
    assert _gap(full[0], part[0]) == 0.0 and _gap(full[2], part[2]) == 0.0
    k, layers = SIZES["num_experts_per_tok"], SIZES["num_hidden_layers"]
    assert router["counts"].shape == (layers, SIZES["num_experts"])
    assert np.asarray(router["counts"]).sum(1).tolist() == [4 * 24 * k] * 2
    assert np.asarray(masked["counts"]).sum(1).tolist() \
        == [(24 + 24 + 10) * k] * 2


# -- (c) the training loss and its gradients ----------------------------------

def test_loss_and_gradients_are_the_references(model):
    """The loss function ``ds.initialize`` is handed (cross entropy plus
    0.01 x the load-balancing loss) and its gradients, against
    ``jax.grad`` of the reference's loss. A gradient's entries are 1e-5
    to 1e-2 here; the limit is relative to each leaf's largest entry."""
    module, params = model
    batch = {"input_ids": jnp.asarray(_ids(4, 33))}
    with reference.highest():
        got, grads = jax.value_and_grad(lambda p: olmoe_loss_fn(
            module, {"params": p}, batch, None, True))(params)
        want, want_grads = jax.value_and_grad(lambda p: family.reference_loss(
            p, batch["input_ids"], SIZES, CONFIG))(params)
    assert abs(float(got) - float(want)) < 1e-5
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), w in zip(flat, jax.tree.leaves(want_grads)):
        scale = float(jnp.max(jnp.abs(w)))
        assert scale > 0.0, path
        assert _gap(g, w) < 1e-3 * scale, jax.tree_util.keystr(path)


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


# -- (e) the controls: each wrong model fails (a)'s tolerance -----------------

def _no_qk_norm(x, p, n_head, eps, theta):
    """``family._attention`` with the two QK norms left out."""
    b, s, d = x.shape
    n = family._rms(x, p["ln_1"]["scale"], eps)
    q, k, v = jnp.split(n @ p["attn"]["qkv"]["kernel"], 3, axis=-1)
    q, k, v = (t.reshape(b, s, n_head, d // n_head) for t in (q, k, v))
    return _attend(x, p, family._rope(q, theta), family._rope(k, theta), v)


def _qk_norm_per_head(x, p, n_head, eps, theta):
    """``family._attention`` with the QK norms after the split into
    heads: each head normalised by its own mean square."""
    b, s, d = x.shape
    n = family._rms(x, p["ln_1"]["scale"], eps)
    q, k, v = (t.reshape(b, s, n_head, d // n_head) for t in jnp.split(
        n @ p["attn"]["qkv"]["kernel"], 3, axis=-1))
    q = family._rms(q, p["attn"]["q_norm"]["scale"].reshape(n_head, -1), eps)
    k = family._rms(k, p["attn"]["k_norm"]["scale"].reshape(n_head, -1), eps)
    return _attend(x, p, family._rope(q, theta), family._rope(k, theta), v)


def _attend(x, p, q, k, v):
    b, s, d = x.shape
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    return x + a.reshape(b, s, d) @ p["attn"]["out"]["kernel"]


CONTROLS = {
    "top-(k-1)": dict(sizes=dict(SIZES, num_experts_per_tok=2)),
    "renormalised top-k weights": dict(
        config=dict(CONFIG, norm_topk_prob=True)),
    "no QK-norm": dict(patch=("_attention", _no_qk_norm)),
    "QK-norm after the head split": dict(
        patch=("_attention", _qk_norm_per_head)),
    "RoPE left out": dict(patch=("_rope", lambda x, theta: x)),
}


@pytest.mark.parametrize("name", sorted(CONTROLS))
def test_a_wrong_model_fails_the_tolerance(model, monkeypatch, name):
    """The program against a reference that is wrong in one published
    detail: the gap is 100 times the tolerance or more (they read 0.27
    to 0.66). (The QK norms'
    scales are drawn at random here: at their initial ones a norm over
    the whole projection and no norm differ by a factor the softmax
    sees, but a test should not lean on that.)"""
    module, params = model
    scales = jax.random.uniform(jax.random.PRNGKey(1), (2, 2, 64),
                                minval=0.5, maxval=1.5)
    params = jax.tree.map(lambda a: a, params)
    params["h"]["attn"]["q_norm"]["scale"] = scales[0]
    params["h"]["attn"]["k_norm"]["scale"] = scales[1]
    ids = _ids(2, 70)
    with reference.highest():
        got = module.apply({"params": params}, jnp.asarray(ids))
    assert _gap(got, _reference(params, ids)) < TOL
    control = CONTROLS[name]
    if "patch" in control:
        monkeypatch.setattr(family, *control["patch"])
    wrong = _reference(params, ids, control.get("sizes", SIZES),
                       control.get("config", CONFIG))
    assert _gap(got, wrong) > 100 * TOL


# -- the router and the dispatch by themselves --------------------------------

def test_topk_gate_names_the_dropless_path_for_more_than_two_experts():
    from deepspeed_tpu.moe import TopKGate
    gate = TopKGate(d_model=8, num_experts=4, k=3)
    with pytest.raises(ValueError, match="DroplessMoE"):
        gate.init(jax.random.PRNGKey(0), jnp.ones((5, 8)))


@pytest.mark.parametrize("rows,live", [(300, 300), (260, 131), (128, 128),
                                       (130, 0), (1500, 1400)])
def test_the_grouped_matmul_in_row_tiles_is_one_ragged_dot(rows, live):
    """``grouped_matmul`` calls ``ragged_dot`` on 128 rows at a time (on
    more where that would take over 8 calls: 1500 rows go 188 at a time),
    each call with the part of every group that lies in its rows: the same
    product as one call over all rows, rows past the groups' sum (masked
    assignments) included — they come out as zeros either way."""
    from deepspeed_tpu.moe.sharded_moe import (MAX_CALLS, ROW_TILE,
                                               grouped_matmul)
    rng = np.random.default_rng(rows)
    sizes = rng.multinomial(live, rng.dirichlet(np.ones(12))).astype(np.int32)
    x = jnp.asarray(rng.normal(size=(rows, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(12, 16, 24)), jnp.float32)
    assert (ROW_TILE, MAX_CALLS) == (128, 8)
    want = jax.lax.ragged_dot(x, w, jnp.asarray(sizes))
    got = jax.jit(grouped_matmul)(x, w, jnp.asarray(sizes))
    assert got.shape == (rows, 24)
    assert _gap(got, want) < 1e-5
    assert float(jnp.abs(got[live:]).max(initial=0.0)) == 0.0


def test_a_matmul_over_a_page_pools_tokens_is_eight_calls_not_thousands():
    """The page pool's shape-only init traces the model over every
    token the pool holds (513 pages x 128 tokens x 8 experts a token):
    at 128 rows a call that would be 4,104 calls a matmul, and a minute
    of tracing in every process's set-up (my chip run, PR 28)."""
    from deepspeed_tpu.moe.sharded_moe import MAX_CALLS, grouped_matmul
    shape = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(grouped_matmul)(
        shape((513 * 128 * 8, 16), jnp.bfloat16),
        shape((12, 16, 24), jnp.bfloat16), shape((12,), jnp.int32))
    calls = [e for e in jaxpr.eqns if "ragged_dot" in e.primitive.name]
    assert len(calls) == MAX_CALLS


def test_a_layer_reads_its_own_experts_of_the_stack_and_no_others():
    """``DroplessMoE`` takes the model's ``[L, E, ...]`` stack and its
    ``layer``: a lone layer's ``[1, E, d, f]`` weights, and the same as
    layer 1 of three with noise in the other two, give the same output
    and counts — every token through its two experts, computed here
    expert by expert."""
    from deepspeed_tpu.moe import DroplessMoE
    layer = DroplessMoE(num_experts=4, num_experts_per_tok=2,
                        dtype=jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(keys[0], (2, 5, 16))
    lone = tuple(jax.random.normal(k, shape) / 4 for k, shape in zip(
        keys[1:4], [(1, 4, 16, 8), (1, 4, 16, 8), (1, 4, 8, 16)]))
    params = layer.init(keys[4], x, experts=lone, layer=0)["params"]
    own, aux = layer.apply({"params": params}, x, experts=lone, layer=0)
    stacks = tuple(jax.random.normal(keys[4], (3,) + w.shape[1:]
                                     ).at[1].set(w[0]) for w in lone)
    stacked, aux2 = layer.apply({"params": params}, x, experts=stacks,
                                layer=jnp.int32(1))
    assert _gap(own, stacked) < 1e-6
    assert np.array_equal(aux["counts"], aux2["counts"])
    assert int(aux["counts"].sum()) == 2 * 5 * 2

    import flax.core.meta as flax_meta
    router = flax_meta.unbox(params)["router"]
    probs = jax.nn.softmax(x @ router, -1)
    weight, chosen = jax.lax.top_k(probs, 2)
    gate, up, down = (w[0] for w in lone)
    every = jnp.einsum("bsef,efd->bsed", jax.nn.silu(jnp.einsum(
        "bsd,edf->bsef", x, gate)) * jnp.einsum("bsd,edf->bsef", x, up), down)
    want = jnp.sum(jnp.take_along_axis(every, chosen[..., None], 2)
                   * weight[..., None], 2)
    assert _gap(own, want) < 1e-5


def test_the_cells_configuration_is_the_catalogs_but_for_depth():
    """``configs/olmoe-1b-7b-8l-serve.json``: every published key as the
    source has it, the depth halved, and the module it builds has the
    published shapes."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmarks", "chip", "configs",
                           "olmoe-1b-7b-8l-serve.json")) as f:
        config = json.load(f)
    changed = {k for k, v in config["published"].items() if config[k] != v}
    assert changed == {"num_hidden_layers"}
    assert (config["num_hidden_layers"], config["published"][
        "num_hidden_layers"]) == (8, 16)
    module = family.build(config, False)
    import flax.core.meta as flax_meta
    shapes = jax.eval_shape(lambda r: flax_meta.unbox(module.init(
        r, jnp.ones((1, 8), jnp.int32)))["params"], jax.random.PRNGKey(0))
    assert shapes["experts"]["w_gate"].shape == (8, 64, 2048, 1024)
    assert shapes["experts"]["w_down"].shape == (8, 64, 1024, 2048)
    assert shapes["h"]["mlp"]["router"].shape == (8, 2048, 64)
    assert shapes["h"]["mlp"]["router"].dtype == jnp.float32
    assert shapes["h"]["attn"]["qkv"]["kernel"].shape == (8, 2048, 3 * 2048)
    assert shapes["lm_head"]["kernel"].shape == (2048, 50304)
    assert "wpe" not in shapes
    nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    assert 7.0e9 < nbytes < 7.2e9          # the file's 7.1 GB
