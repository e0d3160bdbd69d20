"""Phi-4-mini-flash (SambaY with differential attention) through the
program's normal paths against the plain float32 reference of
``benchmarks/chip/families/phi4flash.py`` (its mixer's recurrence one
token at a time, every layer on every position), on the CPU at a small
size (hidden 64, 8 layers: mixers 0, 2, 4, window layers 1 and 3, the
full layer 5, a memory unit 6 and a cross layer 7; 8 query heads on 4
K/V heads of 8; a window of 32 = two pages of 16; a state of 16 x 128;
MLP width 96; vocabulary 256), seeded weights, logits and never tokens:

(a) the full forward pass, and the cross-decoder on one position a row;
(b) the mixer's three forms and the ring's write; (c) prefill in chunks
— one that crosses the window's end and one that starts past it — then
decoding through the paged pool, the rings and the slots' states until
the ring has wrapped twice, by the gathered path and by the kernels
(interpreted); (d) batch invariance, a slot reused by a shorter request;
(e) the chunk program's two widths; (f) the refusals by name; (g) the
controls, each of which must fail the tolerance the sound program passes.

The tolerance. Program and reference both compute in float32 on the CPU
(``reference.highest()``) and differ in the order of their sums: the
program's mixer scans a block by ``associative_scan`` where the
reference steps the recurrence a token at a time, its attention reads a
ring in lane order and a cache of stacked halves where the reference has
one softmax a pair over the positions in order, the decode kernels
accumulate a softmax online. Such differences read 1e-6 to 5e-6 of a
standard deviation of the reference's logits here. ``TOL`` = 5e-5 sigma
leaves a decade for another backend's summation order and is a fifth of
the smallest control (an SSM state kept in bf16: ``CONTROL_FACTOR``)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from benchmarks.chip import model as bench_model
from benchmarks.chip import reference
from benchmarks.chip.families import phi4flash as family
from benchmarks.chip.tools.lfm2_check import DispatchLog
from deepspeed_tpu.inference import cache as cache_mod
from deepspeed_tpu.inference.generation import generate
from deepspeed_tpu.models import layers
from deepspeed_tpu.observability import metrics as registry_mod
from deepspeed_tpu.ops.pallas import tuning
from deepspeed_tpu.serving.paging import manager

PAGE, WINDOW = 16, 32
CONFIG = {
    "hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 8,
    "num_attention_heads": 8, "num_key_value_heads": 4,
    "sliding_window": WINDOW, "mb_per_layer": 2,
    "max_position_embeddings": 512, "vocab_size": 256,
    "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_bc_gain": 2.0,
    "layer_norm_eps": 1e-5, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "compute_dtype": "float32",
    "param_dtype": "float32", "ssm_state_dtype": "float32",
    "kv_cache_dtype": "float32"}
SIZES = family.sizes(CONFIG, False)
TOL = 5e-5            # sigmas of the reference's logits: the docstring
CONTROL_FACTOR = 5    # every control reads at least this many TOL
SEED = 2 ** 31 + 56


@pytest.fixture(scope="module")
def model():
    """The module and its seeded weights; every bias and norm scale that
    starts at zero or one is drawn, so that a dropped one reads."""
    module = family.build(CONFIG, False)
    params = jax.tree.map(lambda a: a,
                          bench_model.seeded_params(module, SEED))
    key = jax.random.PRNGKey(1)

    def draw(tree):
        nonlocal key
        for name, leaf in tree.items():
            if isinstance(leaf, dict):
                draw(leaf)
            elif name in ("bias", "conv_b"):
                key, k = jax.random.split(key)
                tree[name] = 0.3 * jax.random.normal(k, leaf.shape)
            elif name == "scale":
                key, k = jax.random.split(key)
                tree[name] = jax.random.uniform(k, leaf.shape, minval=0.5,
                                                maxval=1.5)
    draw(params)
    mixer = params["layers_0"]["mixer"]
    # Mamba-1's published initialisation
    np.testing.assert_allclose(np.exp(np.asarray(mixer["A_log"]))[:, 0],
                               np.arange(1, 17), rtol=1e-6)
    assert np.all(np.asarray(mixer["D"]) == 1.0)
    return module, params


def _ids(rows, length, seed=3):
    return np.random.default_rng(seed).integers(
        1, SIZES["vocab_size"], size=(rows, length), dtype=np.int32)


def _reference(params, ids, **wrong):
    with reference.highest():
        return np.asarray(family.reference_logits(
            params, jnp.asarray(ids), SIZES, CONFIG, **wrong))


def _gap(got, want):
    """The largest difference, in standard deviations of ``want``."""
    want = np.asarray(want)
    return float(np.max(np.abs(np.asarray(got) - want)) / want.std())


# -- (a) the forward pass -----------------------------------------------------

def test_the_layers_are_the_published_pattern(model):
    module, params = model
    kinds = [module.config.layer_kind(i) for i in range(8)]
    assert kinds == ["mixer", "window_attn", "mixer", "window_attn", "mixer",
                     "shared_attn", "gmu", "cross_attn"]
    assert kinds == [family.layer_kind(SIZES, i) for i in range(8)]
    for i, kind in enumerate(kinds):
        assert kind in params[f"layers_{i}"]
    # a cross layer projects queries only; a memory unit keeps no state
    assert set(params["layers_7"]["cross_attn"]) == {
        "q", "out", "subln", "lambda_q1", "lambda_k1", "lambda_q2",
        "lambda_k2"}
    published = type(module.config)()
    assert [published.layer_kind(i) for i in (0, 15, 16, 17, 18, 31)] == [
        "mixer", "window_attn", "mixer", "shared_attn", "gmu", "cross_attn"]
    assert (published.d_inner, published.dt_rank, published.head_dim) == (
        5120, 160, 64)


def test_forward_logits_are_the_references(model):
    module, params = model
    ids = _ids(3, 3 * WINDOW + 5)
    with reference.highest():
        got = module.apply({"params": params}, jnp.asarray(ids))
    want = _reference(params, ids)
    assert want.shape == (3, 3 * WINDOW + 5, SIZES["vocab_size"])
    assert _gap(got, want) < TOL


def test_the_cross_decoder_on_one_position_is_its_row_of_the_forward(model):
    """``positions_needed``: layers 6 and 7 run on one position a row and
    give that position's logits of the whole forward."""
    module, params = model
    ids = jnp.asarray(_ids(2, 70))
    at = jnp.array([12, 69])
    with reference.highest():
        whole = module.apply({"params": params}, ids)
        one = module.apply({"params": params}, ids, positions_needed=at)
    assert one.shape == (2, 1, SIZES["vocab_size"])
    want = np.stack([whole[0, 12], whole[1, 69]])[:, None]
    assert _gap(one, want) < TOL


# -- (b) the mixer and the ring -----------------------------------------------

def _mixer(**kw):
    return layers.MambaMixer(d_model=32, d_inner=64, dt_rank=2, d_state=16,
                             scan_block=8, bc_gain=3.0, hand_on=True,
                             dtype=jnp.float32, **kw)


def test_the_mixers_three_forms_agree():
    """A whole sequence from zero state; the same in two chunks, the
    second from the first's carried states, the first right-padded with
    positions outside ``token_mask``; the same one token at a time
    against a slot's state, an idle row beside it keeping its own."""
    mixer = _mixer()
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 21, 32))
    variables = mixer.init(jax.random.PRNGKey(1), x)
    whole, memory = mixer.apply(variables, x)

    def step(cache, part, mask=None):
        (out, m), new = mixer.apply({**variables, "cache": cache}, part,
                                    decode=True, token_mask=mask,
                                    mutable=["cache"])
        return out, m, new["cache"]
    zeros = {"conv_state": jnp.zeros((1, 3, 64)),
             "ssm_state": jnp.zeros((1, 16, 64))}
    padded = jnp.concatenate([x[:, :13], jnp.ones((1, 3, 32))], axis=1)
    first, m1, cache = step(zeros, padded, jnp.arange(16)[None] < 13)
    _, _, want = step(zeros, x[:, :13])
    for k in want:                       # the padding wrote nothing
        np.testing.assert_allclose(cache[k], want[k], atol=1e-6)
    second, m2, cache = step(cache, x[:, 13:])
    np.testing.assert_allclose(
        jnp.concatenate([first[:, :13], second], axis=1), whole, atol=2e-6)
    np.testing.assert_allclose(
        jnp.concatenate([m1[:, :13], m2], axis=1), memory, atol=2e-6)

    two = jax.tree.map(lambda a: jnp.concatenate([a, a + 1.0]), zeros)
    outs = []
    for t in range(21):
        tokens = jnp.concatenate([x[:, t:t + 1], x[:, t:t + 1]])
        out, _, two = step(two, tokens, jnp.array([[True], [False]]))
        outs.append(out[:1])
    np.testing.assert_allclose(jnp.concatenate(outs, axis=1), whole,
                               atol=2e-6)
    for k in zeros:                      # the idle row kept its state
        np.testing.assert_array_equal(two[k][1], zeros[k][0] + 1.0)


@pytest.mark.parametrize("block", [1, 5, 64])
def test_the_scan_does_not_depend_on_its_block(block):
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 21, 32))
    variables = _mixer().init(jax.random.PRNGKey(1), x)
    want, _ = _mixer().apply(variables, x)
    got, _ = dataclasses.replace(_mixer(), scan_block=block).apply(
        variables, x)
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_the_ring_write_touches_the_live_rows_lane_and_nothing_else():
    ring = jax.random.normal(jax.random.PRNGKey(0), (4, 2, 8, 256))
    col = jax.random.normal(jax.random.PRNGKey(1), (4, 2, 8, 1))
    lane = jnp.array([0, 130, 255, 7])
    live = jnp.array([True, False, True, True])
    new, twice = jax.jit(layers._ring_append)((ring, 2 * ring),
                                              (col, 2 * col), lane, live)
    want = np.array(ring)
    for row in (0, 2, 3):
        want[row, :, :, int(lane[row])] = np.asarray(col)[row, :, :, 0]
    np.testing.assert_array_equal(new, want)
    np.testing.assert_array_equal(twice, 2 * want)


# -- (c), (d) through ServingEngine -------------------------------------------

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


def _serving(slots=3, kernel="auto", chunk=PAGE, **paging):
    return {"num_slots": slots, "max_len": 256,
            "paging": {"page_len": PAGE, "prefill_chunk": chunk,
                       "kernel": kernel, "enable_prefix_cache": False,
                       **paging}}


class Served(DispatchLog):
    """A ``ServingEngine`` (paged pool, pages of 16, no prefix cache,
    greedy) whose dispatches are logged in order: ``rows(handle)`` are
    the float32 logits each of a request's tokens was sampled from."""

    def __init__(self, module, params, seen, **kw):
        super().__init__(ds.init_inference(
            module, params=params, dtype=jnp.float32).serve(_serving(**kw)),
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


@pytest.mark.parametrize("kernel,chunk,prompt_len", [
    ("auto", PAGE, 53), ("auto", 2 * PAGE, 75), ("on", 2 * PAGE, 75)],
    ids=["one-page-chunks", "chunks-of-the-window", "kernels"])
def test_chunked_prefill_then_decode_past_two_windows_gives_the_references(
        model, seen, kernel, chunk, prompt_len):
    """A prompt is prefilled in chunks of one page or of two (the window:
    the third chunk starts past it, its ring lanes all held by the chunk
    before), the last one padded; then 70 tokens are decoded, more than
    two windows,
    so that every ring lane is written three times — by the gathered
    path the CPU takes by default, and by the paged kernel on the full
    layer's pages and the contiguous kernel on the rings (interpreted
    here) that the chip runs."""
    module, params = model
    prompt = _ids(1, prompt_len, seed=5)[0]
    served = Served(module, params, seen, kernel=kernel, chunk=chunk)
    handle, = served.run(prompt, new_tokens=70)
    got = served.rows(handle)
    served.close()
    want = _teacher_forced(params, prompt, handle)
    assert got.shape == want.shape == (70, SIZES["vocab_size"])
    assert _gap(got, want) < TOL


def test_a_chunk_that_crosses_the_windows_end_reads_ring_and_chunk(
        model, seen):
    """Chunks of one page, two pages, one page (the widths scripted; a
    server with ``prefill_chunk`` unset mixes widths the same way): the
    chunk [16, 48) crosses position 32 — its queries past 32 see lanes
    the first chunk wrote only as far back as the window, and its own
    tokens past 32 land on lanes the first chunk's held; the chunk [48,
    60) starts past the window, every lane it reads the second's."""
    module, params = model
    prompt = _ids(1, 60, seed=6)[0]
    served = Served(module, params, seen, chunk=2 * PAGE)
    widths = iter([PAGE, 2 * PAGE, PAGE])
    served.srv._chunk_width = lambda left: next(widths)
    handle, = served.run(prompt, new_tokens=6)
    assert [e[3] for e in served.log if e[0] == "chunk"] == [
        False, False, True]
    got = served.rows(handle)
    served.close()
    assert _gap(got, _teacher_forced(params, prompt, handle)) < TOL


def test_a_requests_logits_do_not_depend_on_its_company(model, seen):
    module, params = model
    a, b, c = _ids(1, 40, 7)[0], _ids(1, 11, 8)[0], _ids(1, 66, 9)[0]
    served = Served(module, params, seen)
    h1, = served.run(a, new_tokens=40)
    alone = served.rows(h1)
    served.close()
    served = Served(module, params, seen)
    h2, _, _ = served.run(a, b, c, new_tokens=40)
    among = served.rows(h2)
    served.close()
    assert list(h1.output_tokens) == list(h2.output_tokens)
    assert _gap(alone, among) < TOL
    assert _gap(among, _teacher_forced(params, a, h2)) < TOL


def test_a_slot_reused_by_a_shorter_request_reads_nothing_of_the_last(
        model, seen):
    """One slot, a request that wraps the ring, then a shorter one: it
    starts from zero states and sees no lane the first one wrote."""
    module, params = model
    first, second = _ids(1, 70, 11)[0], _ids(1, 9, 12)[0]
    served = Served(module, params, seen, slots=1)
    served.run(first, new_tokens=20)
    pool = served.srv._paged.pool
    assert float(jnp.abs(pool["layers_0"]["mixer"]["ssm_state"]).max()) > 0
    ring = pool["layers_1"]["window_attn"]["ring_key"]
    assert ring.shape == (1, 2, 16, WINDOW)
    assert float(jnp.abs(ring).min()) > 0          # every lane written
    handle, = served.run(second, new_tokens=12)
    got = served.rows(handle)
    served.close()
    assert _gap(got, _teacher_forced(params, second, handle)) < TOL


# -- (e) the chunk program's two widths ---------------------------------------

def test_a_chunk_program_runs_the_cross_decoder_on_one_position(model):
    """Layers 0-5 on the chunk, 6 and 7 on one position a row: the MLPs'
    results say what each layer was handed, and the registry counts the
    positions each half computed in chunk programs."""
    module, params = model
    eng = ds.init_inference(module, params=params, dtype=jnp.float32)
    srv = eng.serve(_serving(chunk=2 * PAGE))
    pool = srv._paged.pool
    row = cache_mod.chunk_state_view(
        cache_mod.set_cache_index(cache_mod.gather_pages(
            pool, srv._paged.page_table[:1], scalar_index=True), 0),
        pool, 0, True, 0)
    ids = jnp.asarray(_ids(1, 2 * PAGE))

    def run(p):
        return module.apply(
            {"params": p, "cache": row}, ids, decode=True,
            positions=jnp.arange(2 * PAGE),
            token_mask=jnp.ones((1, 2 * PAGE), bool),
            positions_needed=jnp.array([20]),
            mutable=["cache", "kv_token", "intermediates"],
            capture_intermediates=lambda mdl, _: type(mdl).__name__
            == "GatedMLP")
    logits, out = jax.eval_shape(run, params)
    assert logits.shape == (1, 1, SIZES["vocab_size"])
    widths = {name: layer["mlp"]["__call__"][0].shape[1]
              for name, layer in out["intermediates"].items()}
    assert widths == {**{f"layers_{i}": 2 * PAGE for i in range(6)},
                      "layers_6": 1, "layers_7": 1}

    reg = registry_mod.get_registry()
    names = ("serving/self_decoder_positions",
             "serving/cross_decoder_positions", "serving/ring_tokens_written",
             "serving/shared_kv_tokens_walked", "serving/ring_tokens_read")
    before = {n: reg.counter(n).value for n in names}
    with reference.highest():
        srv.submit(_ids(1, 40, 4)[0], max_new_tokens=3)
        srv.run()
    srv.close()
    grew = {n.split("/")[1]: reg.counter(n).value - before[n]
            for n in names}
    # chunks [0, 32) and [32, 48 of 40): 48 positions below, 2 above; 40
    # prompt tokens and 2 decode steps' onto the rings (the first token
    # comes from the last chunk); the steps read contexts of 40 and 41
    # from the pages, and 32 lanes each of a wrapped ring
    assert grew == {"self_decoder_positions": 48,
                    "cross_decoder_positions": 2, "ring_tokens_written": 42,
                    "shared_kv_tokens_walked": 81, "ring_tokens_read": 64}


def test_the_pool_holds_pages_for_one_layer_rings_and_states(model):
    module, params = model
    srv = ds.init_inference(module, params=params, dtype=jnp.float32).serve(
        _serving(slots=2))
    mgr = srv._paged
    pool = mgr.pool
    assert [k for k, v in pool.items() if "cached_key" in str(v.keys())
            or any("cached_key" in u for u in v.values())] == ["layers_5"]
    assert sorted(pool) == [f"layers_{i}" for i in range(6)]
    assert set(pool["layers_5"]["shared_attn"]) == {
        "cached_key", "cached_value", "cache_index"}
    assert pool["layers_5"]["shared_attn"]["cached_key"].shape == (
        mgr.num_pages, 2, 16, PAGE)
    assert set(pool["layers_3"]["window_attn"]) == {"ring_key", "ring_value"}
    assert set(pool["layers_4"]["mixer"]) == {"conv_state", "ssm_state"}
    assert pool["layers_4"]["mixer"]["ssm_state"].shape == (2, 16, 128)
    assert mgr.snapshots is None and not mgr.page_states
    rings = 2 * 2 * (2 * 2 * 16 * WINDOW * 4)        # layers, K and V
    states = 3 * 2 * (16 * 128 + 3 * 128) * 4
    assert mgr.state_bytes() == cache_mod.state_bytes(pool) \
        == rings + states
    assert mgr.pool_bytes() == 2 * mgr.num_pages * 2 * 16 * PAGE * 4
    assert "ring" in cache_mod.describe_state(pool)
    assert mgr.stats()["state_bytes"] == rings + states      # /statusz
    srv.close()


# -- (f) the refusals ---------------------------------------------------------

def test_what_the_rings_cannot_serve_is_refused_by_name(model):
    module, params = model
    eng = ds.init_inference(module, params=params, dtype=jnp.float32)
    with pytest.raises(NotImplementedError,
                       match="enable_prefix_cache is not built for "
                             "Phi4Flash.*ring"):
        eng.serve(_serving(enable_prefix_cache=True))
    with pytest.raises(NotImplementedError,
                       match="serving.kv_int8 is not built for Phi4Flash"):
        eng.serve({**_serving(), "quantize": {"kv": "int8"}})
    with pytest.raises(NotImplementedError,
                       match="speculation.*recurrent state: Phi4Flash"):
        eng.serve({**_serving(), "speculation": {"enabled": True}})
    srv = eng.serve(_serving())
    with pytest.raises(NotImplementedError, match="export_slot"):
        srv._paged.export_slot(0, 16)
    with pytest.raises(NotImplementedError, match="import_slot"):
        srv._paged.import_slot(0, [], 1, 2)
    srv.close()
    with pytest.raises(NotImplementedError,
                       match="a prefill chunk of 48 tokens over "
                             "Phi4Flash's window of 32"):
        eng.serve(_serving(chunk=3 * PAGE))


def test_ragged_generate_refuses_it_and_equal_rows_are_the_references(model):
    module, params = model
    ids = _ids(2, 20, 13)
    with pytest.raises(NotImplementedError, match="recurrent"):
        generate(module, params, jnp.asarray(ids), max_new_tokens=4,
                 prompt_lengths=jnp.array([20, 11]))
    with reference.highest():
        out = np.asarray(generate(module, params, jnp.asarray(ids),
                                  max_new_tokens=40))
    want = _reference(params, out[:, :-1])
    assert out.shape == (2, 60)          # past the window: the ring wraps
    assert np.array_equal(out[:, 20:], want[:, 19:].argmax(-1))


# -- (g) the controls ---------------------------------------------------------

@pytest.mark.parametrize("wrong", ["memory_after_gate", "lambda_zero",
                                   "window_less_one", "wrong_pairing"])
def test_a_wrong_reading_of_the_architecture_fails_the_tolerance(
        model, seen, wrong):
    """The served logits against the reference computed wrongly — the
    memory taken after the mixer's gate, lambda = 0, a window of 31, the
    last cross layer reading the shared keys at another head pairing —
    each over the tolerance the sound program passes."""
    module, params = model
    prompt = _ids(1, 45, seed=21)[0]
    served = Served(module, params, seen)
    handle, = served.run(prompt, new_tokens=12)
    got = served.rows(handle)
    served.close()
    assert _gap(got, _teacher_forced(params, prompt, handle)) < TOL
    assert _gap(got, _teacher_forced(params, prompt, handle, wrong=wrong)) \
        > CONTROL_FACTOR * TOL


def test_a_zero_state_and_a_bf16_state_read_over_the_programs_rounding(
        model, seen):
    """The next precision down: the SSM state a slot kept in bf16 reads
    over the tolerance; and a decode that starts from a zeroed state
    (what a prefix hit without a stored state would be) far over it."""
    module, params = model
    prompt = _ids(1, 45, seed=22)[0]
    lower = type(module)(dataclasses.replace(
        module.config, ssm_state_dtype=jnp.bfloat16))
    served = Served(lower, params, seen)
    handle, = served.run(prompt, new_tokens=12)
    got = served.rows(handle)
    served.close()
    assert _gap(got, _teacher_forced(params, prompt, handle)) \
        > CONTROL_FACTOR * TOL

    served = Served(module, params, seen, slots=1)
    srv = served.srv
    with reference.highest():
        handle = srv.submit(prompt, max_new_tokens=12)
        while not handle.output_tokens:
            srv.advance()
        pool = srv._paged.pool
        for i in (0, 2, 4):
            unit = pool[f"layers_{i}"]["mixer"]
            unit["ssm_state"] = jnp.zeros_like(unit["ssm_state"])
        srv.run()
    got = served.rows(handle)
    served.close()
    want = _teacher_forced(params, prompt, handle)
    assert _gap(got[:1], want[:1]) < TOL
    assert _gap(got[1:], want[1:]) > 100 * TOL
