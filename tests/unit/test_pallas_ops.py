"""Kernel-vs-reference numerical equivalence (reference analog:
tests/unit/test_cuda_forward.py / test_cuda_backward.py, which sweep the
fused CUDA transformer kernel against a PyTorch baseline with tolerances).

Kernels run in Pallas interpreter mode on CPU.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.pallas import (flash_attention, fused_adamw,
                                      fused_layer_norm, quantize, dequantize)
from deepspeed_tpu.ops.transformer.attention import _reference_attention


def rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(key), shape, dtype)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_forward(causal):
    b, s, h, d = 2, 256, 4, 64
    q, k, v = rand(0, (b, s, h, d)), rand(1, (b, s, h, d)), rand(2, (b, s, h, d))
    out = flash_attention(q, k, v, causal=causal, block_q=128)
    ref = _reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_flash_attention_backward():
    b, s, h, d = 1, 128, 2, 64
    q, k, v = rand(0, (b, s, h, d)), rand(1, (b, s, h, d)), rand(2, (b, s, h, d))

    def f_kernel(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, block_q=64) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(_reference_attention(q, k, v, causal=True) ** 2)

    gk = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=5e-3, atol=5e-3)


def test_flash_attention_bf16():
    b, s, h, d = 1, 128, 2, 64
    q = rand(0, (b, s, h, d), jnp.bfloat16)
    k = rand(1, (b, s, h, d), jnp.bfloat16)
    v = rand(2, (b, s, h, d), jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, block_q=128)
    ref = _reference_attention(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=3e-2, atol=3e-2)


def test_flash_attention_rejects_ragged_seq():
    q = rand(0, (1, 100, 2, 64))
    with pytest.raises(ValueError):
        flash_attention(q, q, q, block_q=64)


def test_fused_adamw_matches_optax():
    import optax
    params = {"w": rand(0, (37, 50)), "b": rand(1, (7,))}
    grads = {"w": rand(2, (37, 50)), "b": rand(3, (7,))}

    fused = fused_adamw(1e-2, weight_decay=0.01)
    ref = optax.adamw(1e-2, weight_decay=0.01)
    fs, rs = fused.init(params), ref.init(params)
    p_f, p_r = params, params
    for step in range(3):
        uf, fs = fused.update(grads, fs, p_f)
        p_f = optax.apply_updates(p_f, uf)
        ur, rs = ref.update(grads, rs, p_r)
        p_r = optax.apply_updates(p_r, ur)
    for k in params:
        np.testing.assert_allclose(np.asarray(p_f[k]), np.asarray(p_r[k]),
                                   rtol=1e-5, atol=1e-6)


def test_fused_layer_norm_fwd_bwd():
    x = rand(0, (4, 33, 256))
    gamma = 1.0 + 0.1 * rand(1, (256,))
    beta = 0.1 * rand(2, (256,))

    out = fused_layer_norm(x, gamma, beta)
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    ref = (x - mean) * jax.lax.rsqrt(var + 1e-5) * gamma + beta
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)

    def lk(x, g, b):
        return jnp.sum(fused_layer_norm(x, g, b) ** 2)

    def lr(x, g, b):
        mean = x.mean(-1, keepdims=True)
        var = ((x - mean) ** 2).mean(-1, keepdims=True)
        return jnp.sum(((x - mean) * jax.lax.rsqrt(var + 1e-5) * g + b) ** 2)

    gk = jax.grad(lk, argnums=(0, 1, 2))(x, gamma, beta)
    gr = jax.grad(lr, argnums=(0, 1, 2))(x, gamma, beta)
    for a, b_ in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-3, atol=1e-3)


def test_quantize_sym_roundtrip():
    x = rand(0, (16, 128))
    q, scale = quantize(x, groups=16)
    assert q.dtype == jnp.int8
    x2 = dequantize(q, scale)
    err = np.abs(np.asarray(x) - np.asarray(x2)).max()
    granularity = float(np.asarray(scale).max())
    assert err <= granularity  # max error is one quantization step


def test_quantize_asym_roundtrip():
    x = jnp.abs(rand(0, (8, 64))) + 3.0  # shifted distribution
    q, scale, zp = quantize(x, groups=8, asymmetric=True)
    assert q.dtype == jnp.uint8
    x2 = dequantize(q, scale, zp)
    err = np.abs(np.asarray(x) - np.asarray(x2)).max()
    assert err <= float(np.asarray(scale).max())


def test_quantize_stochastic_unbiased():
    x = jnp.full((1, 1024), 0.3)
    q, scale = quantize(x, groups=1, stochastic=True, seed=7)
    x2 = dequantize(q, scale)
    # stochastic rounding is unbiased in expectation
    assert abs(float(x2.mean()) - 0.3) < 0.02


class TestDecodeAttention:
    """Parity of the KV-cache decode kernel (reference analog:
    softmax_context, pt_binding.cpp:1197-1244) vs an explicit-mask dense
    reference. Caches are in the kernel's K^T layout [B, H, d, S]."""

    @staticmethod
    def _dense(q, kt, vt, lengths, slopes=None):
        """q [B,1,H,D]; kt,vt [B,H,D,S] — builds the [B,H,1,S] mask the
        engine's old fallback materialized every decode step."""
        d = q.shape[-1]
        s = kt.shape[3]
        logits = jnp.einsum("bqhd,bhdk->bhqk", q, kt).astype(jnp.float32)
        logits = logits / np.sqrt(d)
        col = jnp.arange(s)[None, None, None, :]
        ln = lengths[:, None, None, None]
        if slopes is not None:
            logits = logits + slopes[None, :, None, None] * (col - (ln - 1))
        logits = jnp.where(col < ln, logits, -1e30)
        p = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("bhqk,bhdk->bqhd", p, vt)

    def test_matches_dense_varied_lengths(self):
        from deepspeed_tpu.ops.pallas import decode_attention
        b, h, s, d = 2, 4, 640, 64
        q = rand(0, (b, 1, h, d))
        kt, vt = rand(1, (b, h, d, s)), rand(2, (b, h, d, s))
        lengths = jnp.asarray([1, 640], jnp.int32)  # extremes incl. full
        out = decode_attention(q, kt, vt, lengths, block_k=128)
        ref = self._dense(q, kt, vt, lengths)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_alibi_in_kernel(self):
        from deepspeed_tpu.ops.pallas import decode_attention
        from deepspeed_tpu.models.layers import alibi_slopes
        b, h, s, d = 2, 8, 256, 32
        q = rand(3, (b, 1, h, d))
        kt, vt = rand(4, (b, h, d, s)), rand(5, (b, h, d, s))
        lengths = jnp.asarray([100, 250], jnp.int32)
        sl = alibi_slopes(h)
        out = decode_attention(q, kt, vt, lengths, alibi_slopes=sl, block_k=128)
        ref = self._dense(q, kt, vt, lengths, slopes=sl)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_bf16_and_scalar_length(self):
        from deepspeed_tpu.ops.pallas import decode_attention
        b, h, s, d = 1, 2, 384, 64
        q = rand(6, (b, 1, h, d), jnp.bfloat16)
        kt = rand(7, (b, h, d, s), jnp.bfloat16)
        vt = rand(8, (b, h, d, s), jnp.bfloat16)
        out = decode_attention(q, kt, vt, 77, block_k=128)
        ref = self._dense(q.astype(jnp.float32), kt.astype(jnp.float32),
                          vt.astype(jnp.float32), jnp.full((b,), 77, jnp.int32))
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref), rtol=2e-2, atol=2e-2)

    def test_ragged_maxlen_dense_fallback(self):
        """max_len not a multiple of 128 takes the fused-dense fallback
        with identical semantics (generate() always allocates aligned)."""
        from deepspeed_tpu.ops.pallas import decode_attention
        b, h, s, d = 2, 4, 200, 64
        q = rand(10, (b, 1, h, d))
        kt, vt = rand(11, (b, h, d, s)), rand(12, (b, h, d, s))
        lengths = jnp.asarray([3, 200], jnp.int32)
        out = decode_attention(q, kt, vt, lengths, block_k=128)
        ref = self._dense(q, kt, vt, lengths)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    @staticmethod
    def _numpy_ref(q, kt, vt, lengths):
        """Independent numpy attention over the valid prefix of each row
        (not a jnp re-derivation — the serving satellite's external
        reference). Rows with length <= 0 are defined as zeros."""
        qn = np.asarray(q, np.float64)
        kn = np.asarray(kt, np.float64)
        vn = np.asarray(vt, np.float64)
        b, _, h, d = qn.shape
        out = np.zeros_like(qn)
        for i, ln in enumerate(np.asarray(lengths)):
            if ln <= 0:
                continue
            for j in range(h):
                s = (qn[i, 0, j] @ kn[i, j][:, :ln]) / np.sqrt(d)
                s = np.exp(s - s.max())
                w = s / s.sum()
                out[i, 0, j] = w @ vn[i, j][:, :ln].T
        return out

    @pytest.mark.parametrize("s", [256,    # 128-aligned -> DMA kernel
                                   130])   # ragged -> dense fallback
    def test_per_slot_ragged_lengths_vs_numpy(self, s):
        """Serving slot batches mix lengths {0, 1, 127, 128, 129} (empty
        slot, single token, both sides of the 128 tile edge): each row
        must match a pure-numpy reference over ITS prefix, the length-0
        row must come back exactly zero, and no row may bleed into its
        neighbors."""
        from deepspeed_tpu.ops.pallas import decode_attention
        b, h, d = 5, 2, 32
        q = rand(20, (b, 1, h, d))
        kt, vt = rand(21, (b, h, d, s)), rand(22, (b, h, d, s))
        lengths = jnp.asarray([0, 1, 127, 128, min(129, s)], jnp.int32)
        out = np.asarray(decode_attention(q, kt, vt, lengths, block_k=128))
        assert np.isfinite(out).all()
        np.testing.assert_array_equal(out[0], 0.0)       # empty slot
        ref = self._numpy_ref(q, kt, vt, lengths)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)
        # isolation: perturbing another row's cache leaves this row's
        # output bitwise unchanged
        kt2 = kt.at[0].set(9.0)
        vt2 = vt.at[0].set(-9.0)
        out2 = np.asarray(decode_attention(q, kt2, vt2, lengths,
                                           block_k=128))
        np.testing.assert_array_equal(out[1:], out2[1:])

    def test_layer_cache_path_matches_reference_mask_path(self):
        """SelfAttention's kernel fast path == full causal attention,
        end to end through the flax module (cache len 128-aligned)."""
        import flax.linen as nn
        from deepspeed_tpu.models.layers import SelfAttention

        attn = SelfAttention(n_heads=4, d_model=32, causal=True,
                             dtype=jnp.float32)
        b, max_len = 2, 128
        ids = rand(9, (b, max_len, 32))
        variables = attn.init(jax.random.PRNGKey(0), ids, decode=True)
        params, cache = variables["params"], variables["cache"]

        # prefill 8 tokens, then decode 1 (kernel path)
        prompt = ids[:, :8]
        out_p, vs = attn.apply({"params": params, "cache": cache}, prompt,
                               decode=True, positions=jnp.arange(8),
                               mutable=["cache"])
        tok = ids[:, 8:9]
        out_d, vs = attn.apply({"params": params, "cache": vs["cache"]}, tok,
                               decode=True, positions=jnp.arange(8, 9),
                               mutable=["cache"])
        # reference: full causal attention over the 9 tokens, last position
        out_full = attn.apply({"params": params}, ids[:, :9],
                              positions=jnp.arange(9))
        np.testing.assert_allclose(np.asarray(out_d[:, 0]),
                                   np.asarray(out_full[:, -1]),
                                   rtol=2e-4, atol=2e-4)


class TestFusedLamb:
    """Pallas fused LAMB (VERDICT #8; reference:
    csrc/lamb/fused_lamb_cuda.cpp:108 in-kernel trust-ratio reductions)."""

    @pytest.mark.slow
    def test_matches_optax_lamb(self):
        from deepspeed_tpu.ops.pallas import fused_lamb
        import optax
        rng = np.random.default_rng(0)
        params = {"w": jnp.asarray(rng.standard_normal((130, 33)),
                                   jnp.float32),
                  # >1 grid block with a ragged tail: the in-kernel norm
                  # reductions must not fold block padding into the trust
                  # ratio (1200*129 elems -> 1210 lanes-rows vs 1024/block)
                  "big": jnp.asarray(rng.standard_normal((1200, 129)),
                                     jnp.float32),
                  "b": jnp.asarray(rng.standard_normal(17), jnp.float32)}
        ref = optax.lamb(1e-2, weight_decay=0.01, eps=1e-6)
        fus = fused_lamb(1e-2, weight_decay=0.01, eps=1e-6)
        sr, sf = ref.init(params), fus.init(params)
        pr = pf = params
        for step in range(4):
            g = jax.tree.map(
                lambda p: jnp.asarray(
                    rng.standard_normal(p.shape), jnp.float32), params)
            ur, sr = ref.update(g, sr, pr)
            pr = optax.apply_updates(pr, ur)
            uf, sf = fus.update(g, sf, pf)
            pf = optax.apply_updates(pf, uf)
            for a, b in zip(jax.tree.leaves(pr), jax.tree.leaves(pf)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=2e-5, atol=2e-6)

    def test_registry_resolves_fused_lamb(self):
        from deepspeed_tpu.runtime.optimizers import build_optimizer
        tx = build_optimizer("FusedLamb", {"lr": 1e-3})
        p = {"w": jnp.ones((8, 8))}
        s = tx.init(p)
        u, s = tx.update({"w": jnp.ones((8, 8))}, s, p)
        assert jnp.all(jnp.isfinite(u["w"]))


class TestOneBitLamb:
    def test_warmup_matches_exact_lamb(self):
        from deepspeed_tpu.runtime.comm_compression import onebit_lamb
        import optax
        rng = np.random.default_rng(2)
        p0 = {"w": jnp.asarray(rng.standard_normal((64, 16)), jnp.float32)}
        ob = onebit_lamb(1e-2, freeze_step=100, eps=1e-6)
        ref = optax.lamb(1e-2, eps=1e-6)
        so, sr = ob.init(p0), ref.init(p0)
        po = pr = p0
        for _ in range(3):
            g = {"w": jnp.asarray(rng.standard_normal((64, 16)), jnp.float32)}
            uo, so = ob.update(g, so, po)
            po = optax.apply_updates(po, uo)
            ur, sr = ref.update(g, sr, pr)
            pr = optax.apply_updates(pr, ur)
        np.testing.assert_allclose(np.asarray(po["w"]), np.asarray(pr["w"]),
                                   rtol=1e-5, atol=1e-6)

    def test_post_freeze_compresses_and_freezes(self):
        from deepspeed_tpu.runtime.comm_compression import onebit_lamb
        rng = np.random.default_rng(3)
        p = {"w": jnp.asarray(rng.standard_normal((32, 8)), jnp.float32)}
        ob = onebit_lamb(1e-2, freeze_step=2, eps=1e-6)
        s = ob.init(p)
        for i in range(5):
            g = {"w": jnp.asarray(rng.standard_normal((32, 8)), jnp.float32)}
            u, s = ob.update(g, s, p)
            p = {"w": p["w"] + u["w"]}
            if i == 1:
                nu_frozen = np.asarray(s.nu["w"]).copy()
                ratio_frozen = float(s.frozen_ratio["w"])
        # variance and trust ratio frozen after step 2
        np.testing.assert_array_equal(np.asarray(s.nu["w"]), nu_frozen)
        assert float(s.frozen_ratio["w"]) == ratio_frozen
        # error feedback is live (non-zero residual)
        assert float(jnp.max(jnp.abs(s.error["w"]))) > 0


class TestWOInt8Matmul:
    """Fused-dequant int8 matmul (reference: pt_binding.cpp int8 gemms)."""

    def _mk(self, m, k, n, seed=0):
        key = jax.random.PRNGKey(seed)
        x = jax.random.normal(key, (m, k), jnp.float32)
        w = jax.random.normal(jax.random.fold_in(key, 1), (k, n), jnp.float32)
        from deepspeed_tpu.module_inject.module_quantize import _quantize_array
        ql = _quantize_array(w, axis=1)
        return x, w, ql["q"], ql["scale"]

    @pytest.mark.parametrize("shape", [(8, 1024, 512), (1, 2048, 1024)])
    def test_kernel_matches_dequant_matmul(self, shape):
        from deepspeed_tpu.ops.pallas.wo_int8_matmul import wo_int8_matmul
        m, k, n = shape
        x, w, q, scale = self._mk(m, k, n)
        out = wo_int8_matmul(x, q, scale, block_n=256, block_k=512)
        ref = x @ (np.asarray(q, np.float32) * np.asarray(scale))
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-3, atol=2e-3)

    def test_fallback_on_ragged_shapes(self):
        from deepspeed_tpu.ops.pallas.wo_int8_matmul import wo_int8_matmul
        x, w, q, scale = self._mk(3, 100, 50)   # nothing 128-aligned
        out = wo_int8_matmul(x, q, scale)
        ref = x @ (np.asarray(q, np.float32) * np.asarray(scale))
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-3, atol=2e-3)

    def test_leading_dims_and_out_dtype(self):
        from deepspeed_tpu.ops.pallas.wo_int8_matmul import wo_int8_matmul
        x, w, q, scale = self._mk(4, 256, 256)
        x3 = x.reshape(2, 2, 256).astype(jnp.bfloat16)
        out = wo_int8_matmul(x3, q, scale, block_n=128, block_k=128,
                             out_dtype=jnp.float32)
        assert out.shape == (2, 2, 256) and out.dtype == jnp.float32

    def test_qdense_consumes_quantized_kernel(self):
        from deepspeed_tpu.models.layers import QDense
        layer = QDense(features=256, dtype=jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 256))
        params = layer.init(jax.random.PRNGKey(1), x)["params"]
        dense_out = layer.apply({"params": params}, x)
        from deepspeed_tpu.module_inject.module_quantize import \
            quantize_param_tree
        qparams = quantize_param_tree(params, min_size=64, only_kernels=True)
        assert isinstance(qparams["kernel"], dict)
        qout = layer.apply({"params": qparams}, x)
        # int8 quantization noise only
        np.testing.assert_allclose(np.asarray(qout), np.asarray(dense_out),
                                   rtol=0.05, atol=0.05)


@pytest.mark.slow
def test_flash_streamed_structure_matches_resident(monkeypatch):
    """Long-seq (streamed-grid) kernel structure must agree exactly with
    the resident structure it replaces above the VMEM threshold."""
    import importlib
    fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")
    q, k, v = (rand(i, (1, 256, 2, 64)) for i in range(3))

    def grads(fn):
        return jax.grad(lambda q, k, v: (fn(q, k, v) ** 2).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    run = lambda q, k, v: fa.flash_attention(q, k, v, causal=True,
                                             block_q=128)
    o_res, g_res = run(q, k, v), grads(run)
    # drop BOTH gates so the long-seq structures actually run: the
    # monolithic-backward length gate and the K/V residency check
    monkeypatch.setattr(fa, "MONOLITHIC_BWD_MAX_SEQ", 0)
    jax.clear_caches()
    o_2p, g_2p = run(q, k, v), grads(run)      # resident two-pass bwd
    monkeypatch.setattr(fa, "_kv_fits_vmem", lambda s, d, i=2: False)
    jax.clear_caches()
    o_str, g_str = run(q, k, v), grads(run)    # streamed fwd + bwd
    np.testing.assert_array_equal(np.asarray(o_res), np.asarray(o_2p))
    np.testing.assert_array_equal(np.asarray(o_res), np.asarray(o_str))
    for a, b, c in zip(g_res, g_str, g_2p):
        # two-pass and streamed share tiles and order -> identical; the
        # one-pass backward reads the same LSE but sums dK/dV over q
        # blocks in another order: it agrees to fp tolerance
        np.testing.assert_array_equal(np.asarray(c), np.asarray(b))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)
    jax.clear_caches()
