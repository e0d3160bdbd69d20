"""Fused bias/mask/dropout in the Pallas flash attention kernel.

VERDICT r4 #2: the flash kernel must take dropout/bias/mask operands in
fwd AND bwd (reference analog: csrc/transformer/ds_transformer_cuda.cpp
fused attention + dropout_kernels.cu), the dispatch must stop falling
back to the dense O(s^2) core for them, and Ulysses with dropout must
materialize nothing of shape [sq, sk].

Parity strategy: dropout is a counter-based hash of (seed, batch, head,
row, col) — `attention_dropout_keep` computes the identical bits at full
shape outside Pallas, so the dense reference with that precomputed mask
is an exact oracle for the kernel's in-tile sampling.
"""

import importlib

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.transformer.attention import (_reference_attention,
                                                     attention)

fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")

B, S, H, D = 2, 256, 4, 64
RATE = 0.3
KEY = jax.random.PRNGKey(7)


def _qkv(seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.standard_normal((B, S, H, D)), dtype)
    return mk(), mk(), mk()


def _keep():
    return fa.attention_dropout_keep(KEY, RATE, (B, H, S, S))


def _dense(q, k, v, bias=None, mask=None, causal=True, dropout=False):
    return _reference_attention(
        q, k, v, bias=bias, mask=mask, causal=causal,
        dropout_rate=RATE if dropout else 0.0,
        dropout_mask=_keep() if dropout else None,
        deterministic=not dropout)


def _flash(q, k, v, bias=None, mask=None, causal=True, dropout=False,
           **kw):
    from deepspeed_tpu.ops.transformer.attention import _combined_bias
    return fa.flash_attention(
        q, k, v, bias=_combined_bias(bias, mask), causal=causal,
        dropout_rate=RATE if dropout else 0.0,
        dropout_rng=KEY if dropout else None, **kw)


class TestKeepMask:
    def test_rate_and_determinism(self):
        keep = np.asarray(_keep())
        assert abs(keep.mean() - (1 - RATE)) < 0.01
        np.testing.assert_array_equal(keep, np.asarray(_keep()))

    def test_no_row_col_structure(self):
        """Avalanche sanity: per-row and per-column keep rates stay near
        the global rate (a weak hash shows stripes)."""
        keep = np.asarray(_keep()).reshape(-1, S)
        assert np.abs(keep.mean(axis=0) - (1 - RATE)).max() < 0.1
        assert np.abs(keep.mean(axis=1) - (1 - RATE)).max() < 0.1

    def test_offset_windows_tile_the_global_sample(self):
        """The property Ulysses relies on: a (head, batch)-offset local
        sample equals the corresponding slice of the global sample."""
        full = _keep()
        local = fa.attention_dropout_keep(
            KEY, RATE, (1, 2, S, S), total_heads=H, head_offset=2,
            batch_offset=1)
        np.testing.assert_array_equal(np.asarray(full[1:2, 2:4]),
                                      np.asarray(local))


@pytest.mark.parametrize("case", ["bias_row", "bias_full", "mask",
                                  "dropout", "all"])
def test_fwd_and_grads_match_dense(case):
    q, k, v = _qkv(seed=1)
    rng = np.random.default_rng(9)
    kw = {}
    if case in ("bias_row", "all"):
        kw["bias"] = jnp.asarray(rng.standard_normal((1, H, 1, S)),
                                 jnp.float32)
    if case == "bias_full":
        kw["bias"] = jnp.asarray(rng.standard_normal((B, H, S, S)),
                                 jnp.float32)
    if case in ("mask", "all"):
        kw["mask"] = jnp.ones((B, 1, 1, S), bool).at[:, :, :, -17:].set(False)
    dropout = case in ("dropout", "all")

    want = _dense(q, k, v, dropout=dropout, **kw)
    got = _flash(q, k, v, dropout=dropout, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)

    gw = jax.grad(lambda *a: (_dense(*a, dropout=dropout, **kw) ** 2).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    gg = jax.grad(lambda *a: (_flash(*a, dropout=dropout, **kw) ** 2).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gw, gg):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=2e-4,
                                   atol=2e-4, err_msg=f"d{name} ({case})")


@pytest.mark.parametrize("shape", [(1, H, 1, S), (B, H, S, S)])
def test_dbias_matches_dense(shape):
    """Trainable-bias cotangent (dense recompute in the bwd rule),
    including reduction to broadcast shapes."""
    q, k, v = _qkv(seed=2)
    bias = jnp.asarray(np.random.default_rng(3).standard_normal(shape),
                       jnp.float32)
    gw = jax.grad(lambda b_: (_dense(q, k, v, bias=b_) ** 2).sum())(bias)
    gg = jax.grad(lambda b_: (_flash(q, k, v, bias=b_) ** 2).sum())(bias)
    assert gg.shape == bias.shape
    np.testing.assert_allclose(np.asarray(gg), np.asarray(gw), rtol=2e-4,
                               atol=2e-4)


def test_bf16_dropout_mask(monkeypatch):
    """The training hot path: bf16 q/k/v with fused dropout + padding
    mask, fwd and bwd, against the fp32 dense oracle at bf16 tolerance."""
    q, k, v = _qkv(seed=11, dtype=jnp.bfloat16)
    mask = jnp.ones((B, 1, 1, S), bool).at[:, :, :, -13:].set(False)
    qf, kf, vf = (t.astype(jnp.float32) for t in (q, k, v))
    want = _dense(qf, kf, vf, mask=mask, dropout=True)
    got = _flash(q, k, v, mask=mask, dropout=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=0.05, atol=0.05)
    gw = jax.grad(lambda *a: (_dense(*a, mask=mask, dropout=True)
                              ** 2).sum(), argnums=(0, 1, 2))(qf, kf, vf)
    gg = jax.grad(lambda *a: ((_flash(*a, mask=mask, dropout=True)
                               .astype(jnp.float32)) ** 2).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gw, gg):
        np.testing.assert_allclose(np.asarray(b, np.float32),
                                   np.asarray(a), rtol=0.2, atol=0.2,
                                   err_msg=f"d{name}")


def test_streamed_structure_with_operands(monkeypatch):
    """Force the long-sequence streamed kernels and the two-pass backward
    with all operands live."""
    monkeypatch.setattr(fa, "MONOLITHIC_BWD_MAX_SEQ", 0)
    monkeypatch.setattr(fa, "_kv_fits_vmem", lambda *a, **kw: False)
    q, k, v = _qkv(seed=4)
    mask = jnp.ones((B, 1, 1, S), bool).at[:, :, :, -9:].set(False)
    want = _dense(q, k, v, mask=mask, dropout=True)
    got = _flash(q, k, v, mask=mask, dropout=True, block_q=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    gw = jax.grad(lambda *a: (_dense(*a, mask=mask, dropout=True) ** 2
                              ).sum(), argnums=(0, 1, 2))(q, k, v)
    gg = jax.grad(lambda *a: (_flash(*a, mask=mask, dropout=True,
                                     block_q=128) ** 2).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gw, gg):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=2e-4,
                                   atol=2e-4, err_msg=f"d{name}")


class TestDispatch:
    def test_pallas_backend_accepts_operands_without_fallback(self):
        """The r4 behavior warned and ran the dense core; operands now
        ride the kernel."""
        import warnings as w
        q, k, v = _qkv(seed=5)
        mask = jnp.ones((B, 1, 1, S), bool).at[:, :, :, -5:].set(False)
        with w.catch_warnings():
            w.simplefilter("error")
            got = attention(q, k, v, mask=mask, causal=True,
                            dropout_rate=RATE, dropout_rng=KEY,
                            deterministic=False, backend="pallas",
                            seq_parallel="none")
        want = attention(q, k, v, mask=mask, causal=True,
                         dropout_rate=RATE, dropout_rng=KEY,
                         deterministic=False, backend="reference",
                         seq_parallel="none")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_explicit_pallas_on_unsupported_operands_raises(self):
        """An explicit backend="pallas" the kernel's block specs can't
        honour raises — it neither miscomputes nor quietly runs the
        dense path."""
        q, k, v = _qkv(seed=6)
        # broadcast-sk bias: dense broadcasts it, but the kernel's block
        # specs require the sk dim at full extent
        bad_bias = jnp.asarray(
            np.random.default_rng(0).standard_normal((1, H, S, 1)),
            jnp.float32)
        with pytest.raises(ValueError, match="cannot be honoured"):
            attention(q, k, v, bias=bad_bias, causal=True,
                      backend="pallas", seq_parallel="none")
        # live dropout without an rng is the other refused operand set
        with pytest.raises(ValueError, match="dropout rng missing"):
            attention(q, k, v, causal=True, dropout_rate=RATE,
                      deterministic=False, backend="pallas",
                      seq_parallel="none")

    def test_auto_choice_lands_in_the_dispatch_record(self):
        """auto keeps choosing the reference path off-TPU, and says so
        where the chip smoke reads it."""
        from deepspeed_tpu.ops.pallas import tuning
        q, k, v = _qkv(seed=8)
        tuning.clear_last_dispatch()
        attention(q, k, v, causal=True, seq_parallel="none")
        rec = tuning.last_dispatch("attention")["backend"]
        assert rec["backend"] == "reference" and rec["source"] == "auto"
        assert rec["reason"] == "platform is not tpu"
        assert rec["interpret"] is True
        attention(q, k, v, causal=True, backend="pallas",
                  seq_parallel="none")
        rec = tuning.last_dispatch("attention")["backend"]
        assert rec["backend"] == "pallas" and rec["source"] == "explicit"
        assert rec["reason"] is None

    def test_reference_and_pallas_dropout_bits_identical(self):
        """Cross-backend parity: the SAME rng gives the SAME dropout
        pattern on both backends (the hash is the single source of
        randomness)."""
        q, k, v = _qkv(seed=7)
        a = attention(q, k, v, causal=True, dropout_rate=RATE,
                      dropout_rng=KEY, deterministic=False,
                      backend="pallas", seq_parallel="none")
        b = attention(q, k, v, causal=True, dropout_rate=RATE,
                      dropout_rng=KEY, deterministic=False,
                      backend="reference", seq_parallel="none")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)


def test_bias_grad_false_emits_zero_cotangent():
    """bias_grad=False (statically non-trainable bias, e.g. a folded
    mask): the bias cotangent is exact zeros at the bias's shape and the
    q/k/v grads are unchanged — the eager-mode escape from the dense
    dBias recompute."""
    q, k, v = _qkv(seed=16)
    bias = jnp.asarray(np.random.default_rng(4).standard_normal(
        (1, H, 1, S)), jnp.float32)
    gb = jax.grad(lambda b_: (fa.flash_attention(
        q, k, v, bias=b_, causal=True, bias_grad=False) ** 2).sum())(bias)
    assert gb.shape == bias.shape
    np.testing.assert_array_equal(np.asarray(gb), 0.0)
    gq1 = jax.grad(lambda q: (fa.flash_attention(
        q, k, v, bias=bias, causal=True, bias_grad=False) ** 2).sum())(q)
    gq2 = jax.grad(lambda q: (fa.flash_attention(
        q, k, v, bias=bias, causal=True) ** 2).sum())(q)
    np.testing.assert_array_equal(np.asarray(gq1), np.asarray(gq2))


def test_fully_masked_rows_emit_zeros_and_zero_grads():
    """Rows whose every key is masked out must produce exactly 0 output
    (safe-denominator path) and exactly 0 gradients — not NaN from
    exp(-inf - -inf) chains. The reference dense path softmaxes a
    uniform row instead; all-masked rows are a kernel-only contract."""
    q, k, v = _qkv(seed=15)
    mask = jnp.ones((B, 1, S, S), bool).at[:, :, :8, :].set(False)
    out = _flash(q, k, v, mask=mask, causal=True)
    np.testing.assert_array_equal(np.asarray(out[:, :8]), 0.0)
    assert np.isfinite(np.asarray(out)).all()
    g = jax.grad(lambda q, k, v: (_flash(q, k, v, mask=mask, causal=True)
                                  ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    for name, t in zip("qkv", g):
        assert np.isfinite(np.asarray(t)).all(), f"d{name} has NaN/inf"
    np.testing.assert_array_equal(np.asarray(g[0][:, :8]), 0.0)


def test_dropout_stable_under_remat():
    """jax.checkpoint replays the forward during backward; the
    counter-based seeds are operands, so the replayed keep mask is
    bit-identical and remat grads equal non-remat grads. (A stateful
    PRNG would silently decorrelate fwd and replay here.)"""
    q, k, v = _qkv(seed=13)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) ** 2).sum()

    plain = lambda q, k, v: _flash(q, k, v, dropout=True)
    remat = jax.checkpoint(plain)
    base = jax.grad(loss(plain), argnums=(0, 1, 2))(q, k, v)
    ckpt = jax.grad(loss(remat), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", base, ckpt):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"d{name} differs under remat")


@pytest.mark.slow
def test_cross_attention_shapes_with_operands():
    """sq != sk (the causal_shift path): operands + dropout must use the
    right absolute coordinates on both the short-q and long-k sides."""
    rng = np.random.default_rng(14)
    sq, sk = 128, 256
    q = jnp.asarray(rng.standard_normal((B, sq, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, sk, H, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, sk, H, D)), jnp.float32)
    mask = jnp.ones((B, 1, 1, sk), bool).at[:, :, :, -11:].set(False)
    keep = fa.attention_dropout_keep(KEY, RATE, (B, H, sq, sk))
    want = _reference_attention(q, k, v, mask=mask, causal=True,
                                dropout_rate=RATE, dropout_mask=keep,
                                deterministic=False)
    got = _flash(q, k, v, mask=mask, dropout=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    gw = jax.grad(lambda q, k, v: (_reference_attention(
        q, k, v, mask=mask, causal=True, dropout_rate=RATE,
        dropout_mask=keep, deterministic=False) ** 2).sum(),
        argnums=(0, 1, 2))(q, k, v)
    gg = jax.grad(lambda q, k, v: (_flash(q, k, v, mask=mask,
                                          dropout=True) ** 2).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gw, gg):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=2e-4, atol=2e-4,
                                   err_msg=f"d{name} (cross-attn)")


class TestUlyssesFlashDropout:
    """Ulysses + dropout now runs the flash kernel per shard — no global
    [sq, sk] keep mask, no dense core."""

    def test_parity_and_no_global_materialization(self, sp_mesh=None):
        from deepspeed_tpu.comm.mesh import (build_mesh, MeshSpec,
                                             set_global_mesh)
        from deepspeed_tpu.sequence_parallel import ulysses_attention
        mesh = build_mesh(MeshSpec(seq=4), devices=jax.devices()[:4])
        try:
            q, k, v = _qkv(seed=8)
            fn = jax.jit(lambda q, k, v: ulysses_attention(
                q, k, v, causal=True, dropout_rate=RATE, dropout_rng=KEY,
                deterministic=False, mesh=mesh,
                attn_fn=lambda *a, **kw: attention(
                    *a, backend="pallas", seq_parallel="none", **kw)))
            got = fn(q, k, v)
            want = _flash(q, k, v, dropout=True)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-5, atol=2e-5)
            # nothing of global [B, H, S, S] logits/keep shape may appear
            # in the compiled module (r4 materialized exactly that)
            hlo = fn.lower(q, k, v).compile().as_text()
            assert f"{B},{H},{S},{S}" not in hlo
        finally:
            set_global_mesh(None)


# ---------------------------------------------------------------------------
# the one-pass backward (PR 45): [Bq, Bk] tiles up to the diagonal, p from
# the forward's LSE, the causal mask on the diagonal's tiles only
# ---------------------------------------------------------------------------

from deepspeed_tpu.ops.pallas import tuning

OP_S = 512
# (block_q, block_k): 1, 2 and 4 tiles a side of 512, and the two uneven
# pairs (several diagonal tiles a q block; a diagonal tile wider than it)
OP_BLOCKS = [(512, 512), (256, 256), (128, 128), (256, 128), (128, 256)]
OP_VARIANTS = ["causal", "full", "shift", "bias_masked_rows", "dropout"]


def _one_pass_case(variant, d, dtype=jnp.float32, seed=21):
    """(q, k, v, flash kwargs, dense kwargs, rows the dense oracle
    covers) of one variant. ``shift``: sk > sq, so causal_shift > 0.
    ``bias_masked_rows``: a [b, 1, 1, sk] bias that pads batch 0's tail
    and masks EVERY key of batch 1 — rows the kernel owes exact zeros
    and the dense softmax (uniform there) cannot check."""
    rng = np.random.default_rng(seed)
    b, h = 2, 2
    sq = OP_S // 2 if variant == "shift" else OP_S
    mk = lambda s: jnp.asarray(rng.standard_normal((b, s, h, d)), dtype)
    q, k, v = mk(sq), mk(OP_S), mk(OP_S)
    causal = variant != "full"
    fkw, dkw, checked = dict(causal=causal), dict(causal=causal), slice(None)
    if variant == "bias_masked_rows":
        mask = jnp.ones((b, 1, 1, OP_S), bool).at[0, :, :, -37:].set(False)
        mask = mask.at[1].set(False)
        fkw["bias"] = jnp.where(mask, 0.0, fa.NEG_INF).astype(jnp.float32)
        dkw["mask"] = mask
        checked = slice(0, 1)
    if variant == "dropout":
        fkw.update(dropout_rate=RATE, dropout_rng=KEY)
        dkw.update(dropout_rate=RATE, deterministic=False,
                   dropout_mask=fa.attention_dropout_keep(
                       KEY, RATE, (b, h, sq, OP_S)))
    return q, k, v, fkw, dkw, checked


def _grads(fn, q, k, v):
    return jax.grad(lambda *a: (fn(*a).astype(jnp.float32) ** 2).sum(),
                    argnums=(0, 1, 2))(q, k, v)


def _bwd_table(q, k, causal, block_q, block_k, structure="bwd_monolithic"):
    key = tuning.make_key("flash_attention", structure, sq=q.shape[1],
                          sk=k.shape[1], d=q.shape[-1], dtype=q.dtype,
                          causal=causal)
    return tuning.tuning_table({key: {"block_q": block_q,
                                      "block_k": block_k}})


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("variant", OP_VARIANTS)
@pytest.mark.parametrize("blocks", OP_BLOCKS,
                         ids=lambda b: f"{b[0]}x{b[1]}")
def test_one_pass_backward_matches_dense(blocks, variant, d):
    q, k, v, fkw, dkw, checked = _one_pass_case(variant, d)
    tuning.clear_last_dispatch()
    with _bwd_table(q, k, fkw["causal"], *blocks):
        got = _grads(lambda *a: fa.flash_attention(*a, **fkw), q, k, v)
    rec = tuning.last_dispatch()["bwd_monolithic"]
    bq = min(blocks[0], q.shape[1])
    assert (rec["block_q"], rec["block_k"]) == (bq, blocks[1])
    want = _grads(lambda *a: _reference_attention(*a, **dkw), q, k, v)
    for name, a, g in zip("qkv", want, got):
        np.testing.assert_allclose(
            np.asarray(g[checked]), np.asarray(a[checked]), rtol=2e-4,
            atol=2e-4, err_msg=f"d{name} ({variant}, {blocks}, d{d})")
        if variant == "bias_masked_rows":
            # batch 1 sees no key at all: exact zeros, never NaN
            np.testing.assert_array_equal(np.asarray(g[1]), 0.0)


@pytest.mark.parametrize("variant", OP_VARIANTS)
def test_one_pass_backward_matches_two_pass(variant, monkeypatch):
    """Both backwards form p = exp(s - lse) from the forward's LSE on
    the same tiles, so they agree to float32 rounding (the sums over k
    tiles are taken in another order), far inside the dense oracle's
    tolerance."""
    q, k, v, fkw, _, _ = _one_pass_case(variant, 64, seed=22)
    run = lambda: _grads(lambda *a: fa.flash_attention(*a, **fkw), q, k, v)
    tuning.clear_last_dispatch()
    with _bwd_table(q, k, fkw["causal"], 128, 256):
        one = run()
    assert "bwd_resident" not in tuning.last_dispatch()
    monkeypatch.setattr(fa, "MONOLITHIC_BWD_MAX_SEQ", 128)
    tuning.clear_last_dispatch()
    with _bwd_table(q, k, fkw["causal"], 128, 256, "bwd_resident"):
        two = run()
    assert "bwd_monolithic" not in tuning.last_dispatch()
    for name, a, g in zip("qkv", two, one):
        np.testing.assert_allclose(np.asarray(g), np.asarray(a), rtol=2e-6,
                                   atol=2e-6, err_msg=f"d{name} ({variant})")


def test_one_pass_backward_bf16():
    """The training cells' call: bf16 operands, float32 accumulation,
    against the float32 dense oracle at bf16 tolerance."""
    q, k, v, fkw, dkw, _ = _one_pass_case("causal", 64, jnp.bfloat16)
    with _bwd_table(q, k, True, 256, 128):
        got = _grads(lambda *a: fa.flash_attention(*a, **fkw), q, k, v)
    want = _grads(lambda *a: _reference_attention(*a, **dkw),
                  *(t.astype(jnp.float32) for t in (q, k, v)))
    for name, a, g in zip("qkv", want, got):
        assert g.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(g, np.float32), np.asarray(a),
                                   rtol=0.1, atol=0.1, err_msg=f"d{name}")


@pytest.mark.parametrize("shift", [0, 256], ids=["square", "sk_gt_sq"])
@pytest.mark.parametrize("blocks", [(128, 128), (256, 128), (128, 256)],
                         ids=lambda b: f"{b[0]}x{b[1]}")
def test_forward_mask_on_diagonal_only_is_bit_identical(blocks, shift):
    """Skipping the mask on a tile it would leave unchanged changes no
    bit: the resident forward's ``o`` and ``lse`` equal ``_online_step``
    run with ``_causal_mask`` built on EVERY visited tile (the parent's
    forward), to the last bit."""
    bq, bk = blocks
    rng = np.random.default_rng(23)
    sq, sk, d = 512 - shift, 512, 64
    q = jnp.asarray(rng.standard_normal((1, 2, sq, d)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((1, 2, sk, d)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((1, 2, sk, d)), jnp.bfloat16)
    scale = d ** -0.5
    key = tuning.make_key("flash_attention", "fwd_resident", sq=sq, sk=sk,
                          d=d, dtype=q.dtype, causal=True)
    with tuning.tuning_table({key: {"block_q": bq, "block_k": bk}}):
        o, lse = fa._flash_fwd(q, k, v, None, None, scale, True, 0.0, 2,
                               None)
    rec = tuning.last_dispatch()["fwd_resident"]
    assert (rec["block_q"], rec["block_k"]) == blocks
    trips = [min((i * bq + shift + bq - 1) // bk + 1, sk // bk)
             for i in range(sq // bq)]
    assert rec["tiles_visited"] == sum(trips)
    assert rec["tiles_total"] == len(trips) * (sk // bk)

    @jax.jit
    def masked_everywhere(q, k, v):
        rows_o, rows_lse = [], []
        for i in range(sq // bq):
            q_off = i * bq + sk - sq
            carry = (jnp.zeros((bq, d), jnp.float32),
                     jnp.full((bq, 1), fa.NEG_INF, jnp.float32),
                     jnp.zeros((bq, 1), jnp.float32))
            for j in range(trips[i]):
                carry = fa._online_step(
                    q[i * bq:(i + 1) * bq], k[j * bk:(j + 1) * bk],
                    v[j * bk:(j + 1) * bk], scale, True, q_off, j * bk,
                    *carry)
            acc, m, l = carry
            rows_o.append((acc / l).astype(q.dtype))
            rows_lse.append(m + jnp.log(l))
        return jnp.concatenate(rows_o), jnp.concatenate(rows_lse)

    for b_, h_ in [(0, 0), (0, 1)]:
        want_o, want_lse = masked_everywhere(q[b_, h_], k[b_, h_], v[b_, h_])
        np.testing.assert_array_equal(
            np.asarray(o[b_, h_], np.float32), np.asarray(want_o, np.float32))
        np.testing.assert_array_equal(np.asarray(lse[b_, h_]),
                                      np.asarray(want_lse))


@pytest.mark.parametrize("variant", OP_VARIANTS)
def test_fori_loops_equal_the_unrolled_program_bit_for_bit(variant,
                                                           monkeypatch):
    """A (batch, head) of few tiles is straight-line code that masks the
    diagonal's tiles only; one of many walks fori_loops and masks every
    tile it visits. The same tiles in the same order, and a mask that is
    the identity changes no bit: forward and gradients are equal to the
    last bit."""
    q, k, v, fkw, _, _ = _one_pass_case(variant, 64, seed=24)
    keys = {s: tuning.make_key(
        "flash_attention", s, sq=q.shape[1], sk=k.shape[1], d=64,
        dtype=q.dtype, causal=fkw["causal"])
        for s in ("fwd_resident", "bwd_monolithic")}
    table = {key: {"block_q": 128, "block_k": 128} for key in keys.values()}

    def run():
        with tuning.tuning_table(table):
            return (fa.flash_attention(q, k, v, **fkw),
                    *_grads(lambda *a: fa.flash_attention(*a, **fkw),
                            q, k, v))

    assert fa._unrolled(q.shape[1], OP_S, 128, 128, fkw["causal"])
    unrolled = run()
    monkeypatch.setattr(fa, "UNROLLED_SCORES_MAX", 0)
    for name, a, b_ in zip(("o", "dq", "dk", "dv"), unrolled, run()):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_),
                                      err_msg=f"{name} ({variant})")
