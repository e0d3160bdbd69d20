"""Paged decode-attention kernel (ops/pallas/paged_attention.py).

Parity contract: the kernel (page-table-direct, DMA'd pages, online
softmax, current-token fold-in) must match the dense-gather reference
(`impl="dense"`) across ragged lengths straddling every page boundary,
must never read a masked/null-page column (NaN-poison test), and —
wired into the serving engine behind ``serving.paging.kernel`` — must
produce the same greedy tokens as the PR-6 gather path while the
``decode_gather_transient`` figure reads EXACTLY 0.
"""

import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.gpt import GPT, GPTConfig
from deepspeed_tpu.inference.generation import generate
from deepspeed_tpu.ops.pallas import tuning
from deepspeed_tpu.ops.pallas.paged_attention import (KERNEL,
                                                      paged_attention)
from deepspeed_tpu.serving import ServingConfig
from deepspeed_tpu.serving.engine import ServingEngine
from deepspeed_tpu.serving.paging import PagingConfig
from deepspeed_tpu.serving.paging.manager import _paged_decode_jit

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

PAGE = 16


def _pool(seed=0, num_pages=9, heads=4, d=16, page_len=PAGE):
    r = np.random.RandomState(seed)
    kp = r.randn(num_pages, heads, d, page_len).astype(np.float32)
    vp = r.randn(num_pages, heads, d, page_len).astype(np.float32)
    return jnp.asarray(kp), jnp.asarray(vp)


def _operands(seed=1, b=3, heads=4, d=16):
    r = np.random.RandomState(seed)
    q = jnp.asarray(r.randn(b, 1, heads, d).astype(np.float32))
    kn = jnp.asarray(r.randn(b, heads, d, 1).astype(np.float32))
    vn = jnp.asarray(r.randn(b, heads, d, 1).astype(np.float32))
    return q, kn, vn


def _quantize_pool(kp, vp):
    def one(x):
        amax = jnp.max(jnp.abs(x), axis=2, keepdims=True)
        sc = jnp.maximum(amax / 127.0, 1e-12).astype(jnp.float32)
        q = jnp.clip(jnp.round(x / sc), -127, 127).astype(jnp.int8)
        return q, sc
    kq, ks = one(kp)
    vq, vs = one(vp)
    return kq, vq, ks, vs


class TestKernelVsDenseParity:
    # the satellite's ragged-length matrix: 0 (empty slot: attends only
    # the current token), page boundaries +/- 1, and a full table
    @pytest.mark.parametrize("length", [0, 1, PAGE - 1, PAGE, PAGE + 1,
                                        5 * PAGE])
    @pytest.mark.slow
    def test_ragged_lengths(self, length):
        kp, vp = _pool()
        q, kn, vn = _operands()
        ptab = jnp.asarray(
            np.arange(1, 6, dtype=np.int32)[None].repeat(3, 0))  # 5 pages
        lens = jnp.full((3,), length, jnp.int32)
        a = paged_attention(q, kp, vp, ptab, lens, kn, vn, impl="kernel")
        b = paged_attention(q, kp, vp, ptab, lens, kn, vn, impl="dense")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)
        assert np.isfinite(np.asarray(a)).all()

    def test_per_row_ragged_batch(self):
        kp, vp = _pool(seed=2)
        q, kn, vn = _operands(seed=3)
        ptab = np.zeros((3, 5), np.int32)
        ptab[0, :3] = [1, 2, 3]
        ptab[1, :2] = [4, 5]
        ptab = jnp.asarray(ptab)
        lens = jnp.asarray([2 * PAGE + 7, 4, 0], jnp.int32)
        a = paged_attention(q, kp, vp, ptab, lens, kn, vn, impl="kernel")
        b = paged_attention(q, kp, vp, ptab, lens, kn, vn, impl="dense")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)

    def test_null_page_poison_is_masked(self):
        """Page 0 (the null page every unowned table entry points at)
        holds NaN poison; outputs must stay finite and length-correct —
        the kernel may READ the null page (clamped ragged blocks do)
        but a masked column must never contribute."""
        kp, vp = _pool(seed=4)
        kp = kp.at[0].set(jnp.nan)
        vp = vp.at[0].set(jnp.nan)
        q, kn, vn = _operands(seed=5)
        ptab = np.zeros((3, 4), np.int32)         # mostly null pages
        ptab[0, :2] = [1, 2]
        ptab[1, :1] = [3]
        ptab = jnp.asarray(ptab)
        lens = jnp.asarray([PAGE + 3, PAGE, 0], jnp.int32)
        for impl in ("kernel", "dense"):
            out = np.asarray(paged_attention(q, kp, vp, ptab, lens, kn, vn,
                                             impl=impl))
            assert np.isfinite(out).all(), f"{impl} leaked null-page NaN"
        a = paged_attention(q, kp, vp, ptab, lens, kn, vn, impl="kernel")
        b = paged_attention(q, kp, vp, ptab, lens, kn, vn, impl="dense")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)

    def test_alibi_slopes(self):
        kp, vp = _pool(seed=6)
        q, kn, vn = _operands(seed=7)
        ptab = jnp.asarray(np.arange(1, 6, dtype=np.int32)[None]
                           .repeat(3, 0))
        lens = jnp.asarray([3 * PAGE + 2, 1, 2 * PAGE], jnp.int32)
        slopes = np.linspace(0.1, 0.5, 4).astype(np.float32)
        a = paged_attention(q, kp, vp, ptab, lens, kn, vn,
                            alibi_slopes=slopes, impl="kernel")
        b = paged_attention(q, kp, vp, ptab, lens, kn, vn,
                            alibi_slopes=slopes, impl="dense")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)

    @pytest.mark.slow
    def test_int8_pages_kernel_vs_dense_and_error_bound(self):
        """int8 pages: kernel dequant-in-page-loop == dense dequant
        exactly, and both stay within the quantization error bound of
        the fp pool (the KV bounded-error rung's kernel-level anchor)."""
        kp, vp = _pool(seed=8, heads=2, d=32)
        kq, vq, ks, vs = _quantize_pool(kp, vp)
        q, kn, vn = _operands(seed=9, heads=2, d=32)
        ptab = jnp.asarray(np.arange(1, 5, dtype=np.int32)[None]
                           .repeat(3, 0))
        lens = jnp.asarray([4 * PAGE - 1, PAGE + 1, 0], jnp.int32)
        a = paged_attention(q, kq, vq, ptab, lens, kn, vn,
                            k_scale=ks, v_scale=vs, impl="kernel")
        b = paged_attention(q, kq, vq, ptab, lens, kn, vn,
                            k_scale=ks, v_scale=vs, impl="dense")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
        fp = paged_attention(q, kp, vp, ptab, lens, kn, vn, impl="dense")
        assert np.abs(np.asarray(a) - np.asarray(fp)).max() < 0.1

    def test_rank3_q_roundtrip(self):
        kp, vp = _pool(seed=10)
        q, kn, vn = _operands(seed=11)
        ptab = jnp.asarray([[1, 2, 0], [3, 4, 0], [5, 6, 0]], jnp.int32)
        lens = jnp.asarray([PAGE, 3, 0], jnp.int32)
        out4 = paged_attention(q, kp, vp, ptab, lens, kn, vn)
        out3 = paged_attention(q[:, 0], kp, vp, ptab, lens, kn, vn)
        assert out3.shape == (3, 4, 16)
        np.testing.assert_array_equal(np.asarray(out4[:, 0]),
                                      np.asarray(out3))


class TestStackedPool:
    """The layer-stacked pool ``[L, pages, H, d, page_len]`` of a scanned
    model reaches the kernel whole and the kernel picks the layer: the
    result is the call on that layer's own 4-D slice, bit for bit."""
    L = 3

    def _case(self, dtype, heads=8, seed=5):
        r = np.random.RandomState(seed)
        kp, vp = (jnp.asarray(r.randn(self.L, 9, heads, 16, PAGE)
                              .astype(np.float32)) for _ in range(2))
        q, kn, vn = _operands(seed=seed + 1, b=2, heads=heads)
        ptab = jnp.asarray([[1, 4, 2, 0], [3, 5, 6, 7]], np.int32)
        lens = jnp.asarray([PAGE + 3, 4 * PAGE - 1], jnp.int32)
        scales = {}
        if dtype == "int8":
            kq, vq, ks, vs = jax.vmap(_quantize_pool)(kp, vp)
            kp, vp, scales = kq, vq, {"k_scale": ks, "v_scale": vs}
        else:
            kp, vp = kp.astype(jnp.bfloat16), vp.astype(jnp.bfloat16)
        return (q, kp, vp, ptab, lens, kn, vn), scales

    @pytest.mark.parametrize("layer", [0, L - 1])
    @pytest.mark.parametrize("head_block", [1, 2, 4, 8])
    @pytest.mark.parametrize("dtype", ["bf16", "int8"])
    def test_layer_of_stacked_pool_equals_its_slice(self, dtype, head_block,
                                                    layer):
        (q, kp, vp, *rest), scales = self._case(dtype)
        tuning.clear_last_dispatch()
        # the layer is a traced scalar, as inside the model's layer scan
        got = jax.jit(lambda i: paged_attention(
            q, kp, vp, *rest, layer=i, head_block=head_block, impl="kernel",
            **scales))(jnp.int32(layer))
        rec = tuning.last_dispatch(KERNEL)["page%d" % PAGE]
        assert rec["head_block"] == head_block and rec["impl"] == "kernel"
        want = paged_attention(
            q, kp[layer], vp[layer], *rest, head_block=head_block,
            impl="kernel", **{k: v[layer] for k, v in scales.items()})
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        # same key as the 4-D call: the cell must not run other blocks
        assert tuning.last_dispatch(KERNEL)["page%d" % PAGE]["key"] \
            == rec["key"]

    @pytest.mark.parametrize("dtype", ["bf16", "int8"])
    def test_stacked_pool_kernel_vs_dense(self, dtype):
        (q, kp, vp, *rest), scales = self._case(dtype, heads=2)
        got, dense = (paged_attention(q, kp, vp, *rest, layer=self.L - 1,
                                      impl=impl, **scales)
                      for impl in ("kernel", "dense"))
        np.testing.assert_allclose(np.asarray(got), np.asarray(dense),
                                   atol=2e-2 if dtype == "bf16" else 1e-4,
                                   rtol=2e-2)

    def test_rank_and_layer_must_agree(self):
        (q, kp, vp, *rest), _ = self._case("bf16")
        with pytest.raises(ValueError, match="stacked"):
            paged_attention(q, kp, vp, *rest)              # 5-D, no layer
        with pytest.raises(ValueError, match="stacked"):
            paged_attention(q, kp[0], vp[0], *rest, layer=0)

    @pytest.mark.parametrize("dtype", ["bf16", "int8"])
    def test_stacked_pool_over_the_model_axis(self, dtype):
        """``mp_size > 1``: the head axis of the stacked pool is its
        third, and each device runs the kernel on its own heads."""
        from deepspeed_tpu.comm import MeshSpec, build_mesh
        (q, kp, vp, *rest), scales = self._case(dtype, heads=4)
        want = paged_attention(q, kp, vp, *rest, layer=1, impl="kernel",
                               **scales)
        mesh = build_mesh(MeshSpec(model=2, data=4))
        tuning.clear_last_dispatch()
        got = jax.jit(lambda i: paged_attention(
            q, kp, vp, *rest, layer=i, impl="kernel", mesh=mesh,
            **scales))(jnp.int32(1))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert tuning.last_dispatch(KERNEL)["page%d" % PAGE][
            "model_shards"] == 2


class TestGroupedQueryHeads:
    """Fewer K/V heads than query heads: the pool holds ``kv`` heads, the
    kernel's grid walks them and a step computes the whole group of query
    heads that read its K/V heads (query head ``i`` reads K/V head
    ``i // group``) from one walk of their pages. Held to the same call on
    a pool whose K/V heads are repeated once a query head."""
    HEADS, D = 8, 16

    def _case(self, kv, dtype, stacked, seed=11):
        r = np.random.RandomState(seed)
        lead = (2,) if stacked else ()
        kp, vp = (jnp.asarray(r.randn(*lead, 9, kv, self.D, PAGE)
                              .astype(np.float32)) for _ in range(2))
        q = jnp.asarray(r.randn(3, 1, self.HEADS, self.D).astype(np.float32))
        kn, vn = (jnp.asarray(r.randn(3, kv, self.D, 1).astype(np.float32))
                  for _ in range(2))
        ptab = jnp.asarray([[1, 4, 2, 0], [3, 5, 6, 7], [8, 0, 0, 0]],
                           np.int32)
        lens = jnp.asarray([PAGE + 3, 4 * PAGE - 1, 0], jnp.int32)
        scales = {}
        if dtype == "int8":
            quant = jax.vmap(_quantize_pool) if stacked else _quantize_pool
            kp, vp, ks, vs = quant(kp, vp)
            scales = {"k_scale": ks, "v_scale": vs}
        elif dtype == "bf16":
            kp, vp = kp.astype(jnp.bfloat16), vp.astype(jnp.bfloat16)
        return q, kp, vp, ptab, lens, kn, vn, scales

    @staticmethod
    def _repeated(x, group, stacked):
        return jnp.repeat(x, group, axis=2 if stacked else 1)

    @pytest.mark.parametrize("kv,head_block", [(1, 1), (2, 2), (4, 4), (4, 1),
                                               (8, 8)])
    @pytest.mark.parametrize("stacked", [False, True], ids=["4d", "stacked"])
    @pytest.mark.parametrize("dtype", ["f32", "bf16", "int8"])
    def test_kernel_equals_the_call_on_repeated_heads(self, dtype, stacked,
                                                      kv, head_block):
        q, kp, vp, ptab, lens, kn, vn, scales = self._case(kv, dtype,
                                                           stacked)
        group = self.HEADS // kv
        layer = {"layer": 1} if stacked else {}
        tuning.clear_last_dispatch()
        got = paged_attention(q, kp, vp, ptab, lens, kn, vn, impl="kernel",
                              head_block=head_block, **layer, **scales)
        rec = tuning.last_dispatch(KERNEL)["page%d" % PAGE]
        assert rec["impl"] == "kernel" and rec["head_block"] == head_block
        rep = lambda x: self._repeated(x, group, stacked)
        want = paged_attention(
            q, rep(kp), rep(vp), ptab, lens, jnp.repeat(kn, group, axis=1),
            jnp.repeat(vn, group, axis=1), impl="kernel", **layer,
            **{k: rep(v) for k, v in scales.items()})
        # the same products in the same order: a group's rows share one
        # matmul where repeated heads have one each
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)
        dense = paged_attention(q, kp, vp, ptab, lens, kn, vn, impl="dense",
                                **layer, **scales)
        np.testing.assert_allclose(np.asarray(got), np.asarray(dense),
                                   atol=2e-2 if dtype == "bf16" else 1e-4,
                                   rtol=2e-2)

    def test_a_head_reads_its_own_group_and_no_other(self):
        """Query head ``i`` reads K/V head ``i // group``, not ``i % kv``:
        zeroing K/V head 1 changes query heads 4..7 of 8 over 2 and
        leaves 0..3 as they were."""
        q, kp, vp, ptab, lens, kn, vn, _ = self._case(2, "f32", False)
        base = paged_attention(q, kp, vp, ptab, lens, kn, vn, impl="kernel")
        cut = paged_attention(q, kp.at[:, 1].set(0.0), vp.at[:, 1].set(0.0),
                              ptab, lens, kn.at[:, 1].set(0.0),
                              vn.at[:, 1].set(0.0), impl="kernel")
        same = np.asarray(base[:, 0, :4] == cut[:, 0, :4]).all()
        moved = np.abs(np.asarray(base[:2, 0, 4:] - cut[:2, 0, 4:])).max()
        assert same and moved > 1e-2

    def test_heads_must_be_whole_groups(self):
        q, kp, vp, ptab, lens, kn, vn, _ = self._case(3, "f32", False)
        with pytest.raises(ValueError, match="whole group"):
            paged_attention(q, kp, vp, ptab, lens, kn, vn)

    def test_grouped_heads_over_the_model_axis(self):
        from deepspeed_tpu.comm import MeshSpec, build_mesh
        q, kp, vp, ptab, lens, kn, vn, _ = self._case(4, "bf16", True)
        want = paged_attention(q, kp, vp, ptab, lens, kn, vn, layer=1,
                               impl="kernel")
        mesh = build_mesh(MeshSpec(model=2, data=4))
        got = jax.jit(lambda i: paged_attention(
            q, kp, vp, ptab, lens, kn, vn, layer=i, impl="kernel",
            mesh=mesh))(jnp.int32(1))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    # Five query heads a K/V head over a bf16 pool (Falcon-H1's layout:
    # 20 on 4 heads of 128): the eight-row cap of a grid step is the
    # float32 arm's, so the dispatcher hands a step every K/V head —
    # ``head_block`` = the K/V heads, ``rows`` = five times that — and a
    # row's arithmetic does not depend on which heads share its step: the
    # result is ``head_block=1``'s bit for bit. Ragged lengths, a row of
    # length zero, NaN in every page nobody owns.
    GROUP, WIDE = 5, 128
    LENS = [2 * PAGE + 7, 0, 5 * PAGE - 1, PAGE]
    TABLE = [[1, 4, 2, 0, 0], [0, 0, 0, 0, 0], [3, 5, 6, 7, 8],
             [9, 0, 0, 0, 0]]
    L = 2

    def _five_a_head(self, kv, form, seed=61):
        r = np.random.RandomState(seed)
        heads = kv * self.GROUP
        kp, vp = (jnp.asarray(r.randn(self.L, 11, kv, self.WIDE, PAGE),
                              jnp.bfloat16) for _ in range(2))
        # the null page and a page no row's table names
        bad = jnp.asarray([0, 10])
        kp, vp = kp.at[:, bad].set(jnp.nan), vp.at[:, bad].set(jnp.nan)
        q = _bf16_values(r, 4, 1, heads, self.WIDE)
        kn, vn = (_bf16_values(r, 4, kv, self.WIDE, 1) for _ in range(2))
        kw = {"layer": jnp.int32(self.L - 1)}
        if form == "4d":
            kp, vp, kw = kp[-1], vp[-1], {}
        return (q, kp, vp, jnp.asarray(self.TABLE, jnp.int32),
                jnp.asarray(self.LENS, jnp.int32), kn, vn), kw

    @pytest.mark.parametrize("form", ["4d", "stacked"])
    @pytest.mark.parametrize("kv", [2, 4, 8])
    def test_a_step_takes_every_kv_head_and_rows_keep_their_bits(self, kv,
                                                                 form):
        args, kw = self._five_a_head(kv, form)
        tuning.clear_last_dispatch()
        got = paged_attention(*args, impl="kernel", block_tokens=2 * PAGE,
                              **kw)
        rec = tuning.last_dispatch(KERNEL)["page%d" % PAGE]
        assert (rec["head_block"], rec["rows"]) == (kv, self.GROUP * kv)
        assert rec["products"] == "bfloat16" and rec["impl"] == "kernel"
        one = paged_attention(*args, impl="kernel", block_tokens=2 * PAGE,
                              head_block=1, **kw)
        rec = tuning.last_dispatch(KERNEL)["page%d" % PAGE]
        assert (rec["head_block"], rec["rows"]) == (1, self.GROUP)
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_array_equal(np.asarray(got), np.asarray(one))
        dense = paged_attention(*args, impl="dense", **kw)
        np.testing.assert_allclose(np.asarray(got), np.asarray(dense),
                                   atol=1e-5, rtol=1e-5)
        # the row of length zero attends its own token alone
        own = np.repeat(np.asarray(args[6])[1, :, :, 0], self.GROUP, axis=0)
        np.testing.assert_allclose(np.asarray(got)[1, 0], own, atol=1e-6)

    @pytest.mark.parametrize("head_block", [1, 2, 4])
    def test_over_the_model_axis_a_device_takes_its_own_kv_heads(
            self, head_block):
        """Four K/V heads over a model axis of two: each device's step
        holds its two (ten rows), or fewer where the caller asks."""
        from deepspeed_tpu.comm import MeshSpec, build_mesh
        args, kw = self._five_a_head(4, "stacked")
        want = paged_attention(*args, impl="kernel", head_block=1, **kw)
        mesh = build_mesh(MeshSpec(model=2, data=4))
        tuning.clear_last_dispatch()
        got = jax.jit(lambda i: paged_attention(
            *args, layer=i, impl="kernel", head_block=head_block,
            mesh=mesh))(kw["layer"])
        rec = tuning.last_dispatch(KERNEL)["page%d" % PAGE]
        hb = min(head_block, 2)
        assert (rec["head_block"], rec["rows"], rec["model_shards"]) == (
            hb, self.GROUP * hb, 2)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("dtype", ["f32", "int8"])
    def test_float32_products_keep_eight_rows_a_step(self, dtype):
        """The same heads over a float32 pool and an int8 one: the
        float32 arm cuts a row of a boolean mask a K/V head, which
        Mosaic aborts on past the eighth row — one K/V head a step at a
        group of five, and the dense path's result."""
        (q, kp, vp, ptab, lens, kn, vn), _ = self._five_a_head(4, "4d")
        clean = lambda x: jnp.nan_to_num(x.astype(jnp.float32))
        kp, vp, scales = clean(kp), clean(vp), {}
        if dtype == "int8":
            kp, vp, ks, vs = _quantize_pool(kp, vp)
            scales = {"k_scale": ks, "v_scale": vs}
        tuning.clear_last_dispatch()
        got = paged_attention(q, kp, vp, ptab, lens, kn, vn, impl="kernel",
                              **scales)
        rec = tuning.last_dispatch(KERNEL)["page%d" % PAGE]
        assert (rec["head_block"], rec["rows"], rec["products"]) == (
            1, self.GROUP, "float32")
        dense = paged_attention(q, kp, vp, ptab, lens, kn, vn, impl="dense",
                                **scales)
        np.testing.assert_allclose(np.asarray(got), np.asarray(dense),
                                   atol=1e-4, rtol=1e-4)


class TestZeroRowsBetweenLongRows:
    """What the serving decode program hands the kernel since it masks
    the rows that do not decode: length 0 for a released slot or one
    still waiting for its prefill chunks, between rows of several pages.
    Such a row's table still names the pages it held — poisoned here, so
    one column read from them would show — and its output is attention
    over its own token alone."""
    L = 2
    LENS = [3 * PAGE + 5, 0, 4 * PAGE - 1, 0]
    TABLE = [[1, 2, 3, 4], [7, 8, 7, 8], [5, 6, 1, 2], [8, 7, 0, 0]]
    POISONED = (7, 8)          # the zero rows' pages and nobody else's

    def _case(self, dtype, stacked, seed=21):
        r = np.random.RandomState(seed)
        kp, vp = (jnp.asarray(r.randn(self.L, 9, 4, 16, PAGE)
                              .astype(np.float32)) for _ in range(2))
        scales = {}
        bad = jnp.asarray(self.POISONED)
        if dtype == "int8":
            # an int8 page cannot hold a NaN: its scale plane does
            kq, vq, ks, vs = jax.vmap(_quantize_pool)(kp, vp)
            kp, vp = kq.at[:, bad].set(127), vq.at[:, bad].set(127)
            scales = {"k_scale": ks.at[:, bad].set(jnp.nan),
                      "v_scale": vs.at[:, bad].set(jnp.nan)}
        else:
            kp = kp.astype(jnp.bfloat16).at[:, bad].set(jnp.nan)
            vp = vp.astype(jnp.bfloat16).at[:, bad].set(jnp.nan)
        layer = {"layer": jnp.int32(self.L - 1)}
        if not stacked:
            kp, vp, layer = kp[-1], vp[-1], {}
            scales = {k: v[-1] for k, v in scales.items()}
        q, kn, vn = _operands(seed=seed + 1, b=4)
        return (q, kp, vp, jnp.asarray(self.TABLE, jnp.int32),
                jnp.asarray(self.LENS, jnp.int32), kn, vn), {**scales,
                                                             **layer}

    @pytest.mark.parametrize("alibi", [False, True], ids=["plain", "alibi"])
    @pytest.mark.parametrize("stacked", [False, True], ids=["4d", "stacked"])
    @pytest.mark.parametrize("dtype", ["bf16", "int8"])
    def test_zero_rows_attend_their_own_token_only(self, dtype, stacked,
                                                   alibi):
        args, kw = self._case(dtype, stacked)
        if alibi:
            kw["alibi_slopes"] = np.linspace(0.1, 0.5, 4).astype(np.float32)
        got, dense = (np.asarray(paged_attention(*args, impl=impl, **kw))
                      for impl in ("kernel", "dense"))
        assert np.isfinite(got).all() and np.isfinite(dense).all()
        np.testing.assert_allclose(got, dense,
                                   atol=2e-2 if dtype == "bf16" else 1e-4,
                                   rtol=2e-2)
        # softmax over one column is 1: the row's output is its own V
        own = np.asarray(args[6])[..., 0]                  # [B, H, d]
        for row in (1, 3):
            np.testing.assert_allclose(got[row, 0], own[row], atol=1e-6)
        # and the long rows beside them are not the same thing
        assert np.abs(got[0, 0] - own[0]).max() > 1e-2


def _bf16_values(r, *shape):
    """float32 numbers that bfloat16 holds exactly: the kernel's cast of
    a query to a bf16 pool's type then loses nothing, and the output
    comes back in float32 to be compared to 1e-5."""
    return jnp.asarray(r.randn(*shape), jnp.bfloat16).astype(jnp.float32)


class TestProductsInThePoolsType:
    """A bf16 pool's blocks go to the products as they lie in VMEM (one
    pass of the MXU for the scores, the probabilities as bf16 terms for
    the values), with float32 accumulation: bf16 x bf16 products are
    exact in float32, so the kernel agrees with float32 arithmetic on
    the same bf16 values to the tolerance the float32 pools are held to.
    A float32 pool's products and an int8 pool's dequantised blocks stay
    float32, their program text the parent's."""
    BLOCK = 2 * PAGE                     # two pages a DMA block
    # pooled tokens of rows of 1, 2 and 5 blocks, the last one ragged
    # (in two of them the block's second page holds no valid column)
    LENGTHS = {1: 5, 2: 3 * PAGE + 7, 5: 8 * PAGE + 1}

    def _case(self, blocks, seed=31, heads=4, d=16):
        r = np.random.RandomState(seed)
        kp, vp = (jnp.asarray(r.randn(24, heads, d, PAGE), jnp.bfloat16)
                  for _ in range(2))
        q = _bf16_values(r, 2, 1, heads, d)
        kn, vn = (_bf16_values(r, 2, heads, d, 1) for _ in range(2))
        ptab = jnp.asarray([np.arange(1, 11), np.arange(11, 21)], jnp.int32)
        lens = jnp.asarray([self.LENGTHS[blocks], 0], jnp.int32)
        return q, kp, vp, ptab, lens, kn, vn

    @pytest.mark.parametrize("alibi", [False, True], ids=["plain", "alibi"])
    @pytest.mark.parametrize("blocks", [1, 2, 5])
    def test_bf16_pool_agrees_with_float32_arithmetic(self, blocks, alibi):
        args = self._case(blocks)
        kw = ({"alibi_slopes": np.linspace(0.1, 0.5, 4).astype(np.float32)}
              if alibi else {})
        tuning.clear_last_dispatch()
        got = paged_attention(*args, impl="kernel", block_tokens=self.BLOCK,
                              **kw)
        rec = tuning.last_dispatch(KERNEL)["page%d" % PAGE]
        assert rec["products"] == "bfloat16" and rec["block_k"] == self.BLOCK
        # the dense path widens the same bf16 values and multiplies in
        # float32: what the kernel's products are, term by term
        want = paged_attention(*args, impl="dense", **kw)
        assert got.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("blocks", [1, 2, 5])
    def test_garbage_past_the_length_never_reaches_the_output(self, blocks):
        """The columns of a row's last block past its length hold NaN
        and +-inf (another request's leavings, the null page): the
        output is finite and the one the clean pool gives, bit for bit."""
        q, kp, vp, ptab, lens, kn, vn = self._case(blocks)
        clean = paged_attention(q, kp, vp, ptab, lens, kn, vn, impl="kernel",
                                block_tokens=self.BLOCK)
        page, first = divmod(self.LENGTHS[blocks], PAGE)
        bad = jnp.asarray(np.resize([np.nan, np.inf, -np.inf], PAGE),
                          jnp.bfloat16)
        own = int(ptab[0, page])

        def spoil(x):
            # the null page, the table's pages behind the row's last, and
            # that page's own columns from the length on
            x = x.at[0].set(bad).at[np.asarray(ptab[0, page + 1:])].set(bad)
            return x.at[own].set(jnp.where(jnp.arange(PAGE) >= first, bad,
                                           x[own]))

        got = paged_attention(q, spoil(kp), spoil(vp), ptab, lens, kn, vn,
                              impl="kernel", block_tokens=self.BLOCK)
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_array_equal(np.asarray(got), np.asarray(clean))

    @pytest.mark.parametrize("dtype", ["f32", "int8"])
    def test_lfm2_geometry_keeps_float32_products(self, dtype):
        """32 query heads on 8 K/V heads of 64 (a group of 4, two K/V
        heads a step), a float32 pool and an int8 one: to 1e-5 of the
        dense path, and the dispatch says float32."""
        r = np.random.RandomState(41)
        kp, vp = (jnp.asarray(r.randn(12, 8, 64, PAGE).astype(np.float32))
                  for _ in range(2))
        q = jnp.asarray(r.randn(2, 1, 32, 64).astype(np.float32))
        kn, vn = (jnp.asarray(r.randn(2, 8, 64, 1).astype(np.float32))
                  for _ in range(2))
        ptab = jnp.asarray([[1, 2, 3, 4, 5], [6, 7, 0, 0, 0]], jnp.int32)
        lens = jnp.asarray([4 * PAGE + 3, PAGE + 1], jnp.int32)
        scales = {}
        if dtype == "int8":
            kp, vp, ks, vs = _quantize_pool(kp, vp)
            scales = {"k_scale": ks, "v_scale": vs}
        run = lambda impl: paged_attention(
            q, kp, vp, ptab, lens, kn, vn, impl=impl,
            block_tokens=self.BLOCK, **scales)
        tuning.clear_last_dispatch()
        got = run("kernel")
        rec = tuning.last_dispatch(KERNEL)["page%d" % PAGE]
        assert (rec["head_block"], rec["rows"]) == (2, 8)
        assert rec["products"] == "float32"
        want = run("dense")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)

    # sha256 of the lowered program (StableHLO, no locations: the
    # interpreted kernel inlined) at the parent of the PR that moved the
    # bf16 products, commit 6524da7: a float32 pool's and an int8 pool's
    # path is that program letter for letter. A change that means to
    # move them records the new text's hash here.
    PARENT_TEXT = {
        "f32": "d00cfe70773aaa2fbde7b7382f43ed65"
               "53ce7f1523c1e065065deeba57183802",
        "int8": "47eba3125064c20c06a1f7daf6603de4"
                "6716f0659ae1c1b45d305d594846f214",
        "decode_f32": "78ea081c611c40b2ea4de062cda86ac1"
                      "1d7ed7638a9d8c358d259b74420fd556",
    }

    @staticmethod
    def _lowered(which):
        S = jax.ShapeDtypeStruct
        f32 = jnp.float32
        if which == "decode_f32":
            from deepspeed_tpu.ops.pallas import decode_attention
            cache = S((2, 4, 32, 256), f32)
            return jax.jit(lambda *a: decode_attention(*a, block_k=128)) \
                .lower(S((2, 1, 4, 32), f32), cache, cache,
                       S((2,), jnp.int32)).as_text()
        pool = S((12, 8, 64, PAGE), jnp.int8 if which == "int8" else f32)
        new = S((2, 8, 64, 1), f32)
        args = [S((2, 1, 32, 64), f32), pool, pool, S((2, 5), jnp.int32),
                S((2,), jnp.int32), new, new]
        names = ()
        if which == "int8":
            names = ("k_scale", "v_scale")
            args += [S((12, 8, 1, PAGE), f32)] * 2
        return jax.jit(lambda *a: paged_attention(
            *a[:7], impl="kernel", block_tokens=2 * PAGE,
            **dict(zip(names, a[7:])))).lower(*args).as_text()

    @pytest.mark.parametrize("which", sorted(PARENT_TEXT))
    def test_float32_paths_lower_to_the_parents_text(self, which):
        import hashlib
        text = self._lowered(which)
        assert "bf16" not in text          # every product still float32
        assert hashlib.sha256(text.encode()).hexdigest() \
            == self.PARENT_TEXT[which]

    def test_gpt2_twelve_heads_at_head_block_four(self):
        args = self._case(5, seed=43, heads=12, d=64)
        tuning.clear_last_dispatch()
        got = paged_attention(*args, impl="kernel", block_tokens=self.BLOCK)
        rec = tuning.last_dispatch(KERNEL)["page%d" % PAGE]
        assert rec["head_block"] == 4 and rec["products"] == "bfloat16"
        want = paged_attention(*args, impl="dense")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("dtype", ["bf16", "f32"])
    def test_decode_attention_at_the_same_dtypes(self, dtype):
        """The contiguous cache's kernel shares the block update: a bf16
        cache's products are exact too and its garbage past the length
        guarded, a float32 cache multiplies in float32."""
        from deepspeed_tpu.ops.pallas import decode_attention
        from deepspeed_tpu.ops.pallas.decode_attention import _decode_dense
        r = np.random.RandomState(47)
        cdt = jnp.bfloat16 if dtype == "bf16" else jnp.float32
        k, v = (jnp.asarray(r.randn(3, 4, 32, 640), cdt) for _ in range(2))
        q = _bf16_values(r, 3, 1, 4, 32)
        lens = jnp.asarray([130, 640, 0], jnp.int32)
        past = jnp.arange(640)[None, None, None, :] >= lens[:, None, None,
                                                            None]
        spoil = lambda x: jnp.where(past, jnp.asarray(jnp.nan, cdt), x)
        tuning.clear_last_dispatch()
        got = decode_attention(q, spoil(k), spoil(v), lens, block_k=128)
        rec = tuning.last_dispatch("decode_attention")["dma"]
        assert rec["impl"] == "kernel" and rec["products"] == jnp.dtype(
            cdt).name
        want = _decode_dense(q[:, 0], k, v, lens, jnp.zeros((4,)),
                             scale=32 ** -0.5, alibi=False)
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(np.asarray(got[:, 0]), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)


    @pytest.mark.parametrize("dtype,head_block", [("bf16", 16), ("f32", 8)])
    def test_decode_attention_asked_for_sixteen_heads(self, dtype,
                                                      head_block):
        """The contiguous cache's kernel through the same rule: sixteen
        heads a step over a bf16 cache where the caller asks, eight over
        a float32 one (its arm's mask cut aborts Mosaic past the eighth
        row), and the dense form's result either way."""
        from deepspeed_tpu.ops.pallas import decode_attention
        from deepspeed_tpu.ops.pallas.decode_attention import _decode_dense
        r = np.random.RandomState(53)
        cdt = jnp.bfloat16 if dtype == "bf16" else jnp.float32
        k, v = (jnp.asarray(r.randn(2, 16, 32, 256), cdt) for _ in range(2))
        q = _bf16_values(r, 2, 1, 16, 32)
        lens = jnp.asarray([130, 0], jnp.int32)
        tuning.clear_last_dispatch()
        got = decode_attention(q, k, v, lens, block_k=128, head_block=16)
        rec = tuning.last_dispatch("decode_attention")["dma"]
        assert (rec["impl"], rec["head_block"]) == ("kernel", head_block)
        want = _decode_dense(q[:, 0], k, v, lens, jnp.zeros((16,)),
                             scale=32 ** -0.5, alibi=False)
        np.testing.assert_allclose(np.asarray(got[:, 0]), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)


class TestTenCachedHeads:
    """Ten cached heads of 128 with four query rows each over a bf16
    cache (Phi-4-mini-flash's one paged layer and its window rings): the
    narrow arm's step takes five or ten of them where the table's entry
    or the caller asks (PR 58), two otherwise, and at one ``block_k``
    every head block gives head block 1's bits."""
    KV, GROUP, D = 10, 4, 128
    LENS = [2 * PAGE + 7, 0, 5 * PAGE - 1, PAGE]
    TABLE = [[1, 4, 2, 0, 0], [0, 0, 0, 0, 0], [3, 5, 6, 7, 8],
             [9, 0, 0, 0, 0]]

    def _paged(self, seed=83):
        r = np.random.RandomState(seed)
        kp, vp = (jnp.asarray(r.randn(2, 11, self.KV, self.D, PAGE),
                              jnp.bfloat16) for _ in range(2))
        bad = jnp.asarray([0, 10])      # the null page and nobody's page
        kp, vp = kp.at[:, bad].set(jnp.nan), vp.at[:, bad].set(jnp.nan)
        q = _bf16_values(r, 4, 1, self.KV * self.GROUP, self.D)
        kn, vn = (_bf16_values(r, 4, self.KV, self.D, 1) for _ in range(2))
        return (q, kp, vp, jnp.asarray(self.TABLE, jnp.int32),
                jnp.asarray(self.LENS, jnp.int32), kn, vn)

    @pytest.mark.parametrize("blocks", [1, 2, 4])
    @pytest.mark.parametrize("head_block", [2, 5, 10])
    def test_paged_rows_keep_their_bits_at_every_head_block(self, head_block,
                                                            blocks):
        args = self._paged()
        kw = dict(layer=jnp.int32(1), impl="kernel",
                  block_tokens=blocks * PAGE)
        tuning.clear_last_dispatch()
        got = paged_attention(*args, head_block=head_block, **kw)
        rec = tuning.last_dispatch(KERNEL)["page%d" % PAGE]
        assert (rec["head_block"], rec["rows"], rec["products"]) == (
            head_block, self.GROUP * head_block, "bfloat16")
        one = paged_attention(*args, head_block=1, **kw)
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_array_equal(np.asarray(got), np.asarray(one))
        dense = paged_attention(*args, layer=jnp.int32(1), impl="dense")
        np.testing.assert_allclose(np.asarray(got), np.asarray(dense),
                                   atol=1e-5, rtol=1e-5)
        # the row of length zero attends its own token alone
        own = np.repeat(np.asarray(args[6])[1, :, :, 0], self.GROUP, axis=0)
        np.testing.assert_allclose(np.asarray(got)[1, 0], own, atol=1e-6)

    @pytest.mark.parametrize("dtype,asked,ran", [
        ("bf16", 10, 10), ("bf16", 5, 5), ("bf16", None, 2),
        ("bf16", 16, 2), ("f32", 10, 2), ("f32", 5, 2), ("int8", 10, 2)])
    def test_only_a_bf16_pool_is_answered_five_or_ten(self, dtype, asked,
                                                      ran):
        q, kp, vp, ptab, lens, kn, vn = self._paged()
        scales = {}
        if dtype != "bf16":
            kp, vp = (jnp.nan_to_num(x.astype(jnp.float32)) for x in (kp, vp))
        if dtype == "int8":
            kp, vp, ks, vs = jax.vmap(_quantize_pool)(kp, vp)
            scales = {"k_scale": ks, "v_scale": vs}
        tuning.clear_last_dispatch()
        got = paged_attention(q, kp, vp, ptab, lens, kn, vn, layer=1,
                              impl="kernel", head_block=asked, **scales)
        rec = tuning.last_dispatch(KERNEL)["page%d" % PAGE]
        assert (rec["head_block"], rec["rows"]) == (ran, self.GROUP * ran)
        assert rec["products"] == ("bfloat16" if dtype == "bf16"
                                   else "float32")
        dense = paged_attention(q, kp, vp, ptab, lens, kn, vn, layer=1,
                                impl="dense", **scales)
        np.testing.assert_allclose(np.asarray(got), np.asarray(dense),
                                   atol=1e-4, rtol=1e-4)

    def test_five_heads_a_device_over_the_model_axis(self):
        """Ten cached heads over a model axis of two: a device holds five,
        and a want of ten gives it all five in one step."""
        from deepspeed_tpu.comm import MeshSpec, build_mesh
        args = self._paged()
        want = paged_attention(*args, layer=jnp.int32(1), impl="kernel",
                               head_block=1)
        mesh = build_mesh(MeshSpec(model=2, data=4))
        tuning.clear_last_dispatch()
        got = jax.jit(lambda i: paged_attention(
            *args, layer=i, impl="kernel", head_block=10, mesh=mesh))(
            jnp.int32(1))
        rec = tuning.last_dispatch(KERNEL)["page%d" % PAGE]
        assert (rec["head_block"], rec["rows"], rec["model_shards"]) == (
            5, 20, 2)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    # the rings: ``[b, 10, 128, 512]``, every lane valid once a ring has
    # wrapped (length 512), a prefix of them before
    RING_LENS = [512, 77, 0, 300]

    def _ring(self, seed=89):
        r = np.random.RandomState(seed)
        k, v = (jnp.asarray(r.randn(4, self.KV, self.D, 512), jnp.bfloat16)
                for _ in range(2))
        lens = jnp.asarray(self.RING_LENS, jnp.int32)
        past = jnp.arange(512)[None, None, None, :] >= lens[:, None, None,
                                                           None]
        spoil = lambda x: jnp.where(past, jnp.asarray(jnp.nan, x.dtype), x)
        q = _bf16_values(r, 4, 1, self.KV * self.GROUP, self.D)
        return q, k, v, spoil(k), spoil(v), lens

    @pytest.mark.parametrize("block_k", [128, 256, 512])
    @pytest.mark.parametrize("head_block", [2, 5, 10])
    def test_ring_rows_keep_their_bits_at_every_head_block(self, head_block,
                                                           block_k):
        from deepspeed_tpu.ops.pallas import decode_attention
        from deepspeed_tpu.ops.pallas.decode_attention import _decode_dense
        q, k, v, ks, vs, lens = self._ring()
        tuning.clear_last_dispatch()
        got = decode_attention(q, ks, vs, lens, block_k=block_k,
                               head_block=head_block)
        rec = tuning.last_dispatch("decode_attention")["dma"]
        assert (rec["impl"], rec["block_k"], rec["head_block"], rec["rows"],
                rec["products"], rec["source"]) == (
            "kernel", block_k, head_block, self.GROUP * head_block,
            "bfloat16", "constants")
        one = decode_attention(q, ks, vs, lens, block_k=block_k,
                               head_block=1)
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_array_equal(np.asarray(got), np.asarray(one))
        want = _decode_dense(q[:, 0], k, v, lens,
                             jnp.zeros((self.KV * self.GROUP,)),
                             scale=self.D ** -0.5, alibi=False)
        np.testing.assert_allclose(np.asarray(got[:, 0]), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)
        assert not np.asarray(got[2]).any()         # the empty slot: zeros

    @pytest.mark.parametrize("dtype,rows", [("bf16", 8), ("f32", 8)])
    def test_a_table_miss_keeps_the_rings_constants(self, dtype, rows):
        """No entry for the shape and nothing asked: ``block_k`` 512 and a
        want of eight heads, of which ten cached heads take two — what
        ``SelfAttention``'s dense-cache decode and every caller before PR
        58 got."""
        from deepspeed_tpu.ops.pallas import decode_attention
        q, k, v, _, _, lens = self._ring()
        if dtype == "f32":
            k, v = k.astype(jnp.float32), v.astype(jnp.float32)
        tuning.clear_last_dispatch()
        decode_attention(q, k, v, lens)
        rec = tuning.last_dispatch("decode_attention")["dma"]
        assert rec["key"] == ("decode_attention/dma/sq4_sk512_d128_"
                              f"{k.dtype.name}_causal")
        assert (rec["source"], rec["block_k"], rec["head_block"],
                rec["rows"]) == ("constants", 512, 2, rows)
        # sixteen ungrouped heads: the constant's eight, as before
        decode_attention(q[:, :, :16], k[:, :8].repeat(2, axis=1),
                         v[:, :8].repeat(2, axis=1), lens)
        rec = tuning.last_dispatch("decode_attention")["dma"]
        assert (rec["block_k"], rec["head_block"], rec["rows"]) == (512, 8, 8)

    def test_the_rings_entry_is_consumed_and_wins_over_the_caller(self):
        from deepspeed_tpu.ops.pallas import decode_attention
        q, k, v, _, _, lens = self._ring()
        key = tuning.make_key("decode_attention", "dma", sq=4, sk=512,
                              d=self.D, dtype=jnp.bfloat16, causal=True)
        want = decode_attention(q, k, v, lens, block_k=256, head_block=1)
        tuning.clear_last_dispatch()
        with tuning.tuning_table({key: {"block_k": 256, "head_block": 10}}):
            got = decode_attention(q, k, v, lens, block_k=128, head_block=2)
            rec = tuning.last_dispatch("decode_attention")["dma"]
        assert (rec["key"], rec["source"], rec["block_k"], rec["head_block"],
                rec["rows"]) == (key, "runtime", 256, 10, 40)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("kernel,shape", [
        ("paged_attention/page128", (64, 32)),
        ("decode_attention/dma", (64, 512))])
    def test_the_cells_shapes_are_in_the_committed_table(self, kernel, shape):
        """``serve-phi4flash-reason``: 64 slots, 32 pages of 128 a row
        over ten bf16 heads of 128, and rings of 512 — both keys are in
        ``flash_tuning_defaults.json`` with what was swept on the v5e (PR
        58), and the dispatch records say so."""
        from deepspeed_tpu.ops.pallas import decode_attention
        S, bf = jax.ShapeDtypeStruct, jnp.bfloat16
        slots = shape[0]
        q = S((slots, 1, 40, 128), bf)
        tuning.clear_last_dispatch()
        if kernel.startswith("paged"):
            pool = S((slots * shape[1] + 1, 10, 128, 128), bf)
            new = S((slots, 10, 128, 1), bf)
            jax.eval_shape(lambda *a: paged_attention(*a, impl="kernel"),
                           q, pool, pool, S(shape, jnp.int32),
                           S((slots,), jnp.int32), new, new)
            rec = tuning.last_dispatch(KERNEL)["page128"]
        else:
            ring = S((slots, 10, 128, shape[1]), bf)
            jax.eval_shape(decode_attention, q, ring, ring,
                           S((slots,), jnp.int32))
            rec = tuning.last_dispatch("decode_attention")["dma"]
        entry = tuning.load_artifact(tuning.DEFAULTS_PATH)["entries"][
            rec["key"]]
        assert rec["key"].startswith(kernel + "/sq64_sk")
        assert rec["source"] == "defaults" and rec["products"] == "bfloat16"
        assert rec["head_block"] in (5, 10)
        assert rec["rows"] == 4 * rec["head_block"]
        assert (rec["block_k"], rec["head_block"]) == (
            entry["block_k"], entry["head_block"])
        assert entry["device"] == "TPU v5 lite" and "PR 58" in entry["note"]


class TestTuningDispatch:
    def test_runtime_table_entry_consumed(self):
        """The shape-keyed tuning cache resolves the kernel's blocks at
        trace time: an injected entry shows up in last_dispatch with
        source 'runtime' and its blocks applied."""
        kp, vp = _pool(seed=12)
        q, kn, vn = _operands(seed=13)
        ptab = jnp.asarray(np.arange(1, 5, dtype=np.int32)[None]
                           .repeat(3, 0))
        lens = jnp.asarray([PAGE, PAGE, PAGE], jnp.int32)
        key = tuning.make_key(KERNEL, f"page{PAGE}", sq=3, sk=4 * PAGE,
                              d=16, dtype=jnp.float32, causal=True)
        tuning.clear_last_dispatch()
        with tuning.tuning_table({key: {"block_k": 2 * PAGE,
                                        "head_block": 2}}):
            paged_attention(q, kp, vp, ptab, lens, kn, vn, impl="kernel")
            disp = tuning.last_dispatch(KERNEL)[f"page{PAGE}"]
        assert disp["source"] == "runtime"
        assert disp["block_k"] == 2 * PAGE and disp["head_block"] == 2

    def test_full_miss_falls_back_to_constants(self):
        kp, vp = _pool(seed=14)
        q, kn, vn = _operands(seed=15)
        ptab = jnp.asarray([[1, 2], [3, 4], [5, 6]], jnp.int32)
        lens = jnp.asarray([5, 5, 5], jnp.int32)
        tuning.clear_last_dispatch()
        paged_attention(q, kp, vp, ptab, lens, kn, vn, impl="kernel")
        disp = tuning.last_dispatch(KERNEL)[f"page{PAGE}"]
        assert disp["source"] == "constants"
        # blocks clamp to the table: 2 pages * 16 tokens < the 512 default
        assert disp["block_k"] == 2 * PAGE

    def test_the_serving_cells_shape_takes_the_swept_block(self):
        """32 slots x 2048 tokens, 16 heads of 128 in bf16, pages of 128
        — the key the GPT-2 1.3B serving cells and OLMoE's share — is in
        the committed table with the block and the head block swept on
        the v5e (all sixteen heads a grid step: PR 51); a shape beside
        it still falls to the constants, eight heads a step."""
        S = jax.ShapeDtypeStruct
        bf = jnp.bfloat16

        def dispatch(slots):
            pool = S((4, 16, 128, 128), bf)
            new = S((slots, 16, 128, 1), bf)
            tuning.clear_last_dispatch()
            jax.eval_shape(
                lambda *a: paged_attention(*a, impl="kernel"),
                S((slots, 1, 16, 128), bf), pool, pool,
                S((slots, 16), jnp.int32), S((slots,), jnp.int32), new, new)
            return tuning.last_dispatch(KERNEL)["page128"]

        rec = dispatch(32)
        assert rec["source"] == "defaults" and rec["products"] == "bfloat16"
        assert (rec["block_k"], rec["head_block"], rec["rows"]) == (
            128, 16, 16)
        rec = dispatch(8)
        assert rec["source"] == "constants" and rec["block_k"] == 512
        assert (rec["head_block"], rec["rows"]) == (8, 8)

    # Sixteen ungrouped heads over a bf16 pool at the serving cells' key
    # (32 slots, 16 pages of 128 a row, heads of 128): the table asks for
    # all sixteen in one grid step (PR 51), and a row's arithmetic does
    # not depend on which heads share its step — the result is
    # ``head_block`` 8's bit for bit. Ragged lengths, rows of length
    # zero whose tables name poisoned pages, NaN in every page nobody
    # owns, the stacked pool, ALiBi.
    CELLS_KEY = "paged_attention/page128/sq32_sk2048_d128_bfloat16_causal"
    CELLS_LENS = {0: 2 * 128 + 7, 2: 1, 7: 5 * 128 - 1, 9: 128,
                  31: 7 * 128 + 100}
    CELLS_PAGES = 21                 # the null page, 18 owned, 2 nobody's

    def _cells_case(self, form, seed=71):
        r = np.random.RandomState(seed)
        layers, heads, d, page = 2, 16, 128, 128
        kp, vp = (jnp.asarray(r.standard_normal(
            (layers, self.CELLS_PAGES, heads, d, page)).astype(np.float32),
            jnp.bfloat16) for _ in range(2))
        bad = jnp.asarray([0, 19, 20])
        kp, vp = kp.at[:, bad].set(jnp.nan), vp.at[:, bad].set(jnp.nan)
        lens = np.zeros(32, np.int32)
        # a row that does not decode still names the pages it held
        ptab = np.full((32, 16), 19, np.int32)
        ptab[:, 8:] = 0
        own = iter(r.permutation(18) + 1)
        for row, n in self.CELLS_LENS.items():
            lens[row] = n
            ptab[row] = 0
            for j in range(-(-n // page)):
                ptab[row, j] = next(own)
        q = _bf16_values(r, 32, 1, heads, d).astype(jnp.bfloat16)
        kn, vn = (_bf16_values(r, 32, heads, d, 1).astype(jnp.bfloat16)
                  for _ in range(2))
        kw = {"layer": jnp.int32(layers - 1)}
        if form == "4d":
            kp, vp, kw = kp[-1], vp[-1], {}
        return (q, kp, vp, jnp.asarray(ptab), jnp.asarray(lens), kn,
                vn), kw

    def _at_eight(self, block_k):
        return tuning.tuning_table({self.CELLS_KEY: {"block_k": block_k,
                                                     "head_block": 8}})

    @pytest.mark.parametrize("alibi", [False, True], ids=["plain", "alibi"])
    @pytest.mark.parametrize("form", ["4d", "stacked"])
    def test_sixteen_bf16_heads_take_one_step_and_rows_keep_their_bits(
            self, form, alibi):
        args, kw = self._cells_case(form)
        if alibi:
            kw["alibi_slopes"] = np.linspace(0.05, 0.8, 16).astype(np.float32)
        run = jax.jit(lambda *a: paged_attention(*a, impl="kernel", **kw))
        tuning.clear_last_dispatch()
        got = np.asarray(run(*args).astype(jnp.float32))
        rec = tuning.last_dispatch(KERNEL)["page128"]
        assert rec["key"] == self.CELLS_KEY and rec["source"] == "defaults"
        assert (rec["head_block"], rec["rows"]) == (16, 16)
        assert rec["products"] == "bfloat16" and rec["impl"] == "kernel"
        with self._at_eight(rec["block_k"]):
            jax.clear_caches()
            eight = np.asarray(run(*args).astype(jnp.float32))
            at_eight = tuning.last_dispatch(KERNEL)["page128"]
        jax.clear_caches()
        assert (at_eight["head_block"], at_eight["rows"],
                at_eight["block_k"]) == (8, 8, rec["block_k"])
        assert np.isfinite(got).all()
        np.testing.assert_array_equal(got, eight)
        dense = np.asarray(paged_attention(*args, impl="dense", **kw)
                           .astype(jnp.float32))
        np.testing.assert_allclose(got, dense, atol=2e-2, rtol=2e-2)
        # a row of length zero attends its own token alone
        own = np.asarray(args[6].astype(jnp.float32))[..., 0]
        for row in (1, 30):
            np.testing.assert_array_equal(got[row, 0], own[row])

    @pytest.mark.parametrize("model,data", [(2, 4), (4, 2)],
                             ids=["8-a-shard", "4-a-shard"])
    def test_sixteen_bf16_heads_over_the_model_axis(self, model, data):
        """Under ``mp_size`` 2 | 4 a device holds 8 | 4 of the heads and
        its grid step takes them all, as before: the same bits again."""
        from deepspeed_tpu.comm import MeshSpec, build_mesh
        args, kw = self._cells_case("stacked")
        mesh = build_mesh(MeshSpec(model=model, data=data))
        tuning.clear_last_dispatch()
        got = jax.jit(lambda *a: paged_attention(
            *a, impl="kernel", mesh=mesh, **kw))(*args)
        rec = tuning.last_dispatch(KERNEL)["page128"]
        assert (rec["head_block"], rec["rows"], rec["model_shards"]) == (
            16 // model, 16 // model, model)
        with self._at_eight(rec["block_k"]):
            eight = jax.jit(lambda *a: paged_attention(
                *a, impl="kernel", **kw))(*args)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(eight))

    @pytest.mark.parametrize("dtype", ["f32", "int8"])
    def test_float32_products_of_sixteen_heads_keep_eight_a_step(self,
                                                                 dtype):
        """A float32 pool and an int8 one, asked for sixteen heads a
        step: the float32 arm is held to eight rows (Mosaic aborts on its
        cut of the boolean mask past the eighth), whatever is asked."""
        kp, vp = _pool(seed=16, heads=16)
        q, kn, vn = _operands(seed=17, heads=16)
        ptab = jnp.asarray([[1, 2, 3], [4, 0, 0], [0, 0, 0]], jnp.int32)
        lens = jnp.asarray([2 * PAGE + 5, 3, 0], jnp.int32)
        scales = {}
        if dtype == "int8":
            kp, vp, ks, vs = _quantize_pool(kp, vp)
            scales = {"k_scale": ks, "v_scale": vs}
        run = lambda impl: paged_attention(
            q, kp, vp, ptab, lens, kn, vn, impl=impl, head_block=16,
            **scales)
        tuning.clear_last_dispatch()
        got = run("kernel")
        rec = tuning.last_dispatch(KERNEL)["page%d" % PAGE]
        assert (rec["impl"], rec["head_block"], rec["rows"],
                rec["products"]) == ("kernel", 8, 8, "float32")
        dense = run("dense")
        np.testing.assert_allclose(np.asarray(got), np.asarray(dense),
                                   atol=1e-4, rtol=1e-4)

    def test_kernel_knob_validation(self):
        with pytest.raises(ValueError, match="kernel"):
            PagingConfig(page_len=16, kernel="maybe").validate(128)
        for mode in ("auto", "on", "off"):
            PagingConfig(page_len=16, kernel=mode).validate(128)


# ---------------------------------------------------------------------------
# engine integration: kernel path == gather path, transient == 0
# ---------------------------------------------------------------------------

def _model(vocab, **kw):
    cfg = GPTConfig(vocab_size=vocab, max_seq_len=128, d_model=32,
                    n_layers=2, n_heads=2, dtype=jnp.float32,
                    scan_layers=kw.pop("scan_layers", True), **kw)
    m = GPT(cfg)
    params = m.init(jax.random.PRNGKey(0),
                    jnp.ones((1, 8), jnp.int32))["params"]
    return m, params


def _drive(m, params, prompts, outs, kernel):
    eng = ServingEngine(m, params, ServingConfig(
        num_slots=3, max_len=128, seed=0,
        paging=PagingConfig(page_len=16, prefill_chunk=16, kernel=kernel)))
    reqs = [eng.submit(p, max_new_tokens=o) for p, o in zip(prompts, outs)]
    eng.run()
    return eng, [list(r.output_tokens) for r in reqs]


class TestEngineKernelPath:
    VARIANTS = {
        "gpt2": {},
        "gptj": dict(rotary=True, learned_pos=False, parallel_residual=True,
                     shared_parallel_ln=True, attn_use_bias=False,
                     rotary_dim=8),
        "bloom": dict(alibi=True, learned_pos=False, embed_ln=True),
    }

    # gpt2 stays in the time-boxed tier-1 lane; the rotary/alibi
    # variants and the unstacked sweep ride the CI unit matrix only
    # (pytest.ini slow convention — engine drives cost ~10s each)
    @pytest.mark.parametrize("arch", [
        pytest.param("gpt2", marks=pytest.mark.slow),
        pytest.param("gptj", marks=pytest.mark.slow),
        pytest.param("bloom", marks=pytest.mark.slow),
    ])
    def test_kernel_on_matches_gather_and_generate(self, arch):
        """serving.paging.kernel='on' produces the same greedy tokens as
        the PR-6 gather path AND per-request generate() — on the rotary
        and ALiBi variants too (their position handling rides through
        the kernel's in-kernel bias)."""
        vocab = {"gpt2": 131, "gptj": 137, "bloom": 139}[arch]
        m, params = _model(vocab, **self.VARIANTS[arch])
        r = np.random.RandomState(23)
        prompts = [r.randint(1, vocab, size=int(n)).astype(np.int32)
                   for n in r.randint(3, 40, size=6)]
        outs = [int(o) for o in r.randint(2, 6, size=6)]
        eng_off, toks_off = _drive(m, params, prompts, outs, "off")
        eng_on, toks_on = _drive(m, params, prompts, outs, "on")
        assert not eng_off._paged.use_kernel and eng_on._paged.use_kernel
        assert toks_on == toks_off
        for p, o, t in zip(prompts, outs, toks_on):
            ref = np.asarray(generate(m, params, p[None], max_new_tokens=o,
                                      temperature=0.0, max_len=128)
                             )[0, len(p):]
            assert list(ref) == t, arch

    @pytest.mark.slow
    def test_unstacked_layers_kernel(self):
        m, params = _model(149, scan_layers=False)
        r = np.random.RandomState(29)
        prompts = [r.randint(1, 149, size=int(n)).astype(np.int32)
                   for n in r.randint(3, 30, size=4)]
        outs = [3] * 4
        _, toks_off = _drive(m, params, prompts, outs, "off")
        _, toks_on = _drive(m, params, prompts, outs, "on")
        assert toks_on == toks_off

    @pytest.mark.slow
    def test_transient_gauge_zero_and_compile_once(self):
        """The acceptance figures: decode_gather_transient_bytes == 0 on
        the kernel path (derived AND the live gauge), kernel decode
        still compiles exactly ONCE, and the kernel-off manager keeps
        the honest nonzero figure."""
        from deepspeed_tpu.observability.memory import get_accountant
        m, params = _model(151)
        r = np.random.RandomState(31)
        prompts = [r.randint(1, 151, size=10).astype(np.int32)
                   for _ in range(4)]
        before = _paged_decode_jit._cache_size()
        eng, _ = _drive(m, params, prompts, [4] * 4, "on")
        assert _paged_decode_jit._cache_size() == before + 1
        assert eng._paged.decode_gather_transient_bytes() == 0
        gauge = get_accountant().registry.gauge("mem/decode_gather_transient")
        assert gauge.value == 0
        assert eng.memory_report()["decode_gather_transient_bytes"] == 0
        assert eng.memory_report()["paged_kernel"] is True
        eng_off, _ = _drive(m, params, prompts, [4] * 4, "off")
        assert eng_off._paged.decode_gather_transient_bytes() > 0

    def test_auto_resolves_off_on_cpu(self):
        """'auto' keeps CPU (interpret) runs on the gather path — the
        bit-reproducibility default; the kernel turns on only where it
        is the measured win (real TPU, aligned page_len)."""
        m, params = _model(157)
        eng = ServingEngine(m, params, ServingConfig(
            num_slots=2, max_len=128, seed=0,
            paging=PagingConfig(page_len=16)))
        assert not eng._paged.use_kernel


@pytest.mark.slow
def test_kernels_sweep_writes_paged_entries(tmp_path):
    """`ds_tpu_bench kernels --kernel paged_attention` writes tuning
    entries in the shared artifact format the dispatch consumes."""
    from benchmarks.kernel_tuning import main as kernels_main
    out = str(tmp_path / "paged_tuning.json")
    rc = kernels_main(["--kernel", "paged_attention", "--slots", "2",
                       "--max-pages", "2", "--head-dim", "16", "--heads",
                       "2", "--page-len", "16", "--trials", "1",
                       "--max-candidates", "2", "--out", out])
    assert rc == 0
    art = tuning.load_artifact(out)
    (key, entry), = art["entries"].items()
    assert key.startswith("paged_attention/page16/")
    assert "block_k" in entry and "head_block" in entry and "ms" in entry


def test_paged_attention_lints_clean():
    """The satellite CI gate: the paged kernel ships with ZERO lint
    findings — no baseline, no suppressions."""
    from deepspeed_tpu.analysis.cli import main as lint_main
    assert lint_main([os.path.join(REPO_ROOT, "deepspeed_tpu", "ops",
                                   "pallas", "paged_attention.py"),
                      "-q"]) == 0
