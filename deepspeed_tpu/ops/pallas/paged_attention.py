"""Pallas paged decode-attention kernel (page-table-direct KV attention).

The serving engine's paged KV cache (serving/paging/) stores every
slot's K/V as fixed-size pages in a global pool
``[num_pages, h, d, page_len]`` (K^T layout) addressed by a dense
``[num_slots, max_pages]`` int32 page table. Before this kernel, the
jitted decode step *gathered* each slot's pages into the classic
contiguous ``[slots, h, d, max_pages * page_len]`` view and ran the
contiguous decode kernel over it — correct, but the gathered view is
XLA-managed scratch scaling with ``slots x max_len``
(``decode_gather_transient_bytes``), which silently caps the paged
density win at high slot counts.

This kernel consumes the page table DIRECTLY: grid ``(slot,
head_block)``; each grid step walks the slot's valid pages with
double-buffered ``make_async_copy`` DMAs — the physical page index
comes from the scalar-prefetched page table, so pages stream
HBM->VMEM *in place*, one page (or a tuned multi-page block) at a
time. Flash-style online softmax (the ``_common.online_softmax_block``
inner loop shared with ``decode_attention``) accumulates partial
attention per page block; no contiguous per-slot view ever
materializes, and DMA traffic scales with the VALID length, not the
allocated table width.

Grouped-query heads: the grid walks the pool's K/V heads, and a step
holds ``head_block`` of them with every query head that reads them, one
row each (``rows`` in the dispatch record). A step's cost is mostly
fixed — its first block's fetch, which nothing hides, and the current
token's fold — so a step takes as many K/V heads as the shape's entry of
the tuning table asks for and ``step_head_block`` allows: all four of
Falcon-H1's (20 rows, grid ``(slots, 1)``), all sixteen of the
ungrouped GPT-2 1.3B and OLMoE pools (16 rows, grid ``(slots, 1)``: PR
51, each DMA block then one page of 128 tokens) and all ten of
Phi-4-mini-flash's cached heads with their four query rows each (40
rows, grid ``(slots, 1)``, one page a DMA block: PR 58 — five and ten
are answered on this arm alone, to an entry or a caller that asks for
them; at the constants ten heads go two a step, as they did) where the
pool is bf16, eight rows' worth where the products are float32 (LFM2's
float32 pool: two K/V heads of a group of four; a float32 pool of ten
heads two), eight heads where nobody swept the shape
(``DEFAULT_HEAD_BLOCK``). A row's arithmetic does not depend on
which heads share its step: at one ``block_k`` every head block gives
the same bits (on the chip too: 1, 2, 4 in PR 49, 1, 2, 5, 10 at four
blocks in PR 58's sweep).

A scanned model's pool is layer-stacked, ``[L, num_pages, h, d,
page_len]``. It reaches this kernel whole — the model's layer scan
broadcasts it and passes the layer index — and the DMA source is
``pool.at[layer, page, head_block]``: no layer's slice of the pool is
ever cut out to be the operand (on the v5e that cut was a 168 MB copy
per layer for K and again for V, every decode step). A 4-D pool is the
same kernel over a stack of one; the two are told apart by rank.

All matmuls run in the pool's dtype (bf16 hot path) with fp32
accumulation via preferred_element_type — the same bf16-in/fp32-acc
contract as the flash kernels and the XLA einsum path. Nothing is given
up for it (``_common.online_softmax_block``): bf16 x bf16 score products
are exact in float32, the softmax scale and ALiBi go on the float32
scores, and the float32 probabilities meet a bf16 V block as their three
bf16 terms. A block is never widened: the ``0 x NaN`` guard on V is a
select on the block's bits. A float32 pool's products, and an int8
pool's dequantised blocks, stay float32; the dispatch record
(``tuning.record_dispatch``) names the type under ``products``.

The current decode step's K/V is NOT in the pool yet (the engine
appends it in place after the step, quantized when the pool is int8): it
arrives as separate full-precision ``k_new``/``v_new`` operands and is
folded into the softmax as a final single-column update — bias 0 under
ALiBi (distance 0), always valid, so every row's normalizer is > 0.

int8 KV pages: when ``k_scale``/``v_scale`` page pools are given
(``[num_pages, h, 1, page_len]`` fp32 — one scale per head per token,
stored page-shaped; inference/cache.py quantizes on scatter), the page
DMAs move int8 bytes (HALF the bandwidth of bf16 — decode attention is
cache-bandwidth-bound) plus the small scale planes, and dequantization
happens in VMEM inside the page loop, right before the matmul.

Block sizes resolve through the shape-keyed tuning cache
(``ops/pallas/tuning.py``; ``bin/ds_tpu_bench kernels --kernel
paged_attention`` sweeps them): key
``paged_attention/page<page_len>/sq<slots>_sk<table_tokens>_d<d>_...``,
entries carry ``block_k`` (tokens per DMA block — a page_len multiple;
pages_per_block = block_k / page_len) and ``head_block``.

Caches whose ``page_len`` is not a 128 multiple cannot tile on real
TPU (Mosaic minor-dim alignment) and take a fused-dense jnp fallback
with IDENTICAL semantics; serving defaults page_len to 128 so hardware
always hits the kernel. Inference-only (no custom_vjp).
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from jax.sharding import PartitionSpec as P

from . import tuning
from ._common import NEG_INF
from ._common import block_query as _block_query
from ._common import interpret_mode as _interpret
from ._common import (log_fallback_on_tpu, model_axis_size, over_model_axis,
                      pick_head_block, products_dtype)
from ._common import online_softmax_block as _attend_block
from ._common import read_slopes as _read_slopes

DEFAULT_BLOCK_TOKENS = 512
DEFAULT_HEAD_BLOCK = 8
# query-head rows of one grid step where the products are float32
# (``step_head_block``), and the query heads of one K/V head on either arm
# (``kernel_ok``: no group over eight has been compiled)
MAX_ROWS = 8

KERNEL = "paged_attention"


def step_head_block(kv_heads, group, pool_dtype, want):
    """K/V heads of one grid step: ``pick_head_block`` of ``want``, each
    head with its ``group`` query heads, one row each. Where the products
    are float32 (a float32 pool, an int8 one) a step is held to MAX_ROWS
    rows: Mosaic (jax 0.9.0) aborts the process (``limits[i] <= dim(i)``)
    on the float32 arm's one-row cut of the boolean column mask at a row
    past the eighth (``_common.online_softmax_block``:
    ``valid[h * group:h * group + 1]``), so a group of four there takes
    two K/V heads a step. The narrow arm never cuts that mask (its guard
    is a select on the block's bits): a bf16 pool's step takes every K/V
    head the head block allows, 20 rows at five query heads on each of
    four, 16 at sixteen ungrouped heads where ``want`` is 16 — and, on
    this arm alone, five or ten of ten heads where ``want`` is 5 or 10
    (PR 58: 20 or 40 rows at a group of four; a float32 ten-head pool
    keeps its 2)."""
    narrow = products_dtype(pool_dtype) != jnp.float32
    if not narrow:
        want = min(want, max(1, MAX_ROWS // group))
    return pick_head_block(kv_heads, want, narrow)


def _fold_current_token(q, kn, vn, m_ref, l_ref, acc_ref):
    """Final online-softmax update for the current token's K/V — one
    always-valid column at the query's own position (ALiBi bias 0), so
    ``l`` ends >= exp(0) > 0 for every row including empty slots."""
    s = jnp.sum(q * kn, axis=-1, keepdims=True)              # [hb, 1]
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s)
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                                   # [hb, 1]
    l_ref[...] = corr * l_ref[...] + p
    acc_ref[...] = corr * acc_ref[...] + p * vn
    m_ref[...] = m_new


def _dma_kernel(len_ref, ptab_ref, slopes_ref, layer_ref, q_ref, kn_ref,
                vn_ref, *refs, scale, page_len, ppb, hb, alibi, quant,
                max_pages, group):
    if quant:
        (kp_hbm, vp_hbm, ksp_hbm, vsp_hbm, o_ref,
         kbuf0, vbuf0, kbuf1, vbuf1, ksb0, vsb0, ksb1, vsb1,
         sem, m_ref, l_ref, acc_ref) = refs
        bufs = ((kbuf0, vbuf0, ksb0, vsb0), (kbuf1, vbuf1, ksb1, vsb1))
    else:
        (kp_hbm, vp_hbm, o_ref, kbuf0, vbuf0, kbuf1, vbuf1,
         sem, m_ref, l_ref, acc_ref) = refs
        bufs = ((kbuf0, vbuf0), (kbuf1, vbuf1))
    b, hi = pl.program_id(0), pl.program_id(1)
    length = len_ref[b]
    layer = layer_ref[0]
    bt = ppb * page_len
    nb = pl.cdiv(length, bt)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    slopes = (_read_slopes(slopes_ref, hi * hb * group, hb * group)
              if alibi else None)

    def copies(j, slot):
        """The slot's page DMAs for block ``j``: ``ppb`` physical pages
        looked up in the prefetched table. Logical indices past the
        table (a ragged last block) clamp to the last entry — always a
        VALID physical page (unowned entries hold the null page), whose
        columns the ``col < length`` mask discards."""
        descs = []
        for i in range(ppb):
            logical = jnp.minimum(j * ppb + i, max_pages - 1)
            phys = ptab_ref[b, logical]
            dst = pl.ds(i * page_len, page_len)
            pairs = [(kp_hbm, bufs[slot][0], 0), (vp_hbm, bufs[slot][1], 1)]
            if quant:
                pairs += [(ksp_hbm, bufs[slot][2], 2),
                          (vsp_hbm, bufs[slot][3], 3)]
            for src, buf, ch in pairs:
                descs.append(pltpu.make_async_copy(
                    src.at[layer, phys, hi], buf.at[:, :, dst],
                    sem.at[slot, ch, i]))
        return descs

    # the prologue must not start copies a zero-block row never waits:
    # leaked semaphore signals would satisfy the NEXT grid step's wait()
    # while its own DMA is still in flight (real-TPU hazard; interpret
    # mode doesn't model semaphores)
    @pl.when(nb > 0)
    def _first_copies():
        for c in copies(0, 0):
            c.start()

    def body(j, carry):
        slot = jax.lax.rem(j, 2)

        for parity in (0, 1):
            @pl.when((slot == parity) & (j + 1 < nb))
            def _prefetch():
                for c in copies(j + 1, 1 - parity):
                    c.start()

        for parity in (0, 1):
            @pl.when(slot == parity)
            def _compute():
                for c in copies(j, parity):
                    c.wait()
                q = _block_query(q_ref[0, 0], scale, kp_hbm.dtype)
                if quant:
                    kb, vb, ksb, vsb = bufs[parity]
                    kblk = kb[...].astype(jnp.float32) * ksb[...]
                    vblk = vb[...].astype(jnp.float32) * vsb[...]
                else:
                    kblk, vblk = bufs[parity]
                # pool pages EXCLUDE the current token: valid cols <
                # length, query position = length (folded in below)
                _attend_block(q, kblk, vblk, j * bt, length, length,
                              slopes, m_ref, l_ref, acc_ref, scale=scale,
                              hb=hb, alibi=alibi, group=group)
        return carry

    jax.lax.fori_loop(0, nb, body, 0)
    q = q_ref[0, 0].astype(jnp.float32) * scale
    _fold_current_token(q, kn_ref[0, 0].astype(jnp.float32),
                        vn_ref[0, 0].astype(jnp.float32), m_ref, l_ref,
                        acc_ref)
    o_ref[0, 0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def _paged_dma(q_bhd, kp, vp, ptab, lengths, kn, vn, ks, vs, slopes, layer,
               *, scale, page_len, ppb, hb, alibi):
    b, heads, d = q_bhd.shape
    n_layers, num_pages, kv_heads = kp.shape[:3]
    max_pages = ptab.shape[1]
    # grouped-query attention: the grid walks the pool's K/V heads, and
    # a step takes the whole group of query heads that read its ``hb``:
    # a page is fetched once for all of them
    group = heads // kv_heads
    nhb = kv_heads // hb
    rows = hb * group
    quant = ks is not None
    # the whole stacked pool is the operand (memory_space ANY: it stays in
    # HBM, nothing is copied for the call); the kernel picks the layer
    split = lambda x: x.reshape(n_layers, num_pages, nhb, hb, x.shape[-2],
                                page_len)
    pools = [split(kp), split(vp)]
    if quant:
        pools += [split(ks), split(vs)]
    bt = ppb * page_len
    kv_buf = lambda: pltpu.VMEM((hb, d, bt), kp.dtype)
    scratch = [kv_buf(), kv_buf(), kv_buf(), kv_buf()]
    if quant:
        sc_buf = lambda: pltpu.VMEM((hb, 1, bt), jnp.float32)
        scratch += [sc_buf(), sc_buf(), sc_buf(), sc_buf()]
    scratch += [
        pltpu.SemaphoreType.DMA((2, 4 if quant else 2, ppb)),
        pltpu.VMEM((rows, 1), jnp.float32),
        pltpu.VMEM((rows, 1), jnp.float32),
        pltpu.VMEM((rows, d), jnp.float32),
    ]
    # per-token operands ride as [B, heads/hb, hb, d] so the (hb, d)
    # tile is the array's own last two dims: a (1, hb, d) block of
    # [B, H, d] is refused by the Mosaic lowering unless hb % 8 == 0
    # or hb == H
    # (k_new / v_new arrive one row a QUERY head, as q does)
    tok_spec = lambda: pl.BlockSpec((1, 1, rows, d),
                                    lambda bi, hi, *_: (bi, hi, 0, 0))
    tok = lambda x: x.reshape(b, nhb, rows, d)
    out = pl.pallas_call(
        functools.partial(_dma_kernel, scale=scale, page_len=page_len,
                          ppb=ppb, hb=hb, alibi=alibi, quant=quant,
                          max_pages=max_pages, group=group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, nhb),
            in_specs=[tok_spec(), tok_spec(), tok_spec()]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
            out_specs=tok_spec(),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((b, nhb, rows, d), q_bhd.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=_interpret(),
    )(lengths, ptab, slopes, layer, tok(q_bhd), tok(kn), tok(vn), *pools)
    return out.reshape(b, heads, d)


def _paged_dense(q_bhd, kp, vp, ptab, lengths, kn, vn, ks, vs, slopes,
                 layer, *, scale, alibi):
    """jnp fallback with IDENTICAL semantics for pools the kernel cannot
    tile (page_len not a 128 multiple on real TPU) — and the reference
    the kernel parity suite checks against. Gathers the table's pages
    (XLA scratch — exactly what the kernel path eliminates), attends
    cols < length plus the current token as one extra column."""
    b, heads, d = q_bhd.shape
    page_len = kp.shape[-1]
    at = (layer[0], ptab)
    gk = kp[at]                                    # [B, M, H, d, p]
    gv = vp[at]
    if ks is not None:
        gk = gk.astype(jnp.float32) * ks[at]
        gv = gv.astype(jnp.float32) * vs[at]
    if gk.shape[2] != heads:                       # grouped-query heads
        gk = jnp.repeat(gk, heads // gk.shape[2], axis=2)
        gv = jnp.repeat(gv, heads // gv.shape[2], axis=2)
    m = ptab.shape[1]
    s_tot = m * page_len
    k_all = gk.transpose(0, 2, 3, 1, 4).reshape(b, heads, d, s_tot)
    v_all = gv.transpose(0, 2, 3, 1, 4).reshape(b, heads, d, s_tot)

    qf = q_bhd.astype(jnp.float32) * scale
    logits = jnp.einsum("bhd,bhdk->bhk", qf, k_all.astype(jnp.float32))
    col = jnp.arange(s_tot)[None, None, :]
    ln = lengths[:, None, None]
    if alibi:
        logits = logits + slopes[None, :, None] * (col - ln)
    logits = jnp.where(col < ln, logits, NEG_INF)
    s_cur = jnp.einsum("bhd,bhd->bh", qf,
                       kn.astype(jnp.float32))[..., None]    # [B, H, 1]
    probs = jax.nn.softmax(jnp.concatenate([logits, s_cur], axis=-1),
                           axis=-1)
    # unowned/null-page columns may hold garbage (NaN poison in tests):
    # 0-probability x NaN = NaN, so zero masked V columns explicitly
    v_hist = jnp.where(col[:, :, None, :] < ln[:, :, None, :],
                       v_all.astype(jnp.float32), 0.0)
    out = jnp.einsum("bhk,bhdk->bhd", probs[..., :s_tot], v_hist)
    out = out + probs[..., s_tot:] * vn.astype(jnp.float32)
    return out.astype(q_bhd.dtype)


def paged_attention(q, k_pages, v_pages, page_table, lengths, k_new, v_new,
                    *, layer=None, softmax_scale=None, alibi_slopes=None,
                    k_scale=None, v_scale=None, block_tokens=None,
                    head_block=None, impl=None, mesh=None):
    """Single-token attention straight over a paged KV pool.

    q: [B, 1, H, d] (or [B, H, d]) — the current token's queries.
    k_pages, v_pages: [num_pages, H, d, page_len] page pool (K^T
        layout); int8 when ``k_scale``/``v_scale`` are given. Or the
        layer-stacked pool ``[L, num_pages, H, d, page_len]`` of a
        scanned model, with ``layer`` naming the layer to attend over:
        the pool is never sliced, the kernel indexes it. The two forms
        are told apart by rank.
    layer: int32 scalar (traced inside the layer scan) — required with
        a stacked pool, refused with a 4-D one.
    page_table: [B, max_pages] int32 — physical page per logical page;
        unowned entries hold the null page (always safe to read).
    lengths: [B] int32 — tokens already IN the pool per row (the
        current token is NOT among them; it attends via ``k_new``).
    k_new, v_new: [B, H, d, 1] (or [B, H, d]) — the current token's
        K/V in compute precision (quantized on scatter AFTER the step).
    k_scale, v_scale: optional [num_pages, H, 1, page_len] fp32 per-
        token-per-head scale planes of an int8 pool (stacked like the
        pool when it is).
    impl: None (auto), "kernel", or "dense" — parity/testing override.
    mesh: the caller's mesh when its ``model`` axis splits the heads
        (tensor-parallel serving): the kernel runs once per head shard.
        None = one unpartitioned call.

    Returns [B, 1, H, d] (or [B, H, d], matching q's rank): softmax
    attention over the row's ``lengths`` pool tokens plus the current
    token (``lengths + 1`` total; a row with length 0 attends only
    itself — never NaN).
    """
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    b, one, heads, d = q.shape
    if one != 1:
        raise ValueError(f"paged_attention is single-token (q_len 1), "
                         f"got {one}")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be given together")
    stacked = k_pages.ndim == 5
    if stacked != (layer is not None):
        raise ValueError(
            "paged_attention takes `layer` with a stacked [L, pages, H, d, "
            f"page_len] pool and only then (pool rank {k_pages.ndim}, layer "
            f"{'given' if layer is not None else 'missing'})")
    if not stacked:
        # one kernel for both forms: a 4-D pool is a stack of one layer
        # (a reshape the compiler lowers to nothing)
        layer = 0
        k_pages, v_pages = k_pages[None], v_pages[None]
        if k_scale is not None:
            k_scale, v_scale = k_scale[None], v_scale[None]
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    page_len = k_pages.shape[-1]
    max_pages = page_table.shape[1]
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(d)

    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (b,))
    page_table = jnp.asarray(page_table, jnp.int32)
    kv_heads = k_pages.shape[2]
    if heads % kv_heads:
        raise ValueError(f"{heads} query heads over a pool of {kv_heads} "
                         "K/V heads: not a whole group each")
    kn = k_new.reshape(b, kv_heads, d)
    vn = v_new.reshape(b, kv_heads, d)
    if kv_heads != heads:
        # grouped-query attention: query head i reads K/V head
        # i // group. The pool keeps kv_heads; the current token's K/V
        # (one column, [B, heads, d]) rides one row a query head
        kn = jnp.repeat(kn, heads // kv_heads, axis=1)
        vn = jnp.repeat(vn, heads // kv_heads, axis=1)
    alibi = alibi_slopes is not None
    slopes = (jnp.asarray(alibi_slopes, jnp.float32) if alibi
              else jnp.zeros((heads,), jnp.float32))
    q_bhd = jnp.swapaxes(q, 1, 2)[:, :, 0, :]                # [B, H, d]

    # block resolution through the shape-keyed tuning cache: block_k is
    # tokens per DMA block (a page_len multiple), head_block the grid's
    # head tile — constants only on a full miss
    structure = f"page{page_len}"
    entry, key, source = tuning.lookup(
        KERNEL, structure, sq=b, sk=max_pages * page_len, d=d,
        dtype=k_pages.dtype, causal=True)
    bt = int(entry.get("block_k") or block_tokens or DEFAULT_BLOCK_TOKENS)
    tp = model_axis_size(mesh, kv_heads)
    group = heads // kv_heads
    hb = step_head_block(
        kv_heads // tp, group, k_pages.dtype,
        int(entry.get("head_block") or head_block or DEFAULT_HEAD_BLOCK))
    ppb = max(1, min(bt // page_len, max_pages))

    aligned = page_len % 128 == 0 or _interpret()
    kernel_ok = aligned and group <= MAX_ROWS
    use_kernel = kernel_ok if impl is None else impl == "kernel"
    if impl == "kernel" and not kernel_ok:
        raise ValueError(
            f"paged_attention kernel needs page_len % 128 == 0 on TPU "
            f"(got {page_len}) and at most {MAX_ROWS} query heads a K/V "
            f"head (got {group}); use page_len=128 or impl='dense'")
    reason = None
    if not use_kernel:
        reason = ("impl='dense' requested" if impl == "dense"
                  else f"page_len {page_len} not a multiple of 128"
                  if not aligned else
                  f"{group} query heads a K/V head, over {MAX_ROWS}")
        log_fallback_on_tpu(KERNEL, "dense", reason)
    tuning.record_dispatch(
        KERNEL, structure, key, source, block_k=ppb * page_len,
        head_block=hb, rows=hb * group,
        impl="kernel" if use_kernel else "dense", reason=reason,
        model_shards=tp,
        products=(products_dtype(k_pages.dtype).name if use_kernel
                  else "float32"))
    if use_kernel:
        run = functools.partial(_paged_dma, scale=scale, page_len=page_len,
                                ppb=ppb, hb=hb, alibi=alibi)
    else:
        run = functools.partial(_paged_dense, scale=scale, alibi=alibi)
    # positional: q, kp, vp, ptab, lengths, kn, vn, ks, vs, slopes, layer
    # — the head dim (axis 1 of the per-token operands, axis 2 of the
    # stacked pools, axis 0 of slopes) splits over the model axis
    heads_1 = P(None, "model")
    pool_spec = P(None, None, "model")
    quant_spec = pool_spec if k_scale is not None else None
    out = over_model_axis(
        run, mesh,
        in_specs=(heads_1, pool_spec, pool_spec, P(), P(), heads_1, heads_1,
                  quant_spec, quant_spec, P("model"), P()),
        out_specs=heads_1,
    )(q_bhd, k_pages, v_pages, page_table, lengths, kn, vn, k_scale,
      v_scale, slopes, layer)
    out = out[:, None]                                       # [B, 1, H, d]
    return out[:, 0].reshape(b, heads, d) if squeeze else out
