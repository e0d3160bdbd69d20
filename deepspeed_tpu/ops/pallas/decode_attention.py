"""Pallas decode-attention kernel (KV-cache single-token attention).

TPU-native replacement for THE inference kernel of DS-Inference:
``softmax_context`` (reference: csrc/transformer/inference/csrc/
pt_binding.cpp:1197-1244 + softmax.cu) — one query token per (batch,
head) attends to the valid prefix of a preallocated KV cache.

Design (shaped by Mosaic's constraint that dynamically-indexed slices
need a 128-aligned minor dim):

- **transposed caches**: K/V live as [batch, heads, head_dim, max_len]
  ("K^T layout") so the minor dim is the sequence — any head_dim (64 of
  GPT-2 or 128 of BLOOM/LLaMA class) tiles cleanly, q·K is a direct
  [1,d]x[d,Bk] MXU matmul, and HBM block slices are 128-aligned.
- **manual-DMA kernel**: grid (batch, head_blocks); the kernel streams
  K/V blocks HBM->VMEM with double-buffered ``make_async_copy`` inside a
  ``fori_loop`` whose trip count is ``ceil(length / block_k)`` — DMA
  traffic AND compute scale with the *valid* cache length, not the
  allocated max_len (the reference kernel reads only ``total_count``
  history the same way). Two statically-addressed buffer pairs switched
  by ``pl.when`` on loop parity (Mosaic cannot dynamically index a
  buffer stack with a sub-128 lane dim). Measured on v5e at
  B4/H32/S2048/D128: ~par with the dense XLA path at full cache,
  ~2.5x faster at half length.
- the causal/length mask lives IN the kernel (``col < length`` from a
  scalar-prefetched per-batch length vector) — no [B,H,1,S] mask tensor
  is ever materialized (the dense fallback builds one per decode step).
- ALiBi (BLOOM serving) computed in-kernel from per-head slopes:
  ``slope * (col - (length-1))``, matching models/layers.py alibi_bias.
- caches whose max_len is not a multiple of 128 take a fused-dense jnp
  fallback (kernel semantics, XLA codegen) — the generation path rounds
  its cache allocation up to 128 so serving always hits the kernel.
- grouped query heads and the blocks, as the paged kernel has them: the
  grid walks the cache's K/V heads and a step holds ``head_block`` of
  them with every query head that reads them, one row each (``rows`` in
  the dispatch record); ``block_k`` and ``head_block`` resolve through
  the shape-keyed tuning table (``tuning.lookup``: key
  ``decode_attention/dma/sq<batch>_sk<max_len>_d<d>_<dtype>_causal``),
  then the caller's, then ``DEFAULT_BLOCK_K`` and ``DEFAULT_HEAD_BLOCK``,
  and the head block goes through ``paged_attention.step_head_block``.
  One shape has an entry: Phi-4-mini-flash's window rings ``[64, 10,
  128, 512]`` bf16 take all ten cached heads a step (40 rows) in blocks
  of 128 tokens, swept and run compiled on the v5e in PR 58
  (``bin/ds_tpu_bench kernels --kernel decode_attention``); every other
  caller — ``SelfAttention``'s dense-cache decode, ``generate()`` —
  misses the table and lowers as it did, 512 tokens and eight heads
  (two of ten, four of twelve).

Inference-only: no custom_vjp (the reference kernel is fwd-only too).
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
                      products_dtype)
from ._common import online_softmax_block as _attend_block
from ._common import read_slopes as _read_slopes
from .paged_attention import step_head_block

DEFAULT_BLOCK_K = 512
DEFAULT_HEAD_BLOCK = 8

KERNEL = "decode_attention"


def _dma_kernel(len_ref, slopes_ref, q_ref, k_hbm, v_hbm, o_ref,
                kbuf0, vbuf0, kbuf1, vbuf1, sem, m_ref, l_ref, acc_ref,
                *, scale, block_k, hb, alibi, group=1):
    b, hi = pl.program_id(0), pl.program_id(1)
    length = len_ref[b]
    nb = pl.cdiv(length, block_k)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    slopes = (_read_slopes(slopes_ref, hi * hb * group, hb * group)
              if alibi else None)
    bufs = ((kbuf0, vbuf0), (kbuf1, vbuf1))

    def copies(j, slot):
        start = j * block_k
        kb, vb = bufs[slot]
        ck = pltpu.make_async_copy(
            k_hbm.at[b, hi, :, :, pl.ds(start, block_k)], kb, sem.at[slot, 0])
        cv = pltpu.make_async_copy(
            v_hbm.at[b, hi, :, :, pl.ds(start, block_k)], vb, sem.at[slot, 1])
        return ck, cv

    # the prologue must not start copies a zero-block row never waits:
    # leaked semaphore signals would satisfy the NEXT grid step's wait()
    # while its own DMA is still in flight (real-TPU hazard; interpret
    # mode doesn't model semaphores)
    @pl.when(nb > 0)
    def _first_copies():
        ck, cv = copies(0, 0)
        ck.start()
        cv.start()

    def body(j, carry):
        slot = jax.lax.rem(j, 2)

        for parity in (0, 1):
            @pl.when((slot == parity) & (j + 1 < nb))
            def _prefetch():
                nk, nv = copies(j + 1, 1 - parity)
                nk.start()
                nv.start()

        for parity in (0, 1):
            @pl.when(slot == parity)
            def _compute():
                wk, wv = copies(j, parity)
                wk.wait()
                wv.wait()
                q = _block_query(q_ref[0, 0], scale, k_hbm.dtype)
                kb, vb = bufs[parity]
                _attend_block(q, kb, vb, j * block_k, length, length - 1,
                              slopes, m_ref, l_ref, acc_ref, scale=scale,
                              hb=hb, alibi=alibi, group=group)
        return carry

    jax.lax.fori_loop(0, nb, body, 0)
    # length <= 0 rows (empty serving slots) ran zero blocks: l stays 0 and
    # acc/l would be NaN. Select zeros instead — valid rows always have
    # l >= 1 (the max-score column contributes exp(0)), so this is a no-op
    # for them.
    l = l_ref[...]
    safe = acc_ref[...] / jnp.where(l > 0.0, l, 1.0)
    o_ref[0, 0] = jnp.where(l > 0.0, safe, 0.0).astype(o_ref.dtype)


def _decode_dma(q_bhd, k, v, lengths, slopes, *, scale, block_k, hb, alibi):
    b, heads, d = q_bhd.shape
    kv_heads, s = k.shape[1], k.shape[3]
    # grouped-query heads, as the paged kernel takes them: the grid walks
    # the cache's K/V heads and a step holds every query head that reads
    # its ``hb``, one row each
    group = heads // kv_heads
    nhb = kv_heads // hb
    rows = hb * group
    kr = k.reshape(b, nhb, hb, d, s)
    vr = v.reshape(b, nhb, hb, d, s)
    kv_buf = lambda: pltpu.VMEM((hb, d, block_k), k.dtype)
    # q/out ride as [B, heads/hb, hb, d] so the (hb, d) tile is the
    # array's own last two dims: a (1, hb, d) block of [B, H, d] is
    # refused by the Mosaic lowering unless hb % 8 == 0 or hb == H
    # (12 heads -> hb 4)
    tok_spec = pl.BlockSpec((1, 1, rows, d),
                            lambda bi, hi, *_: (bi, hi, 0, 0))
    out = pl.pallas_call(
        functools.partial(_dma_kernel, scale=scale, block_k=block_k,
                          hb=hb, alibi=alibi, group=group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, nhb),
            in_specs=[
                tok_spec,
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=tok_spec,
            scratch_shapes=[
                kv_buf(), kv_buf(), kv_buf(), kv_buf(),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, nhb, rows, d), q_bhd.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=_interpret(),
    )(lengths, slopes, q_bhd.reshape(b, nhb, rows, d), kr, vr)
    return out.reshape(b, heads, d)


def _decode_dense(q_bhd, k, v, lengths, slopes, *, scale, alibi):
    """jnp fallback with IDENTICAL semantics for caches the kernel cannot
    tile (max_len not a multiple of 128). XLA fuses the chain; the mask
    still never leaves registers as a [B,H,1,S] tensor thanks to fusion."""
    s = k.shape[3]
    if k.shape[1] != q_bhd.shape[1]:               # grouped-query heads
        k = jnp.repeat(k, q_bhd.shape[1] // k.shape[1], axis=1)
        v = jnp.repeat(v, q_bhd.shape[1] // v.shape[1], axis=1)
    logits = jnp.einsum("bhd,bhdk->bhk", q_bhd.astype(jnp.float32) * scale,
                        k.astype(jnp.float32))
    col = jnp.arange(s)[None, None, :]
    ln = lengths[:, None, None]
    if alibi:
        logits = logits + slopes[None, :, None] * (col - (ln - 1))
    logits = jnp.where(col < ln, logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhk,bhdk->bhd", p, v.astype(jnp.float32))
    # length <= 0 rows have every column masked; softmax degenerates to
    # uniform weights over cache garbage. Match the kernel: emit zeros.
    out = jnp.where(lengths[:, None, None] > 0, out, 0.0)
    return out.astype(q_bhd.dtype)


def decode_attention(q, k, v, length, *, softmax_scale=None,
                     alibi_slopes=None, block_k=None, head_block=None,
                     mesh=None):
    """Single-token KV-cache attention over transposed caches.

    q: [B, 1, H, d] (or [B, H, d]) — the current token's queries (BSHD).
    k, v: [B, H, d, S] — the preallocated cache in K^T layout; or
        [B, H_kv, d, S] with fewer heads than q (grouped-query
        attention: query head i reads K/V head i // (H / H_kv), and a
        head's block is fetched once for its whole group).
    length: int32 scalar or [B] — number of valid cache slots per row
        (the query sits at position length-1). Rows with length <= 0
        (empty serving slots) return zeros.
    alibi_slopes: optional [H] per-head ALiBi slopes (BLOOM).
    block_k, head_block: the tokens of one DMA block and the K/V heads of
        one grid step, where the shape has no entry in the tuning table
        (``tuning.lookup``, key ``decode_attention/dma/sq<B>_sk<S>_d<d>_
        <dtype>_causal``: an entry wins, as in ``paged_attention``);
        neither given, ``DEFAULT_BLOCK_K`` and ``DEFAULT_HEAD_BLOCK``.
        What ``step_head_block`` makes of the head block is what runs.
    mesh: the caller's mesh when its ``model`` axis splits the heads
        (tensor-parallel serving): the kernel runs once per head shard.
        None = one unpartitioned call.

    Returns [B, 1, H, d] (or [B, H, d], matching q's rank).
    """
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    b, one, heads, d = q.shape
    if one != 1:
        raise ValueError(f"decode_attention is single-token (q_len 1), got {one}")
    s = k.shape[3]
    kv_heads = k.shape[1]
    if heads % kv_heads:
        raise ValueError(f"{heads} query heads over a cache of {kv_heads} "
                         "K/V heads: not a whole group each")
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(d)
    tp = model_axis_size(mesh, kv_heads)
    group = heads // kv_heads
    # blocks resolve as the paged kernel's do (entry, caller, constants)
    # and the head block goes through its rule: the two kernels share
    # ``online_softmax_block``
    entry, key, source = tuning.lookup(KERNEL, "dma", sq=b, sk=s, d=d,
                                       dtype=k.dtype, causal=True)
    block_k = int(entry.get("block_k") or block_k or DEFAULT_BLOCK_K)
    hb = step_head_block(
        kv_heads // tp, group, k.dtype,
        int(entry.get("head_block") or head_block or DEFAULT_HEAD_BLOCK))

    lengths = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (b,))
    alibi = alibi_slopes is not None
    slopes = (jnp.asarray(alibi_slopes, jnp.float32) if alibi
              else jnp.zeros((heads,), jnp.float32))
    q_bhd = jnp.swapaxes(q, 1, 2)[:, :, 0, :]                # [B, H, d]

    # block size: a 128-multiple divisor of max_len (Mosaic minor-dim
    # alignment); otherwise the dense fallback
    bk = min(block_k, s)
    bk = (bk // 128) * 128
    while bk >= 128 and s % bk != 0:
        bk -= 128
    use_kernel = bk >= 128
    reason = None
    if use_kernel:
        run = functools.partial(_decode_dma, scale=scale, block_k=bk, hb=hb,
                                alibi=alibi)
    else:
        reason = f"cache length {s} not a multiple of 128"
        log_fallback_on_tpu(KERNEL, "dense", reason)
        run = functools.partial(_decode_dense, scale=scale, alibi=alibi)
    tuning.record_dispatch(
        KERNEL, "dma", key, source, block_k=bk, head_block=hb,
        rows=hb * group, impl="kernel" if use_kernel else "dense",
        reason=reason, model_shards=tp,
        products=(products_dtype(k.dtype).name if use_kernel
                  else "float32"))
    heads_1 = P(None, "model")
    out = over_model_axis(
        run, mesh, in_specs=(heads_1, heads_1, heads_1, P(), P("model")),
        out_specs=heads_1)(q_bhd, k, v, lengths, slopes)
    out = out[:, None]                                       # [B, 1, H, d]
    return out[:, 0].reshape(b, heads, d) if squeeze else out
