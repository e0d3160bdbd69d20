"""Flash attention Pallas kernel (fwd + bwd) with fused bias/mask/dropout.

TPU-native replacement for the reference's fused CUDA attention
(csrc/transformer/softmax_kernels.cu + strided batched gemms orchestrated in
ds_transformer_cuda.cpp, attention dropout in
csrc/transformer/dropout_kernels.cu; inference variant softmax_context in
csrc/transformer/inference/). Design:

- layout: kernels run in BHSD ([batch, heads, seq, head_dim]) so block
  shapes keep the (sublane, lane)-aligned last two dims the Mosaic lowering
  requires; the public API takes BSHD and transposes at dispatch.
- TWO kernel structures, selected by whether K/V (lane-padded to 128) fit
  VMEM comfortably (~12MB → seq <= ~8k at head_dim 64):
  * resident: K/V whole in VMEM, loops over [Bq, Bk] score tiles —
    what training lengths run. Kernel alone on a v5e (PR 45's sweep,
    benchmarks/kernel_tuning.py, ms a call, forward | backward):
    [32,12,1024,64] 1.37 | 2.13 (before PR 45: 1.93 | 3.94),
    [4,16,2048,128] 0.64 | 1.24 (0.93 | 2.63), both at 512x512 tiles;
    every block pair's time is in flash_tuning_defaults.json's notes;
  * streamed: grid (b, h, q_blocks, k_blocks) with K/V blocks flowing
    through the grid and the online-softmax state in VMEM scratch —
    compiles and runs at any length (16k/32k+).
- causal mode computes no tile above the diagonal, in EVERY structure:
  the resident forward, the one-pass backward and the two-pass resident
  backward end their loops at the q block's last visible key, the
  streamed kernels predicate the grid step's compute. The dispatch
  records the static count (``tiles_visited`` / ``tiles_total`` of one
  (batch, head): 36 / 64 at 2048 with 256² tiles, 3 / 4 at 1024 with
  512²), so the block sizes decide how much is skipped.
- a (batch, head) of few tiles (``UNROLLED_SCORES_MAX``) is ONE
  straight-line program in the resident forward and the one-pass
  backward: grid (b, h), Python loops over q blocks and k tiles, every
  offset a constant. There the causal mask (two iotas, a compare, a
  select on the float32 tile) is built only on the tiles the diagonal
  crosses — which those are is known while the kernel is traced — and
  nothing is carried through a loop. Longer calls walk fori_loops with
  dynamic trip counts and mask every tile they visit: splitting such a
  loop in two (tiles under the diagonal, then the diagonal's) was
  measured slower than the masks it saved. A mask that is the identity
  changes no bit, so both forms give the same values to the last bit.
- ``bias``: ONE additive [b|1, h|1, sq|1, sk] operand covering both the
  reference kernel's attn-mask input and alibi/relative biases (boolean
  masks are folded to 0/-1e30 by the dispatch layer, the same encoding
  the causal path uses). Broadcast (size-1) dims stay size-1 all the way
  into the kernel tile — a [b,1,1,sk] padding mask costs O(b*sk) HBM,
  never O(s^2).
- ``dropout``: attention-probability dropout fused into every structure
  via a COUNTER-BASED keep mask: murmur-style avalanche hashing of
  (seed, global batch*head, absolute row, absolute col). Stateless
  per-element sampling means the fwd kernel and all three backward
  tilings regenerate bit-identical masks with zero operand traffic, and
  the same pure-jnp helper (attention_dropout_keep) runs OUTSIDE Pallas
  for the dense path and sequence-parallel layouts — replicated, Ulysses
  (via head/batch offsets) and dense-reference runs all sample the same
  bits, which is what makes cross-backend parity exactly testable. The
  keep mask drops softmax PROBS (post-normalization, scaled 1/(1-rate)),
  matching the reference's dropout placement; the softmax denominator
  accumulates UN-dropped probabilities.
- forward emits the log-sum-exp rows and every backward reads them:
  p = exp(s - lse), no row max, row sum or divide, fully masked rows
  exactly 0. Through MONOLITHIC_BWD_MAX_SEQ (4096) and without a full
  [sq, sk] bias the backward is ONE call, grid (b, h): q/do/o and K/V
  read once, a loop over q blocks and inside it one over k blocks up to
  the diagonal, delta = rowsum(do∘o) once a q block, dQ written once a
  q block, dK/dV summed in float32 VMEM scratch a k block at a time.
  Beyond, two passes share the LSE (q-major for dQ, k-major for dK/dV).
  dBias is computed in the custom_vjp bwd rule as a dense recompute that
  XLA dead-code-eliminates whenever the bias is not being differentiated
  (the common case: masks and alibi).
- all matmuls run in the operand dtype (bf16 hot path) with fp32
  accumulation via preferred_element_type — the same bf16-in/fp32-acc
  contract as the XLA einsum path.
- autodiff via jax.custom_vjp (the reference wires fwd/bwd kernels through
  torch.autograd.Function the same way).
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Hand-picked FALLBACK tilings for shapes nobody swept (the two training
# cells' shapes were, on a v5e: flash_tuning_defaults.json). The dispatch consults
# the shape-keyed tuning cache (tuning.py — runtime table, then the
# $DS_TPU_KERNEL_TUNING_CACHE artifact, then the committed default table)
# FIRST; these constants only apply on a full cache miss.
DEFAULT_BLOCK_Q = 512
RESIDENT_BLOCK_K = 512
STREAMED_BLOCK_K = 1024

from . import tuning as _tuning
from ._common import NEG_INF
from ._common import interpret_mode as _interpret


# ---------------------------------------------------------------------------
# counter-based attention dropout
# ---------------------------------------------------------------------------

def _mix32(x):
    """murmur3 finalizer: full avalanche on a uint32 lane."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def _keep_from_coords(s0, s1, bh, i, j, rate):
    """Bernoulli(1-rate) keep decision per (seed, flat batch*head, row,
    col) coordinate. Inputs are broadcastable uint32 arrays/scalars; two
    avalanche rounds decorrelate the structured (i, j) lattice. Pure jnp,
    so the SAME code runs inside Pallas kernels (2-D tiles) and outside
    (4-D full shapes)."""
    x = ((i * jnp.uint32(0x27D4EB2F)) ^ (j * jnp.uint32(0x165667B1))
         ^ (bh * jnp.uint32(0x9E3779B1)) ^ s0)
    x = _mix32(x ^ s1)
    x = _mix32(x + jnp.uint32(0x9E3779B9))
    return x >= jnp.uint32(min(int(rate * 2 ** 32), 2 ** 32 - 1))


def _seed_words(key):
    """Two uint32 words from a JAX PRNG key (typed or raw)."""
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        data = jax.random.key_data(key)
    else:
        data = jnp.asarray(key)
    data = data.astype(jnp.uint32).reshape(-1)
    w1 = data[-1] if data.size > 1 else jnp.uint32(0x6A09E667)
    return data[0], w1


def pack_dropout_seeds(dropout_rng, head_offset=0, batch_offset=0):
    """int32[4] SMEM operand for the in-kernel keep hash:
    [seed0, seed1, head_offset, batch_offset]. Shared by the flash and
    block-sparse kernels."""
    s0, s1 = _seed_words(dropout_rng)
    return jnp.stack([s0, s1, jnp.uint32(head_offset),
                      jnp.uint32(batch_offset)]).astype(jnp.int32)


def resolve_dropout(dropout_rate, dropout_rng, dropout_offsets,
                    default_heads):
    """(rate, seeds, total_heads) for a kernel dispatch — the ONE place
    the offsets contract is interpreted, shared by the flash and
    block-sparse dispatchers so they can never sample different bits.
    rate 0 / missing rng disables (seeds None)."""
    if dropout_rate <= 0.0 or dropout_rng is None:
        return 0.0, None, int(default_heads)
    th, ho, bo = dropout_offsets or (default_heads, 0, 0)
    return float(dropout_rate), pack_dropout_seeds(dropout_rng, ho, bo), \
        int(th)


def attention_dropout_keep(dropout_rng, rate, shape, total_heads=None,
                           head_offset=0, batch_offset=0,
                           q_offset=0, k_offset=0):
    """Full-shape [b, h, sq, sk] keep mask — bit-identical to what the
    flash kernels sample per tile. ``total_heads``/offsets let a
    shard_map region (Ulysses: local heads/batch) reproduce the global
    replicated sample; the defaults are correct for unsharded or
    GSPMD-sharded (global-view) callers."""
    u = functools.partial(jax.lax.broadcasted_iota, jnp.uint32, shape)
    s0, s1 = _seed_words(dropout_rng)
    bi = u(0) + jnp.uint32(batch_offset)
    hi = u(1) + jnp.uint32(head_offset)
    i = u(2) + jnp.uint32(q_offset)
    j = u(3) + jnp.uint32(k_offset)
    bh = bi * jnp.uint32(total_heads if total_heads else shape[1]) + hi
    return _keep_from_coords(s0, s1, bh, i, j, rate)


def _tile_keep(sm_ref, bi, hi, q_start, k_start, shape, rate, total_heads):
    """In-kernel [Bq, Bk] keep tile at absolute coordinates. sm_ref (SMEM,
    int32[4]): [seed0, seed1, head_offset, batch_offset]."""
    s0 = sm_ref[0].astype(jnp.uint32)
    s1 = sm_ref[1].astype(jnp.uint32)
    gh = jnp.uint32(hi) + sm_ref[2].astype(jnp.uint32)
    gb = jnp.uint32(bi) + sm_ref[3].astype(jnp.uint32)
    bh = gb * jnp.uint32(total_heads) + gh
    i = jax.lax.broadcasted_iota(jnp.uint32, shape, 0) + jnp.uint32(q_start)
    j = jax.lax.broadcasted_iota(jnp.uint32, shape, 1) + jnp.uint32(k_start)
    return _keep_from_coords(s0, s1, bh, i, j, rate)


# ---------------------------------------------------------------------------
# shared tile math
# ---------------------------------------------------------------------------

def _causal_mask(s, q_off, k_off):
    row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + q_off
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + k_off
    return jnp.where(col <= row, s, NEG_INF)


def _causal_trips(q_off, block_q, block_k, nkb):
    """(full, trips) of one q block whose first row sees keys up to
    ``q_off``: k tiles ``[0, full)`` lie wholly at or under the diagonal
    (their last column ``<= q_off``: the mask is the identity there),
    tiles ``[full, trips)`` are crossed by it, and tiles from ``trips``
    on hold no visible key. Python ints in, Python ints out (a program
    whose loops are unrolled; the dispatch's count); traced in, traced
    out."""
    clip = (jnp.clip if isinstance(q_off, jax.Array)
            else lambda x, lo, hi: max(lo, min(x, hi)))
    trips = clip((q_off + block_q - 1) // block_k + 1, 1, nkb)
    return clip((q_off + 1) // block_k, 0, trips), trips


def _tiles_visited(sq, sk, block_q, block_k, causal):
    """(visited, total) [block_q, block_k] tiles of one (batch, head) of
    a resident structure — the dispatch record's static count of what
    the causal trip counts leave."""
    nqb, nkb = sq // block_q, sk // block_k
    if not causal:
        return nqb * nkb, nqb * nkb
    return sum(_causal_trips(i * block_q + sk - sq, block_q, block_k,
                             nkb)[1] for i in range(nqb)), nqb * nkb


# A (batch, head) whose visited tiles hold at most this many scores is ONE
# straight-line program: Python loops over q blocks and k tiles, every
# offset a constant, the causal mask built on the diagonal's tiles only.
# Measured on a v5e (PR 45, kernel alone, backward at [32,12,1024,64]
# 512x512 | [4,16,2048,128] 512x512 | 256x256): dynamic-trip fori_loops
# 3.26 | 1.58 | 2.25 ms a call, unrolled 2.15 | 1.26 | 1.33. The bound is
# VMEM's: Mosaic keeps a float32 tile of every unrolled step (36 tiles of
# 512², 4096 long, asked for 38.9 MB of the 16 MB scoped limit; 10 of
# 512² and 36 of 256², 2048 long, fit). Beyond it the loops are
# fori_loops again.
UNROLLED_SCORES_MAX = 3 * 2 ** 20
# what an unrolled call may take of VMEM: the 16 MiB default is 0.2 MB
# short of 10 tiles of 512² with dropout's hash tiles beside the scores;
# the v5e holds 128 MiB (ops/pallas/grouped_matmul.py takes 96)
UNROLLED_VMEM_LIMIT = 48 * 2 ** 20


def _unrolled(sq, sk, block_q, block_k, causal):
    return (_tiles_visited(sq, sk, block_q, block_k, causal)[0]
            * block_q * block_k <= UNROLLED_SCORES_MAX)


def _compiler_params(unrolled):
    return (pltpu.CompilerParams(vmem_limit_bytes=UNROLLED_VMEM_LIMIT)
            if unrolled else None)


def _span(lo, hi, body, carry):
    """``carry = body(j, carry)`` for j in [lo, hi): straight-line code
    over Python ints, a fori_loop over traced bounds."""
    if isinstance(lo, int) and isinstance(hi, int):
        for j in range(lo, hi):
            carry = body(j, carry)
        return carry
    return jax.lax.fori_loop(lo, hi, body, carry)


def _k_tiles(causal, q_off, block_q, block_k, nkb, tile, init):
    """Walk one q block's visible k tiles with ``tile(j, carry,
    masked)``. Where the offsets are Python ints (an unrolled program)
    the tiles under the diagonal build no mask and only those it crosses
    do, at no cost: which is which is known as the kernel is traced.
    Under traced offsets ONE loop masks every tile, as it always did: a
    second loop for the diagonal's tiles cost more than the masks it
    saved at every block size (PR 45: the resident forward 1.93 -> 2.43
    ms a call at [32,12,1024,64] 512x512), and a mask that is the
    identity changes no bit either way."""
    if not causal:
        return _span(0, nkb, functools.partial(tile, masked=False), init)
    full, trips = _causal_trips(q_off, block_q, block_k, nkb)
    if not isinstance(q_off, int):
        return _span(0, trips, functools.partial(tile, masked=True), init)
    carry = _span(0, full, functools.partial(tile, masked=False), init)
    return _span(full, trips, functools.partial(tile, masked=True), carry)


from ._common import pick_block as _block

# training-length gate for the single-pass resident backward (q/do/o, K/V,
# the three results and the fp32 dK/dV accumulators whole in VMEM outgrow
# it beyond this); module constant so tests can lower it to exercise the
# long-seq structures
MONOLITHIC_BWD_MAX_SEQ = 4096

# a full-extent [.., Bq, sk] bias tile shares VMEM with K/V in the
# resident structures; cap its footprint
_BIAS_TILE_BUDGET = 4 * 2 ** 20


def _kv_fits_vmem(s, d, itemsize=2):
    """Lane-padded, double-buffered K+V bytes within a ~12MB budget."""
    return s * max(d, 128) * itemsize * 2 * 2 <= 12 * 2 ** 20


def _probs(q, k, lse, scale, causal, q_off, k_off, bias=None):
    """Probability tile from the saved LSE (one matmul, no running
    softmax): p = exp(s - lse); causal-masked, bias-masked (-1e30) and
    fully-masked (lse = -inf) entries come out exactly 0. ``causal``
    says whether THIS tile builds the mask (the diagonal's do)."""
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if causal:
        s = _causal_mask(s, q_off, k_off)
    return jnp.exp(s - _guard_lse(lse))


def _guard_lse(lse):
    """A fully masked row's lse (-1e30) as +1e30, so that its
    exp(s - lse) is exactly 0 (not exp(-1e30 + 1e30) = 1) with no select
    on the [Bq, Bk] tile: one on the [Bq, 1] column instead."""
    return jnp.where(lse > NEG_INF / 2, lse, -NEG_INF)


def _online_step(q, k, v, scale, causal, q_off, k_off, acc, m_acc, l_acc,
                 bias=None, keep=None, inv_keep=1.0):
    """One [Bq, Bk] online-softmax update (shared by both structures).
    ``keep`` drops post-softmax probabilities: the denominator l
    accumulates the UN-dropped sum (true softmax normalizer), the PV
    numerator the dropped/rescaled one."""
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if causal:
        s = _causal_mask(s, q_off, k_off)
    m_new = jnp.maximum(m_acc, jnp.max(s, axis=-1, keepdims=True))
    # rows with no visible key yet (m still -inf, e.g. shifted-causal top
    # rows or fully bias-masked rows) must contribute p=0, not
    # exp(-inf - -inf) = 1
    p = jnp.where(m_new > NEG_INF / 2, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_acc - m_new)
    l_new = l_acc * alpha + jnp.sum(p, axis=-1, keepdims=True)
    if keep is not None:
        p = jnp.where(keep, p * inv_keep, 0.0)
    # PV matmul in the value dtype (bf16 MXU rate); probs are in [0,1] so
    # the downcast loses at most 2^-9 relative — inside bf16 output noise
    acc = acc * alpha + jnp.dot(p.astype(v.dtype), v,
                                preferred_element_type=jnp.float32)
    return acc, m_new, l_new


def _bwd_tile(p, do, v, delta, scale, keep, inv_keep, q_dtype):
    """Shared backward tile math. With dropout D = keep/(1-rate):
    o = (P∘D)v / l  =>  dV = (P∘D)ᵀ do,  dS = P∘(D∘(do Vᵀ) - delta)·scale
    where delta = rowsum(do∘o) — the same delta as the no-dropout case
    (the dropped terms cancel: delta_i = do_i·o_i either way)."""
    dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
    if keep is not None:
        dfac = jnp.where(keep, inv_keep, 0.0)
        ds = (p * (dfac * dp - delta) * scale).astype(q_dtype)
        pv = (p * dfac).astype(do.dtype)
    else:
        ds = (p * (dp - delta) * scale).astype(q_dtype)
        pv = p.astype(do.dtype)
    return ds, pv


def _emit_o_lse(acc, m, l, o_ref, lse_ref, rows=slice(None)):
    safe_l = jnp.where(l > 0.0, l, 1.0)   # fully-masked rows -> zeros
    o_ref[0, 0, rows, :] = (acc / safe_l).astype(o_ref.dtype)
    # LSE residual for backward; -inf rows stay -inf so bwd re-zeroes them
    lse_ref[0, 0, rows, :] = jnp.where(l > 0.0, m + jnp.log(safe_l), NEG_INF)


def _unpack_refs(refs, has_bias, has_drop):
    """Kernel ref unpacking: [bias_ref?] [sm_ref?] then outputs/scratch."""
    i = 0
    bias_ref = refs[i] if has_bias else None
    i += 1 if has_bias else 0
    sm_ref = refs[i] if has_drop else None
    i += 1 if has_drop else 0
    return (bias_ref, sm_ref) + tuple(refs[i:])


def _bias_rows(bias_ref, bias_q_full, row_ds):
    """Bias tile rows for q rows ``row_ds`` (pl.ds) — all rows when the
    bias q dim is broadcast (size 1)."""
    if bias_q_full:
        return bias_ref[0, 0, row_ds, :]
    return bias_ref[0, 0, :, :]


# ---------------------------------------------------------------------------
# resident structure: K/V whole in VMEM, fori over k tiles
# ---------------------------------------------------------------------------

def _fwd_kernel_resident(q_ref, k_ref, v_ref, *refs, scale, causal, block_q,
                         block_k, causal_shift, has_bias, dropout_rate,
                         total_heads, unrolled):
    """K/V whole in VMEM. ``unrolled``: grid (b, h), every q block of the
    (batch, head) in one straight-line program (``UNROLLED_SCORES_MAX``;
    the bias broadcast over q); else grid (b, h, q_blocks), one q block
    a step and a fori_loop over its k tiles."""
    has_drop = dropout_rate > 0.0
    bias_ref, sm_ref, o_ref, lse_ref = _unpack_refs(refs, has_bias, has_drop)
    bi, hi = pl.program_id(0), pl.program_id(1)
    d = q_ref.shape[-1]
    nkb = k_ref.shape[2] // block_k
    inv_keep = 1.0 / (1.0 - dropout_rate) if has_drop else 1.0

    def q_block(qi, rows):
        q = q_ref[0, 0, rows, :]                       # [Bq, d] native dtype
        q_off = qi * block_q + causal_shift
        q_abs = qi * block_q                           # dropout coordinates

        def tile(j, carry, masked):
            ks = pl.ds(j * block_k, block_k)
            bias = bias_ref[0, 0, :, ks] if has_bias else None
            keep = (_tile_keep(sm_ref, bi, hi, q_abs, j * block_k,
                               (block_q, block_k), dropout_rate, total_heads)
                    if has_drop else None)
            return _online_step(q, k_ref[0, 0, ks, :], v_ref[0, 0, ks, :],
                                scale, masked, q_off, j * block_k, *carry,
                                bias=bias, keep=keep, inv_keep=inv_keep)

        acc, m, l = _k_tiles(
            causal, q_off, block_q, block_k, nkb, tile,
            (jnp.zeros((block_q, d), jnp.float32),
             jnp.full((block_q, 1), NEG_INF, jnp.float32),
             jnp.zeros((block_q, 1), jnp.float32)))
        _emit_o_lse(acc, m, l, o_ref, lse_ref, rows)

    if unrolled:
        for qi in range(q_ref.shape[2] // block_q):
            q_block(qi, pl.ds(qi * block_q, block_q))
    else:
        q_block(pl.program_id(2), slice(None))


def _dq_kernel_resident(q_ref, k_ref, v_ref, do_ref, delta_ref, lse_ref,
                        *refs, scale, causal, block_q, block_k,
                        causal_shift, has_bias, dropout_rate, total_heads):
    has_drop = dropout_rate > 0.0
    bias_ref, sm_ref, dq_ref = _unpack_refs(refs, has_bias, has_drop)
    bi, hi, qi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    q = q_ref[0, 0]
    do = do_ref[0, 0]
    delta = delta_ref[0, 0]
    lse = lse_ref[0, 0]
    d = q.shape[-1]
    nkb = k_ref.shape[2] // block_k
    q_off = qi * block_q + causal_shift
    q_abs = qi * block_q
    inv_keep = 1.0 / (1.0 - dropout_rate) if has_drop else 1.0

    def tile(j, acc, masked):
        ks = pl.ds(j * block_k, block_k)
        k = k_ref[0, 0, ks, :]
        v = v_ref[0, 0, ks, :]
        bias = bias_ref[0, 0, :, ks] if has_bias else None
        p = _probs(q, k, lse, scale, masked, q_off, j * block_k, bias=bias)
        keep = (_tile_keep(sm_ref, bi, hi, q_abs, j * block_k,
                           (block_q, block_k), dropout_rate, total_heads)
                if has_drop else None)
        ds, _ = _bwd_tile(p, do, v, delta, scale, keep, inv_keep, q.dtype)
        return acc + jnp.dot(ds, k, preferred_element_type=jnp.float32)

    acc = _k_tiles(causal, q_off, block_q, block_k, nkb, tile,
                   jnp.zeros((block_q, d), jnp.float32))
    dq_ref[0, 0] = acc.astype(dq_ref.dtype)


def _dkv_kernel_resident(q_ref, k_ref, v_ref, do_ref, delta_ref, lse_ref,
                         *refs, scale, causal, block_q, block_k,
                         seq_q, causal_shift, has_bias, bias_q_full,
                         dropout_rate, total_heads):
    has_drop = dropout_rate > 0.0
    bias_ref, sm_ref, dk_ref, dv_ref = _unpack_refs(refs, has_bias, has_drop)
    bi, hi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    k = k_ref[0, 0]                                    # [Bk, d] this block
    v = v_ref[0, 0]
    d = k.shape[-1]
    nqb = seq_q // block_q
    k_off = ki * block_k
    inv_keep = 1.0 / (1.0 - dropout_rate) if has_drop else 1.0

    if causal:
        # first q block whose bottom row reaches this k block
        q_lo = jnp.clip((k_off - causal_shift) // block_q, 0, nqb - 1)
        trips = nqb - q_lo
    else:
        q_lo = 0
        trips = nqb

    def body(i, carry):
        dk_acc, dv_acc = carry
        j = q_lo + i
        qs = pl.ds(j * block_q, block_q)
        q = q_ref[0, 0, qs, :]
        do = do_ref[0, 0, qs, :]
        delta = delta_ref[0, 0, qs, :]
        lse = lse_ref[0, 0, qs, :]
        bias = _bias_rows(bias_ref, bias_q_full, qs) if has_bias else None
        p = _probs(q, k, lse, scale, causal,
                   j * block_q + causal_shift, k_off, bias=bias)
        keep = (_tile_keep(sm_ref, bi, hi, j * block_q, k_off,
                           (block_q, block_k), dropout_rate, total_heads)
                if has_drop else None)
        ds, pv = _bwd_tile(p, do, v, delta, scale, keep, inv_keep, q.dtype)
        dk_acc = dk_acc + jnp.dot(ds.T, q, preferred_element_type=jnp.float32)
        dv_acc = dv_acc + jnp.dot(pv.T, do, preferred_element_type=jnp.float32)
        return dk_acc, dv_acc

    dk_acc, dv_acc = jax.lax.fori_loop(
        0, trips, body,
        (jnp.zeros((k.shape[0], d), jnp.float32),
         jnp.zeros((k.shape[0], d), jnp.float32)))
    dk_ref[0, 0] = dk_acc.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv_acc.astype(dv_ref.dtype)


def _bwd_kernel_monolithic(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                           *refs, scale, causal, block_q, block_k,
                           causal_shift, has_bias, dropout_rate, total_heads,
                           unrolled):
    """Single-pass resident backward: grid (b, h); q/do/o, K/V, the three
    results and the fp32 dK/dV accumulators (VMEM scratch) whole in VMEM.
    A loop over q blocks, and inside it one over the k blocks up to that
    q block's last visible key: [Bq, Bk] tiles of p = exp(s - lse) from
    the forward's LSE. dQ accumulates over the inner loop and is written
    once a q block. dK/dV accumulate TRANSPOSED, [d, sk]: q and do are
    turned once a q block ([Bq, d], small) and qᵀ·dS, doᵀ·P add into a
    k block's columns, so the [Bq, Bk] tiles dS and P are never turned
    (that was two transposes a tile; measured 2.56 -> 2.14 ms a call at
    [32,12,1024,64]); the two sums are turned back once at the end.
    ``unrolled``: the loops are Python's (``UNROLLED_SCORES_MAX``) and only
    the diagonal's tiles build the causal mask. One launch; K/V and
    q/do/o each loaded once. Bias here is restricted to broadcast-q
    ([.., 1, sk]) by the dispatch — a full [sq, sk] bias won't fit VMEM
    at this structure's lengths."""
    has_drop = dropout_rate > 0.0
    bias_ref, sm_ref, dq_ref, dk_ref, dv_ref, dkt_acc, dvt_acc = _unpack_refs(
        refs, has_bias, has_drop)
    bi, hi = pl.program_id(0), pl.program_id(1)
    d = q_ref.shape[-1]
    nqb, nkb = q_ref.shape[2] // block_q, k_ref.shape[2] // block_k
    inv_keep = 1.0 / (1.0 - dropout_rate) if has_drop else 1.0
    dkt_acc[...] = jnp.zeros_like(dkt_acc)
    dvt_acc[...] = jnp.zeros_like(dvt_acc)

    def q_block(i, _):
        qs = pl.ds(i * block_q, block_q)
        q = q_ref[0, 0, qs, :]                         # [Bq, d]
        do = do_ref[0, 0, qs, :]
        qt, dot = q.T, do.T                            # [d, Bq]
        delta = jnp.sum(do.astype(jnp.float32)
                        * o_ref[0, 0, qs, :].astype(jnp.float32),
                        axis=-1, keepdims=True)
        lse = _guard_lse(lse_ref[0, 0, qs, :])
        q_off = i * block_q + causal_shift

        def tile(j, dq_acc, masked):
            ks = pl.ds(j * block_k, block_k)
            k = k_ref[0, 0, ks, :]                     # [Bk, d]
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
            if has_bias:
                s = s + bias_ref[0, 0, :, ks].astype(jnp.float32)  # [1, Bk]
            if masked:  # ds-tpu: lint-ok[TS001] (a Python bool: _k_tiles)
                s = _causal_mask(s, q_off, j * block_k)
            p = jnp.exp(s - lse)                       # [Bq, Bk] fp32
            keep = (_tile_keep(sm_ref, bi, hi, i * block_q, j * block_k,
                               (block_q, block_k), dropout_rate, total_heads)
                    if has_drop else None)
            ds, pv = _bwd_tile(p, do, v_ref[0, 0, ks, :], delta, scale,
                               keep, inv_keep, q.dtype)
            dkt_acc[:, ks] += jnp.dot(qt, ds,
                                      preferred_element_type=jnp.float32)
            dvt_acc[:, ks] += jnp.dot(dot, pv,
                                      preferred_element_type=jnp.float32)
            return dq_acc + jnp.dot(ds, k, preferred_element_type=jnp.float32)

        dq_acc = _k_tiles(causal, q_off, block_q, block_k, nkb, tile,
                          jnp.zeros((block_q, d), jnp.float32))
        dq_ref[0, 0, qs, :] = dq_acc.astype(dq_ref.dtype)
        return _

    if unrolled:
        for i in range(nqb):
            q_block(i, 0)
    else:
        jax.lax.fori_loop(0, nqb, q_block, 0)
    dk_ref[0, 0] = dkt_acc[...].T.astype(dk_ref.dtype)
    dv_ref[0, 0] = dvt_acc[...].T.astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# streamed structure: K/V blocks flow through the grid, scratch accumulators
# ---------------------------------------------------------------------------

def _fwd_kernel_streamed(q_ref, k_ref, v_ref, *refs, scale, causal, block_q,
                         block_k, causal_shift, nkb, has_bias, dropout_rate,
                         total_heads):
    has_drop = dropout_rate > 0.0
    bias_ref, sm_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = _unpack_refs(
        refs, has_bias, has_drop)
    bi, hi = pl.program_id(0), pl.program_id(1)
    qi, ki = pl.program_id(2), pl.program_id(3)
    q_off = qi * block_q + causal_shift
    inv_keep = 1.0 / (1.0 - dropout_rate) if has_drop else 1.0

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    live = True if not causal else ki * block_k <= q_off + block_q - 1

    @pl.when(live)
    def _compute():
        bias = bias_ref[0, 0] if has_bias else None
        keep = (_tile_keep(sm_ref, bi, hi, qi * block_q, ki * block_k,
                           (block_q, block_k), dropout_rate, total_heads)
                if has_drop else None)
        acc, m, l = _online_step(
            q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], scale, causal, q_off,
            ki * block_k, acc_ref[...], m_ref[...], l_ref[...],
            bias=bias, keep=keep, inv_keep=inv_keep)
        acc_ref[...], m_ref[...], l_ref[...] = acc, m, l

    @pl.when(ki == nkb - 1)
    def _emit():
        _emit_o_lse(acc_ref[...], m_ref[...], l_ref[...], o_ref, lse_ref)


def _dq_kernel_streamed(q_ref, k_ref, v_ref, do_ref, delta_ref, lse_ref,
                        *refs, scale, causal, block_q, block_k,
                        causal_shift, nkb, has_bias, dropout_rate,
                        total_heads):
    has_drop = dropout_rate > 0.0
    bias_ref, sm_ref, dq_ref, acc_ref = _unpack_refs(refs, has_bias, has_drop)
    bi, hi = pl.program_id(0), pl.program_id(1)
    qi, ki = pl.program_id(2), pl.program_id(3)
    q_off = qi * block_q + causal_shift
    inv_keep = 1.0 / (1.0 - dropout_rate) if has_drop else 1.0

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    live = True if not causal else ki * block_k <= q_off + block_q - 1

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0]
        do = do_ref[0, 0]
        bias = bias_ref[0, 0] if has_bias else None
        p = _probs(q, k_ref[0, 0], lse_ref[0, 0], scale, causal, q_off,
                   ki * block_k, bias=bias)
        keep = (_tile_keep(sm_ref, bi, hi, qi * block_q, ki * block_k,
                           (block_q, block_k), dropout_rate, total_heads)
                if has_drop else None)
        ds, _ = _bwd_tile(p, do, v_ref[0, 0], delta_ref[0, 0], scale,
                          keep, inv_keep, q.dtype)
        acc_ref[...] += jnp.dot(ds, k_ref[0, 0],
                                preferred_element_type=jnp.float32)

    @pl.when(ki == nkb - 1)
    def _emit():
        dq_ref[0, 0] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel_streamed(q_ref, k_ref, v_ref, do_ref, delta_ref, lse_ref,
                         *refs, scale, causal, block_q, block_k,
                         causal_shift, nqb, has_bias, dropout_rate,
                         total_heads):
    has_drop = dropout_rate > 0.0
    bias_ref, sm_ref, dk_ref, dv_ref, dk_acc, dv_acc = _unpack_refs(
        refs, has_bias, has_drop)
    bi, hi = pl.program_id(0), pl.program_id(1)
    ki, qi = pl.program_id(2), pl.program_id(3)
    q_off = qi * block_q + causal_shift
    k_off = ki * block_k
    inv_keep = 1.0 / (1.0 - dropout_rate) if has_drop else 1.0

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    live = True if not causal else q_off + block_q - 1 >= k_off

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0]
        do = do_ref[0, 0]
        bias = bias_ref[0, 0] if has_bias else None
        p = _probs(q, k_ref[0, 0], lse_ref[0, 0], scale, causal, q_off,
                   k_off, bias=bias)
        keep = (_tile_keep(sm_ref, bi, hi, qi * block_q, k_off,
                           (block_q, block_k), dropout_rate, total_heads)
                if has_drop else None)
        ds, pv = _bwd_tile(p, do, v_ref[0, 0], delta_ref[0, 0], scale,
                           keep, inv_keep, q.dtype)
        dk_acc[...] += jnp.dot(ds.T, q, preferred_element_type=jnp.float32)
        dv_acc[...] += jnp.dot(pv.T, do, preferred_element_type=jnp.float32)

    @pl.when(qi == nqb - 1)
    def _emit():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------

def _resolve_blocks(structure, sq, sk, d, dtype, causal, block_q,
                    fallback_bq, fallback_bk=None, record=True):
    """Trace-time block-size resolution for one kernel structure: an
    explicit caller ``block_q`` wins, else the shape-keyed tuning cache,
    else the hand-picked fallback constants. Every size passes through
    ``_block`` (divisor + 128-lane alignment), so a stale or foreign
    cache entry can never produce an illegal tiling. Returns
    (block_q, block_k-or-None) and — unless this is a provisional
    resolution (``record=False``: the caller may still demote the
    structure on a bias VMEM-budget check) — records the dispatch for
    the ``tuning.last_dispatch`` probe, which must only ever name
    structures that actually run (the sweep harness tunes exactly what
    the probe reports)."""
    entry, key, source = _tuning.lookup(
        "flash_attention", structure, sq=sq, sk=sk, d=d, dtype=dtype,
        causal=causal)
    want_q = (block_q if block_q is not None
              else int(entry.get("block_q", fallback_bq)))
    bq = _block(sq, min(want_q, sq))
    rec = dict(block_q=bq)
    bk = None
    if fallback_bk is not None:
        bk = _block(sk, min(int(entry.get("block_k", fallback_bk)), sk))
        rec["block_k"] = bk
        if "streamed" not in structure:
            rec["tiles_visited"], rec["tiles_total"] = _tiles_visited(
                sq, sk, bq, bk, causal)
    if record:
        _tuning.record_dispatch(
            "flash_attention", structure, key,
            "caller" if block_q is not None else source, **rec)
    return bq, bk


def _bias_meta(bias):
    """(batched, headed, q_full) broadcast flags of a [b', h', sq', sk]
    bias operand."""
    return bias.shape[0] > 1, bias.shape[1] > 1, bias.shape[2] > 1


def _bias_spec3(bias, block_q):
    """BlockSpec for 3-D grids (b, h, qi): full sk extent per tile."""
    bb, bh, bq_full = _bias_meta(bias)
    sk = bias.shape[3]
    shape = (1, 1, block_q if bq_full else 1, sk)
    return pl.BlockSpec(shape, lambda bi, hi, qi: (
        bi if bb else 0, hi if bh else 0, qi if bq_full else 0, 0))


def _bias_spec3_k(bias, block_k, seq_q):
    """BlockSpec for the resident dkv grid (b, h, ki): full sq extent,
    one k block."""
    bb, bh, bq_full = _bias_meta(bias)
    shape = (1, 1, seq_q if bq_full else 1, block_k)
    return pl.BlockSpec(shape, lambda bi, hi, ki: (
        bi if bb else 0, hi if bh else 0, 0, ki))


def _bias_spec4(bias, block_q, block_k, q_pos, k_pos):
    """BlockSpec for 4-D streamed grids; q_pos/k_pos say which grid axes
    carry the q/k block indices (2, 3) or (3, 2)."""
    bb, bh, bq_full = _bias_meta(bias)
    shape = (1, 1, block_q if bq_full else 1, block_k)

    def idx(*g):
        return (g[0] if bb else 0, g[1] if bh else 0,
                g[q_pos] if bq_full else 0, g[k_pos])

    return pl.BlockSpec(shape, idx)


def _bias_spec2(bias):
    """BlockSpec for the monolithic (b, h) grid: bias is broadcast-q
    ([.., 1, sk]) here by construction."""
    bb, bh, _ = _bias_meta(bias)
    return pl.BlockSpec((1, 1, 1, bias.shape[3]), lambda bi, hi: (
        bi if bb else 0, hi if bh else 0, 0, 0))


_SM_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def _extra_ops(bias, seeds, bias_spec):
    """(operands, specs) for the optional bias/seed inputs."""
    ops, specs = [], []
    if bias is not None:
        ops.append(bias)
        specs.append(bias_spec)
    if seeds is not None:
        ops.append(seeds)
        specs.append(_SM_SPEC)
    return tuple(ops), tuple(specs)


# The two calls the training cells run, each a jit of its own: a step's
# program reaches the same call several times while it is traced (the
# primal, the custom_vjp rule, remat's replay), and an unrolled kernel's
# body is traced a tile at a time — 0.5 s a visit at 2048 with 512² tiles,
# +1.6 s of a four-chip cell's set-up (PR 45, my chip runs). Traced and
# lowered once a shape and tiling, the other visits are calls of one
# function, which XLA inlines. Every decision the tables or a test's
# monkeypatch can change is a static argument. (Never dispatched by
# themselves: traced into the programs the registry tracks.)

@functools.partial(jax.jit,  # ds-tpu: lint-ok[CC001]
                   static_argnames=("scale", "causal", "has_bias",
                                    "dropout_rate", "total_heads", "block_q",
                                    "block_k", "unrolled", "interpret"))
def _fwd_resident_call(q, k, v, bias, seeds, *, block_q, block_k, unrolled,
                       interpret, **common):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    rows = sq if unrolled else block_q
    # (the grid has no q axis when unrolled: the one block is block 0)
    at = lambda bi, hi, qi=0: (bi, hi, qi, 0)
    whole = lambda bi, hi, qi=0: (bi, hi, 0, 0)
    bias_spec = None
    if bias is not None:
        bias_spec = (_bias_spec2(bias) if unrolled
                     else _bias_spec3(bias, block_q))
    extra, extra_specs = _extra_ops(bias, seeds, bias_spec)
    q_blk = pl.BlockSpec((1, 1, rows, d), at)
    kv_full = pl.BlockSpec((1, 1, sk, d), whole)
    return pl.pallas_call(
        functools.partial(_fwd_kernel_resident, block_q=block_q,
                          block_k=block_k, causal_shift=sk - sq,
                          unrolled=unrolled, **common),
        grid=(b, h) if unrolled else (b, h, sq // block_q),
        in_specs=[q_blk, kv_full, kv_full, *extra_specs],
        out_specs=(q_blk, pl.BlockSpec((1, 1, rows, 1), at)),
        out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32)),
        compiler_params=_compiler_params(unrolled),
        interpret=interpret,
    )(q, k, v, *extra)


@functools.partial(jax.jit,  # ds-tpu: lint-ok[CC001]
                   static_argnames=("scale", "causal", "has_bias",
                                    "dropout_rate", "total_heads", "block_q",
                                    "block_k", "unrolled", "interpret"))
def _bwd_monolithic_call(q, k, v, o, g, lse, bias, seeds, *, block_q,
                         block_k, unrolled, interpret, **common):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    extra, extra_specs = _extra_ops(
        bias, seeds, _bias_spec2(bias) if bias is not None else None)
    full_q = pl.BlockSpec((1, 1, sq, d), lambda bi, hi: (bi, hi, 0, 0))
    full_k = pl.BlockSpec((1, 1, sk, d), lambda bi, hi: (bi, hi, 0, 0))
    stat_q = pl.BlockSpec((1, 1, sq, 1), lambda bi, hi: (bi, hi, 0, 0))
    return pl.pallas_call(
        functools.partial(
            _bwd_kernel_monolithic, block_q=block_q, block_k=block_k,
            causal_shift=sk - sq, unrolled=unrolled, **common),
        grid=(b, h),
        in_specs=[full_q, full_k, full_k, full_q, full_q, stat_q,
                  *extra_specs],
        out_specs=(full_q, full_k, full_k),
        out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)),
        scratch_shapes=[pltpu.VMEM((d, sk), jnp.float32),
                        pltpu.VMEM((d, sk), jnp.float32)],
        compiler_params=_compiler_params(unrolled),
        interpret=interpret,
    )(q, k, v, o, g, lse, *extra)


def _flash_fwd(q, k, v, bias, seeds, scale, causal, dropout_rate,
               total_heads, block_q):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    has_bias = bias is not None
    drop = dropout_rate if seeds is not None else 0.0
    common = dict(scale=scale, causal=causal, has_bias=has_bias,
                  dropout_rate=drop, total_heads=total_heads)
    out_shape = (jax.ShapeDtypeStruct(q.shape, q.dtype),
                 jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32))
    caller_bq = block_q   # keep the caller's request distinct from the
    resident = _kv_fits_vmem(sk, d, q.dtype.itemsize)   # resolved values
    if resident:
        block_q, block_k = _resolve_blocks(
            "fwd_resident", sq, sk, d, q.dtype, causal, caller_bq,
            DEFAULT_BLOCK_Q, RESIDENT_BLOCK_K, record=False)
        if has_bias and bias.shape[2] > 1 and (
                # a full-extent bias tile [Bq, sk] shares VMEM with
                # resident K/V
                block_q * sk * bias.dtype.itemsize > _BIAS_TILE_BUDGET):
            resident = False
    if resident:
        _resolve_blocks("fwd_resident", sq, sk, d, q.dtype, causal,
                        caller_bq, DEFAULT_BLOCK_Q, RESIDENT_BLOCK_K)
    else:
        block_q, block_k = _resolve_blocks(
            "fwd_streamed", sq, sk, d, q.dtype, causal, caller_bq,
            DEFAULT_BLOCK_Q, STREAMED_BLOCK_K)
    if resident:
        # few tiles and no [sq, sk] bias: one straight-line program a
        # (batch, head), grid (b, h); else a q block a grid step
        unrolled = (not (has_bias and bias.shape[2] > 1)
                    and _unrolled(sq, sk, block_q, block_k, causal))
        return _fwd_resident_call(
            q, k, v, bias, seeds, block_q=block_q, block_k=block_k,
            unrolled=unrolled, interpret=_interpret(), **common)
    nkb = sk // block_k
    extra, extra_specs = _extra_ops(
        bias, seeds,
        _bias_spec4(bias, block_q, block_k, 2, 3) if has_bias else None)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel_streamed, block_q=block_q,
                          block_k=block_k, causal_shift=sk - sq, nkb=nkb,
                          **common),
        grid=(b, h, sq // block_q, nkb),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki: (bi, hi, ki, 0)),
            *extra_specs,
        ],
        out_specs=(
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        ),
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32)],
        interpret=_interpret(),
    )(q, k, v, *extra)
    return o, lse


def _dbias_dense(q, k, v, o, lse, g, bias, seeds, scale, causal,
                 dropout_rate, total_heads):
    """dBias via dense recompute from the saved LSE, reduced to the bias's
    broadcast shape. Lives OUTSIDE the Pallas kernels on purpose: when the
    bias is not differentiated (masks, alibi — the common case) XLA
    dead-code-eliminates this whole chain, so the flash path pays nothing;
    when it IS differentiated (T5-style trainable bias) the caller already
    holds O(s^2) bias storage, and XLA fuses the elementwise chain into
    the reduction."""
    f32 = jnp.float32
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(f32), k.astype(f32)) * scale
    s = s + bias.astype(f32)
    sq, sk = q.shape[2], k.shape[2]
    if causal:
        cm = jnp.tril(jnp.ones((sq, sk), bool), sk - sq)
        s = jnp.where(cm, s, NEG_INF)
    p = jnp.where(lse > NEG_INF / 2, jnp.exp(s - lse), 0.0)
    dp = jnp.einsum("bhqd,bhkd->bhqk", g.astype(f32), v.astype(f32))
    if dropout_rate > 0.0 and seeds is not None:
        keep = attention_dropout_keep(
            seeds[:2], dropout_rate, p.shape, total_heads=total_heads,
            head_offset=seeds[2], batch_offset=seeds[3])
        dp = jnp.where(keep, dp / (1.0 - dropout_rate), 0.0)
    delta = jnp.sum(g.astype(f32) * o.astype(f32), axis=-1, keepdims=True)
    dbias_full = p * (dp - delta)
    reduce_dims = tuple(i for i in range(3) if bias.shape[i] == 1)
    dbias = jnp.sum(dbias_full, axis=reduce_dims, keepdims=True)
    return dbias.astype(bias.dtype)


def _flash_bwd(scale, causal, dropout_rate, block_q, total_heads,
               bias_grad, res, g):
    q, k, v, bias, seeds, o, lse = res
    b, h, sq, d = q.shape
    sk = k.shape[2]
    has_bias = bias is not None
    drop = dropout_rate if seeds is not None else 0.0
    bias_q_full = has_bias and bias.shape[2] > 1
    common = dict(scale=scale, causal=causal, has_bias=has_bias,
                  dropout_rate=drop, total_heads=total_heads)

    # bias_grad=False (statically known non-trainable bias, e.g. a folded
    # mask): zero cotangent at the bias's own (broadcast) shape — the
    # dense O(s^2) recompute is never built, which matters in EAGER grads
    # where XLA's DCE can't elide it
    if not has_bias:
        dbias = None
    elif bias_grad:
        dbias = _dbias_dense(q, k, v, o, lse, g, bias, seeds, scale,
                             causal, drop, total_heads)
    else:
        dbias = jnp.zeros_like(bias)
    dseeds = (np.zeros(seeds.shape, jax.dtypes.float0)
              if seeds is not None else None)

    # Training lengths: the single-pass resident backward (one launch;
    # K/V, q, do, o each read once). Its VMEM budget: those and the
    # results whole, fp32 dK/dV accumulators, a few [Bq, Bk] fp32 tiles.
    # A full-extent bias can't ride in this structure (its [sq, sk] tile
    # outgrows VMEM) — two-pass then.
    if (sk <= MONOLITHIC_BWD_MAX_SEQ and sq <= MONOLITHIC_BWD_MAX_SEQ
            and not bias_q_full):
        bq, bk = _resolve_blocks(
            "bwd_monolithic", sq, sk, d, q.dtype, causal, block_q,
            DEFAULT_BLOCK_Q, RESIDENT_BLOCK_K)
        dq, dk, dv = _bwd_monolithic_call(
            q, k, v, o, g, lse, bias, seeds, block_q=bq, block_k=bk,
            unrolled=_unrolled(sq, sk, bq, bk, causal),
            interpret=_interpret(), **common)
        return (dq, dk, dv, dbias, dseeds)

    caller_bq = block_q
    resident = (_kv_fits_vmem(sk, d, q.dtype.itemsize)
                and _kv_fits_vmem(sq, d, q.dtype.itemsize))
    if resident:
        block_q, block_k = _resolve_blocks(
            "bwd_resident", sq, sk, d, q.dtype, causal, caller_bq,
            DEFAULT_BLOCK_Q, RESIDENT_BLOCK_K, record=False)
        if bias_q_full and (
                # both passes load full-extent bias tiles: [Bq, sk] in dq
                # and [sq, Bk] in dkv — budget the larger one
                max(block_q * sk, sq * block_k) * bias.dtype.itemsize
                > _BIAS_TILE_BUDGET):
            resident = False
    if resident:
        _resolve_blocks("bwd_resident", sq, sk, d, q.dtype, causal,
                        caller_bq, DEFAULT_BLOCK_Q, RESIDENT_BLOCK_K)
    else:
        block_q, block_k = _resolve_blocks(
            "bwd_streamed", sq, sk, d, q.dtype, causal, caller_bq,
            DEFAULT_BLOCK_Q, STREAMED_BLOCK_K)
    nqb, nkb = sq // block_q, sk // block_k
    # delta = rowsum(do * o): cheap elementwise outside the kernels
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)

    if resident:
        q_blk = pl.BlockSpec((1, 1, block_q, d),
                             lambda bi, hi, qi: (bi, hi, qi, 0))
        q_stat = pl.BlockSpec((1, 1, block_q, 1),
                              lambda bi, hi, qi: (bi, hi, qi, 0))
        kv_full = pl.BlockSpec((1, 1, sk, d),
                               lambda bi, hi, qi: (bi, hi, 0, 0))
        extra, extra_specs = _extra_ops(
            bias, seeds, _bias_spec3(bias, block_q) if has_bias else None)
        dq = pl.pallas_call(
            functools.partial(_dq_kernel_resident, block_q=block_q,
                              block_k=block_k, causal_shift=sk - sq,
                              **common),
            grid=(b, h, nqb),
            in_specs=[q_blk, kv_full, kv_full, q_blk, q_stat, q_stat,
                      *extra_specs],
            out_specs=q_blk,
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            interpret=_interpret(),
        )(q, k, v, g, delta, lse, *extra)

        k_blk = pl.BlockSpec((1, 1, block_k, d),
                             lambda bi, hi, ki: (bi, hi, ki, 0))
        q_full = pl.BlockSpec((1, 1, sq, d),
                              lambda bi, hi, ki: (bi, hi, 0, 0))
        stat_full = pl.BlockSpec((1, 1, sq, 1),
                                 lambda bi, hi, ki: (bi, hi, 0, 0))
        extra_k, extra_k_specs = _extra_ops(
            bias, seeds,
            _bias_spec3_k(bias, block_k, sq) if has_bias else None)
        dk, dv = pl.pallas_call(
            functools.partial(_dkv_kernel_resident, block_q=block_q,
                              block_k=block_k, seq_q=sq,
                              causal_shift=sk - sq,
                              bias_q_full=bias_q_full, **common),
            grid=(b, h, nkb),
            in_specs=[q_full, k_blk, k_blk, q_full, stat_full, stat_full,
                      *extra_k_specs],
            out_specs=(k_blk, k_blk),
            out_shape=(jax.ShapeDtypeStruct(k.shape, k.dtype),
                       jax.ShapeDtypeStruct(v.shape, v.dtype)),
            interpret=_interpret(),
        )(q, k, v, g, delta, lse, *extra_k)
        return (dq, dk, dv, dbias, dseeds)

    q_blk = lambda bi, hi, qi, ki: (bi, hi, qi, 0)
    k_blk = lambda bi, hi, qi, ki: (bi, hi, ki, 0)
    extra, extra_specs = _extra_ops(
        bias, seeds,
        _bias_spec4(bias, block_q, block_k, 2, 3) if has_bias else None)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel_streamed, block_q=block_q,
                          block_k=block_k, causal_shift=sk - sq, nkb=nkb,
                          **common),
        grid=(b, h, nqb, nkb),
        in_specs=[pl.BlockSpec((1, 1, block_q, d), q_blk),
                  pl.BlockSpec((1, 1, block_k, d), k_blk),
                  pl.BlockSpec((1, 1, block_k, d), k_blk),
                  pl.BlockSpec((1, 1, block_q, d), q_blk),
                  pl.BlockSpec((1, 1, block_q, 1), q_blk),
                  pl.BlockSpec((1, 1, block_q, 1), q_blk),
                  *extra_specs],
        out_specs=pl.BlockSpec((1, 1, block_q, d), q_blk),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=_interpret(),
    )(q, k, v, g, delta, lse, *extra)

    kq_k = lambda bi, hi, ki, qi: (bi, hi, ki, 0)
    kq_q = lambda bi, hi, ki, qi: (bi, hi, qi, 0)
    extra_k, extra_k_specs = _extra_ops(
        bias, seeds,
        _bias_spec4(bias, block_q, block_k, 3, 2) if has_bias else None)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel_streamed, block_q=block_q,
                          block_k=block_k, causal_shift=sk - sq, nqb=nqb,
                          **common),
        grid=(b, h, nkb, nqb),
        in_specs=[pl.BlockSpec((1, 1, block_q, d), kq_q),
                  pl.BlockSpec((1, 1, block_k, d), kq_k),
                  pl.BlockSpec((1, 1, block_k, d), kq_k),
                  pl.BlockSpec((1, 1, block_q, d), kq_q),
                  pl.BlockSpec((1, 1, block_q, 1), kq_q),
                  pl.BlockSpec((1, 1, block_q, 1), kq_q),
                  *extra_k_specs],
        out_specs=(pl.BlockSpec((1, 1, block_k, d), kq_k),
                   pl.BlockSpec((1, 1, block_k, d), kq_k)),
        out_shape=(jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)),
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=_interpret(),
    )(q, k, v, g, delta, lse, *extra_k)
    return (dq, dk, dv, dbias, dseeds)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash_attention_bhsd(q, k, v, bias, seeds, scale, causal,
                          dropout_rate, block_q, total_heads, bias_grad):
    o, _ = _flash_fwd(q, k, v, bias, seeds, scale, causal, dropout_rate,
                      total_heads, block_q)
    return o


def _fwd_rule(q, k, v, bias, seeds, scale, causal, dropout_rate, block_q,
              total_heads, bias_grad):
    o, lse = _flash_fwd(q, k, v, bias, seeds, scale, causal, dropout_rate,
                        total_heads, block_q)
    return o, (q, k, v, bias, seeds, o, lse)


_flash_attention_bhsd.defvjp(_fwd_rule, _flash_bwd)


def flash_attention(q, k, v, *, bias=None, causal=True, softmax_scale=None,
                    dropout_rate=0.0, dropout_rng=None, dropout_offsets=None,
                    bias_grad=True, block_q=None):
    """q,k,v: [batch, seq, heads, head_dim] (BSHD). Returns like q.

    block_q: None (default) = table-driven — each kernel structure reads
    its block sizes from the shape-keyed tuning cache (ops.pallas.tuning:
    runtime table > $DS_TPU_KERNEL_TUNING_CACHE artifact > committed
    default table > hand-picked constants). An explicit int forces that
    q-block for every structure (block_k stays table-driven).

    bias: optional additive [b|1, h|1, sq|1, sk] operand (fold boolean
    masks to 0/-1e30 before calling — ``ops.transformer.attention`` does).
    bias_grad=False declares the bias non-trainable (masks, alibi): the
    backward rule then emits a zero cotangent instead of the dense dBias
    recompute — under jit the recompute is DCE'd anyway when unused, but
    eager-mode grads would otherwise pay its O(s^2) cost.
    dropout_rate/dropout_rng: fused attention-probability dropout (active
    when both are set). dropout_offsets: (total_heads, head_offset,
    batch_offset) so shard_map callers with local head/batch windows
    sample the same global keep mask as a replicated run.
    """
    d = q.shape[-1]
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(d)
    sq = q.shape[1]
    bq = None
    if block_q is not None:
        bq = min(int(block_q), sq)
        if sq % bq != 0:
            raise ValueError(f"flash_attention: seq {sq} must be divisible "
                             f"by block_q {bq}")
    bias4 = None
    if bias is not None:
        full = (q.shape[0], q.shape[2], sq)
        if (bias.ndim != 4 or bias.shape[3] != k.shape[1]
                or any(bias.shape[i] not in (1, full[i]) for i in range(3))):
            # dims 0-2 must each be broadcast (1) or full-size: a partial
            # extent would make the BlockSpec index maps read clamped
            # (wrong) blocks instead of failing
            raise ValueError(
                f"flash_attention: bias must be [b|1, h|1, sq|1, sk], got "
                f"{bias.shape} for q {q.shape}, sk={k.shape[1]}")
        # full-extent biases ride VMEM in bf16 (the kernel adds in fp32);
        # broadcast-q biases (masks, alibi rows) are small — keep fp32
        bias4 = bias.astype(q.dtype if bias.shape[2] > 1 else jnp.float32)
    rate, seeds, total_heads = resolve_dropout(
        dropout_rate, dropout_rng, dropout_offsets, q.shape[2])
    qt, kt, vt = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
    o = _flash_attention_bhsd(qt, kt, vt, bias4, seeds, scale, causal,
                              rate, bq, total_heads, bool(bias_grad))
    return jnp.swapaxes(o, 1, 2)
