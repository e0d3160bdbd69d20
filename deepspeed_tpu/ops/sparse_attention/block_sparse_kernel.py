"""Block-sparse attention Pallas kernel (splash-attention style).

The real TPU replacement for the reference's Triton block-sparse SDD/DSD
matmuls + sparse softmax (reference: deepspeed/ops/sparse_attention/
matmul.py:6, softmax.py, csrc/sparse_attention/utils.cpp): compute is
proportional to the number of ACTIVE layout blocks, not S².

Design (vs the reference's separate sdd/softmax/dsd kernel pipeline — one
fused pass per direction):

- The [H, S/B, S/B] block layout from a SparsityConfig is compiled
  host-side into per-(head, q-tile) lists of active 128-aligned k-tiles
  (scalar-prefetched to SMEM). The grid is (batch, heads, q_tiles); each
  kernel invocation keeps the full K/V for its (batch, head) resident in
  VMEM (refetched only when the head changes) and runs a
  dynamic-trip-count ``fori_loop`` over exactly that row's active tiles —
  BigBird's dense global rows simply loop longer, without padding the
  sparse window rows.
- Fine-grained layouts (block < 128, the DeepSpeed default of 16) keep
  exact semantics: each (q-tile, k-tile) pair applies a [128,128] mask
  expanded from the fine layout. Masks are deduplicated host-side
  (window/global patterns produce a handful of distinct tiles) and live
  as one [U,128,128] VMEM-resident array indexed per loop step.
- Backward = two more sparse passes sharing the plan: a q-major pass for
  dQ and a k-major pass (transposed lists) for dK/dV, both recomputing
  probabilities from the saved softmax stats (m, l).

Falls back to the dense-mask path (sparse_self_attention.py) for shapes
it cannot tile (S % 128 != 0, 128 % block != 0, all-empty rows).
"""

import functools
import math
from dataclasses import dataclass
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..pallas._common import NEG_INF
from ..pallas._common import interpret_mode as _interpret
from ..pallas.flash_attention import resolve_dropout, _tile_keep

DEFAULT_TILE = 256     # fewer, fatter loop iterations when seq % 256 == 0
MIN_TILE = 128


# ---------------------------------------------------------------------------
# host-side layout compilation
# ---------------------------------------------------------------------------

@dataclass
class LayoutPlan:
    """Compiled work lists for one (layout, seq) pair. All numpy."""
    kv_idx: np.ndarray         # [H, NQ, MAXK] int32, padded with 0
    kv_pid: np.ndarray         # [H, NQ, MAXK] int32 mask pattern ids
    kv_cnt: np.ndarray         # [H, NQ] int32
    qt_idx: np.ndarray         # [H, NQ, MAXQ] int32 (k-major lists)
    qt_pid: np.ndarray         # [H, NQ, MAXQ] int32
    qt_cnt: np.ndarray         # [H, NQ] int32
    masks: np.ndarray          # [U, tile, tile] int8
    tile: int
    n_heads: int
    nq: int
    active_tiles: int
    total_tiles: int

    @property
    def density(self):
        return self.active_tiles / max(self.total_tiles, 1)


_PLAN_CACHE = {}


def compile_layout(config, seq_len: int) -> Optional[LayoutPlan]:
    """Build tile work lists from a SparsityConfig. Returns None when the
    layout cannot be tiled at 128 granularity (caller falls back dense)."""
    try:
        key = (config.cache_key(), seq_len)
    except TypeError:
        key = None
    if key is not None and key in _PLAN_CACHE:
        return _PLAN_CACHE[key]

    block = config.block
    layout = np.asarray(config.make_layout(seq_len))  # [H, nb, nb] 0/1
    nheads, nb, _ = layout.shape

    def coarse_active(t):
        """Active kernel tiles at tile size t (np coarsening)."""
        if block >= t:
            return int(layout.sum()) * (block // t) ** 2
        r = t // block
        n = seq_len // t
        c = layout.reshape(nheads, n, r, n, r).any(axis=(2, 4))
        return int(c.sum())

    # Pick the tile by compute volume (active_tiles * tile²): 256-tiles
    # quarter the loop-iteration overhead but over-include on fine
    # scattered patterns (BigBird randoms); take the fat tile only when
    # its coarsening waste is small (<=1.3x the fine tile's volume).
    cands = [t for t in (DEFAULT_TILE, MIN_TILE)
             if seq_len % t == 0 and (t % block == 0 or block % t == 0)]
    if not cands:
        return None
    vols = {t: coarse_active(t) * t * t for t in cands}
    tile = cands[0]
    if len(cands) == 2 and vols[cands[0]] > 1.3 * vols[cands[1]]:
        tile = cands[1]

    if block >= tile:
        r = block // tile
        fine = np.repeat(np.repeat(layout, r, axis=1), r, axis=2)
        nq = nb * r
        rq = 1
    else:
        rq = tile // block
        nq = seq_len // tile
        fine = layout

    # every fine q row needs >= 1 active block, else the two paths diverge
    # on the empty row (dense gives a uniform softmax)
    if not fine.any(axis=-1).all():
        return None

    causal = getattr(config, "attention", None) == "unidirectional"
    masks: list = []
    mask_ids: dict = {}

    def pattern_id(sub, rel):
        """rel: "past" = tile fully before the diagonal, "diag" = the
        triangular tile (unidirectional semantics are causal at the
        ELEMENT level — the reference triton kernel's in-block masking)."""
        key_ = (sub.tobytes(), rel)
        if key_ not in mask_ids:
            expanded = np.kron(sub, np.ones((tile // sub.shape[0],
                                             tile // sub.shape[1]), np.int8))
            if rel == "diag":
                expanded = expanded * np.tril(
                    np.ones((tile, tile), np.int8))
            mask_ids[key_] = len(masks)
            masks.append(expanded.astype(np.int8))
        return mask_ids[key_]

    rows = [[[] for _ in range(nq)] for _ in range(nheads)]
    cols = [[[] for _ in range(nq)] for _ in range(nheads)]
    total = 0
    for h in range(nheads):
        for qi in range(nq):
            subrows = fine[h, qi * rq:(qi + 1) * rq] if rq > 1 else \
                fine[h, qi:qi + 1]
            for ki in range(nq):
                if causal and ki > qi:
                    continue   # entirely future: elementwise all-zero
                sub = subrows[:, ki * rq:(ki + 1) * rq] if rq > 1 else \
                    subrows[:, ki:ki + 1]
                if sub.any():
                    rel = "diag" if (causal and ki == qi) else "past"
                    pid = pattern_id(np.ascontiguousarray(sub), rel)
                    rows[h][qi].append((ki, pid))
                    cols[h][ki].append((qi, pid))
                    total += 1

    def pad(lists):
        mx = max(1, max(len(l) for hl in lists for l in hl))
        idx = np.zeros((nheads, nq, mx), np.int32)
        pid = np.zeros((nheads, nq, mx), np.int32)
        cnt = np.zeros((nheads, nq), np.int32)
        for h in range(nheads):
            for i, l in enumerate(lists[h]):
                cnt[h, i] = len(l)
                for j, (x, p) in enumerate(l):
                    idx[h, i, j] = x
                    pid[h, i, j] = p
        return idx, pid, cnt

    kv_idx, kv_pid, kv_cnt = pad(rows)
    qt_idx, qt_pid, qt_cnt = pad(cols)
    plan = LayoutPlan(kv_idx=kv_idx, kv_pid=kv_pid, kv_cnt=kv_cnt,
                      qt_idx=qt_idx, qt_pid=qt_pid, qt_cnt=qt_cnt,
                      masks=np.stack(masks), tile=tile, n_heads=nheads,
                      nq=nq, active_tiles=total, total_tiles=nheads * nq * nq)
    if key is not None:
        if len(_PLAN_CACHE) >= 16:
            _PLAN_CACHE.pop(next(iter(_PLAN_CACHE)))
        _PLAN_CACHE[key] = plan
    return plan


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _masked_scores(q, k_ref, mask_ref, ki, pid, scale, tile):
    """[tile,d]x[tile,d] scores for one active tile, fine-masked.

    q/k stay in their native dtype (bf16 hot path) so the MXU runs at its
    bf16 rate; scores accumulate fp32 via preferred_element_type."""
    k = k_ref[0, 0, pl.ds(ki * tile, tile), :]
    live = mask_ref[pid] != 0
    s = jnp.where(live, jnp.dot(q, k.T,
                                preferred_element_type=jnp.float32) * scale,
                  NEG_INF)
    return s, live, k


def _fwd_kernel(*refs, scale, d, tile, dropout_rate, total_heads):
    # refs: [idx, pid, cnt, seeds?] (SMEM) + [q, k, v, masks] + outputs
    has_drop = dropout_rate > 0.0
    (idx_ref, pid_ref, cnt_ref), rest = refs[:3], refs[3:]
    sm_ref = rest[0] if has_drop else None
    q_ref, k_ref, v_ref, mask_ref, o_ref, m_ref, l_ref = rest[1 if has_drop
                                                              else 0:]
    bi, hi, qi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    q = q_ref[0, 0]
    inv_keep = 1.0 / (1.0 - dropout_rate) if has_drop else 1.0

    def body(j, carry):
        acc, m_acc, l_acc = carry
        ki = idx_ref[hi, qi, j]
        pid = pid_ref[hi, qi, j]
        s, live, _ = _masked_scores(q, k_ref, mask_ref, ki, pid, scale, tile)
        v = v_ref[0, 0, pl.ds(ki * tile, tile), :]
        m_new = jnp.maximum(m_acc, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(live, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_acc - m_new)
        l_new = l_acc * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if has_drop:
            # same counter-based keep bits as the flash kernel: the
            # dense-mask fallback path samples identically, so the two
            # sparse paths stay bit-compatible under dropout
            keep = _tile_keep(sm_ref, bi, hi, qi * tile, ki * tile,
                              (tile, tile), dropout_rate, total_heads)
            p = jnp.where(keep, p * inv_keep, 0.0)
        acc = acc * alpha + jnp.dot(p.astype(v.dtype), v,
                                    preferred_element_type=jnp.float32)
        return acc, m_new, l_new

    acc, m, l = jax.lax.fori_loop(
        0, cnt_ref[hi, qi], body,
        (jnp.zeros((tile, d), jnp.float32),
         jnp.full((tile, 1), NEG_INF, jnp.float32),
         jnp.zeros((tile, 1), jnp.float32)))
    safe = jnp.where(l > 0.0, l, 1.0)
    o_ref[0, 0] = (acc / safe).astype(o_ref.dtype)
    m_ref[0, 0] = m
    l_ref[0, 0] = safe


def _dq_kernel(*refs, scale, d, tile, dropout_rate, total_heads):
    has_drop = dropout_rate > 0.0
    (idx_ref, pid_ref, cnt_ref), rest = refs[:3], refs[3:]
    sm_ref = rest[0] if has_drop else None
    (q_ref, k_ref, v_ref, do_ref, dl_ref, m_ref, l_ref, mask_ref,
     dq_ref) = rest[1 if has_drop else 0:]
    bi, hi, qi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    q = q_ref[0, 0]
    do = do_ref[0, 0]
    delta = dl_ref[0, 0]
    m, l = m_ref[0, 0], l_ref[0, 0]
    inv_keep = 1.0 / (1.0 - dropout_rate) if has_drop else 1.0

    def body(j, acc):
        ki = idx_ref[hi, qi, j]
        pid = pid_ref[hi, qi, j]
        s, live, k = _masked_scores(q, k_ref, mask_ref, ki, pid, scale, tile)
        v = v_ref[0, 0, pl.ds(ki * tile, tile), :]
        p = jnp.where(live, jnp.exp(s - m), 0.0) / l
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        if has_drop:
            keep = _tile_keep(sm_ref, bi, hi, qi * tile, ki * tile,
                              (tile, tile), dropout_rate, total_heads)
            dp = jnp.where(keep, dp * inv_keep, 0.0)
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        return acc + jnp.dot(ds, k, preferred_element_type=jnp.float32)

    acc = jax.lax.fori_loop(0, cnt_ref[hi, qi], body,
                            jnp.zeros((tile, d), jnp.float32))
    dq_ref[0, 0] = acc.astype(dq_ref.dtype)


def _dkv_kernel(*refs, scale, d, tile, dropout_rate, total_heads):
    has_drop = dropout_rate > 0.0
    (idx_ref, pid_ref, cnt_ref), rest = refs[:3], refs[3:]
    sm_ref = rest[0] if has_drop else None
    (q_ref, k_ref, v_ref, do_ref, dl_ref, m_ref, l_ref, mask_ref,
     dk_ref, dv_ref) = rest[1 if has_drop else 0:]
    bi, hi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    k = k_ref[0, 0]                          # this column's k tile
    v = v_ref[0, 0]
    inv_keep = 1.0 / (1.0 - dropout_rate) if has_drop else 1.0

    def body(j, carry):
        dk_acc, dv_acc = carry
        qi = idx_ref[hi, ki, j]
        pid = pid_ref[hi, ki, j]
        qs = pl.ds(qi * tile, tile)
        q = q_ref[0, 0, qs, :]
        do = do_ref[0, 0, qs, :]
        delta = dl_ref[0, 0, qs, :]
        m = m_ref[0, 0, qs, :]
        l = l_ref[0, 0, qs, :]
        live = mask_ref[pid] != 0
        s = jnp.where(live, jnp.dot(q, k.T,
                                    preferred_element_type=jnp.float32)
                      * scale, NEG_INF)
        p = jnp.where(live, jnp.exp(s - m), 0.0) / l
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        if has_drop:
            keep = _tile_keep(sm_ref, bi, hi, qi * tile, ki * tile,
                              (tile, tile), dropout_rate, total_heads)
            dfac = jnp.where(keep, inv_keep, 0.0)
            dp = dp * dfac
            pl_ = (p * dfac).astype(do.dtype)
        else:
            pl_ = p.astype(do.dtype)
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        dk_acc = dk_acc + jnp.dot(ds.T, q, preferred_element_type=jnp.float32)
        dv_acc = dv_acc + jnp.dot(pl_.T, do, preferred_element_type=jnp.float32)
        return dk_acc, dv_acc

    dk_acc, dv_acc = jax.lax.fori_loop(
        0, cnt_ref[hi, ki], body,
        (jnp.zeros((tile, d), jnp.float32),
         jnp.zeros((tile, d), jnp.float32)))
    dk_ref[0, 0] = dk_acc.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv_acc.astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------

def _specs(d, S, U, tile):
    tile_q = pl.BlockSpec((1, 1, tile, d),
                          lambda bi, hi, qi, *_: (bi, hi, qi, 0))
    full_kv = pl.BlockSpec((1, 1, S, d), lambda bi, hi, qi, *_: (bi, hi, 0, 0))
    stat_q = pl.BlockSpec((1, 1, tile, 1),
                          lambda bi, hi, qi, *_: (bi, hi, qi, 0))
    full_stat = pl.BlockSpec((1, 1, S, 1),
                             lambda bi, hi, qi, *_: (bi, hi, 0, 0))
    masks = pl.BlockSpec((U, tile, tile), lambda bi, hi, qi, *_: (0, 0, 0))
    return tile_q, full_kv, stat_q, full_stat, masks


def _drop_args(seeds):
    """(extra scalar-prefetch operands, n_scalar, static kwargs pieces)."""
    return ((seeds,), 4) if seeds is not None else ((), 3)


def _sparse_fwd(q, k, v, masks, idx, pid, cnt, scale, tile, seeds=None,
                dropout_rate=0.0, total_heads=1):
    b, h, S, d = q.shape
    U = masks.shape[0]
    tile_q, full_kv, stat_q, _, mask_spec = _specs(d, S, U, tile)
    extra, nsp = _drop_args(seeds)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=nsp,
        grid=(b, h, S // tile),
        in_specs=[tile_q, full_kv, full_kv, mask_spec],
        out_specs=[tile_q, stat_q, stat_q])
    o, m, l = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, d=d, tile=tile,
                          dropout_rate=dropout_rate if seeds is not None
                          else 0.0, total_heads=total_heads),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, h, S, 1), jnp.float32),
                   jax.ShapeDtypeStruct((b, h, S, 1), jnp.float32)),
        interpret=_interpret(),
    )(idx, pid, cnt, *extra, q, k, v, masks)
    return o, m, l


def _sparse_dq(q, k, v, do, delta, m, l, masks, idx, pid, cnt, scale, tile,
               seeds=None, dropout_rate=0.0, total_heads=1):
    b, h, S, d = q.shape
    U = masks.shape[0]
    tile_q, full_kv, stat_q, _, mask_spec = _specs(d, S, U, tile)
    extra, nsp = _drop_args(seeds)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=nsp,
        grid=(b, h, S // tile),
        in_specs=[tile_q, full_kv, full_kv, tile_q, stat_q, stat_q, stat_q,
                  mask_spec],
        out_specs=[tile_q])
    (dq,) = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, d=d, tile=tile,
                          dropout_rate=dropout_rate if seeds is not None
                          else 0.0, total_heads=total_heads),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype),),
        interpret=_interpret(),
    )(idx, pid, cnt, *extra, q, k, v, do, delta, m, l, masks)
    return dq


def _sparse_dkv(q, k, v, do, delta, m, l, masks, idx, pid, cnt, scale, tile,
                seeds=None, dropout_rate=0.0, total_heads=1):
    b, h, S, d = q.shape
    U = masks.shape[0]
    _, full_kv, _, full_stat, mask_spec = _specs(d, S, U, tile)
    tile_k = pl.BlockSpec((1, 1, tile, d),
                          lambda bi, hi, ki, *_: (bi, hi, ki, 0))
    extra, nsp = _drop_args(seeds)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=nsp,
        grid=(b, h, S // tile),
        in_specs=[full_kv, tile_k, tile_k, full_kv, full_stat, full_stat,
                  full_stat, mask_spec],
        out_specs=[tile_k, tile_k])
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, d=d, tile=tile,
                          dropout_rate=dropout_rate if seeds is not None
                          else 0.0, total_heads=total_heads),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)),
        interpret=_interpret(),
    )(idx, pid, cnt, *extra, q, k, v, do, delta, m, l, masks)
    return dk, dv


@functools.lru_cache(maxsize=16)
def _build_sparse_fn(plan_key, scale, dropout_rate, total_heads):
    """custom_vjp'd BHSD sparse attention bound to one compiled plan.
    The plan's arrays are jit constants (they ARE the program). With
    dropout_rate > 0 the function takes a seeds operand (int32[4]:
    [seed0, seed1, head_offset, batch_offset]) feeding the in-kernel
    counter-based keep hash shared with the flash kernel."""
    plan = _PLAN_CACHE[plan_key]
    # numpy, not jnp: this builder is cached and is first called under
    # whichever jit happens to trace it — a jnp array made here would be
    # that trace's tracer, leaked into every later one
    masks = plan.masks
    kv = (plan.kv_idx, plan.kv_pid, plan.kv_cnt)
    qt = (plan.qt_idx, plan.qt_pid, plan.qt_cnt)
    dkw = dict(dropout_rate=dropout_rate, total_heads=total_heads)

    @jax.custom_vjp
    def fn(q, k, v, seeds):
        o, _, _ = _sparse_fwd(q, k, v, masks, *kv, scale, plan.tile,
                              seeds=seeds, **dkw)
        return o

    def fwd(q, k, v, seeds):
        o, m, l = _sparse_fwd(q, k, v, masks, *kv, scale, plan.tile,
                              seeds=seeds, **dkw)
        return o, (q, k, v, seeds, o, m, l)

    def bwd(res, g):
        q, k, v, seeds, o, m, l = res
        delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1, keepdims=True)
        dq = _sparse_dq(q, k, v, g, delta, m, l, masks, *kv, scale,
                        plan.tile, seeds=seeds, **dkw)
        dk, dv = _sparse_dkv(q, k, v, g, delta, m, l, masks, *qt, scale,
                             plan.tile, seeds=seeds, **dkw)
        dseeds = (np.zeros(seeds.shape, jax.dtypes.float0)
                  if seeds is not None else None)
        return dq, dk, dv, dseeds

    fn.defvjp(fwd, bwd)
    return fn


def block_sparse_attention(q, k, v, sparsity_config, *, softmax_scale=None,
                           dropout_rate=0.0, dropout_rng=None,
                           dropout_offsets=None):
    """q/k/v: [batch, seq, heads, head_dim] (BSHD). Sparse Pallas path;
    returns None when the layout can't be tiled (caller falls back).
    Attention-probability dropout (reference: the Triton softmax kernel's
    fused dropout) samples the flash kernel's position-keyed hash —
    active when both dropout_rate and dropout_rng are set."""
    b, s, h, d = q.shape
    plan = compile_layout(sparsity_config, s)
    if plan is None or plan.n_heads != h:
        return None
    try:
        plan_key = (sparsity_config.cache_key(), s)
    except TypeError:
        return None   # uncacheable config: dense fallback
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(d)
    rate, seeds, total_heads = resolve_dropout(
        dropout_rate, dropout_rng, dropout_offsets, h)
    fn = _build_sparse_fn(plan_key, float(scale), rate, total_heads)
    qt, kt, vt = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
    o = fn(qt, kt, vt, seeds)
    return jnp.swapaxes(o, 1, 2)
