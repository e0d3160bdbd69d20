"""Weight-only int8 matmul: fused in-kernel dequantization.

Reference: the int8 inference gemms (csrc/transformer/inference/csrc/
pt_binding.cpp:1197-1244 qkv_gemm_int8 / mlp_gemm_int8 / vector_matmul_int8)
— activations stay half precision, weights are stored int8 with
per-output-channel scales and dequantized inside the gemm.

Why a kernel instead of `x @ (q * scale).astype(bf16)`: inside a jitted
decode loop XLA hoists that loop-invariant dequantization out of the
`lax.scan`, materializing the full bf16 weight copy in HBM — doubling
weight memory (fatal for 6.7B-class serving on a 16 GB chip) and reading
bf16 bytes every step. This kernel reads int8 HBM bytes (half the
bandwidth of bf16 — decode is weight-bandwidth-bound) and converts
tile-by-tile in VMEM.

Grid (m_blocks, n_blocks, k_blocks), k innermost; fp32 accumulator
scratch persists across the k walk; the per-channel scale multiplies the
accumulated tile once at the end (x @ (q·s) == (x @ q)·s for per-n
scales). Decode (m small) runs one m-block exactly as before; prefill
(m large) tiles the row dim so long prompts stay int8-resident too —
no full bf16 weight copy ever lands in HBM.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import tuning
from ._common import interpret_mode as _interpret
from ._common import log_fallback_on_tpu

DEFAULT_BLOCK_M = 512
DEFAULT_BLOCK_N = 512
DEFAULT_BLOCK_K = 1024

# decode (m=1) GEMV tiles: bigger than the matmul tiles — the VPU path
# has no MXU residency pressure and wants long HBM bursts
GEMV_BLOCK_N = 2048
GEMV_BLOCK_K = 1024


def _gemv_enabled() -> bool:
    """The m=1 VPU GEMV is numerically proven (interpret-mode parity
    across the shape matrix) but has not been timed against the MXU path
    on a chip, so the default stays the MXU path; ``DS_TPU_INT8_GEMV=1``
    routes m=1 through it. ROADMAP item 1.3 times both and keeps the
    winner, selected from the shape."""
    from ...utils import env_flag
    return env_flag("DS_TPU_INT8_GEMV")


def _kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *, n_kb, out_dtype):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                       # [bm, bk] activation dtype
    w = q_ref[...].astype(x.dtype)       # int8 -> activation dtype (VPU)
    acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(ki == n_kb - 1)
    def _emit():
        o_ref[...] = (acc_ref[...] * s_ref[...].astype(jnp.float32)) \
            .astype(out_dtype)


def _gemv_kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *, n_kb, out_dtype):
    """Decode GEMV on the VPU. With m=1 the MXU path is bound by weight
    ingestion into the systolic array (~146 GB/s measured on v5e,
    2026-07-31 — the array loads weights at a fixed rate no matter how
    few rows flow through), not by HBM. Elementwise multiply + sublane
    reduction reads the same int8 bytes but never touches the MXU.
    ``x`` arrives as a COLUMN [bk, 1] so the product broadcasts along
    lanes; an in-kernel [1,bk]->[bk,1] transpose would be a cross-vreg
    shuffle Mosaic compiles catastrophically (hung the backend when
    tried — ``chip_smoke.py`` runs this kernel under a timeout)."""
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xc = x_ref[...].astype(jnp.float32)          # [bk, 1]
    w = q_ref[...].astype(jnp.float32)           # [bk, bn] int8 -> f32
    acc_ref[...] += jnp.sum(xc * w, axis=0, keepdims=True)

    @pl.when(ki == n_kb - 1)
    def _emit():
        o_ref[...] = (acc_ref[...] * s_ref[...].astype(jnp.float32)) \
            .astype(out_dtype)


def _wo_int8_gemv(x, q, scale, block_n, block_k, out_dtype):
    """m=1 fast path: grid (n_blocks, k_blocks), k innermost; fp32
    accumulator row persists across the k walk."""
    from ._common import pick_block
    k, n = q.shape
    block_n = pick_block(n, block_n)
    block_k = pick_block(k, block_k)
    if block_n * block_k > 8 * 2 ** 20:
        # ragged dims forced a >8MB VMEM weight tile (pick_block always
        # returns a divisor, so e.g. a 50257-vocab head yields the whole
        # dim) — fall back to the matmul path, which has its own guard
        return None
    n_kb = k // block_k
    grid = (n // block_n, n_kb)
    return pl.pallas_call(
        functools.partial(_gemv_kernel, n_kb=n_kb, out_dtype=out_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_k, 1), lambda ni, ki: (ki, 0)),
            pl.BlockSpec((block_k, block_n), lambda ni, ki: (ki, ni)),
            pl.BlockSpec((1, block_n), lambda ni, ki: (0, ni)),
        ],
        out_specs=pl.BlockSpec((1, block_n), lambda ni, ki: (0, ni)),
        out_shape=jax.ShapeDtypeStruct((1, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((1, block_n), jnp.float32)],
        interpret=_interpret(),
    )(x.reshape(k, 1), q, scale.reshape(1, n))


def _wo_int8_2d(x, q, scale, block_m, block_n, block_k, out_dtype):
    from ._common import pick_block
    m, k = x.shape
    _, n = q.shape
    block_n = pick_block(n, block_n)
    block_k = pick_block(k, block_k)
    if n % block_n or k % block_k:
        return None   # caller falls back
    if block_n * block_k > 8 * 2 ** 20:
        return None   # ragged dims forced a >8MB VMEM weight tile
    # decode: one row-block of exactly m; prefill: tile m. Prefer an
    # aligned divisor of m (no padding, no extra x round-trip); only a
    # ragged m with no VMEM-sized divisor pays a zero-padded tail (rows
    # are independent — padding contributes nothing and is sliced off).
    block_m = min(block_m, m)
    bm = pick_block(m, block_m)
    if bm <= 2 * block_m:   # caller's block_m is the VMEM budget
        block_m, pad_m = bm, 0
    else:
        pad_m = (-m) % block_m
        x = jnp.pad(x, ((0, pad_m), (0, 0)))
    m_pad = m + pad_m
    n_kb = k // block_k
    grid = (m_pad // block_m, n // block_n, n_kb)
    out = pl.pallas_call(
        functools.partial(_kernel, n_kb=n_kb, out_dtype=out_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((block_k, block_n), lambda mi, ni, ki: (ki, ni)),
            pl.BlockSpec((1, block_n), lambda mi, ni, ki: (0, ni)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((m_pad, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=_interpret(),
    )(x, q, scale.reshape(1, n))
    return out[:m] if pad_m else out


def wo_int8_matmul(x, q, scale, *, block_m=None, block_n=None,
                   block_k=None, out_dtype=None):
    """``x @ (q * scale)`` with int8 ``q`` dequantized in-kernel.

    x: [..., k] activations (bf16/f32); q: [k, n] int8; scale: per-output
    -channel, any shape broadcastable to [1, n] (module_quantize stores
    [1, n]). Returns [..., n] in ``out_dtype`` (default: x.dtype).
    Any m is supported (decode m=1 through long-prompt prefill — the m
    dim is tiled at ``block_m`` with zero-padded ragged tails).

    ``block_*``: VMEM tile budget knobs. Defaults differ per path
    (decode GEMV wants longer tiles than the MXU matmul), so None means
    "the path's default"; an explicit value is honored on both paths.

    Shapes the kernel cannot tile (ragged dims forcing an oversized
    VMEM tile) fall back to the jnp dequant matmul — numerically
    identical, but subject to XLA's loop hoisting; serving-size models
    are always 128-aligned in practice.
    """
    out_dtype = out_dtype or x.dtype
    k, n = q.shape
    lead = x.shape[:-1]
    x2 = x.reshape(-1, k)
    scale = jnp.asarray(scale).reshape(-1)
    if scale.size == 1:
        scale = jnp.broadcast_to(scale, (n,))
    if scale.size != n:
        raise ValueError(f"scale has {scale.size} elements for n={n}")
    out, impl, reason = None, "gemv", None
    if x2.shape[0] == 1 and _gemv_enabled():
        out = _wo_int8_gemv(x2, q, scale, block_n or GEMV_BLOCK_N,
                            block_k or GEMV_BLOCK_K, out_dtype)
    if out is None:
        impl = "mxu"
        out = _wo_int8_2d(x2, q, scale, block_m or DEFAULT_BLOCK_M,
                          block_n or DEFAULT_BLOCK_N,
                          block_k or DEFAULT_BLOCK_K, out_dtype)
    if out is None:
        impl = "dense"
        reason = f"[{k}, {n}] weight has no 128-aligned tiling under 8MB"
        log_fallback_on_tpu("wo_int8_matmul", "jnp dequant", reason)
        w = (q.astype(jnp.float32) * scale[None, :]).astype(x.dtype)
        out = jnp.dot(x2, w, preferred_element_type=jnp.float32) \
            .astype(out_dtype)
    tuning.record_dispatch("wo_int8_matmul", "m1" if x2.shape[0] == 1
                           else "mN", f"m{x2.shape[0]}_k{k}_n{n}", None,
                           impl=impl, reason=reason)
    return out.reshape(*lead, n)
