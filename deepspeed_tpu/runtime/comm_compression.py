"""Communication-compressed optimization (1-bit Adam family).

Reference: runtime/fp16/onebit/{adam,lamb,zoadam}.py over the compressed
allreduce in runtime/comm/nccl.py:51 — after a warmup of exact Adam, the
variance term is frozen and the *momentum* is communicated as 1-bit signs
+ a scale, with the quantization error fed back into the next step
(error-feedback compression).

TPU mapping: XLA already reduces gradients in-network over ICI, so the
wire format of the default path is not ours to change. What this module
provides:

- ``compressed_allreduce(x, axis_name)``: the 1-bit collective itself
  (sign + mean-|x| scale, psum of signs, error feedback returned to the
  caller) for shard_map-based pipelines that own their collectives —
  the EQuARX-style quantized-collective analog.
- ``onebit_adam(...)``: an optax GradientTransformation implementing the
  reference's optimizer math: exact Adam during warmup, then frozen
  variance + error-feedback sign compression of the momentum. The
  compression error lives in the transform state, so convergence behavior
  matches the reference even where the transport is XLA's.
"""

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
import optax

from ..comm.comm import _note_collective, _payload_nbytes


def compress_1bit(x, error):
    """Error-feedback sign compression: returns (signs, scale, new_error).
    corrected = x + error; scale = mean(|corrected|); decompressed =
    scale * sign(corrected); new_error = corrected - decompressed
    (reference: nccl.py compressed_allreduce's server/worker error)."""
    corrected = x + error
    scale = jnp.mean(jnp.abs(corrected))
    signs = jnp.sign(corrected)
    signs = jnp.where(signs == 0, 1.0, signs)  # sign(0) -> +1, like packbits
    new_error = corrected - scale * signs
    return signs, scale, new_error


def _sign_wire_dtype(n):
    """Wire dtype for the sign psum. bf16 (half the fp32 bytes) carries
    partial sums of ±1 EXACTLY only while they fit 8 significand bits —
    integers through 256; at 257 participants a ring partial sum can land
    on a non-representable odd integer and silently round. Past that the
    signs ship fp32 (correctness over compression; chunking the axis
    would preserve the ratio but no current mesh is that deep). ``n`` is
    static (lax.psum of a python int) under shard_map/pmap; a traced size
    conservatively gets fp32."""
    if isinstance(n, int) and n <= 256:
        return jnp.bfloat16
    return jnp.float32


def compressed_allreduce(x, error, axis_name: str):
    """1-bit mean-allreduce inside shard_map/pmap: TWO psums actually on
    the wire — the sign tensor (bf16 while the axis size keeps the ±1
    partial sums exactly representable, see ``_sign_wire_dtype``) and one
    fp32 scalar. Result = mean_scale * mean_sign — the mean-scale
    approximation of mean_i(scale_i*sign_i) (exact when scales agree,
    e.g. axis size 1 or homogeneous shards). Error feedback compensates
    against the value the aggregate ACTUALLY used on this worker's
    behalf, mean_scale*sign_i — i.e. the per-worker aggregation residual
    (scale_i - mean_scale)*sign_i is folded into the carried error
    alongside the local quantization residual, so the mean-scale
    approximation error is re-injected (and corrected) on later steps
    instead of silently accumulating. Returns (reduced, new_error).

    NOTE: upcasting signs to fp32 before the psum (when bf16 is exact)
    would silently ship full fp32 traffic — the whole point of the
    compression (r5 review)."""
    n = lax.psum(1, axis_name)
    corrected = x + error
    scale = jnp.mean(jnp.abs(corrected))
    signs = jnp.sign(corrected)
    signs = jnp.where(signs == 0, 1.0, signs)  # sign(0) -> +1, like packbits
    wire_signs = signs.astype(_sign_wire_dtype(n))
    # wire accounting: the sign tensor in its WIRE dtype plus one fp32
    # scalar — the whole point of the compression is that this is what
    # ships, so this is what the collective accountant records
    wire_bytes = _payload_nbytes(wire_signs) + 4
    _note_collective("compressed_allreduce", axis_name, wire_signs,
                     nbytes=wire_bytes)
    summed_signs = lax.psum(wire_signs, axis_name).astype(jnp.float32)
    mean_scale = lax.psum(scale, axis_name) / n
    # EF identity per worker: mean_scale*sign_i + new_error_i == x_i + e_i
    new_error = corrected - mean_scale * signs
    return mean_scale * summed_signs / n, new_error


def int8_compressed_allreduce(x, error, axis_name: str, chunk: int = 256):
    """int8 mean-allreduce inside shard_map (pattern: EQuARX — quantized
    AllReduce in XLA, PAPERS.md — and the reference's quantized-gradient
    backends): both wire phases carry int8 + per-chunk fp32 scales, a 4x
    comm-volume cut vs fp32.

    reduce-scatter phase: each participant splits its (error-corrected)
    tensor into N shards, quantizes per ``chunk`` elements, and
    all-to-alls the int8 shards; every participant dequantizes the N
    received shards and sums them in fp32. all-gather phase: the reduced
    shard is re-quantized and all-gathered int8. Error feedback keeps
    the phase-1 quantization residual local, like compress_1bit.
    Returns (mean-reduced x, new_error)."""
    n = lax.psum(1, axis_name)
    flat = x.reshape(-1) + error.reshape(-1)
    size = flat.shape[0]
    pad = (-size) % (n * chunk)
    flat = jnp.pad(flat, (0, pad))

    def quant(v):                       # v [..., chunk] -> int8 + scale
        c = v.reshape(*v.shape[:-1], -1, chunk)
        scale = jnp.max(jnp.abs(c), axis=-1, keepdims=True) / 127.0
        scale = jnp.maximum(scale, 1e-12)
        q = jnp.clip(jnp.round(c / scale), -127, 127).astype(jnp.int8)
        return q, scale.astype(jnp.float32)

    def dequant(q, scale):
        return (q.astype(jnp.float32) * scale).reshape(
            *q.shape[:-2], q.shape[-2] * chunk)

    parts = flat.reshape(n, -1)          # my contribution, one row/peer
    q, s = quant(parts)
    new_error = (flat - dequant(q, s).reshape(-1))[:size].reshape(x.shape)
    # exchange: row j goes to participant j (int8 + scales on the wire);
    # each phase records its ACTUAL wire payload (int8 tensors + fp32
    # scales) in the collective accountant — the 4x comm-volume cut vs
    # fp32 is visible in comm/traced_bytes, not just claimed
    _note_collective("int8_allreduce", axis_name, q,
                     nbytes=_payload_nbytes(q) + _payload_nbytes(s))
    qx = lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0,
                        tiled=True)
    sx = lax.all_to_all(s, axis_name, split_axis=0, concat_axis=0,
                        tiled=True)
    my_shard = dequant(qx, sx).sum(axis=0)          # fp32 accumulate
    q2, s2 = quant(my_shard)                        # re-quantize reduced
    _note_collective("int8_allreduce", axis_name, q2,
                     nbytes=_payload_nbytes(q2) + _payload_nbytes(s2))
    qg = lax.all_gather(q2, axis_name, tiled=True)
    sg = lax.all_gather(s2, axis_name, tiled=True)
    out = dequant(qg, sg)[: size] / n
    return out.reshape(x.shape), new_error


def _map_compressed(warm, compress, mu, error):
    """Per-leaf (used_momentum, new_error) under a traced warm/frozen
    switch. The pair rides as a {"m","e"} DICT, not a tuple — a tuple
    marker would misfire on params pytrees whose containers are
    themselves tuples (optax allows them), grabbing a subtree as a
    'pair'."""
    pairs = jax.tree.map(
        lambda m, e: jax.lax.cond(
            warm, lambda me: {"m": me["m"], "e": me["e"]},
            lambda me: dict(zip(("m", "e"), compress(me["m"], me["e"]))),
            {"m": m, "e": e}),
        mu, error)
    is_pair = lambda x: isinstance(x, dict) and set(x) == {"m", "e"}
    return (jax.tree.map(lambda p: p["m"], pairs, is_leaf=is_pair),
            jax.tree.map(lambda p: p["e"], pairs, is_leaf=is_pair))


class OneBitAdamState(NamedTuple):
    count: jnp.ndarray
    mu: optax.Updates        # momentum (the compressed quantity)
    nu: optax.Updates        # variance — frozen after warmup
    error: optax.Updates     # error-feedback residual


def onebit_adam(learning_rate, b1: float = 0.9, b2: float = 0.999,
                eps: float = 1e-8, weight_decay: float = 0.0,
                freeze_step: int = 100) -> optax.GradientTransformation:
    """1-bit Adam (reference: OnebitAdam, onebit/adam.py:10): exact Adam
    for ``freeze_step`` warmup steps, then the variance stops updating and
    the momentum passes through error-feedback 1-bit quantization."""

    def init_fn(params):
        z = lambda: jax.tree.map(jnp.zeros_like, params)
        return OneBitAdamState(jnp.zeros((), jnp.int32), z(), z(), z())

    def update_fn(grads, state, params=None):
        count = state.count + 1
        warm = count <= freeze_step

        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state.mu, grads)
        nu = jax.tree.map(
            lambda v, g: jnp.where(warm, b2 * v + (1 - b2) * g * g, v),
            state.nu, grads)

        # after warmup: quantize momentum with error feedback (the values
        # the reference would put on the wire)
        def compress(m, e):
            signs, scale, new_e = compress_1bit(m, e)
            return scale * signs, new_e

        mu_used, error = _map_compressed(warm, compress, mu, state.error)

        bc1 = 1 - b1 ** count.astype(jnp.float32)
        bc2 = 1 - b2 ** jnp.minimum(count, freeze_step).astype(jnp.float32)
        lr = learning_rate(count) if callable(learning_rate) else learning_rate

        if weight_decay and params is None:
            raise ValueError(
                "onebit_adam with weight_decay > 0 needs params (call "
                "update(grads, state, params) — decaying anything else "
                "would be silently wrong)")

        def upd(m, v, p):
            step = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
            if weight_decay:
                step = step + weight_decay * p
            return -lr * step

        updates = jax.tree.map(upd, mu_used, nu,
                               params if params is not None else mu_used)
        return updates, OneBitAdamState(count, mu, nu, error)

    return optax.GradientTransformation(init_fn, update_fn)


def zero_one_adam(learning_rate, b1=0.9, b2=0.999, eps=1e-8,
                  weight_decay=0.0, var_freeze_step: int = 100,
                  var_update_scaler: int = 16,
                  local_step_scaler: int = 1000):
    """0/1 Adam (reference: ZeroOneAdam, onebit/zoadam.py:10): like 1-bit
    Adam but the variance keeps refreshing at a DECAYED cadence after the
    freeze point — intervals start at ``var_update_scaler`` and double
    each refresh (the paper's k_{j+1} = 2 k_j policy), capped at
    ``local_step_scaler`` (fixed cadence from there on). The schedule is
    static, so the traced predicate is a small OR over precomputed
    refresh steps."""

    base = onebit_adam(learning_rate, b1, b2, eps, weight_decay,
                       freeze_step=var_freeze_step)

    # refresh offsets past the freeze point: S, S+2S, S+2S+4S, ... with
    # the interval capped at local_step_scaler
    thresholds = []
    t, interval = 0, var_update_scaler
    while interval < local_step_scaler:
        t += interval
        thresholds.append(t)
        interval *= 2
    cap_anchor = thresholds[-1] if thresholds else 0

    def init_fn(params):
        return base.init(params)

    def update_fn(grads, state, params=None):
        count = state.count + 1
        t_post = count - var_freeze_step
        refresh = jnp.asarray(False)
        for th in thresholds:
            refresh = jnp.logical_or(refresh, t_post == th)
        refresh = jnp.logical_or(
            refresh,
            jnp.logical_and(t_post > cap_anchor,
                            (t_post - cap_anchor) % local_step_scaler == 0))
        refresh = jnp.logical_and(t_post > 0, refresh)
        # borrow the 1-bit step, then optionally refresh the variance
        updates, new_state = base.update(grads, state, params)
        nu = jax.tree.map(
            lambda v, g: jnp.where(refresh, b2 * v + (1 - b2) * g * g, v),
            new_state.nu, grads)
        return updates, new_state._replace(nu=nu)

    return optax.GradientTransformation(init_fn, update_fn)


class OneBitLambState(NamedTuple):
    count: jnp.ndarray
    mu: optax.Updates        # momentum (compressed after warmup)
    nu: optax.Updates        # variance — frozen after warmup
    error: optax.Updates     # error-feedback residual
    frozen_ratio: optax.Updates  # per-leaf trust ratio captured at freeze


def onebit_lamb(learning_rate, b1: float = 0.9, b2: float = 0.999,
                eps: float = 1e-6, weight_decay: float = 0.0,
                freeze_step: int = 100) -> optax.GradientTransformation:
    """1-bit LAMB (reference: OnebitLamb, onebit/lamb.py:11): exact LAMB
    during warmup while recording each layer's trust ratio; after
    ``freeze_step`` the variance stops updating, the momentum passes
    through error-feedback 1-bit quantization (the wire format of the
    reference's compressed allreduce), and the per-layer trust ratios are
    FROZEN at their last warmup value — the reference's 'fused scaling
    coefficients', which cannot be recomputed from compressed momentum."""

    def init_fn(params):
        z = lambda: jax.tree.map(jnp.zeros_like, params)
        ones = jax.tree.map(lambda p: jnp.ones((), jnp.float32), params)
        return OneBitLambState(jnp.zeros((), jnp.int32), z(), z(), z(), ones)

    def update_fn(grads, state, params=None):
        assert params is not None, "onebit_lamb requires params"
        count = state.count + 1
        warm = count <= freeze_step

        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state.mu, grads)
        nu = jax.tree.map(
            lambda v, g: jnp.where(warm, b2 * v + (1 - b2) * g * g, v),
            state.nu, grads)

        def compress(m, e):
            signs, scale, new_e = compress_1bit(m, e)
            return scale * signs, new_e

        mu_used, error = _map_compressed(warm, compress, mu, state.error)

        bc1 = 1 - b1 ** count.astype(jnp.float32)
        bc2 = 1 - b2 ** jnp.minimum(count, freeze_step).astype(jnp.float32)
        lr = learning_rate(count) if callable(learning_rate) else learning_rate

        def leaf_update(m, v, p, fr):
            u = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
            if weight_decay:
                u = u + weight_decay * p
            p_norm = jnp.sqrt(jnp.sum(jnp.square(p)))
            u_norm = jnp.sqrt(jnp.sum(jnp.square(u)))
            live_ratio = jnp.where((p_norm > 0.0) & (u_norm > 0.0),
                                   p_norm / jnp.maximum(u_norm, 1e-30), 1.0)
            # the applied ratio IS the carried state: captured live while
            # warm, frozen (reused) afterwards
            ratio = jnp.where(warm, live_ratio, fr)
            return {"u": -lr * ratio * u, "r": ratio}

        outs = jax.tree.map(leaf_update, mu_used, nu, params,
                            state.frozen_ratio)
        is_out = lambda x: isinstance(x, dict) and set(x) == {"u", "r"}
        updates = jax.tree.map(lambda o: o["u"], outs, is_leaf=is_out)
        frozen = jax.tree.map(lambda o: o["r"], outs, is_leaf=is_out)
        return updates, OneBitLambState(count, mu, nu, error, frozen)

    return optax.GradientTransformation(init_fn, update_fn)
