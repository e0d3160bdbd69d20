"""Fused LAMB Pallas kernel.

TPU-native replacement for the reference's fused LAMB
(csrc/lamb/fused_lamb_cuda.cpp:108 + fused_lamb_cuda_kernel.cu): LAMB is
Adam plus a per-layer trust ratio ||p|| / ||update||, which the CUDA
kernel computes with in-kernel block reductions. Here phase 1 is one
fused pass that updates the moments, forms the Adam-style update AND
accumulates the squared-norm partials per grid block (the in-kernel
reduction); phase 2 — scaling by lr * trust_ratio — is a trivially fused
elementwise op left to XLA.

Math matches optax.lamb exactly (scale_by_adam -> add_decayed_weights ->
scale_by_trust_ratio -> scale(-lr)), proven by the parity test.
"""

import functools
from typing import NamedTuple, Union, Callable

import jax
import jax.numpy as jnp
import optax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import interpret_mode as _interpret

BLOCK = 1024 * 128
LANE = 128
_PART_TILE = (8, LANE)   # one fp32 tile per norm partial


def _lamb_phase1_kernel(p_ref, g_ref, m_ref, v_ref, sc_ref,
                        u_ref, new_m_ref, new_v_ref, pn_ref, un_ref,
                        *, b1, b2, eps, wd):
    c1 = sc_ref[0]   # 1/(1-b1^t)
    c2 = sc_ref[1]   # 1/(1-b2^t)
    g = g_ref[:].astype(jnp.float32)
    p = p_ref[:].astype(jnp.float32)
    m = b1 * m_ref[:] + (1.0 - b1) * g
    v = b2 * v_ref[:] + (1.0 - b2) * g * g
    u = (m * c1) / (jnp.sqrt(v * c2) + eps) + wd * p
    u_ref[:] = u
    new_m_ref[:] = m
    new_v_ref[:] = v
    # in-kernel norm reduction partials, one per grid block. Mosaic
    # cannot store a scalar to VMEM, so each partial fills one (8, 128)
    # tile and the caller reads a single element of it.
    pn_ref[:] = jnp.full(pn_ref.shape, jnp.sum(p * p), jnp.float32)
    un_ref[:] = jnp.full(un_ref.shape, jnp.sum(u * u), jnp.float32)


def _lamb_phase1_flat(p, g, m, v, scalars, *, b1, b2, eps, wd):
    n = p.shape[0]
    rows = BLOCK // LANE
    block_rows = min(rows, n)
    # pad the ragged last block with explicit zeros: the in-kernel norm
    # reductions would otherwise fold Pallas's UNSPECIFIED out-of-bounds
    # padding into p_norm/u_norm (zeros are exact — they add nothing)
    pad_rows = (-n) % block_rows
    if pad_rows:
        p, g, m, v = (jnp.pad(x, ((0, pad_rows), (0, 0)))
                      for x in (p, g, m, v))
        n = n + pad_rows
    grid = (pl.cdiv(n, block_rows),)
    spec = pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))
    part = pl.BlockSpec(_PART_TILE, lambda i: (i, 0))
    nblocks = grid[0]
    part_shape = jax.ShapeDtypeStruct(
        (nblocks * _PART_TILE[0], _PART_TILE[1]), jnp.float32)
    out_shape = (jax.ShapeDtypeStruct(p.shape, jnp.float32),     # u
                 jax.ShapeDtypeStruct(m.shape, jnp.float32),
                 jax.ShapeDtypeStruct(v.shape, jnp.float32),
                 part_shape, part_shape)
    u, nm, nv, pn, un = pl.pallas_call(
        functools.partial(_lamb_phase1_kernel, b1=b1, b2=b2, eps=eps, wd=wd),
        grid=grid,
        in_specs=[spec, spec, spec, spec,
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=(spec, spec, spec, part, part),
        out_shape=out_shape,
        input_output_aliases={2: 1, 3: 2},
        interpret=_interpret(),
    )(p, g, m, v, scalars)
    return u, nm, nv, pn[::_PART_TILE[0], 0], un[::_PART_TILE[0], 0]


class FusedLambState(NamedTuple):
    count: jnp.ndarray
    mu: optax.Updates
    nu: optax.Updates


def fused_lamb(learning_rate: Union[float, Callable] = 1e-3,
               b1: float = 0.9, b2: float = 0.999, eps: float = 1e-6,
               weight_decay: float = 0.0) -> optax.GradientTransformation:
    """Drop-in for optax.lamb backed by the fused Pallas phase-1 kernel."""

    def init(params):
        return FusedLambState(
            count=jnp.zeros((), jnp.int32),
            mu=jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
            nu=jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params))

    def update(grads, state, params=None):
        assert params is not None, "fused_lamb requires params"
        count = state.count + 1
        lr = learning_rate(count) if callable(learning_rate) else learning_rate
        c1 = 1.0 / (1.0 - b1 ** count.astype(jnp.float32))
        c2 = 1.0 / (1.0 - b2 ** count.astype(jnp.float32))
        scalars = jnp.stack([c1, c2])

        def one(p, g, m, v):
            shape, dt = p.shape, p.dtype
            n = max(1, int(jnp.size(p)))
            pad = (-n) % LANE

            def flat(x, xdt):
                f = x.reshape(-1).astype(xdt)
                if pad:
                    f = jnp.pad(f, (0, pad))
                return f.reshape(-1, LANE)

            fu, nm, nv, pn, un = _lamb_phase1_flat(
                flat(p, jnp.float32), flat(g, jnp.float32),
                flat(m, jnp.float32), flat(v, jnp.float32), scalars,
                b1=b1, b2=b2, eps=eps, wd=weight_decay)
            p_norm = jnp.sqrt(jnp.sum(pn))
            u_norm = jnp.sqrt(jnp.sum(un))
            # optax scale_by_trust_ratio: zero norms -> ratio 1
            trust = jnp.where((p_norm > 0.0) & (u_norm > 0.0),
                              p_norm / jnp.maximum(u_norm, 1e-30), 1.0)
            unflat = lambda x: x.reshape(-1)[:n].reshape(shape)
            upd = (-lr * trust * unflat(fu)).astype(dt)
            return upd, unflat(nm), unflat(nv)

        p_leaves, treedef = jax.tree.flatten(params)
        g_leaves = treedef.flatten_up_to(grads)
        m_leaves = treedef.flatten_up_to(state.mu)
        v_leaves = treedef.flatten_up_to(state.nu)
        outs = [one(p, g, m, v) for p, g, m, v in
                zip(p_leaves, g_leaves, m_leaves, v_leaves)]
        updates = jax.tree.unflatten(treedef, [o[0] for o in outs])
        mu = jax.tree.unflatten(treedef, [o[1] for o in outs])
        nu = jax.tree.unflatten(treedef, [o[2] for o in outs])
        return updates, FusedLambState(count=count, mu=mu, nu=nu)

    return optax.GradientTransformation(init, update)
