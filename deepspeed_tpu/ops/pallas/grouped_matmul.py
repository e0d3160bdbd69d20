"""Pallas grouped matmul: ``rows [m, k]``, sorted by group, times
``w [G, k, n]``, row i by its group's matrix — a dropless expert layer's
three products (``moe/sharded_moe.py grouped_matmul``), one call a
matmul.

The walk. Rows are cut into tiles of ``block_m``; a *visit* is one
(group with rows, row tile it has rows in) pair, in row order, so the
visits of one row tile are consecutive and so are those of one group.
The visits' group, tile and row bounds are worked out by XLA from the
groups' sizes (a few small fusions, shared by matmuls over the same
sizes) and scalar-prefetched; the grid is ``(n / block_n, visits)`` with
the number of visits a value of the run, not a bound from the shapes: no
step is made for a group without rows and its weights are never fetched,
whatever ``G`` is — a model hands the ``[L·E, k, n]`` stack of all its
layers whole (a slice feeding a custom call would be a copy) with one
layer's groups holding rows.

A step multiplies its row tile by its group's ``[k, block_n]`` weights
(bf16 x bf16 products accumulated in float32 along ``k``, one pass of
the MXU) and keeps the rows that are the group's. Pallas' pipeline has
the next visit's weights in flight meanwhile: the stream stops only
before the first step of a call. A group whose rows straddle two tiles
is visited twice running and fetched once (the block index repeats), a
row tile is fetched once and its output written back once. Rows past the
groups' sum are in no group: their tiles are visited once each, with the
weights already there, and come out zero.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import tuning
from ._common import interpret_mode as _interpret
from ._common import log_fallback_on_tpu

KERNEL = "grouped_matmul"
# its instruction in a device trace is ``%ragged-dot-grouped.N``: the
# family XLA's own kernel for ``jax.lax.ragged_dot`` is in
# (``%ragged-dot-none*``), which is how the benchmark's patterns know a
# grouped expert matmul whatever implements it
TRACE_NAME = "ragged-dot-grouped"

DEFAULT_BLOCK_M = 128
# what a call may take of VMEM: two buffers each of a row tile, a
# ``[k, block_n]`` weights block and an output tile, and the float32
# product. Over Mosaic's default of 16 MiB (one LFM2 expert matrix is
# 6.3 MB), under the v5e's 128 MiB
VMEM_LIMIT = 96 * 2 ** 20


def _vmem_bytes(bm, bn, k, in_size, out_size):
    return (2 * (bm * k + k * bn) * in_size + 2 * bm * bn * out_size
            + 2 * bm * bn * 4)


def visits(groups, m, bm):
    """The walk of ``groups`` (sizes ``[G]``) over ``m`` rows in tiles of
    ``bm``: ``(n, group, tile, lo, hi)`` — how many visits there are, and
    of each of the ``V`` a grid may hold (a bound from the shapes) the
    group whose weights it takes, its row tile, and the rows
    ``[lo, hi)`` of the whole array that it keeps. A visit past the n-th
    repeats the n-th's blocks and keeps no row, so is a grid over all
    ``V`` right too, if longer (the interpreter's, which takes no grid
    of a length it is not told)."""
    n_groups = groups.shape[0]
    tiles = -(-m // bm)
    bound = tiles + min(n_groups, m)
    groups = groups.astype(jnp.int32)
    ends = jnp.cumsum(groups)
    total = ends[-1]
    starts = ends - groups
    first = starts // bm
    # (an empty group's tiles: none)
    count = jnp.where(groups > 0, (ends - 1) // bm - first + 1, 0)
    # the rows in no group, as one more group with no rows of its own:
    # the tiles from the first that holds none of a group's rows
    beyond = -(-total // bm)
    first = jnp.append(first, beyond)
    count = jnp.append(count, tiles - beyond)
    upto = jnp.cumsum(count)
    n = upto[-1]
    at = jnp.minimum(jnp.arange(bound, dtype=jnp.int32), n - 1)
    group = jnp.sum(upto[None, :] <= at[:, None], axis=1).astype(jnp.int32)
    tile = first[group] + at - (upto - count)[group]
    real = (group < n_groups) & (jnp.arange(bound) < n)
    inside = jnp.minimum(group, n_groups - 1)
    lo = jnp.where(real, starts[inside], 0)
    hi = jnp.where(real, ends[inside], 0)
    # rows in no group multiply by the last group's weights, which are
    # in VMEM already (or by group 0's when no group has a row)
    last = jnp.max(jnp.where(groups > 0, jnp.arange(n_groups), 0))
    weights = jnp.where(group < n_groups, group, last).astype(jnp.int32)
    return n, weights, tile.astype(jnp.int32), lo, hi


def _kernel(group_ref, tile_ref, lo_ref, hi_ref, x_ref, w_ref, o_ref, *, bm):
    del group_ref                                  # the index maps' alone
    v = pl.program_id(1)
    tile = tile_ref[v]
    opens = (v == 0) | (tile != tile_ref[jnp.maximum(v - 1, 0)])
    prod = jnp.dot(x_ref[...], w_ref[...],
                   preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.DEFAULT)        # [bm, bn]
    row = tile * bm + jax.lax.broadcasted_iota(jnp.int32, prod.shape, 0)
    mine = (row >= lo_ref[v]) & (row < hi_ref[v])
    # what the tile's earlier visits kept; nothing when this one opens
    # it (the buffer then holds another tile's rows, or nothing yet).
    # Selected in float32: Mosaic lays no boolean mask over a bf16 tile
    kept = jnp.where(opens, 0.0, o_ref[...].astype(jnp.float32))
    o_ref[...] = jnp.where(mine, prod, kept).astype(o_ref.dtype)


# A jit of its own: a model lays the same call out once a matmul and
# layer (LFM2: 24 in a program), and tracing the walk's arithmetic and
# the kernel, then lowering both, takes ~0.1 s a call — seconds of every
# process's set-up. Traced and lowered once a shape, the others are calls
# of one function, which XLA inlines. (Never dispatched by itself: it is
# traced into the serving programs, which the registry tracks.)
@functools.partial(jax.jit,  # ds-tpu: lint-ok[CC001]
                   static_argnames=("bm", "bn", "out_dtype", "interpret"))
def _call(rows, w, groups, *, bm, bn, out_dtype, interpret):
    m, k = rows.shape
    n = w.shape[-1]
    n_visits, group, tile, lo, hi = visits(groups, m, bm)
    steps = group.shape[0] if interpret else n_visits
    return pl.pallas_call(
        functools.partial(_kernel, bm=bm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // bn, steps),
            in_specs=[
                pl.BlockSpec((bm, k), lambda j, v, g, t, *_: (t[v], 0)),
                # the whole stack stays in HBM: a visit's block of it
                pl.BlockSpec((None, k, bn),
                             lambda j, v, g, *_: (g[v], 0, j)),
            ],
            out_specs=pl.BlockSpec((bm, bn),
                                   lambda j, v, g, t, *_: (t[v], j)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=TRACE_NAME,
    )(group, tile, lo, hi, rows, w)


def blocks(m, k, n, n_groups, dtype, out_dtype):
    """``(block_m, block_n, key, source)`` for a call of these shapes:
    the tuning table's where it names them, else a row tile of
    ``DEFAULT_BLOCK_M`` (a multiplication of up to 128 rows costs the
    MXU what one row does, the loading of the weights; more rows a visit
    cost more) and the widest ``block_n`` that fits ``VMEM_LIMIT``."""
    entry, key, source = tuning.lookup(
        KERNEL, f"groups{n_groups}", sq=m, sk=k, d=n, dtype=dtype,
        causal=False)
    bm = int(entry.get("block_m") or DEFAULT_BLOCK_M)
    bm = m if m <= bm else bm
    bn = int(entry.get("block_n") or n)
    sizes = jnp.dtype(dtype).itemsize, jnp.dtype(out_dtype).itemsize
    while bn % 256 == 0 and _vmem_bytes(bm, bn, k, *sizes) > VMEM_LIMIT // 2:
        bn //= 2
    return bm, bn, key, source


def refusal(rows, w, preferred_element_type=None):
    """Why a call of these shapes does not take the kernel — it goes to
    ``jax.lax.ragged_dot`` then — or None."""
    m, k = rows.shape
    n = w.shape[-1]
    if rows.dtype != jnp.bfloat16 or w.dtype != jnp.bfloat16:
        return (f"{rows.dtype.name} rows over {w.dtype.name} weights: the "
                "kernel multiplies bfloat16 by bfloat16")
    if k % 128 or n % 128:
        return f"k {k} or n {n} is no multiple of 128"
    if m < 16 or m % 8:
        return f"{m} rows: fewer than 16, or no multiple of 8"
    out = jnp.dtype(preferred_element_type or rows.dtype)
    bm, bn, _, _ = blocks(m, k, n, w.shape[0], rows.dtype, out)
    need = _vmem_bytes(bm, bn, k, 2, out.itemsize)
    if need > VMEM_LIMIT:
        return (f"a [{k}, {bn}] block of the weights and {bm} rows take "
                f"{need} bytes of VMEM, over {VMEM_LIMIT}")
    return None


def grouped_matmul(rows, w, groups, preferred_element_type=None):
    """``rows [m, k]`` x ``w [G, k, n]`` by ``groups [G]`` (sizes; rows
    sorted by group, rows past the sizes' sum come out zero) as one
    Pallas call, ``[m, n]`` in ``preferred_element_type`` (default: the
    rows'). Interpreted off the TPU. The caller has asked ``refusal``."""
    m, k = rows.shape
    n_groups, _, n = w.shape
    out_dtype = jnp.dtype(preferred_element_type or rows.dtype)
    bm, bn, key, source = blocks(m, k, n, n_groups, rows.dtype, out_dtype)
    tuning.record_dispatch(KERNEL, f"groups{n_groups}", key, source,
                           block_m=bm, block_n=bn, impl="kernel",
                           reason=None)
    return _call(rows, w, groups, bm=bm, bn=bn, out_dtype=out_dtype,
                 interpret=_interpret())


def record_fallback(rows, w, reason):
    """The dispatch record and the log's line of a call that went to
    ``jax.lax.ragged_dot``."""
    m, k = rows.shape
    n_groups, _, n = w.shape
    key = tuning.make_key(KERNEL, f"groups{n_groups}", sq=m, sk=k, d=n,
                          dtype=rows.dtype, causal=False)
    log_fallback_on_tpu(KERNEL, "ragged_dot",
                        f"rows {m} x [{n_groups}, {k}, {n}]: {reason}")
    tuning.record_dispatch(KERNEL, f"groups{n_groups}", key, None,
                           impl="ragged_dot", reason=reason)
