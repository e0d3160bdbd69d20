"""One decode step's new key and value column written onto a window
layer's rings, in place (``models/layers.py _ring_append``,
``DifferentialAttention`` of ``kind="window"``).

A ring is ``[rows, heads, d, window]`` with token ``t`` on lane ``t mod
window``, so a token is one lane of every ``(16, 128)`` tile of its row:
the least that can be moved is the 128 lanes around it, ``[heads, d,
128]``, read, one lane replaced, and written back where it was. The
bytes are the step's whole cost (Phi-4-mini-flash: 2 x 320 KB a ring a
row, 1.6 us a row for both rings at the v5e's 819 GB/s) if no row waits
for another.

The kernel. One call takes both rings of the layer in ``pl.ANY`` memory,
aliased to its two results, and touches only the tiles it writes. The
rows that decode (in row order, ahead of the others), every row's lane
and the count of the first are scalar-prefetched; the one loop makes as
many trips as rows decode — a number the program reads off its own
arguments — so a row that does not decode costs nothing. A trip starts
the next row's two tile reads into the other of ``SLOTS`` buffers, waits
for its own, replaces the lane by a select on a lane iota, and starts
the two write-backs; a slot's writes are waited for only before that
slot is fetched into again. No trip waits for the trip before it.

The column arrives ``[rows, heads, d]`` with the ``d`` features on lanes
and is needed down the sublanes: a masked lane sum against an identity
iota, one non-zero term a sum, so not one bit moves.

There is no second implementation: off the TPU the same kernel runs
interpreted.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import tuning
from ._common import interpret_mode as _interpret

KERNEL = "ring_append"
# its instruction in a device trace is ``%ring_append.N``
TRACE_NAME = "ring_append"
TILE = 128
SLOTS = 2


def _kernel(order_ref, lane_ref, count_ref, kcol_ref, vcol_ref, k_in, v_in,
            k_out, v_out, kbuf, vbuf, read_sem, write_sem, *, tile):
    # (a ring and its result are the same memory: aliased)
    n = count_ref[0]
    rings = ((k_in, k_out, kbuf, kcol_ref), (v_in, v_out, vbuf, vcol_ref))
    heads, d = kbuf.shape[1:3]

    def tile_of(ring, i):
        row = order_ref[i]
        start = pl.multiple_of(lane_ref[row] // tile * tile, tile)
        return ring.at[row, :, :, pl.ds(start, tile)]

    def reads(i, slot):
        return [pltpu.make_async_copy(tile_of(ring, i), buf.at[slot],
                                      read_sem.at[slot, r])
                for r, (ring, _, buf, _) in enumerate(rings)]

    def writes(i, slot):
        return [pltpu.make_async_copy(buf.at[slot], tile_of(ring, i),
                                      write_sem.at[slot, r])
                for r, (_, ring, buf, _) in enumerate(rings)]

    def start(copies):
        for copy in copies:
            copy.start()

    def wait(copies):
        for copy in copies:
            copy.wait()

    @pl.when(n > 0)
    def _first_reads():
        start(reads(0, 0))

    eye = (jax.lax.broadcasted_iota(jnp.int32, (d, d), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (d, d), 1))
    lanes = jax.lax.broadcasted_iota(jnp.int32, (d, tile), 1)

    def trip(i, carry):
        slot = jax.lax.rem(i, SLOTS)

        @pl.when(i + 1 < n)
        def _fetch_next():
            into = jax.lax.rem(i + 1, SLOTS)

            @pl.when(i + 1 >= SLOTS)
            def _slot_is_written():
                wait(writes(i + 1 - SLOTS, into))
            start(reads(i + 1, into))

        wait(reads(i, slot))
        row = order_ref[i]
        mine = lanes == lane_ref[row] % tile
        for _, _, buf, col_ref in rings:
            new = col_ref[row]                                 # [heads, d]
            for h in range(heads):
                col = jnp.sum(jnp.where(eye, new[h:h + 1], 0.0), axis=-1,
                              keepdims=True)                   # [d, 1]
                buf[slot, h] = jnp.where(
                    mine, jnp.broadcast_to(col, (d, tile)).astype(buf.dtype),
                    buf[slot, h])
        start(writes(i, slot))
        return carry

    jax.lax.fori_loop(0, n, trip, 0)
    for back in range(1, SLOTS + 1):
        @pl.when(n >= back)
        def _last_writes():
            wait(writes(n - back, jax.lax.rem(n - back, SLOTS)))


# a jit of its own, as ``ssm_update._call`` and for its reason: a model
# lays the call out once a window layer, and one trace serves them all.
# (Never dispatched by itself: it is traced into the decode program.)
@functools.partial(jax.jit,  # ds-tpu: lint-ok[CC001]
                   static_argnames=("tile", "interpret"))
def _call(ring_k, ring_v, col_k, col_v, lane, live, *, tile, interpret):
    rows, heads, d, _ = ring_k.shape
    # the rows that decode, in row order, ahead of the others
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    count = jnp.sum(live, dtype=jnp.int32)
    # float32 holds a bf16 or float32 column exactly; the kernel rounds
    # it to the ring's type, once, as ``astype`` does
    cols = [c.reshape(rows, heads, d).astype(jnp.float32)
            for c in (col_k, col_v)]
    whole = pl.BlockSpec((rows, heads, d), lambda i, *_: (0, 0, 0))
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    buf = lambda ring: pltpu.VMEM((SLOTS, heads, d, tile), ring.dtype)
    return pl.pallas_call(
        functools.partial(_kernel, tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[whole, whole, anywhere, anywhere],
            out_specs=[anywhere, anywhere],
            scratch_shapes=[buf(ring_k), buf(ring_v),
                            pltpu.SemaphoreType.DMA((SLOTS, 2)),
                            pltpu.SemaphoreType.DMA((SLOTS, 2))],
        ),
        out_shape=[jax.ShapeDtypeStruct(r.shape, r.dtype)
                   for r in (ring_k, ring_v)],
        # operands 5 and 6 (after three prefetched scalars and the two
        # columns) are the rings
        input_output_aliases={5: 0, 6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=TRACE_NAME,
    )(order, lane.astype(jnp.int32), count[None], *cols, ring_k, ring_v)


def ring_append(rings, cols, lane, live):
    """``rings`` (a layer's keys' and values', ``[rows, heads, d,
    window]`` each) with row ``b``'s columns ``cols[i][b]`` (``[heads, d,
    1]`` or ``[heads, d]``) on lane ``lane[b]``, for the rows that are
    ``live``; every other row, head and lane as it was. The rings are
    aliased to the results: a donated ring is the ring that comes back."""
    ring_k, ring_v = rings
    rows, heads, d, window = ring_k.shape
    tile = min(TILE, window)
    if window % tile:
        raise ValueError(f"a ring of {window} tokens is no multiple of "
                         f"{tile}")
    if ring_v.shape != ring_k.shape:
        raise ValueError(f"a layer's rings differ in shape: {ring_k.shape} "
                         f"and {ring_v.shape}")
    key = f"b{rows}_h{heads}_d{d}_w{window}_{ring_k.dtype.name}"
    tuning.record_dispatch(KERNEL, "tile", key, None, impl="kernel",
                           tile=tile, slots=SLOTS)
    return tuple(_call(ring_k, ring_v, *cols, lane, live, tile=tile,
                       interpret=_interpret()))
