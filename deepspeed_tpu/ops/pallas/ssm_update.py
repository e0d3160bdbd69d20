"""One decode token of a selective state-space layer (Mamba-2; ``models/
layers.py Mamba2Mixer``) against every slot's matrix state, in place:

    S'[h] = a[h] * S[h] + B (dt x)[h]^T        y[h] = S'[h]^T C

for the rows that decode; ``S`` is ``[rows, heads, d_state, d_head]``
float32 (Falcon-H1-34B: 32 x 256 x 128, 4 MB a row a layer), ``B`` and
``C`` are one vector a group of heads. The state is the layer's largest
operand by far — a row's is what 2,048 tokens of its K/V weigh — so the
step is its bytes: each decoding row's state read once and written once
where it lies, and no other row's touched.

The kernel. The grid is ``(rows that decode, head blocks)``, the first a
value of the run (scalar-prefetched with the rows' order, the decoding
ones first): a row that does not decode has no step, so its state is
neither read nor written, and the state is aliased in and out, so a
donated pool's leaf is the leaf that comes back. A step holds a block of
``HEAD_BLOCK`` heads' states (``[d_state, d_head]`` each: the state
dimension on sublanes, the head's on lanes), so ``dt x`` and the decay
broadcast along sublanes as they arrive and ``y`` is a sum over
sublanes. ``B`` and ``C`` have to stand as columns: they arrive
transposed with the *rows* on lanes (``[groups, d_state, rows]``, a few
hundred KB that stay in VMEM for the whole call), and a step picks its
row's column with one masked sum over lanes. Everything is float32 on
the VPU: no product of this step goes through the MXU.

Off the TPU (and for a state Mosaic's tiling does not take) the same
update is four lines of ``jax.numpy``, with ``where`` keeping the rows
that do not decode.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import tuning
from ._common import log_fallback_on_tpu, on_tpu

KERNEL = "ssm_update"
# its instruction in a device trace is ``%ssm_update.N``
TRACE_NAME = "ssm_update"
HEAD_BLOCK = 8


def _update_xla(state, dx, decay, b, c, active):
    """The update in plain ``jax.numpy``: ``(y, new state)``."""
    per = state.shape[1] // b.shape[1]
    bh, ch = jnp.repeat(b, per, axis=1), jnp.repeat(c, per, axis=1)
    new = (decay[..., None, None] * state
           + bh[..., :, None] * dx[..., None, :])
    y = jnp.sum(new * ch[..., :, None], axis=2)
    return y, jnp.where(active[:, None, None, None], new, state)


def _kernel(order_ref, count_ref, s_ref, dx_ref, a_ref, bt_ref, ct_ref,
            y_ref, o_ref, *, hb, per_group, masked):
    i, j = pl.program_id(0), pl.program_id(1)

    def step():
        row = order_ref[i]
        group = (j * hb) // per_group
        lane = jax.lax.broadcasted_iota(jnp.int32, bt_ref.shape[1:], 1)
        mine = lane == row

        def column(ref):
            return jnp.sum(jnp.where(mine, ref[group], 0.0), axis=1,
                           keepdims=True)                      # [N, 1]

        bcol, ccol = column(bt_ref), column(ct_ref)
        for h in range(hb):
            new = (s_ref[0, h] * a_ref[0, h:h + 1, :]
                   + bcol * dx_ref[0, h:h + 1, :])             # [N, P]
            o_ref[0, h] = new
            y_ref[0, h:h + 1, :] = jnp.sum(new * ccol, axis=0,
                                           keepdims=True)

    if masked:
        pl.when(i < count_ref[0])(step)
    else:
        step()


# a jit of its own, as ``grouped_matmul._call`` and for its reason: a
# model lays the call out once a layer, and one trace serves them all.
# (Never dispatched by itself: it is traced into the serving programs.)
@functools.partial(jax.jit,  # ds-tpu: lint-ok[CC001]
                   static_argnames=("hb", "interpret"))
def _call(state, dx, decay, b, c, active, *, hb, interpret):
    rows, heads, n, p = state.shape
    groups = b.shape[1]
    # the rows that decode, in row order, ahead of the others
    order = jnp.argsort(~active, stable=True).astype(jnp.int32)
    count = jnp.sum(active, dtype=jnp.int32)
    lanes = -(-rows // 128) * 128

    def columns(v):                         # [rows, G, N] -> [G, N, lanes]
        v = jnp.transpose(v.astype(jnp.float32), (1, 2, 0))
        return jnp.pad(v, ((0, 0), (0, 0), (0, lanes - rows)))

    a = jnp.broadcast_to(decay.astype(jnp.float32)[..., None],
                         (rows, heads, p))
    # compiled, a row that does not decode has no step; interpreted, the
    # grid is the shapes' and such a step is skipped
    steps = rows if interpret else count
    row_block = pl.BlockSpec((1, hb, p), lambda i, j, o, n_: (o[i], j, 0))
    whole = pl.BlockSpec((groups, n, lanes), lambda i, j, o, n_: (0, 0, 0))
    state_block = pl.BlockSpec((1, hb, n, p),
                               lambda i, j, o, n_: (o[i], j, 0, 0))
    y, new = pl.pallas_call(
        functools.partial(_kernel, hb=hb, per_group=heads // groups,
                          masked=interpret),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(steps, heads // hb),
            in_specs=[state_block, row_block, row_block, whole, whole],
            out_specs=[row_block, state_block],
        ),
        out_shape=[jax.ShapeDtypeStruct((rows, heads, p), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 2 (after the two prefetched scalars) is the state
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name=TRACE_NAME,
    )(order, count[None], state, dx.astype(jnp.float32), a, columns(b),
      columns(c))
    # a row without a step left its block of y unwritten
    return jnp.where(active[:, None, None], y, 0.0), new


def _why_not_kernel(state, groups):
    rows, heads, n, p = state.shape
    if state.dtype != jnp.float32:
        return f"state is {state.dtype}, the kernel's is float32"
    if heads % HEAD_BLOCK or (heads // groups) % HEAD_BLOCK:
        return (f"{heads} heads in {groups} groups do not cut into blocks "
                f"of {HEAD_BLOCK} inside a group")
    if n % 8 or p % 128:
        return f"a head's state [{n}, {p}] is not whole (8, 128) tiles"
    return None


def ssm_update(state, dx, decay, b, c, active, interpret=None):
    """``(y [rows, heads, d_head] float32, new state)`` of one token a
    row: ``state [rows, heads, d_state, d_head]``, ``dx = dt * x`` ``[rows,
    heads, d_head]``, ``decay = exp(A dt)`` ``[rows, heads]``, ``b`` and
    ``c`` ``[rows, groups, d_state]``, ``active [rows]`` bool. A row
    outside ``active`` keeps its state and reads ``y = 0``. On one TPU
    the Pallas kernel above; elsewhere plain ``jax.numpy``
    (``interpret=True`` runs the kernel interpreted, for the tests)."""
    rows, heads, n, p = state.shape
    structure = f"rows{rows}_h{heads}_n{n}_p{p}"
    reason = _why_not_kernel(state, b.shape[1])
    if interpret is None and not on_tpu():
        reason = reason or "platform is not tpu"
    use = reason is None
    tuning.record_dispatch(KERNEL, structure, structure, None,
                           impl="kernel" if use else "xla", reason=reason,
                           head_block=HEAD_BLOCK)
    if not use:
        log_fallback_on_tpu(KERNEL, "xla", reason)
        return _update_xla(state.astype(jnp.float32), dx, decay, b, c,
                           active)
    return _call(state, dx, decay, b, c, active, hb=HEAD_BLOCK,
                 interpret=bool(interpret))
