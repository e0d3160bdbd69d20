"""DeepSpeed-Ulysses-style sequence parallelism.

The idea (absent from the reference snapshot; modern DeepSpeed's
``DistributedAttention`` wraps a local attention with two all-to-alls):
activations arrive sharded on the sequence dim over the ``seq`` mesh
axis. Attention needs the full sequence, but is embarrassingly parallel
over heads — so an all-to-all converts the seq shard into a head shard,
the unmodified local attention core runs on full sequences, and a second
all-to-all converts back.

TPU-native: a ``shard_map`` region with ``jax.lax.all_to_all`` over the
``seq`` axis (lowering to XLA AllToAll on ICI), composing with batch
sharding over data/fsdp and head sharding over model (tensor parallel).
"""

from functools import partial
from typing import Optional

import jax
from jax import lax
from jax.sharding import PartitionSpec as P

from ..comm.mesh import get_global_mesh
from ..utils.jax_compat import shard_map

# q/k/v/out layout everywhere: [batch, seq, heads, head_dim]
_BATCH_AXES = ("data", "fsdp")
_HEAD_AXIS = "model"
_SEQ_AXIS = "seq"


def _qkv_spec(q_shape, mesh, batch_axes, seq_axis, head_axis):
    return P(_fit_axes(q_shape[0], batch_axes, mesh), seq_axis,
             _fit_axes(q_shape[2], head_axis, mesh), None)


def _fit_axes(dim_size, axes, mesh):
    """Longest prefix of ``axes`` whose cumulative product divides dim_size.

    The engine traces the model on tiny sample batches (batch=1) where the
    full data/fsdp sharding can't apply; sharding the batch dim is a
    throughput concern, not a correctness one, so degrade gracefully."""
    kept = []
    prod = 1
    for a in (axes if isinstance(axes, (tuple, list)) else (axes,)):
        nxt = prod * mesh.shape.get(a, 1)
        if dim_size % nxt != 0:
            break
        kept.append(a)
        prod = nxt
    if not kept:
        return None
    return tuple(kept) if len(kept) > 1 else kept[0]


def _bhqk_spec(shape, mesh, batch_axes, head_sub_axes):
    """Spec for a [b|1, h|1, sq|1, sk] operand (mask/bias/keep) entering
    the shard_map region: batch sharded when real, the head dim sharded
    the way the post-all-to-all q/k/v heads are laid out (outer TP axis,
    then the seq axis — the a2a keeps chunk ``seq_index`` of each local
    head block), q/k dims replicated (the local core sees full sequence).
    Broadcast (size-1) dims stay replicated."""
    b, h = shape[0], shape[1]
    return P(_fit_axes(b, batch_axes, mesh) if b > 1 else None,
             _fit_axes(h, head_sub_axes, mesh) if h > 1 else None,
             None, None)


def ulysses_attention(q, k, v, *, bias=None, mask=None, causal=False,
                      softmax_scale=None, dropout_rate=0.0, dropout_rng=None,
                      deterministic=True, attn_fn=None, mesh=None,
                      axis_name=_SEQ_AXIS, batch_axes=_BATCH_AXES,
                      head_axis=_HEAD_AXIS, local_region=False):
    """Full-sequence attention over seq-sharded inputs, [B, S, H, D] global.

    ``attn_fn(q, k, v, causal=..., softmax_scale=...)`` is the local
    attention core (default: the ops.transformer dispatch, so the Pallas
    flash kernel is used on TPU when eligible). Requires
    ``H / tp_degree`` divisible by the seq-axis size.

    bias/mask ([b|1, h|1, sq|1, sk]) ride into the region pre-sharded on
    the head dim to match the post-all-to-all head layout — no extra
    collective. Dropout keeps EXACT parity with the replicated path with
    ZERO operand traffic: the attention core's counter-based keep hash is
    keyed on GLOBAL (batch, head, row, col) coordinates, so each device
    passes its head/batch offsets and regenerates precisely its tile of
    the replicated sample — nothing of shape [sq, sk] is ever
    materialized (on TPU the flash kernel samples in-tile; the dense
    fallback fuses the hash into the softmax chain).

    ``local_region=True`` keeps the shard_map region even when the seq
    axis is 1 (the all-to-alls drop out): the attention dispatch uses it
    to run a Pallas core per batch/head shard, which GSPMD cannot
    partition by itself.
    """
    mesh = mesh or get_global_mesh()
    sp = mesh.shape[axis_name]
    if attn_fn is None:
        from ..ops.transformer.attention import attention
        attn_fn = partial(attention, seq_parallel="none")
    dropout_on = dropout_rate > 0.0 and not deterministic
    if dropout_on and dropout_rng is None:
        raise ValueError("ulysses_attention: dropout_rate > 0 with "
                         "deterministic=False requires dropout_rng")
    if sp == 1 and not local_region:
        # keep the documented (q, k, v, causal=, softmax_scale=) attn_fn
        # contract when no operands ride along; only operand-carrying
        # calls need the full attention() signature
        extra_kwargs = {}
        if bias is not None:
            extra_kwargs["bias"] = bias
        if mask is not None:
            extra_kwargs["mask"] = mask
        if dropout_on:
            extra_kwargs.update(dropout_rate=dropout_rate,
                                dropout_rng=dropout_rng,
                                deterministic=deterministic)
        return attn_fn(q, k, v, causal=causal, softmax_scale=softmax_scale,
                       **extra_kwargs)

    n_heads, seq_len = q.shape[2], q.shape[1]
    tp = mesh.shape.get(head_axis, 1)
    local_heads = n_heads // tp
    if local_heads % sp != 0:
        raise ValueError(
            f"Ulysses needs heads/tp ({n_heads}/{tp}={local_heads}) divisible "
            f"by the seq-parallel degree {sp}")
    if seq_len % sp != 0:
        raise ValueError(f"sequence length {seq_len} not divisible by sp={sp}")

    spec = _qkv_spec(q.shape, mesh, batch_axes, axis_name, head_axis)
    head_sub = ((head_axis, axis_name) if tp > 1 else (axis_name,))

    extras = [(name, t) for name, t in
              (("bias", bias), ("mask", mask),
               ("dropout_rng", dropout_rng if dropout_on else None))
              if t is not None]
    extra_specs = tuple(P() if name == "dropout_rng"
                        else _bhqk_spec(t.shape, mesh, batch_axes, head_sub)
                        for name, t in extras)
    extra_names = tuple(name for name, _ in extras)

    # which batch axes the q spec actually shards (batch offset inputs)
    batch_used = spec[0]
    batch_used = (() if batch_used is None else
                  batch_used if isinstance(batch_used, tuple)
                  else (batch_used,))

    def local_fn(q, k, v, *extra):
        ops = dict(zip(extra_names, extra))
        if sp > 1:
            # [b, s/sp, h, d] -> [b, s, h/sp, d]: the head<->seq swap
            q, k, v = (lax.all_to_all(t, axis_name, split_axis=2,
                                      concat_axis=1, tiled=True)
                       for t in (q, k, v))
        kwargs = {n: t for n, t in ops.items() if n != "dropout_rng"}
        if dropout_on:
            # global coordinates of this device's head/batch window, so
            # the core's position-keyed dropout hash regenerates exactly
            # the replicated sample's tile (see module docstring)
            h_per_dev = local_heads // sp
            head_off = lax.axis_index(axis_name) * h_per_dev
            if tp > 1:
                head_off = head_off + lax.axis_index(head_axis) * local_heads
            batch_off = 0
            for a in batch_used:
                batch_off = batch_off * mesh.shape[a] + lax.axis_index(a)
            batch_off = batch_off * q.shape[0]
            kwargs.update(dropout_rate=dropout_rate,
                          dropout_rng=ops["dropout_rng"],
                          deterministic=False,
                          dropout_offsets=(n_heads, head_off, batch_off))
        out = attn_fn(q, k, v, causal=causal, softmax_scale=softmax_scale,
                      **kwargs)
        if sp == 1:
            return out
        # [b, s, h/sp, d] -> [b, s/sp, h, d]
        return lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    return shard_map(
        local_fn, mesh=mesh, in_specs=(spec, spec, spec) + extra_specs,
        out_specs=spec)(q, k, v, *(t for _, t in extras))
