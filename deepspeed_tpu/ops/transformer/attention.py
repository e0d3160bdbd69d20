"""Attention op with a swappable backend.

The reference fuses attention inside its CUDA transformer kernel
(csrc/transformer/softmax_kernels.cu + strided_batch_gemm, orchestrated by
ds_transformer_cuda.cpp). Here the same surface is one function whose
backend is either

- ``reference``: pure jnp einsum path (runs everywhere; XLA already fuses
  the softmax chain), or
- ``pallas``: the flash-attention Pallas kernel (deepspeed_tpu.ops.pallas)
  when running on TPU with compatible shapes.

Backend selection lives here so models never care.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp


def _reference_attention(q, k, v, bias=None, mask=None, *, causal=False,
                         softmax_scale=None, dropout_rate=0.0,
                         dropout_rng=None, deterministic=True,
                         dropout_mask=None, dropout_offsets=None):
    """q,k,v: [batch, seq, heads, head_dim] (BSHD, the JAX-native layout).

    Dropout samples the SAME counter-based keep mask as the Pallas flash
    kernel (``ops.pallas.flash_attention.attention_dropout_keep``): bits
    are a pure function of (rng, batch, head, row, col), so dense and
    flash backends — and replicated vs sequence-parallel layouts — are
    bit-identical given the same rng. ``dropout_offsets``
    (total_heads, head_offset, batch_offset) lets a shard_map-local
    caller reproduce the global sample. ``dropout_mask`` (a precomputed
    boolean keep mask) overrides sampling."""
    *_, q_len, _, head_dim = q.shape
    k_len = k.shape[-3]
    scale = softmax_scale if softmax_scale is not None else head_dim ** -0.5

    # [b, h, sq, sk] logits in fp32 for numerical stability (the reference's
    # attn_softmax kernel also upcasts).
    logits = jnp.einsum("...qhd,...khd->...hqk", q, k,
                        preferred_element_type=jnp.float32) * scale

    if bias is not None:
        logits = logits + bias
    if causal:
        causal_mask = jnp.tril(jnp.ones((q_len, k_len), dtype=bool), k_len - q_len)
        logits = jnp.where(causal_mask, logits, jnp.finfo(jnp.float32).min)
    if mask is not None:
        # mask: [batch, 1|heads, 1|sq, sk] boolean, True = attend
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)

    probs = jax.nn.softmax(logits, axis=-1)
    if dropout_mask is not None:
        probs = jnp.where(dropout_mask, probs / (1.0 - dropout_rate), 0.0)
    elif dropout_rate > 0.0 and not deterministic:
        from ..pallas.flash_attention import attention_dropout_keep
        th, ho, bo = dropout_offsets or (probs.shape[1], 0, 0)
        keep = attention_dropout_keep(dropout_rng, dropout_rate, probs.shape,
                                      total_heads=th, head_offset=ho,
                                      batch_offset=bo)
        probs = jnp.where(keep, probs / (1.0 - dropout_rate), 0.0)

    probs = probs.astype(v.dtype)
    return jnp.einsum("...hqk,...khd->...qhd", probs, v)


def attention(q, k, v, bias=None, mask=None, *, causal=False,
              softmax_scale=None, dropout_rate=0.0, dropout_rng=None,
              deterministic=True, backend: Optional[str] = None,
              seq_parallel: Optional[str] = None, ring_block_q: int = 1024,
              dropout_offsets=None):
    """Multi-head attention, BSHD layout.

    backend: None = auto (pallas flash kernel on TPU when eligible,
    reference otherwise) | "reference" | "pallas". Bias, mask and dropout
    are FUSED into the flash kernel (mask folds into one additive bias
    operand; dropout samples a counter-based keep mask in-kernel) — only
    operand shapes the kernel's block specs can't express fall back.
    seq_parallel: None = auto (ulysses when the mesh's ``seq`` axis > 1)
    | "ulysses" | "ring" | "none". Bias, mask and dropout ride along on
    both sequence-parallel paths (ulysses reproduces the replicated
    path's exact dropout bits via the position-keyed hash + head/batch
    offsets; ring samples per k/v block). Only shape constraints fall
    back.
    dropout_offsets: (total_heads, head_offset, batch_offset) — set by
    shard_map-local callers (Ulysses) so local tiles sample the global
    keep mask; leave None under plain jit/pjit (global view).
    """
    sp_mode = _resolve_seq_parallel(seq_parallel, q, bias, mask)
    if sp_mode == "ulysses":
        from ...sequence_parallel import ulysses_attention
        inner = functools.partial(attention, backend=backend,
                                  seq_parallel="none")
        return ulysses_attention(q, k, v, bias=bias, mask=mask,
                                 causal=causal, softmax_scale=softmax_scale,
                                 dropout_rate=dropout_rate,
                                 dropout_rng=dropout_rng,
                                 deterministic=deterministic, attn_fn=inner)
    if sp_mode == "ring":
        from ...sequence_parallel import ring_attention
        return ring_attention(q, k, v, bias=bias, mask=mask, causal=causal,
                              softmax_scale=softmax_scale,
                              dropout_rate=dropout_rate,
                              dropout_rng=dropout_rng,
                              deterministic=deterministic,
                              block_q=ring_block_q)

    drop_on = dropout_rate > 0.0 and not deterministic
    source, reason = "explicit", None
    if backend is None:
        source = "auto"
        backend, reason = _auto_backend(q, k, bias, mask, drop_on,
                                        dropout_rng)
    elif backend == "pallas" and not _pallas_operands_ok(
            q, k, bias, mask, drop_on, dropout_rng):
        raise ValueError(
            "attn_backend='pallas' cannot be honoured: the flash kernel "
            "needs 4-D bias/mask operands shaped [b|1, h|1, sq|1, sk] and "
            f"an rng when dropout is live (q {q.shape}, bias "
            f"{None if bias is None else bias.shape}, mask "
            f"{None if mask is None else mask.shape}, dropout rng "
            f"{'missing' if drop_on and dropout_rng is None else 'ok'}); "
            "use attn_backend='reference' or leave it unset")
    from ..pallas import tuning
    from ..pallas._common import log_fallback_on_tpu
    # shapes as THIS dispatch sees them: inside a shard_map region (the
    # kernel-partition path below re-enters here per shard) they are one
    # device's share, and that inner record is the one left standing
    tuning.record_dispatch(
        "attention", "backend",
        f"sq{q.shape[-3]}_sk{k.shape[-3]}_d{q.shape[-1]}", source,
        backend=backend, reason=reason, batch=q.shape[0],
        heads=q.shape[-2])
    if reason is not None:
        log_fallback_on_tpu("attention", "reference", reason)
    if backend == "pallas":
        mesh = _kernel_partition_mesh(q)
        if mesh is not None:
            # a Mosaic custom call cannot be auto-partitioned (jax
            # refuses to compile one under a multi-device jit). Map it
            # over the batch/head axes instead — the Ulysses region with
            # a seq axis of 1 is exactly that.
            from ...comm.mesh import DENSE_DP_AXES
            from ...sequence_parallel import ulysses_attention
            inner = functools.partial(attention, backend="pallas",
                                      seq_parallel="none")
            return ulysses_attention(
                q, k, v, bias=bias, mask=mask, causal=causal,
                softmax_scale=softmax_scale, dropout_rate=dropout_rate,
                dropout_rng=dropout_rng, deterministic=deterministic,
                attn_fn=inner, mesh=mesh, batch_axes=DENSE_DP_AXES,
                local_region=True)
        from ..pallas import flash_attention
        return flash_attention(
            q, k, v, bias=_combined_bias(bias, mask), causal=causal,
            softmax_scale=softmax_scale,
            dropout_rate=dropout_rate if drop_on else 0.0,
            dropout_rng=dropout_rng if drop_on else None,
            dropout_offsets=dropout_offsets,
            # a mask-only combined bias is statically non-trainable: let
            # eager grads skip the dense dBias recompute
            bias_grad=bias is not None)
    return _reference_attention(q, k, v, bias=bias, mask=mask, causal=causal,
                                softmax_scale=softmax_scale,
                                dropout_rate=dropout_rate,
                                dropout_rng=dropout_rng,
                                deterministic=deterministic,
                                dropout_offsets=dropout_offsets)


def _combined_bias(bias, mask):
    """Fold a boolean keep mask into the additive bias operand the flash
    kernel takes (0 where attending, NEG_INF where masked — the encoding
    the kernels' fully-masked-row thresholds depend on)."""
    if mask is None:
        return bias
    from ..pallas._common import NEG_INF
    mb = jnp.where(mask, 0.0, NEG_INF).astype(jnp.float32)
    return mb if bias is None else bias + mb


def _resolve_seq_parallel(seq_parallel, q, bias, mask):
    """Pick the sequence-parallel mode; "none" when inapplicable.
    Dropout never disqualifies (both SP paths sample it locally)."""
    if seq_parallel == "none":
        return "none"
    from ...comm.mesh import get_global_mesh, _GLOBAL_MESH
    if seq_parallel is None and _GLOBAL_MESH is None:
        return "none"  # auto never forces a mesh into existence
    sp = get_global_mesh().shape.get("seq", 1)
    if sp == 1:
        if seq_parallel in ("ulysses", "ring"):
            _warn_sp_no_axis()  # explicit request, but no seq axis to use
        return "none"
    # bias/mask/dropout ride along (sharded operands / the position-keyed
    # keep hash); only SHAPES disqualify: decode-time q (seq=1 chunks,
    # XLA all-gathers the seq shards transparently) and operands whose
    # broadcast dims the region specs can't express (b/h/sq must be 1 or
    # full-size, the forms every model in models/ produces).
    def _op_ok(t):
        return t is None or (
            t.ndim == 4
            and all(t.shape[i] in (1, full)
                    for i, full in ((0, q.shape[0]), (1, q.shape[2]),
                                    (2, q.shape[1])))
            and t.shape[3] == q.shape[1])
    eligible = (q.ndim == 4 and q.shape[1] % sp == 0
                and _op_ok(bias) and _op_ok(mask))
    if not eligible:
        if seq_parallel is not None:
            _warn_sp_fallback()
        return "none"
    if seq_parallel is None:
        # auto mode must degrade, never raise: ulysses additionally needs
        # heads/tp divisible by sp — fall back to ring (no head constraint)
        tp = get_global_mesh().shape.get("model", 1)
        if (q.shape[2] // max(tp, 1)) % sp != 0:
            return "ring"
        return "ulysses"
    return seq_parallel


@functools.lru_cache(None)
def _warn_sp_no_axis():
    import warnings
    warnings.warn("seq_parallel requested but the active mesh has no 'seq' "
                  "axis (size 1) — running fully replicated. Build the mesh "
                  "with MeshSpec(seq=N) to enable it.")


@functools.lru_cache(None)
def _warn_sp_fallback():
    import warnings
    warnings.warn("sequence-parallel attention requested but the q/bias/"
                  "mask shapes (decode-time seq=1 chunks, non-broadcast "
                  "operand dims) require the replicated path; falling back")


def _kernel_partition_mesh(q):
    """The global mesh when a Pallas attention call must be mapped over
    it by hand: the batch or the heads would really be split (an axis of
    more than one device divides them), and we are not already inside a
    manual (shard_map) region. None otherwise — with nothing to split
    the call stays on the devices its operands live on. Like the
    sequence-parallel choice above, this dispatch reads the mesh of the
    engine that is tracing: the trainer installs its own for its
    lifetime, an InferenceEngine scopes its own around its traces."""
    from ...comm.mesh import DENSE_DP_AXES, axis_size, peek_global_mesh
    from ...sequence_parallel.ulysses import _fit_axes
    from ..pallas._common import in_manual_region
    mesh = peek_global_mesh()
    if mesh is None or q.ndim != 4 or in_manual_region():
        return None
    # the axes the region's specs would really put on batch and heads
    split = [_fit_axes(q.shape[0], DENSE_DP_AXES, mesh),
             _fit_axes(q.shape[2], "model", mesh)]
    if all(a is None or axis_size(a, mesh) == 1 for a in split):
        return None
    return mesh


def _pallas_operands_ok(q, k, bias, mask, drop_on, dropout_rng):
    """Shapes the flash kernel's block specs can express: 4-D operands
    with b/h/sq each full-size or broadcast (1) and sk full; dropout
    needs an rng to seed the in-kernel hash."""
    if drop_on and dropout_rng is None:
        return False
    b, sq, h, _ = q.shape
    sk = k.shape[1]

    def ok(t):
        return t is None or (
            t.ndim == 4
            and t.shape[0] in (1, b) and t.shape[1] in (1, h)
            and t.shape[2] in (1, sq) and t.shape[3] == sk)

    return ok(bias) and ok(mask)


def _auto_backend(q, k, bias, mask, drop_on, dropout_rng):
    """("pallas", None) when the flash kernel can take the call, else
    ("reference", reason) — the reason lands in the dispatch record and,
    on TPU, in the log."""
    from ..pallas._common import on_tpu
    head_dim = q.shape[-1]
    seq = q.shape[-3]
    if not on_tpu():
        return "reference", "platform is not tpu"
    if head_dim not in (64, 128, 256):
        return "reference", f"head_dim {head_dim} not in (64, 128, 256)"
    if seq % 128 != 0:
        return "reference", f"seq {seq} not a multiple of 128"
    if not _pallas_operands_ok(q, k, bias, mask, drop_on, dropout_rng):
        return "reference", "bias/mask operand shapes or missing dropout rng"
    return "pallas", None
