"""Transformer building blocks, TPU-first.

Replaces the reference's fused CUDA transformer layer surface
(deepspeed/ops/transformer/transformer.py DeepSpeedTransformerLayer +
csrc/transformer/*) with flax modules whose params carry *logical axis
names*; the engine maps those names to mesh axes per ZeRO stage / TP degree
(see runtime/zero/sharding.py). XLA then inserts the collectives the
reference implemented by hand.

Logical axis vocabulary:
  "embed"  - d_model dim            "mlp"   - ffn hidden dim
  "qkv"    - fused attention heads  "vocab" - vocabulary dim
  "pos"    - position-embedding dim "layers" - stacked-layer axis (nn.scan)
  "batch"/"seq" - activation dims (constraints only, never params)
"""

import contextlib
import functools
import math
from typing import Any, Callable, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
import flax.linen as nn
from jax.ad_checkpoint import checkpoint_name

from ..ops.pallas._common import NEG_INF
from ..ops.transformer.attention import attention

# Set by the training engine: dict logical-name -> mesh axis (or None).
# Activation constraints no-op when empty so models run un-meshed.
_ACTIVATION_RULES = {}


def set_activation_rules(rules: dict):
    """Install the table and return it: the engine that installed it
    clears it at ``destroy()`` only if it is still the one in place."""
    global _ACTIVATION_RULES
    _ACTIVATION_RULES = dict(rules or {})
    return _ACTIVATION_RULES


def activation_rules_installed(table) -> bool:
    """True while ``table`` (a ``set_activation_rules`` result) is the one
    in place."""
    return _ACTIVATION_RULES is table


def _usable_global_mesh():
    """The global mesh if a sharding constraint can be applied here, else
    None. Inside shard_map (Manual axes) the global-mesh NamedSharding is
    from a different (Auto) mesh view and would poison downstream ops.
    Also the mesh handed to the decode kernels, which split their heads
    over its ``model`` axis and read no global themselves."""
    from ..ops.pallas._common import in_manual_region
    if in_manual_region():
        return None
    from ..comm.mesh import peek_global_mesh
    return peek_global_mesh()


def activation_constraint(x, logical_names):
    """Apply with_sharding_constraint if the engine installed rules.

    Builds a concrete NamedSharding against the global mesh — a bare
    PartitionSpec needs an ambient ``use_mesh`` context and silently
    fails under plain ``jit``."""
    if not _ACTIVATION_RULES:
        return x
    from jax.sharding import PartitionSpec as P, NamedSharding
    axes = tuple(_ACTIVATION_RULES.get(n) for n in logical_names)
    if all(a is None for a in axes):
        return x
    mesh = _usable_global_mesh()
    if mesh is None:   # un-meshed model run: nothing to constrain against
        return x
    # drop constraints the array can't honor (dim not divisible by the
    # axis degree — e.g. batch 1 on an 8-way dp axis in eval paths)
    def ok(dim, a):
        if a is None:
            return None
        from ..comm.mesh import axis_size
        return a if dim % axis_size(a, mesh) == 0 else None
    axes = [ok(d, a) for d, a in zip(x.shape, axes)]
    # a mesh axis may shard one dim only. partition_activations maps
    # "seq" to the model axis, which a tensor-parallel feature dim
    # ("mlp", "qkv") of the same tensor also claims: the later (feature)
    # dim keeps it, as the weights it meets are sharded that way.
    claimed = set()
    for i in reversed(range(len(axes))):
        names = axes[i] if isinstance(axes[i], (tuple, list)) else (axes[i],)
        if axes[i] is not None and claimed.intersection(names):
            axes[i] = None
        claimed.update(n for n in names if n is not None)
    if all(a is None for a in axes):
        return x
    # a constraint that throws propagates: skipping it would change
    # placement on a real mesh without a word
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*axes)))


# Set by the engine from the compression_training.activation_quantization
# block (reference: basic_layer.py:378/:424 — there a per-module forward
# hook; here a module-level rule table the engine toggles at
# schedule_offset, recompiling once). Empty = off.
_ACT_QUANT_RULES = []


def set_activation_quantization(rules):
    """rules: list of {"modules": [patterns], "bits": n, "symmetric": b}
    or None/[] to disable."""
    global _ACT_QUANT_RULES
    _ACT_QUANT_RULES = list(rules or [])


class training_rules_suspended:
    """Context manager: trace with both training-side rule tables
    (activation sharding, activation quantization) empty, then restore.
    Lets an InferenceEngine (e.g. a distillation teacher) compile clean
    forwards in the same process as a training engine whose global rules
    must survive its own retraces."""

    def __enter__(self):
        global _ACTIVATION_RULES, _ACT_QUANT_RULES
        self._saved = _ACTIVATION_RULES, _ACT_QUANT_RULES
        _ACTIVATION_RULES, _ACT_QUANT_RULES = {}, []
        return self

    def __exit__(self, *exc):
        global _ACTIVATION_RULES, _ACT_QUANT_RULES
        _ACTIVATION_RULES, _ACT_QUANT_RULES = self._saved
        return False


def _maybe_quantize_activation(x, module_path):
    if not _ACT_QUANT_RULES:
        return x
    path = "/".join(str(p) for p in module_path)
    for r in _ACT_QUANT_RULES:
        if any(p == "*" or p in path for p in r.get("modules", ["*"])):
            from ..compression.compress import fake_quantize_activation
            return fake_quantize_activation(
                x, bits=int(r.get("bits", 8)),
                symmetric=bool(r.get("symmetric", True)))
    return x


def replicated_constraint(x):
    """Constrain ``x`` to fully-replicated on the global mesh.

    Used on small lookup tables (e.g. learned position embeddings) right
    before a gather: a ZeRO-3 "embed"-dim shard would force the SPMD
    partitioner to move the fsdp axis from the feature dim onto the
    (data, fsdp) batch tile of the gather output — a transition it can
    only do by involuntary full rematerialization. One explicit
    all-gather of the tiny table is the efficient form of the same data
    movement, and the transposed constraint makes the backward scatter a
    clean psum instead of the reverse reshard."""
    if not _ACTIVATION_RULES:
        return x
    from jax.sharding import PartitionSpec as P, NamedSharding
    mesh = _usable_global_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P()))


def dense_init(names, scale=1.0):
    """lecun_normal-style init wrapped with logical partitioning names."""
    init = nn.initializers.variance_scaling(scale, "fan_in", "normal")
    return nn.with_logical_partitioning(init, names)


def _is_qleaf(x):
    """THE quantized-leaf predicate: a {"q", "scale"} dict produced by
    module_inject.module_quantize (which imports this — one definition,
    or QDense and the quantizer silently disagree on the layout)."""
    return isinstance(x, dict) and set(x.keys()) == {"q", "scale"}


def _check_sparse_compat(sparsity_config, bias, causal, alibi=False):
    """The sparse path's config refusals, shared by the training
    forward and the KV-cache decode branch so the two can never drift."""
    if alibi or bias is not None:
        raise ValueError("sparse attention does not take an additive "
                         "bias (disable alibi or sparsity_config)")
    if causal and getattr(sparsity_config, "attention",
                          "bidirectional") != "unidirectional":
        raise ValueError(
            "causal attention needs a sparsity config with "
            "attention='unidirectional' (the layout encodes causality)")


def split_terms(x, n=3):
    """A float32 array as ``n`` bfloat16 terms whose sum is it to 8 x n
    bits: ``[n, *x.shape]``, largest first (three are float32's 24).
    Each term is rounded by ``lax.reduce_precision``, which a compiler
    may not remove: XLA:TPU takes a float32 -> bfloat16 -> float32 round
    trip for excess precision it is allowed to keep, the remainder is
    then zero, and the "three terms" are one bfloat16 product (my chip
    run, PR 33: 0.92 sigma where this reads under 0.01)."""
    terms, rest = [], x.astype(jnp.float32)
    for _ in range(n):
        term = jax.lax.reduce_precision(rest, exponent_bits=8,
                                        mantissa_bits=7)
        terms.append(term.astype(jnp.bfloat16))      # exact
        rest = rest - term
    return jnp.stack(terms)


def exact_weights(x, w) -> bool:
    """Whether ``x @ w`` goes as ``dot_exact_weights`` and
    ``moe.sharded_moe.grouped_matmul`` make it exact: float32
    activations over weights kept in bfloat16, **traced under**
    ``jax.default_matmul_precision("highest")``. The caller asks for it
    (``models/lfm2.py`` does, for a float32 ``dtype``); without that
    ambient setting the pair is the plain product it always was, one
    pass of the MXU."""
    return (x.dtype == jnp.float32 and w.dtype == jnp.bfloat16
            and jax.config.jax_default_matmul_precision == "highest")


def dot_exact_weights(x, w):
    """``x @ w``; where ``exact_weights`` holds, to float32's accuracy:
    the activations go as three bfloat16 terms (``split_terms``) through
    one matmul that accumulates in float32, and the weights as they are,
    which they are exactly — three passes of the MXU over weights read
    once, and no float32 copy of a weight is ever made (what XLA makes of
    ``Precision.HIGHEST`` on a cast weight is six passes, three of them
    over the zeros of the weight's lower terms). Otherwise the plain
    product in ``x``'s type."""
    if exact_weights(x, w):
        return jnp.sum(jnp.dot(split_terms(x), w,
                               precision=jax.lax.Precision.DEFAULT,
                               preferred_element_type=jnp.float32), axis=0)
    return jnp.dot(x, w.astype(x.dtype))


class QDense(nn.Module):
    """DenseGeneral twin that can consume weight-only int8 params.

    Identical param surface to ``nn.DenseGeneral`` ("kernel" [in, out],
    "bias" [out]) and identical math for dense weights. When the bound
    kernel is a ``{"q": int8, "scale": f32}`` node (module_inject/
    module_quantize.py, the analog of the reference's int8 serving gemms,
    pt_binding.cpp:1197-1244), the matmul consumes the int8 weights
    directly via the fused-dequant Pallas kernel — weights stay int8 in
    HBM across the whole decode loop instead of being re-materialized
    bf16 (which XLA's loop hoisting would otherwise do).
    """
    features: int
    use_bias: bool = True
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    kernel_init: Any = None
    bias_init: Any = None

    @nn.compact
    def __call__(self, x):
        kinit = self.kernel_init or nn.initializers.lecun_normal()
        # int8-quantized kernels are {"q", "scale"} dicts bound in place
        # of the array: read them via the scope directly — self.param's
        # shape check happens to pass on current flax only because leaf
        # comparison zip-truncates (ADVICE r3); don't rely on that
        bound = (self.scope.get_variable("params", "kernel")
                 if self.scope.has_variable("params", "kernel") else None)
        if _is_qleaf(bound):
            kernel = bound
        else:
            kernel = self.param("kernel", kinit,
                                (jnp.shape(x)[-1], self.features),
                                self.param_dtype)
        bias = None
        if self.use_bias:
            binit = self.bias_init or nn.initializers.zeros
            bias = self.param("bias", binit, (self.features,), self.param_dtype)
        x = x.astype(self.dtype)
        x = _maybe_quantize_activation(x, self.path)
        if _is_qleaf(kernel):
            from ..ops.pallas.wo_int8_matmul import wo_int8_matmul
            y = wo_int8_matmul(x, kernel["q"], kernel["scale"],
                               out_dtype=self.dtype)
        else:
            y = dot_exact_weights(x, kernel)
        if bias is not None:
            y = y + bias.astype(self.dtype)
        return y


class LayerNorm(nn.Module):
    """LayerNorm with fp32 accumulation (reference: normalize_kernels.cu
    fused layernorm; XLA fuses this chain on TPU without a custom kernel)."""
    epsilon: float = 1e-5
    dtype: Any = jnp.float32
    use_bias: bool = True
    use_scale: bool = True

    @nn.compact
    def __call__(self, x):
        orig_dtype = x.dtype
        x = x.astype(jnp.float32)
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
        y = (x - mean) * jax.lax.rsqrt(var + self.epsilon)
        if self.use_scale:
            scale = self.param("scale", nn.with_logical_partitioning(
                nn.initializers.ones, ("embed",)), (x.shape[-1],), jnp.float32)
            y = y * scale
        if self.use_bias:
            bias = self.param("bias", nn.with_logical_partitioning(
                nn.initializers.zeros, ("embed",)), (x.shape[-1],), jnp.float32)
            y = y + bias
        return y.astype(orig_dtype)


class RMSNorm(nn.Module):
    """Root-mean-square norm with a learned scale and no bias, the mean
    of squares in float32: ``x / sqrt(mean(x^2) + eps) * scale``."""
    epsilon: float = 1e-5

    @nn.compact
    def __call__(self, x):
        orig_dtype = x.dtype
        x = x.astype(jnp.float32)
        y = x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), axis=-1, keepdims=True) + self.epsilon)
        scale = self.param("scale", nn.with_logical_partitioning(
            nn.initializers.ones, ("embed",)), (x.shape[-1],), jnp.float32)
        return (y * scale).astype(orig_dtype)


NORMS = {"layernorm": LayerNorm, "rmsnorm": RMSNorm}


class SelfAttention(nn.Module):
    """Fused-QKV multi-head attention (reference: DeepSpeedSelfAttention,
    ops/transformer/inference/transformer_inference.py:473, training kernel
    csrc/transformer/ds_transformer_cuda.cpp).

    ``decode=True`` enables the preallocated KV cache (reference: the
    softmax_context KV-cache kernel, csrc/transformer/inference): cache
    variables live in the "cache" collection; prefill writes the whole
    prompt at index 0, each decode step appends one token with
    ``lax.dynamic_update_slice``. Initialize the cache by applying the
    model once on a [batch, max_len] input with ``mutable=["cache"]``.
    """
    n_heads: int
    d_model: int
    causal: bool = True
    dropout_rate: float = 0.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    use_bias: bool = True
    rotary: bool = False
    rotary_dim: Optional[int] = None
    rotary_base: float = 10000.0
    qk_norm: Any = False                 # True: RMSNorm over the whole q and k
                                         # projections, before the heads split;
                                         # "head": over each head's values,
                                         # after it
    norm_epsilon: float = 1e-5           # (qk_norm's)
    n_kv_heads: Optional[int] = None     # fewer K/V heads than query heads:
                                         # query head i reads K/V head
                                         # i // (n_heads // n_kv_heads)
    head_dim: Optional[int] = None       # a head's width where it is not
                                         # d_model // n_heads (Falcon-H1)
    key_multiplier: float = 1.0          # on k as projected (Falcon-H1's muP)
    attn_backend: Optional[str] = None
    alibi: bool = False
    seq_parallel: Optional[str] = None   # None=auto, "ulysses", "ring", "none"
    sparsity_config: Any = None          # SparsityConfig -> block-sparse path
    sparsity_pattern_len: Optional[int] = None   # the TRAINED pattern length
                                         # (decode serves this exact pattern)

    @nn.compact
    def __call__(self, x, mask=None, bias=None, deterministic=True,
                 decode=False, positions=None):
        head_dim = self.head_dim or self.d_model // self.n_heads
        n_kv = self.n_kv_heads or self.n_heads
        group = self.n_heads // n_kv         # query heads a K/V head serves
        q_width, kv_width = self.n_heads * head_dim, n_kv * head_dim
        qkv = QDense(
            features=q_width + 2 * kv_width, use_bias=self.use_bias,
            dtype=self.dtype, param_dtype=self.param_dtype,
            kernel_init=dense_init(("embed", "qkv")),
            bias_init=nn.with_logical_partitioning(nn.initializers.zeros, ("qkv",)),
            name="qkv")(x)
        if group == 1:
            q, k, v = jnp.split(qkv, 3, axis=-1)
        else:
            q, k, v = jnp.split(
                qkv, [q_width, q_width + kv_width], axis=-1)
        if self.key_multiplier != 1.0:
            k = k * self.key_multiplier
        # the QK norm sits here and nowhere else: the flash path, the
        # chunked prefill and the paged decode all take q and k from
        # this point
        if self.qk_norm is True:
            q = RMSNorm(epsilon=self.norm_epsilon, name="q_norm")(q)
            k = RMSNorm(epsilon=self.norm_epsilon, name="k_norm")(k)
        b, s = x.shape[0], x.shape[1]
        q = q.reshape(b, s, self.n_heads, head_dim)
        k = k.reshape(b, s, n_kv, head_dim)
        v = v.reshape(b, s, n_kv, head_dim)
        if self.qk_norm == "head":
            q = RMSNorm(epsilon=self.norm_epsilon, name="q_norm")(q)
            k = RMSNorm(epsilon=self.norm_epsilon, name="k_norm")(k)

        if self.rotary:
            from ..ops.transformer.rotary import apply_rotary_pos_emb
            rdim = self.rotary_dim or head_dim
            q, k = apply_rotary_pos_emb(q, k, rotary_dim=rdim,
                                        positions=positions,
                                        base=self.rotary_base)

        causal = self.causal
        decode_out = None
        if decode:
            # Cache lives TRANSPOSED ([b, heads, d, max_len], "K^T
            # layout") so the Pallas decode kernel streams 128-aligned
            # (d, block_k) tiles for any head_dim and q.K^T is a direct
            # MXU matmul (see ops/pallas/decode_attention.py).
            kc = k.transpose(0, 2, 3, 1)                 # [b, h, d, s]
            vc = v.transpose(0, 2, 3, 1)
            paged = self.has_variable("cache", "page_table")
            if not paged:
                cached_key = self.variable("cache", "cached_key", jnp.zeros,
                                           kc.shape, kc.dtype)
                cached_value = self.variable("cache", "cached_value",
                                             jnp.zeros, vc.shape, vc.dtype)
            cache_index = self.variable("cache", "cache_index",
                                        lambda: jnp.zeros((), jnp.int32))
            if not self.is_initializing() and \
                    self.is_mutable_collection("kv_token"):
                # Paged-serving hook (serving/paging): publish THIS call's
                # K/V (post-rotary, K^T layout) so the caller can scatter
                # it straight into its page pool instead of re-slicing the
                # full cache. Structural opt-in: only appears when the
                # caller lists "kv_token" as mutable, so the classic
                # contiguous programs (generate(), slot serving) keep
                # their exact tree structure and compiled executables.
                self.variable("kv_token", "k", lambda: kc).value = kc
                self.variable("kv_token", "v", lambda: vc).value = vc
            if self.is_initializing():
                max_len = s
            elif paged:
                # Paged-pool decode (serving/paging kernel path,
                # inference/cache.py make_paged_view): "cache" holds the
                # slot page table, the per-row lengths and — inside a
                # layer scan — this layer's index; the page pool itself
                # ([pages, h, d, page_len], or the whole layer-stacked
                # [L, pages, ...] broadcast through the scan; int8 +
                # scale planes when KV-quantized) is the read-only
                # "kv_pool" collection. The paged-attention kernel reads
                # its pages in place, so neither a per-slot view nor a
                # layer's slice of the pool ever materializes. The
                # current token's K/V attends via explicit operands and
                # is appended to the pool by the ENGINE after the step
                # (quantized on write), which is why kv_token
                # publication is mandatory here.
                if s != 1:
                    raise NotImplementedError(
                        "paged-pool decode is single-token (got chunk "
                        f"length {s}); chunked prefill runs through the "
                        "gathered-row path")
                if mask is not None or self.sparsity_config is not None \
                        or (self.dropout_rate > 0.0 and not deterministic):
                    raise NotImplementedError(
                        "paged-pool decode does not support external "
                        "masks, block-sparse patterns, or live attention "
                        "dropout")
                if not self.is_mutable_collection("kv_token"):
                    raise ValueError(
                        "paged-pool decode requires 'kv_token' in the "
                        "mutable collections — the engine scatters this "
                        "step's K/V into the pool after the step")
                from ..ops.pallas.paged_attention import paged_attention
                ptab = self.get_variable("cache", "page_table")
                idx = cache_index.value          # [slots] pooled tokens
                pool = self.variables["kv_pool"]
                slopes = (alibi_slopes(self.n_heads) if self.alibi
                          else None)
                decode_out = paged_attention(
                    q, pool["cached_key"], pool["cached_value"], ptab, idx,
                    kc, vc, alibi_slopes=slopes,
                    layer=(self.get_variable("cache", "layer")
                           if self.has_variable("cache", "layer") else None),
                    k_scale=pool.get("key_scale"),
                    v_scale=pool.get("value_scale"),
                    mesh=_usable_global_mesh())
                cache_index.value = idx + 1
            else:
                max_len = cached_key.value.shape[3]
                idx = cache_index.value
                if idx.ndim == 1:
                    # Per-row cache index ([b] vector — the serving slot
                    # batch / ragged-prompt decode): every row appends its
                    # s tokens at its OWN length. s == 1 is the kernel hot
                    # path; s > 1 is the ragged multi-token step the
                    # speculative verification program drives (each row's
                    # candidate block lands at its own frontier, attention
                    # masked per row below) — prefill and masked chunks
                    # stay on the shared-scalar path.
                    if mask is not None or self.sparsity_config is not None \
                            or (self.dropout_rate > 0.0 and not deterministic):
                        raise NotImplementedError(
                            "per-row cache_index decode does not support "
                            "external masks, block-sparse patterns, or live "
                            "attention dropout (the dense cache path is "
                            "shared-scalar only)")
                    if s != 1 and self.alibi:
                        raise NotImplementedError(
                            "per-row multi-token decode (speculative "
                            "verification) does not support ALiBi — the "
                            "shared additive bias cannot express per-row "
                            "positions; serve ALiBi models without "
                            "serving.speculation")
                    row_update = jax.vmap(
                        lambda c, u, i: jax.lax.dynamic_update_slice(
                            c, u, (0, 0, i)))
                    k_all = row_update(cached_key.value, kc, idx)
                    v_all = row_update(cached_value.value, vc, idx)
                else:
                    k_all = jax.lax.dynamic_update_slice(cached_key.value, kc,
                                                         (0, 0, 0, idx))
                    v_all = jax.lax.dynamic_update_slice(cached_value.value, vc,
                                                         (0, 0, 0, idx))
                cached_key.value = k_all
                cached_value.value = v_all
                cache_index.value = idx + s
                # sparsity pattern at decode: the current query rows'
                # slice of the TRAINED block pattern becomes a key mask
                # over the cache — same semantics as training, no dense
                # fallback drift (reference class: sparse models served
                # by masking, sparse_self_attention.py)
                pattern = None
                if self.sparsity_config is not None:
                    # same config refusals as the training forward —
                    # silently different serving semantics would be
                    # worse than the error
                    _check_sparse_compat(self.sparsity_config, bias,
                                         self.causal, self.alibi)
                    # the pattern is pinned to the TRAINED length: random
                    # block layouts (BigBird) are length-dependent, so
                    # building at the cache length would silently serve a
                    # pattern the model never trained with
                    import numpy as _np
                    blk = self.sparsity_config.block
                    plen = self.sparsity_pattern_len or (
                        max_len if max_len % blk == 0
                        else (max_len // blk + 1) * blk)
                    layout = _np.asarray(
                        self.sparsity_config.make_layout(plen))
                    nbp = layout.shape[-1]
                    lay = jnp.asarray(layout.astype(bool))  # [H, nbp, nbp]
                    # gather rows/cols per position: exact [s, max_len]
                    # coverage for ANY block-vs-cache-length relation
                    # (generate() rounds the cache to 128s, which need
                    # not align with plen or block). Positions beyond
                    # plen are clamped AND masked off — a query past the
                    # trained pattern can only occur past max_seq_len,
                    # which the position embeddings refuse first.
                    row_pos = idx + jnp.arange(s)
                    row_blocks = jnp.clip(row_pos // blk, 0, nbp - 1)
                    col_pos = jnp.arange(max_len)
                    col_blocks = jnp.clip(col_pos // blk, 0, nbp - 1)
                    rows = jnp.take(lay, row_blocks, axis=1)  # [H,s,nbp]
                    pattern = jnp.take(rows, col_blocks, axis=2)
                    pattern = jnp.logical_and(
                        pattern, (col_pos < plen)[None, None, :])[None]
                    # [1, H, s, max_len]; elementwise causality comes
                    # from the cache validity mask ANDed below
                if s == 1 and mask is None and pattern is None and (
                        self.dropout_rate == 0.0 or deterministic):
                    # THE serving hot path (reference: softmax_context,
                    # pt_binding.cpp:1197-1244): single-token KV-cache
                    # attention with the length mask — and ALiBi — handled
                    # in-kernel. No [b,h,1,S] mask tensor, no bias tensor.
                    from ..ops.pallas import decode_attention
                    slopes = (alibi_slopes(self.n_heads)
                              if self.alibi else None)
                    if group > 1:
                        # the contiguous decode kernel takes one K/V
                        # head a query head (the paged one groups them)
                        k_all = jnp.repeat(k_all, group, axis=1)
                        v_all = jnp.repeat(v_all, group, axis=1)
                    decode_out = decode_attention(
                        q, k_all, v_all, idx + 1, alibi_slopes=slopes,
                        mesh=_usable_global_mesh())
                else:
                    # prefill / externally-masked chunks: dense path over
                    # the cache with an explicit validity+causality mask
                    # (query row i = global pos idx+i attends slots <= it)
                    k = k_all.transpose(0, 3, 1, 2)      # [b, s, h, d]
                    v = v_all.transpose(0, 3, 1, 2)
                    if idx.ndim == 1:
                        # ragged multi-token decode (speculative verify):
                        # batch row b's query i sits at global position
                        # idx[b]+i, so the validity mask is per-row
                        rows = idx[:, None] + jnp.arange(s)[None, :]
                        cache_mask = (jnp.arange(max_len)[None, None, None, :]
                                      <= rows[:, None, :, None])
                    else:
                        rows = idx + jnp.arange(s)[:, None]
                        cols = jnp.arange(max_len)[None, :]
                        cache_mask = (cols <= rows)[None, None, :, :]
                    if mask is not None and mask.shape[-1] != max_len:
                        # caller's mask covers only the current chunk:
                        # scatter it into cache key space at the offset.
                        full = jnp.ones(mask.shape[:-1] + (max_len,), bool)
                        mask = jax.lax.dynamic_update_slice(
                            full, mask.astype(bool),
                            (0,) * (mask.ndim - 1) + (idx,))
                    mask = cache_mask if mask is None else jnp.logical_and(
                        mask, cache_mask)
                    if pattern is not None:
                        mask = jnp.logical_and(mask, pattern)
                    causal = False

        if decode_out is not None:
            out = decode_out.reshape(b, s, q_width)
            out = activation_constraint(out, ("batch", "seq", "embed"))
            return QDense(
                features=self.d_model, use_bias=self.use_bias,
                dtype=self.dtype, param_dtype=self.param_dtype,
                kernel_init=dense_init(("qkv", "embed")),
                bias_init=nn.with_logical_partitioning(
                    nn.initializers.zeros, ("embed",)),
                name="out")(out)

        if group > 1:
            # the flash path and the chunk's gathered row: each K/V head
            # stands for its group of query heads
            k = jnp.repeat(k, group, axis=2)
            v = jnp.repeat(v, group, axis=2)

        if self.alibi:
            # computed HERE (not in the model) because only the attention op
            # knows the true key length once the KV cache is spliced in.
            q_pos = positions if positions is not None else jnp.arange(s)
            ab = alibi_bias(self.n_heads, jnp.broadcast_to(q_pos, (s,)),
                            jnp.arange(k.shape[1]), dtype=jnp.float32)
            bias = ab if bias is None else bias + ab

        dropout_rng = None
        if self.dropout_rate > 0.0 and not deterministic:
            dropout_rng = self.make_rng("dropout")

        if self.sparsity_config is not None and not decode:
            # Block-sparse pattern path (reference: SparseSelfAttention
            # wired into BERT via SparseAttentionUtils). The layout encodes
            # causality for unidirectional configs; additive bias (ALiBi)
            # has no reference sparse analog (dropout does ride it — below).
            _check_sparse_compat(self.sparsity_config, bias, causal)
            plen = self.sparsity_pattern_len
            pinned_mask = None
            if (plen and plen != q.shape[1]
                    and not getattr(self.sparsity_config,
                                    "prefix_stable", True)):
                # random-block layouts are length-dependent: a forward at
                # s != trained length must slice the TRAINED pattern.
                # sparse_attention would AND in its own layout(s) — a
                # DIFFERENT random pattern — so this case goes straight
                # to dense attention with the sliced trained mask
                # (correctness over the kernel's FLOP savings).
                from ..ops.sparse_attention.sparse_self_attention import \
                    layout_to_dense_mask
                sl = q.shape[1]
                pinned_mask = layout_to_dense_mask(
                    self.sparsity_config, plen)[:, :, :sl, :sl]
                if mask is not None:
                    pinned_mask = jnp.logical_and(pinned_mask, mask)
            # attention dropout rides both sparse sub-paths (r5): the
            # block-sparse kernel fuses the flash kernel's counter-based
            # keep hash; the dense-mask fallback samples identical bits
            if pinned_mask is not None:
                out = attention(q, k, v, mask=pinned_mask,
                                dropout_rate=self.dropout_rate,
                                dropout_rng=dropout_rng,
                                deterministic=deterministic,
                                seq_parallel="none")
            else:
                from ..ops.sparse_attention import sparse_attention
                out = sparse_attention(q, k, v, self.sparsity_config,
                                       attn_mask=mask,
                                       dropout_rate=self.dropout_rate,
                                       dropout_rng=dropout_rng,
                                       deterministic=deterministic)
        else:
            out = attention(q, k, v, bias=bias, mask=mask, causal=causal,
                            dropout_rate=self.dropout_rate,
                            dropout_rng=dropout_rng,
                            deterministic=deterministic,
                            backend=self.attn_backend,
                            seq_parallel=self.seq_parallel)
        # named for the "attn_out" remat policy (save_only_these_names):
        # under that policy the backward keeps THIS tensor and recomputes
        # everything else, so the flash kernel never runs twice
        out = checkpoint_name(out, "attn_out")
        out = out.reshape(b, s, q_width)
        out = activation_constraint(out, ("batch", "seq", "embed"))
        return QDense(
            features=self.d_model, use_bias=self.use_bias, dtype=self.dtype,
            param_dtype=self.param_dtype,
            kernel_init=dense_init(("qkv", "embed")),
            bias_init=nn.with_logical_partitioning(nn.initializers.zeros, ("embed",)),
            name="out")(out)


class LatentAttention(nn.Module):
    """Multi-head latent attention (DeepSeek-V2/V3; HF
    ``modeling_deepseek_v3.py`` with ``q_lora_rank`` null): keys and
    values are up-projections of ONE compressed vector a token, and that
    vector, not the heads, is what a cache keeps. With ``x`` the normed
    input:

        q = x W_q -> [H, nope + rope] = q_nope | q_pe
        x W_kva -> c_raw [rank] | k_pe_raw [rope];  c = RMSNorm(c_raw)
        [k_nope | v] = c W_kvb -> [H, nope + v];  q_pe, k_pe = RoPE(.),
        k_pe one vector shared by every head
        score = (q_nope . k_nope + q_pe . k_pe) / sqrt(nope + rope)

    ``rope_interleave``: the ``rope`` lanes are de-interleaved
    (``x0 x1 x2 .. -> x0 x2 .. x1 x3 ..``) and then rotated by halves, as
    published.

    Two forms of one attention. **Expanded** (``decode=False``: a whole
    sequence, training, the shape-only init): ``k_nope`` and ``v`` are
    computed for every position and attention runs over heads, keys
    ``nope + rope`` wide and values ``v`` wide. **Absorbed** (every
    ``decode=True`` step — a prefill chunk over its slot's gathered row,
    the gather path's token, and the paged kernel's): ``W_kvb``'s key
    half is folded into the query, ``q_lat = q_nope W_kvb^K[h]``, so
    ``score = q_lat . c + q_pe . k_pe`` reads the cached vector
    ``c | k_pe`` (``rank + rope`` wide) directly, and the value half is
    applied after the softmax, ``o = (sum p c) W_kvb^V[h]``: nothing the
    width of the heads is ever made for a cached position.

    The cache unit is ``cached_key`` ``[b, 1, rank + rope, max_len]``
    (K^T layout, one "head") and ``cache_index``; there is no
    ``cached_value`` (inference/cache.py). A step publishes its tokens'
    vectors as ``kv_token/k``. Under the paged view (``page_table`` in
    "cache") the step is one token and
    ``ops.pallas.latent_attention`` walks the pool in place."""
    n_heads: int
    d_model: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rotary_base: float = 10000.0
    rope_interleave: bool = True
    norm_epsilon: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, decode=False, positions=None):
        from ..ops.transformer.rotary import apply_rotary_pos_emb
        heads, rank = self.n_heads, self.kv_lora_rank
        nope, rope, vd = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                          self.v_head_dim)
        b, s = x.shape[0], x.shape[1]
        scale = (nope + rope) ** -0.5

        def dense(features, names, name):
            return QDense(features=features, use_bias=False, dtype=self.dtype,
                          param_dtype=self.param_dtype,
                          kernel_init=dense_init(names), name=name)

        q = dense(heads * (nope + rope), ("embed", "qkv"), "q_proj")(x)
        q = q.reshape(b, s, heads, nope + rope)
        q_nope, q_pe = q[..., :nope], q[..., nope:]
        kva = dense(rank + rope, ("embed", None), "kv_a_proj")(x)
        c = RMSNorm(epsilon=self.norm_epsilon,
                    name="kv_a_norm")(kva[..., :rank])        # [b, s, rank]
        k_pe = kva[..., rank:][:, :, None, :]                 # [b, s, 1, rope]
        if self.rope_interleave:
            unweave = lambda t: t.reshape(t.shape[:-1] + (rope // 2, 2)) \
                .swapaxes(-1, -2).reshape(t.shape)
            q_pe, k_pe = unweave(q_pe), unweave(k_pe)
        q_pe, k_pe = apply_rotary_pos_emb(q_pe, k_pe, positions=positions,
                                          base=self.rotary_base)
        # [rank, H, nope + v]: a head's key and value up-projections
        w_kvb = self.param(
            "kv_b_proj", nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), (None, "qkv")),
            (rank, heads * (nope + vd)), self.param_dtype) \
            .reshape(rank, heads, nope + vd)
        out_proj = dense(self.d_model, ("qkv", "embed"), "out")

        if not decode or self.is_initializing():
            if decode:
                # the shape-only init of a cache (or of a page pool:
                # batch = pages, length = one page)
                self.variable("cache", "cached_key", jnp.zeros,
                              (b, 1, rank + rope, s), c.dtype)
                self.variable("cache", "cache_index",
                              lambda: jnp.zeros((), jnp.int32))
            kv = dot_exact_weights(c, w_kvb.reshape(rank, -1)) \
                .reshape(b, s, heads, nope + vd)
            k = jnp.concatenate(
                [kv[..., :nope],
                 jnp.broadcast_to(k_pe, (b, s, heads, rope))], axis=-1)
            qf = jnp.concatenate([q_nope, q_pe], axis=-1)
            sc = jnp.einsum("bqhd,bkhd->bhqk", qf, k,
                            preferred_element_type=jnp.float32) * scale
            sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, NEG_INF)
            o = jnp.einsum("bhqk,bkhd->bqhd",
                           jax.nn.softmax(sc, axis=-1).astype(kv.dtype),
                           kv[..., nope:],
                           preferred_element_type=jnp.float32)
            return out_proj(o.astype(self.dtype).reshape(b, s, heads * vd))

        mesh = _usable_global_mesh()
        if mesh is not None and mesh.shape.get("model", 1) > 1:
            raise ValueError(
                "mp_size > 1 (tensor-parallel serving) is not built for "
                "latent attention: every head reads one compressed vector "
                "a token, and neither the latent pool nor its kernel is "
                "split over the model axis")
        if not self.is_mutable_collection("kv_token"):
            raise ValueError(
                "latent attention under decode=True publishes the step's "
                "vectors as the 'kv_token' collection (the serving "
                "programs list it as mutable); generate() is not built "
                "for a latent cache: serve() the model")
        # absorbed: the key up-projection folded into the query
        wk = w_kvb[..., :nope].astype(self.dtype)             # [rank, H, nope]
        wv = w_kvb[..., nope:].astype(self.dtype)             # [rank, H, v]
        q_lat = jnp.einsum("bshn,chn->bshc", q_nope, wk,
                           preferred_element_type=jnp.float32) \
            .astype(self.dtype)
        q_full = jnp.concatenate([q_lat, q_pe], axis=-1)     # [b,s,H,rank+rope]
        latent = jnp.concatenate([c, k_pe[:, :, 0, :]], axis=-1)
        lat_t = latent.transpose(0, 2, 1)[:, None]            # [b,1,D,s]
        self.variable("kv_token", "k", lambda: lat_t).value = lat_t
        cache_index = self.variable("cache", "cache_index",
                                    lambda: jnp.zeros((), jnp.int32))
        idx = cache_index.value
        if self.has_variable("cache", "page_table"):
            if s != 1:
                raise NotImplementedError(
                    f"paged-pool decode is single-token (got chunk length "
                    f"{s}); chunked prefill runs through the gathered-row "
                    "path")
            from ..ops.pallas.latent_attention import latent_attention
            o_lat = latent_attention(
                q_full[:, 0], self.variables["kv_pool"]["cached_key"],
                self.get_variable("cache", "page_table"), idx, latent[:, 0],
                value_width=rank, softmax_scale=scale,
                layer=(self.get_variable("cache", "layer")
                       if self.has_variable("cache", "layer") else None))
            o_lat = o_lat[:, None]                            # [b,1,H,rank]
            cache_index.value = idx + 1
        else:
            cached = self.variable("cache", "cached_key", jnp.zeros,
                                   lat_t.shape, lat_t.dtype)
            max_len = cached.value.shape[-1]
            if idx.ndim == 1:
                lat_all = jax.vmap(
                    lambda row, u, i: jax.lax.dynamic_update_slice(
                        row, u, (0, 0, i)))(cached.value, lat_t, idx)
                rows = idx[:, None] + jnp.arange(s)[None, :]  # [b, s]
            else:
                lat_all = jax.lax.dynamic_update_slice(
                    cached.value, lat_t, (0, 0, 0, idx))
                rows = jnp.broadcast_to(idx + jnp.arange(s), (b, s))
            cached.value = lat_all
            cache_index.value = idx + s
            lat_all = lat_all[:, 0]                           # [b, D, T]
            sc = jnp.einsum("bshd,bdt->bhst", q_full, lat_all,
                            preferred_element_type=jnp.float32) * scale
            seen = jnp.arange(max_len)[None, None, :] <= rows[:, :, None]
            sc = jnp.where(seen[:, None], sc, NEG_INF)
            o_lat = jnp.einsum("bhst,bct->bshc",
                               jax.nn.softmax(sc, axis=-1).astype(self.dtype),
                               lat_all[:, :rank],
                               preferred_element_type=jnp.float32)
        o = jnp.einsum("bshc,chv->bshv", o_lat.astype(self.dtype), wv,
                       preferred_element_type=jnp.float32)
        return out_proj(o.astype(self.dtype).reshape(b, s, heads * vd))


class MLP(nn.Module):
    """Transformer FFN (reference: fused bias-GELU csrc/transformer/gelu_kernels.cu
    + feed_forward.h; XLA fuses the bias+gelu epilogue into the matmul)."""
    d_model: int
    d_ff: int
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    use_bias: bool = True
    activation: str = "gelu"
    dropout_rate: float = 0.0

    @nn.compact
    def __call__(self, x, deterministic=True):
        h = QDense(
            features=self.d_ff, use_bias=self.use_bias, dtype=self.dtype,
            param_dtype=self.param_dtype,
            kernel_init=dense_init(("embed", "mlp")),
            bias_init=nn.with_logical_partitioning(nn.initializers.zeros, ("mlp",)),
            name="fc_in")(x)
        if self.activation == "gelu":
            h = jax.nn.gelu(h, approximate=True)
        elif self.activation == "gelu_exact":
            h = jax.nn.gelu(h, approximate=False)
        elif self.activation == "relu":
            h = jax.nn.relu(h)
        elif self.activation == "silu":
            h = jax.nn.silu(h)
        else:
            raise ValueError(f"unknown activation {self.activation}")
        h = activation_constraint(h, ("batch", "seq", "mlp"))
        h = QDense(
            features=self.d_model, use_bias=self.use_bias, dtype=self.dtype,
            param_dtype=self.param_dtype,
            kernel_init=dense_init(("mlp", "embed")),
            bias_init=nn.with_logical_partitioning(nn.initializers.zeros, ("embed",)),
            name="fc_out")(h)
        if self.dropout_rate > 0.0 and not deterministic:
            h = nn.Dropout(rate=self.dropout_rate)(h, deterministic=False)
        return h


class GatedMLP(nn.Module):
    """The gated (SwiGLU) feed-forward of the Llama line and its kin:
    ``w2(silu(w1 x) * w3 x)``, no bias. Falcon-H1's muP lays a
    multiplier on the gate before its activation and one on the
    result."""
    d_model: int
    d_ff: int
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    gate_multiplier: float = 1.0
    down_multiplier: float = 1.0

    @nn.compact
    def __call__(self, x):
        def dense(features, names, name):
            return QDense(features=features, use_bias=False, dtype=self.dtype,
                          param_dtype=self.param_dtype,
                          kernel_init=dense_init(names), name=name)
        gate = dense(self.d_ff, ("embed", "mlp"), "w1")(x)
        if self.gate_multiplier != 1.0:
            gate = gate * self.gate_multiplier
        h = jax.nn.silu(gate) * dense(self.d_ff, ("embed", "mlp"), "w3")(x)
        h = activation_constraint(h, ("batch", "seq", "mlp"))
        y = dense(self.d_model, ("mlp", "embed"), "w2")(h)
        return y if self.down_multiplier == 1.0 else y * self.down_multiplier


class ShortConv(nn.Module):
    """The gated short convolution of the LFM2 line (Liquid AI; HF
    ``Lfm2ShortConv``): ``[B, C, u] = W_in x`` (three parts of
    ``d_model``), a depthwise causal convolution of ``kernel`` taps over
    time on ``B * u`` — ``z_t = sum_j w_j (B u)_{t - (kernel-1) + j}``, no
    bias, no activation — and ``W_out (C * z)``.

    Its recurrent state is the last ``kernel - 1`` columns of ``B * u``,
    float32. One computation serves the three forms the engines need: it
    lays the state before the sequence's own ``B * u`` and sums the
    ``kernel`` shifted products, so a whole sequence (state zero: no
    ``decode``), a prefill chunk that takes the carry of the chunk before
    it and one decode token against its slot's carry are the same
    products in the same order, and agree to the bit in float32.

    ``decode=True`` keeps the state in the "cache" collection
    (``conv_state`` ``[batch, kernel - 1, d_model]``) as
    ``SelfAttention`` keeps its keys and values. ``token_mask``
    (``[batch, seq]`` bool; the live rows are a prefix of each row) names
    the positions that hold a token: the new state is the one after the
    last live position, so an idle slot (no live position) keeps its
    state and a chunk's right padding writes none. A caller that lists
    "kv_token" as mutable also gets ``trail`` there: the state followed
    by the sequence's ``B * u``, ``[batch, kernel - 1 + seq, d_model]``,
    from which the state at the end of any position is a slice (the
    paged server keeps one a page, ``inference/cache.py``)."""
    d_model: int
    kernel: int = 3
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, decode=False, token_mask=None):
        b, s, d = x.shape
        taps = self.kernel
        bcu = QDense(features=3 * d, use_bias=False, dtype=self.dtype,
                     param_dtype=self.param_dtype,
                     kernel_init=dense_init(("embed", "mlp")),
                     name="in_proj")(x)
        gate_b, gate_c, u = jnp.split(bcu.astype(jnp.float32), 3, axis=-1)
        # [taps, d]: tap j multiplies the column (taps - 1 - j) steps back
        w = self.param("w", nn.with_logical_partitioning(
            nn.initializers.variance_scaling(1.0, "fan_in", "normal",
                                             in_axis=0, out_axis=1),
            (None, "embed")), (taps, d), self.param_dtype)
        bu = gate_b * u                                    # [b, s, d] f32
        state = None
        if decode:
            state = self.variable("cache", "conv_state", jnp.zeros,
                                  (b, taps - 1, d), jnp.float32)
        carry = (jnp.zeros((b, taps - 1, d), jnp.float32)
                 if state is None or self.is_initializing() else state.value)
        trail = jnp.concatenate([carry, bu], axis=1)   # [b, taps-1+s, d]
        z = sum(w[j].astype(jnp.float32) * trail[:, j:j + s]
                for j in range(taps))
        if state is not None and not self.is_initializing():
            live = (jnp.full((b,), s, jnp.int32) if token_mask is None
                    else jnp.sum(token_mask, axis=1, dtype=jnp.int32))
            state.value = jax.vmap(
                lambda t, n: jax.lax.dynamic_slice_in_dim(t, n, taps - 1))(
                trail, live)
            if self.is_mutable_collection("kv_token"):
                self.variable("kv_token", "trail", lambda: trail).value = \
                    trail
        return QDense(features=d, use_bias=False, dtype=self.dtype,
                      param_dtype=self.param_dtype,
                      kernel_init=dense_init(("mlp", "embed")),
                      name="out_proj")(gate_c * z)


def _a_log_init(key, shape, dtype=jnp.float32):
    """``A = -exp(A_log)`` with ``A`` uniform in 1-16, as published."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0,
                                      16.0)).astype(dtype)


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """``softplus(dt_bias)`` log-uniform in 1e-3 - 1e-1, as published:
    the inverse softplus of such a step."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _mixer_in_proj_init(d_ssm, bc, in_multiplier, mup):
    """Fan-in normal over ``z | x | B | C | dt``, with B's and C's columns
    widened by ``1 / (in_multiplier * mup)`` so that both arrive at unit
    variance for a normed input whatever the muP factors in front are. At
    fan-in scale behind Falcon-H1's factors (0.25 x 0.18 and 0.25 x 0.5)
    ``C . B`` is ~2e-2 a source position and the recurrent part of ``y``
    ~1% of the skip path's ``D x``: a seeded state that no bf16 logit can
    tell from zeros. A trained model's B and C are of order one."""
    base = nn.initializers.variance_scaling(1.0, "fan_in", "normal")
    gain = np.ones(2 * d_ssm + 2 * bc, np.float32)
    gain[2 * d_ssm:2 * d_ssm + bc] = 1.0 / (in_multiplier * mup[2])
    gain[2 * d_ssm + bc:] = 1.0 / (in_multiplier * mup[3])

    def init(key, shape, dtype=jnp.float32):
        w = base(key, shape, jnp.float32)
        return w.at[:, :gain.size].multiply(gain).astype(dtype)
    return nn.with_logical_partitioning(init, ("embed", "mlp"))


class Mamba2Mixer(nn.Module):
    """The selective state-space mixer of Mamba-2 as Falcon-H1 lays it
    out (HF ``FalconH1Mixer``). With ``u = x * in_multiplier``:

        [z | xBC | dt] = (W_in u) * mup     (mup: one factor over each of
                                             z, x, B, C, dt)
        xBC = silu(conv1d(xBC) + b)          depthwise, causal, d_conv taps
        [x | B | C] = xBC                    x: heads x d_head; B, C:
                                             groups x d_state
        dt = softplus(dt + dt_bias);  a = exp(-exp(A_log) dt)
        S_t = a_t S_{t-1} + dt_t x_t B_t^T;  y_t = S_t C_t + D x_t
        out = W_out RMSNorm_grouped(y * silu(z))

    Two recurrent states, float32, in the "cache" collection when
    ``decode``: ``conv_state [batch, d_conv - 1, conv width]``, the last
    columns of xBC before the convolution (as ``ShortConv`` keeps them),
    and ``ssm_state [batch, heads, d_state, d_head]`` — ``S`` with the
    state dimension before the head's, which is how
    ``ops/pallas/ssm_update.py`` wants it on the chip.

    One computation serves a whole sequence from zero state (no
    ``decode``), a prefill chunk from a carried state and a decode token
    against its slot's state. A sequence goes through the published
    chunked form, ``chunk`` positions at a time: inside a chunk the
    outputs are matrix products over the chunk's decays, between chunks
    the state is carried; a position outside ``token_mask`` (``[batch,
    seq]`` bool, the live positions a prefix of each row) gets a step of
    0, which leaves the state as it was, so an idle row and a chunk's
    padding write nothing. One token (``decode`` and ``seq == 1``) is the
    recurrence itself (``ssm_update``: a Pallas kernel on the TPU, the
    rows that do not decode skipped and the state aliased).

    A caller that lists "kv_token" as mutable also gets ``chunk_states``
    there: both states as they stood at the end of every ``chunk``
    positions, ``[batch, seq / chunk, ...]`` — with ``chunk`` the page
    length, the states at the page ends, of which the paged server keeps
    some as snapshots (``inference/cache.py``). The scan's products are
    float32 at ``Precision.HIGHEST`` whatever ``dtype`` is: they are
    under 1% of a layer's operations and the state they build is carried
    over thousands of tokens."""
    d_model: int
    d_ssm: int
    n_heads: int
    d_head: int
    d_state: int
    n_groups: int = 1
    d_conv: int = 4
    chunk: int = 128
    conv_bias: bool = True
    in_multiplier: float = 1.0
    mup: Tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)   # z, x, B, C, dt
    norm_epsilon: float = 1e-5
    norm_before_gate: bool = False
    state_dtype: Any = jnp.float32
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, decode=False, token_mask=None):
        b, s, _ = x.shape
        h, p, n, g = self.n_heads, self.d_head, self.d_state, self.n_groups
        taps = self.d_conv
        bc = g * n
        conv_dim = self.d_ssm + 2 * bc
        proj = QDense(features=self.d_ssm + conv_dim + h, use_bias=False,
                      dtype=self.dtype, param_dtype=self.param_dtype,
                      kernel_init=_mixer_in_proj_init(
                          self.d_ssm, bc, self.in_multiplier, self.mup),
                      name="in_proj")(x * self.in_multiplier)
        mup = np.concatenate([np.full(w, m, np.float32) for w, m in zip(
            (self.d_ssm, self.d_ssm, bc, bc, h), self.mup)])
        proj = proj * mup.astype(proj.dtype)
        z, xbc, dt = jnp.split(proj, [self.d_ssm, self.d_ssm + conv_dim],
                               axis=-1)
        w = self.param("conv_w", nn.with_logical_partitioning(
            nn.initializers.variance_scaling(1.0, "fan_in", "normal",
                                             in_axis=0, out_axis=1),
            (None, "mlp")), (taps, conv_dim), self.param_dtype)
        vec = lambda name, init, width: self.param(
            name, nn.with_logical_partitioning(init, (None,)), (width,),
            jnp.float32)
        a_log = vec("A_log", _a_log_init, h)
        dt_bias = vec("dt_bias", _dt_bias_init, h)
        d_skip = vec("D", nn.initializers.ones, h)

        conv_state = ssm_state = None
        if decode:
            conv_state = self.variable("cache", "conv_state", jnp.zeros,
                                       (b, taps - 1, conv_dim), jnp.float32)
            ssm_state = self.variable("cache", "ssm_state", jnp.zeros,
                                      (b, h, n, p), self.state_dtype)
        carried = conv_state is not None and not self.is_initializing()
        live = (jnp.ones((b, s), bool) if token_mask is None
                else token_mask)

        # the convolution, as ShortConv's: the carry before the sequence
        carry = (conv_state.value if carried
                 else jnp.zeros((b, taps - 1, conv_dim), jnp.float32))
        trail = jnp.concatenate([carry, xbc.astype(jnp.float32)], axis=1)
        conv = sum(w[j].astype(jnp.float32) * trail[:, j:j + s]
                   for j in range(taps))
        if self.conv_bias:
            conv = conv + vec("conv_b", nn.initializers.zeros, conv_dim)
        xs, bm, cm = jnp.split(jax.nn.silu(conv),
                               [self.d_ssm, self.d_ssm + bc], axis=-1)
        xs = xs.reshape(b, s, h, p)
        bm, cm = bm.reshape(b, s, g, n), cm.reshape(b, s, g, n)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
        dt = jnp.where(live[..., None], dt, 0.0)             # [b, s, h]
        log_a = -jnp.exp(a_log) * dt                         # <= 0
        s0 = (ssm_state.value.astype(jnp.float32) if carried
              else jnp.zeros((b, h, n, p), jnp.float32))

        if carried and s == 1:
            from ..ops.pallas.ssm_update import ssm_update
            y, s_new = ssm_update(s0, (dt[..., None] * xs)[:, 0],
                                  jnp.exp(log_a[:, 0]), bm[:, 0], cm[:, 0],
                                  live[:, 0])
            y, ends = y[:, None], None
        else:
            y, s_new, ends = _ssd_chunked(xs, dt, log_a, bm, cm, s0,
                                          self.chunk)
        y = y + d_skip[:, None] * xs

        if carried:
            n_live = jnp.sum(live, axis=1, dtype=jnp.int32)
            conv_state.value = jax.vmap(
                lambda t, k: jax.lax.dynamic_slice_in_dim(t, k, taps - 1))(
                trail, n_live)
            ssm_state.value = s_new.astype(self.state_dtype)
            if ends is not None and self.is_mutable_collection("kv_token"):
                q = self.chunk
                conv_ends = jnp.stack(
                    [trail[:, (i + 1) * q:(i + 1) * q + taps - 1]
                     for i in range(s // q)], axis=1)
                states = {"conv_state": conv_ends,
                          "ssm_state": ends.astype(self.state_dtype)}
                self.variable("kv_token", "chunk_states",
                              lambda: states).value = states

        # the gated norm: one root-mean-square a group
        y = y.reshape(b, s, self.d_ssm)
        gate = jax.nn.silu(z.astype(jnp.float32))
        scale = self.param("norm", nn.with_logical_partitioning(
            nn.initializers.ones, ("mlp",)), (self.d_ssm,), jnp.float32)

        def grouped_rms(v):
            v = v.reshape(b, s, g, self.d_ssm // g)
            v = v * jax.lax.rsqrt(jnp.mean(jnp.square(v), -1, keepdims=True)
                                  + self.norm_epsilon)
            return v.reshape(b, s, self.d_ssm) * scale
        y = (grouped_rms(y) * gate if self.norm_before_gate
             else grouped_rms(y * gate))
        return QDense(features=self.d_model, use_bias=False,
                      dtype=self.dtype, param_dtype=self.param_dtype,
                      kernel_init=dense_init(("mlp", "embed")),
                      name="out_proj")(y)


def _ssd_chunked(x, dt, log_a, bm, cm, s0, q):
    """The chunked form of ``S_t = a_t S_{t-1} + dt_t x_t B_t^T``, ``y_t =
    S_t C_t`` over ``x [b, s, h, p]``, ``dt`` and ``log_a = log a`` ``[b,
    s, h]``, ``bm`` and ``cm`` ``[b, s, g, n]``, from ``s0 [b, h, n,
    p]``: ``(y [b, s, h, p], the last state, the state at the end of
    every q positions [b, s / q, h, n, p])``. A sequence that is no
    multiple of ``q`` is padded with steps of 0 (the state stands still)
    and the padding's chunk ends are dropped."""
    b, s, h, p = x.shape
    g, n = bm.shape[2:]
    k = h // g                                   # heads a group serves
    pad = -s % q
    if pad:
        cut = lambda v: jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),)
                                * (v.ndim - 2))
        x, dt, log_a, bm, cm = map(cut, (x, dt, log_a, bm, cm))
    nc = (s + pad) // q
    hi = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)
    dx = (x * dt[..., None]).reshape(b, nc, q, g, k, p)
    bm, cm = bm.reshape(b, nc, q, g, n), cm.reshape(b, nc, q, g, n)
    cs = jnp.cumsum(log_a.reshape(b, nc, q, g, k), axis=2)  # decay so far
    # inside a chunk: position t reads position r <= t through C_t . B_r
    # and the decay between them
    between = cs[:, :, :, None] - cs[:, :, None, :]          # [b,c,t,r,g,k]
    causal = jnp.tril(jnp.ones((q, q), bool))[None, None, :, :, None, None]
    between = jnp.exp(jnp.where(causal, between, -jnp.inf))
    cb = hi("bctgn,bcrgn->bctrg", cm, bm)
    y = hi("bctrgk,bcrgkp->bctgkp", cb[..., None] * between, dx)
    # what a chunk adds to the state by its end, and the state's own decay
    to_end = jnp.exp(cs[:, :, -1:] - cs)                     # [b,c,r,g,k]
    added = hi("bcrgn,bcrgkp->bcgknp", bm, dx * to_end[..., None])
    whole = jnp.exp(cs[:, :, -1])                            # [b,c,g,k]
    s0 = s0.reshape(b, g, k, n, p)

    def carry(state, chunk):
        decay, add = chunk
        new = decay[..., None, None] * state + add
        return new, (state, new)

    last, (before, after) = jax.lax.scan(
        carry, s0, (jnp.moveaxis(whole, 1, 0), jnp.moveaxis(added, 1, 0)))
    before, after = jnp.moveaxis(before, 0, 1), jnp.moveaxis(after, 0, 1)
    # the state a chunk starts from, read by each of its positions
    y = y + hi("bctgn,bcgknp->bctgkp", cm, before) \
        * jnp.exp(cs)[..., None]
    y = y.reshape(b, nc * q, h, p)[:, :s]
    ends = after.reshape(b, nc, h, n, p)[:, :s // q]
    return y, last.reshape(b, h, n, p), ends


class Block(nn.Module):
    """One transformer layer. pre_ln=True is the GPT/modern layout; False is
    the original BERT post-LN layout (reference supports both via the
    pre_layer_norm flag, ds_transformer_cuda.cpp). parallel_residual=True is
    the GPT-J/NeoX layout: y = x + attn(ln1(x)) + mlp(ln_parallel(x))."""
    n_heads: int
    d_model: int
    d_ff: int
    causal: bool = True
    pre_ln: bool = True
    dropout_rate: float = 0.0
    attn_dropout_rate: float = 0.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    use_bias: bool = True
    ln_epsilon: float = 1e-5
    rotary: bool = False
    rotary_dim: Optional[int] = None
    rotary_base: float = 10000.0
    norm: str = "layernorm"              # key into NORMS
    qk_norm: bool = False
    activation: str = "gelu"
    mlp_factory: Optional[Callable[..., nn.Module]] = None
    attn_backend: Optional[str] = None
    parallel_residual: bool = False
    shared_parallel_ln: bool = False     # GPT-J: one LN feeds attn AND mlp
    attn_use_bias: Optional[bool] = None  # None -> use_bias (GPT-J: False)
    alibi: bool = False
    seq_parallel: Optional[str] = None
    sparsity_config: Any = None
    sparsity_pattern_len: Optional[int] = None

    @nn.compact
    def __call__(self, x, mask=None, bias=None, deterministic=True,
                 layer_keep_prob=None, decode=False, positions=None,
                 mlp_kwargs=None):
        """``mlp_kwargs`` go to the ``mlp_factory`` module's call and to
        nothing else: what an expert layer takes beside the hidden state
        (``moe.layer.DroplessMoE``: the mask of rows that hold a token,
        the layers' stacked expert weights). Such a module returns
        ``(out, aux)``, and so does the block."""
        attn_bias = self.use_bias if self.attn_use_bias is None else self.attn_use_bias
        attn = SelfAttention(n_heads=self.n_heads, d_model=self.d_model,
                             causal=self.causal, dropout_rate=self.attn_dropout_rate,
                             dtype=self.dtype, param_dtype=self.param_dtype,
                             use_bias=attn_bias, rotary=self.rotary,
                             rotary_dim=self.rotary_dim,
                             rotary_base=self.rotary_base,
                             qk_norm=self.qk_norm,
                             norm_epsilon=self.ln_epsilon,
                             attn_backend=self.attn_backend,
                             alibi=self.alibi, seq_parallel=self.seq_parallel,
                             sparsity_config=self.sparsity_config,
                             sparsity_pattern_len=self.sparsity_pattern_len,
                             name="attn")
        mlp_cls = self.mlp_factory or (lambda name: MLP(
            d_model=self.d_model, d_ff=self.d_ff, dtype=self.dtype,
            param_dtype=self.param_dtype, use_bias=self.use_bias,
            activation=self.activation, dropout_rate=self.dropout_rate, name=name))
        mlp = mlp_cls(name="mlp")
        if mlp_kwargs:
            mlp = functools.partial(mlp, **mlp_kwargs)
        norm = NORMS[self.norm]
        ln1 = norm(epsilon=self.ln_epsilon, name="ln_1")

        aux = None
        if self.parallel_residual:
            h1 = ln1(x)
            if self.shared_parallel_ln:
                h2 = h1
            else:
                h2 = norm(epsilon=self.ln_epsilon, name="ln_2")(x)
            a = attn(h1, mask=mask, bias=bias, deterministic=deterministic,
                     decode=decode, positions=positions)
            m = mlp(h2, deterministic=deterministic)
            if isinstance(m, tuple):
                m, aux = m
            y = x + a + m
        elif self.pre_ln:
            ln2 = norm(epsilon=self.ln_epsilon, name="ln_2")
            a = attn(ln1(x), mask=mask, bias=bias, deterministic=deterministic,
                     decode=decode, positions=positions)
            x = x + a
            m = mlp(ln2(x), deterministic=deterministic)
            if isinstance(m, tuple):  # MoE returns (out, aux_loss)
                m, aux = m
            y = x + m
        else:
            ln2 = norm(epsilon=self.ln_epsilon, name="ln_2")
            a = attn(x, mask=mask, bias=bias, deterministic=deterministic,
                     decode=decode, positions=positions)
            x = ln1(x + a)
            m = mlp(x, deterministic=deterministic)
            if isinstance(m, tuple):
                m, aux = m
            y = ln2(x + m)

        if layer_keep_prob is not None:
            # Progressive layer drop (reference: progressive_layer_drop.py +
            # the theta gate in the BERT kernels): residual-scale by keep prob.
            y = x + layer_keep_prob * (y - x)
        y = activation_constraint(y, ("batch", "seq", "embed"))
        return (y, aux) if aux is not None else y


def alibi_slopes(n_heads: int):
    """ALiBi per-head slopes (BLOOM; reference analog: the alibi tensor fed
    to the inference softmax kernel, csrc/transformer/inference softmax.cu
    handles an `alibi` operand)."""
    import math
    closest = 2 ** math.floor(math.log2(n_heads))
    base = [2 ** (-(2 ** -(math.log2(closest) - 3)) * (i + 1))
            for i in range(closest)]
    if closest != n_heads:
        extra = [2 ** (-(2 ** -(math.log2(2 * closest) - 3)) * (i + 1))
                 for i in range(0, 2 * (n_heads - closest), 2)]
        base += extra
    return jnp.asarray(base, jnp.float32)


def alibi_bias(n_heads: int, q_positions, k_positions, dtype=jnp.float32):
    """[1, heads, q, k] additive attention bias: slope * (k_pos - q_pos),
    clamped to <=0 on the causal side (standard ALiBi: bias depends only on
    key distance)."""
    slopes = alibi_slopes(n_heads)
    rel = (k_positions[None, :] - q_positions[:, None]).astype(jnp.float32)
    bias = slopes[:, None, None] * rel[None, :, :]
    return bias[None].astype(dtype)


# ---------------------------------------------------------------------------
# the layers of a decoder-hybrid-decoder (SambaY, arXiv:2507.06607): a
# Mamba-1 mixer, differential attention (arXiv:2410.05258) full, windowed
# or reading another layer's keys and values, and a gated memory unit
# ---------------------------------------------------------------------------

def _a_log_rows_init(key, shape, dtype=jnp.float32):
    """Mamba-1's ``A = -(1..d_state)`` a channel: ``A_log [d_state,
    d_inner]``, row ``n`` the log of ``n + 1``."""
    return jnp.broadcast_to(
        jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32))[:, None],
        shape).astype(dtype)


def _widened_columns_init(first, gain):
    """Fan-in normal whose columns from ``first`` on are ``gain`` times
    as wide (``MambaMixer``'s B and C; ``_mixer_in_proj_init`` has the
    reason)."""
    base = nn.initializers.variance_scaling(1.0, "fan_in", "normal")

    def init(key, shape, dtype=jnp.float32):
        w = base(key, shape, jnp.float32)
        return w.at[:, first:].multiply(gain).astype(dtype)
    return nn.with_logical_partitioning(init, ("mlp", None))


def _diagonal_scan(dt, u, bm, cm, a, s0, q):
    """``S_t = exp(dt_t (x) a) * S_{t-1} + (dt_t u_t) (x) B_t``, ``y_t =
    S_t C_t`` over ``dt``, ``u`` ``[b, s, d]`` and ``bm``, ``cm`` ``[b,
    s, n]`` with ``a [n, d]``, from ``s0 [b, n, d]``: ``(y [b, s, d],
    the last state)``. ``q`` positions at a time (``lax.associative_scan``
    inside a block, the state carried between blocks), so that the
    ``[b, q, n, d]`` decays and states of one block are all that is ever
    whole; a sequence that is no multiple of ``q`` is padded with steps
    of 0, which leave the state as it was."""
    b, s, d = u.shape
    q = min(q, s)
    pad = -s % q
    if pad:
        cut = lambda v: jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
        dt, u, bm, cm = map(cut, (dt, u, bm, cm))

    def combine(left, right):
        return left[0] * right[0], right[0] * left[1] + right[1]

    def block(state, part):
        dt_, u_, b_, c_ = part                               # [b, q, ...]
        decay = jnp.exp(dt_[:, :, None, :] * a)              # [b, q, n, d]
        add = (dt_ * u_)[:, :, None, :] * b_[..., None]
        decay, add = jax.lax.associative_scan(combine, (decay, add), axis=1)
        states = decay * state[:, None] + add
        return states[:, -1], jnp.sum(states * c_[..., None], axis=2)

    blocks = lambda v: jnp.moveaxis(
        v.reshape(b, (s + pad) // q, q, v.shape[-1]), 1, 0)
    last, y = jax.lax.scan(block, s0, tuple(map(blocks, (dt, u, bm, cm))))
    return jnp.moveaxis(y, 0, 1).reshape(b, s + pad, d)[:, :s], last


class MambaMixer(nn.Module):
    """The selective state-space mixer of Mamba-1 (arXiv:2312.00752), a
    diagonal state with a decay a channel AND a state column:

        [x | z] = W_in n
        x = silu(conv1d(x) + b)              depthwise, causal, d_conv taps
        [delta | B | C] = W_x x              dt_rank | d_state | d_state
        dt = softplus(W_dt delta + dt_bias)  a channel
        S_t = exp(dt_t (x) A) * S_{t-1} + (dt_t x_t) (x) B_t,  A = -exp(A_log)
        y_t = S_t C_t + D x_t
        out = W_out (y * silu(z))

    Two recurrent states, float32, in the "cache" collection when
    ``decode``, as ``Mamba2Mixer`` keeps them: ``conv_state [batch, d_conv
    - 1, d_inner]`` and ``ssm_state [batch, d_state, d_inner]`` (the
    channels on the minor dimension; ``A_log`` is laid out the same way).
    One computation serves a whole sequence from zero state, a prefill
    chunk from a carried state and a decode token against its slot's
    state; a position outside ``token_mask`` gets a step of 0 and leaves
    the state as it was. The convolution, the step sizes, the decays and
    the scan are float32 whatever ``dtype`` is.

    ``hand_on``: also return ``y`` — the scan's output with the ``D``
    term, before the gate — which a ``GatedMemoryUnit`` further up reads
    at the same position (SambaY's memory)."""
    d_model: int
    d_inner: int
    dt_rank: int
    d_state: int = 16
    d_conv: int = 4
    scan_block: int = 128
    bc_gain: float = 1.0         # seeded B and C columns of W_x, widened
    hand_on: bool = False
    state_dtype: Any = jnp.float32
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, decode=False, token_mask=None):
        b, s, _ = x.shape
        d, n, taps = self.d_inner, self.d_state, self.d_conv

        def dense(features, names, name, init=None):
            return QDense(features=features, use_bias=False,
                          dtype=self.dtype, param_dtype=self.param_dtype,
                          kernel_init=init or dense_init(names), name=name)
        xs, z = jnp.split(dense(2 * d, ("embed", "mlp"), "in_proj")(x), 2,
                          axis=-1)
        w = self.param("conv_w", nn.with_logical_partitioning(
            nn.initializers.variance_scaling(1.0, "fan_in", "normal",
                                             in_axis=0, out_axis=1),
            (None, "mlp")), (taps, d), self.param_dtype)
        vec = lambda name, init, shape: self.param(
            name, nn.with_logical_partitioning(init, (None,) * len(shape)),
            shape, jnp.float32)
        conv_b = vec("conv_b", nn.initializers.zeros, (d,))
        a_log = vec("A_log", _a_log_rows_init, (n, d))
        dt_bias = vec("dt_bias", _dt_bias_init, (d,))
        d_skip = vec("D", nn.initializers.ones, (d,))

        conv_state = ssm_state = None
        if decode:
            conv_state = self.variable("cache", "conv_state", jnp.zeros,
                                       (b, taps - 1, d), jnp.float32)
            ssm_state = self.variable("cache", "ssm_state", jnp.zeros,
                                      (b, n, d), self.state_dtype)
        carried = conv_state is not None and not self.is_initializing()
        live = (jnp.ones((b, s), bool) if token_mask is None
                else token_mask)

        carry = (conv_state.value if carried
                 else jnp.zeros((b, taps - 1, d), jnp.float32))
        trail = jnp.concatenate([carry, xs.astype(jnp.float32)], axis=1)
        u = jax.nn.silu(sum(w[j].astype(jnp.float32) * trail[:, j:j + s]
                            for j in range(taps)) + conv_b)  # [b, s, d]
        dbc = dense(self.dt_rank + 2 * n, ("mlp", None), "x_proj",
                    _widened_columns_init(self.dt_rank, self.bc_gain))(u)
        delta, bm, cm = jnp.split(dbc, [self.dt_rank, self.dt_rank + n],
                                  axis=-1)
        dt = dense(d, (None, "mlp"), "dt_proj")(delta)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
        dt = jnp.where(live[..., None], dt, 0.0)
        bm, cm = bm.astype(jnp.float32), cm.astype(jnp.float32)
        a = -jnp.exp(a_log)                                  # [n, d]
        s0 = (ssm_state.value.astype(jnp.float32) if carried
              else jnp.zeros((b, n, d), jnp.float32))
        if carried and s == 1:
            # one token: the recurrence itself, every slot's state read
            # once and written once
            s_new = jnp.exp(dt[:, 0, None, :] * a) * s0 \
                + (dt * u)[:, 0, None, :] * bm[:, 0, :, None]
            y = jnp.sum(s_new * cm[:, 0, :, None], axis=1)[:, None]
        else:
            y, s_new = _diagonal_scan(dt, u, bm, cm, a, s0, self.scan_block)
        y = y + d_skip * u
        if carried:
            n_live = jnp.sum(live, axis=1, dtype=jnp.int32)
            conv_state.value = jax.vmap(
                lambda t, k: jax.lax.dynamic_slice_in_dim(t, k, taps - 1))(
                trail, n_live)
            ssm_state.value = s_new.astype(self.state_dtype)
        out = dense(self.d_model, ("mlp", "embed"), "out_proj")(
            y * jax.nn.silu(z.astype(jnp.float32)))
        return (out, y) if self.hand_on else out


class GatedMemoryUnit(nn.Module):
    """SambaY's gated memory unit: ``W2 (m * silu(W1 n))`` with ``m`` an
    earlier mixer's scan output at the same position (``MambaMixer``'s
    ``hand_on``). No bias, no state: a layer that reads the memory
    instead of keeping one."""
    d_model: int
    d_memory: int
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, memory):
        def dense(features, names, name):
            return QDense(features=features, use_bias=False, dtype=self.dtype,
                          param_dtype=self.param_dtype,
                          kernel_init=dense_init(names), name=name)
        gate = dense(self.d_memory, ("embed", "mlp"), "w1")(x)
        return dense(self.d_model, ("mlp", "embed"), "w2")(
            memory.astype(jnp.float32)
            * jax.nn.silu(gate.astype(jnp.float32)))


def _ring_append(rings, cols, lane, live):
    """Row ``b``'s columns ``cols[i][b]`` (``[h, d, 1]``) written onto
    lane ``lane[b]`` of ``rings[i][b]`` (``[h, d, window]``) in place, for
    the rows that are ``live``: one Pallas call for a layer's keys and
    values (``ops/pallas/ring_append.py``), which reads the 128 lanes
    around a live row's token, replaces one and writes them back where
    they were under the next row's read (``inference/cache.py
    _append_rows`` has why no scatter over the lane dimension)."""
    from ..ops.pallas.ring_append import ring_append
    return ring_append(rings, cols, lane, live)


class DifferentialAttention(nn.Module):
    """Differential attention (Diff Transformer, arXiv:2410.05258) as
    SambaY lays it out, in its three places:

    - ``kind="full"``: causal over everything before; its keys and
      values are the one paged unit (``cached_key`` / ``cached_value``,
      as ``SelfAttention`` keeps them), and it hands them on;
    - ``kind="window"``: over the last ``window`` tokens, the token
      itself counted; its keys and values are a ring a slot
      (``ring_key`` / ``ring_value [batch, h, d, window]``, token ``t``
      on lane ``t mod window``: with no position encoding the order of
      the lanes says nothing);
    - ``kind="cross"``: queries of its own over the keys and values a
      ``full`` layer handed on (``shared``); no K/V projection, no cache
      unit.

    Query heads ``0..H/2-1`` are ``q1``, the rest ``q2``; K/V heads
    ``0..G/2-1`` are ``k1`` and ``v1``, the rest ``k2``, ``v2``; pair
    ``p`` reads key head ``p // (H / G)`` of its half and the values
    ``[v1 | v2]`` of that head, ``2 d`` wide:

        a1 = softmax(q1 k1^T / sqrt(d)) v,  a2 = softmax(q2 k2^T / sqrt(d)) v
        lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init
        head = RMSNorm_2d(a1 - lam a2) * (1 - lam_init)

    then ``W_o`` over the ``H / 2`` heads of ``2 d``. **How a page is read
    once**: the cache keeps ``G / 2`` heads ``2 d`` wide, ``[k1_j ; k2_j]``
    for the keys and ``[v1_j ; v2_j]`` for the values, and a query goes
    in ``2 d`` wide with zeros over the half it does not read, so that
    ``q1 . [k1 ; k2] = q1 . k1``. The decode kernels then see plain
    grouped-query attention, ``2 H / G`` query rows a cached head, and one
    call gives ``a1`` and ``a2`` of every pair from one pass over the
    cache. No rotary or other position encoding.

    ``cache_dtype`` rounds the keys and values once, as they leave their
    projection, for a cache narrower than the activations (bf16 under
    float32 activations): a chunk attends the same rounded values a later
    decode step reads back."""
    n_heads: int
    n_kv_heads: int
    d_model: int
    head_dim: int
    layer_index: int = 0
    kind: str = "full"
    window: Optional[int] = None
    use_bias: bool = True
    norm_epsilon: float = 1e-5
    cache_dtype: Any = None      # the keys' and values' type from the
                                 # projection on (cache, ring, pages and
                                 # what attends them); None: ``dtype``
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, decode=False, positions=None, token_mask=None,
                 shared=None):
        if self.kind not in ("full", "window", "cross"):
            raise ValueError(f"kind {self.kind!r}")
        b, s, _ = x.shape
        d, heads = self.head_dim, self.n_heads
        pairs, kvh = heads // 2, self.n_kv_heads // 2        # 20, 10
        group = pairs // kvh                                 # 2
        cross = self.kind == "cross"

        def dense(features, names, name):
            return QDense(
                features=features, use_bias=self.use_bias, dtype=self.dtype,
                param_dtype=self.param_dtype, kernel_init=dense_init(names),
                bias_init=nn.with_logical_partitioning(
                    nn.initializers.zeros, (names[1],)), name=name)
        if cross:
            q = dense(heads * d, ("embed", "qkv"), "q")(x)
        else:
            qkv = dense((heads + 2 * self.n_kv_heads) * d, ("embed", "qkv"),
                        "qkv")(x)
            q, k, v = jnp.split(
                qkv, [heads * d, (heads + self.n_kv_heads) * d], axis=-1)
            # K^T layout, the halves of a head stacked: [b, kvh, 2 d, s]
            stack = lambda t: t.reshape(b, s, 2, kvh, d).transpose(
                0, 3, 2, 4, 1).reshape(b, kvh, 2 * d, s).astype(
                    self.cache_dtype or t.dtype)
            kc, vc = stack(k), stack(v)
        # [b, s, kvh, half, group, d]: the rows of one cached head together
        q = q.reshape(b, s, 2, kvh, group, d).transpose(0, 1, 3, 2, 4, 5)
        if positions is None:
            positions = jnp.arange(s)
        q_pos = jnp.broadcast_to(positions, (b, s)) if positions.ndim == 2 \
            else jnp.broadcast_to(positions[None], (b, s))
        live = (jnp.ones((b, s), bool) if token_mask is None else token_mask)
        scale = 1.0 / math.sqrt(d)
        new_shared = None

        def dense_attend(keys, values, k_pos, k_ok):
            """``[b, s, kvh, 2, group, 2 d]`` float32 over cached heads
            ``[b, kvh, 2 d, S]`` at positions ``k_pos`` ``[b | 1, S]``."""
            see = (k_pos[:, None, :] <= q_pos[:, :, None]) & k_ok[:, None, :]
            if self.window is not None:
                see &= k_pos[:, None, :] > q_pos[:, :, None] - self.window
            kk = keys.reshape(b, kvh, 2, d, keys.shape[-1])
            sc = jnp.einsum("bqjhgd,bjhdk->bjhgqk", q, kk,
                            preferred_element_type=jnp.float32) * scale
            sc = jnp.where(see[:, None, None, None], sc, NEG_INF)
            pr = jax.nn.softmax(sc, axis=-1)
            # an unwritten column may hold anything: 0 x NaN is NaN
            seen = jnp.any(see, axis=1)[:, None, None, :]
            vals = jnp.where(seen, values.astype(jnp.float32), 0.0)
            return jnp.einsum("bjhgqk,bjek->bqjhge", pr, vals)

        def kernel_rows():
            """The one token's queries as the decode kernels take them:
            ``[b, 1, 2 heads, 2 d]`` float32, zeros over the other half."""
            q1 = jnp.pad(q[:, :, :, 0], ((0, 0),) * 4 + ((0, d),))
            q2 = jnp.pad(q[:, :, :, 1], ((0, 0),) * 4 + ((d, 0),))
            rows = jnp.stack([q1, q2], axis=3)       # [b,1,kvh,2,group,2d]
            return rows.reshape(b, 1, heads, 2 * d).astype(jnp.float32)

        def from_kernel(out):
            return out.reshape(b, 1, kvh, 2, group, 2 * d)

        def kernel_precision(cached):
            """The ambient matmul precision for a decode kernel's trace:
            over a cache narrower than float32 the kernel's products are
            bf16 x bf16 by construction (exact in float32), and Mosaic
            refuses them a float32 contract precision, which float32
            activations' "highest" would ask for."""
            if jnp.dtype(cached.dtype).itemsize >= 4:
                return contextlib.nullcontext()
            return jax.default_matmul_precision("default")

        if cross:
            if shared is None:
                raise ValueError("a cross layer reads the keys and values "
                                 "a full layer hands on (shared)")
            if "pool" in shared:
                from ..ops.pallas.paged_attention import paged_attention
                pool_k, pool_v, ptab, idx, kn, vn = shared["pool"]
                with kernel_precision(pool_k):
                    a = from_kernel(paged_attention(
                        kernel_rows(), pool_k, pool_v, ptab, idx, kn, vn,
                        softmax_scale=scale))
            elif "lengths" in shared and s == 1:
                from ..ops.pallas import decode_attention
                with kernel_precision(shared["keys"]):
                    a = from_kernel(decode_attention(
                        kernel_rows(), shared["keys"], shared["values"],
                        shared["lengths"], softmax_scale=scale))
            else:
                a = dense_attend(shared["keys"], shared["values"],
                                 shared["k_pos"], shared["k_ok"])
        elif not decode or self.is_initializing():
            if decode:
                if self.kind == "window":
                    for name in ("ring_key", "ring_value"):
                        self.variable("cache", name, jnp.zeros,
                                      (b, kvh, 2 * d, self.window), kc.dtype)
                else:
                    for name in ("cached_key", "cached_value"):
                        self.variable("cache", name, jnp.zeros, kc.shape,
                                      kc.dtype)
                    self.variable("cache", "cache_index",
                                  lambda: jnp.zeros((), jnp.int32))
            k_pos = q_pos[:1]
            k_ok = jnp.ones_like(k_pos, bool)
            a = dense_attend(kc, vc, k_pos, k_ok)
            if self.kind == "full":
                new_shared = {"keys": kc, "values": vc, "k_pos": k_pos,
                              "k_ok": k_ok}
        elif self.kind == "window":
            ring_k = self.variable("cache", "ring_key")
            ring_v = self.variable("cache", "ring_value")
            w = self.window
            if s == 1:
                from ..ops.pallas import decode_attention
                at = q_pos[:, 0]
                ring_k.value, ring_v.value = _ring_append(
                    (ring_k.value, ring_v.value), (kc, vc), at % w,
                    live[:, 0])
                with kernel_precision(kc):
                    a = from_kernel(decode_attention(
                        kernel_rows(), ring_k.value, ring_v.value,
                        jnp.minimum(at + 1, w), softmax_scale=scale))
            else:
                if s > w:
                    raise NotImplementedError(
                        f"a chunk of {s} tokens over a ring of {w}: a "
                        "chunk is at most the window")
                # the chunk's positions are one run from ``start``, the
                # same in every row; lane r holds the last token before
                # it that is r mod w
                start = q_pos[:1, 0]                             # [1]
                lanes = jnp.arange(w)[None]
                held = start[:, None] - 1 - (start[:, None] - 1 - lanes) % w
                k_pos = jnp.concatenate([held, q_pos[:1]], axis=1)
                k_ok = jnp.concatenate(
                    [jnp.broadcast_to(held >= 0, (b, w)), live], axis=1)
                a = dense_attend(
                    jnp.concatenate([ring_k.value, kc], axis=-1),
                    jnp.concatenate([ring_v.value, vc], axis=-1), k_pos, k_ok)
                # lane r takes the chunk's live token that is r mod w
                src = (lanes - start[:, None]) % w               # [1, w]
                fresh = (src < s) & jnp.take_along_axis(
                    live, jnp.broadcast_to(jnp.minimum(src, s - 1), (b, w)),
                    axis=1)
                take = lambda t: jnp.take(t, jnp.minimum(src[0], s - 1),
                                          axis=-1)
                ring_k.value = jnp.where(fresh[:, None, None], take(kc),
                                         ring_k.value)
                ring_v.value = jnp.where(fresh[:, None, None], take(vc),
                                         ring_v.value)
        else:                                               # full, cached
            paged = self.has_variable("cache", "page_table")
            cache_index = self.variable("cache", "cache_index",
                                        lambda: jnp.zeros((), jnp.int32))
            if self.is_mutable_collection("kv_token"):
                self.variable("kv_token", "k", lambda: kc).value = kc
                self.variable("kv_token", "v", lambda: vc).value = vc
            idx = cache_index.value
            if paged:
                if s != 1:
                    raise NotImplementedError(
                        "paged-pool decode is single-token (got chunk "
                        f"length {s})")
                from ..ops.pallas.paged_attention import paged_attention
                pool = self.variables["kv_pool"]
                if "key_scale" in pool:
                    raise NotImplementedError(
                        "int8 K/V pages under differential attention: the "
                        "layers that read this pool are not handed its "
                        "scale planes")
                ptab = self.get_variable("cache", "page_table")
                operands = (pool["cached_key"], pool["cached_value"], ptab,
                            idx, kc, vc)
                with kernel_precision(kc):
                    a = from_kernel(paged_attention(
                        kernel_rows(), *operands, softmax_scale=scale))
                new_shared = {"pool": operands}
                cache_index.value = idx + 1
            else:
                ck = self.variable("cache", "cached_key")
                cv = self.variable("cache", "cached_value")
                if idx.ndim == 1:
                    write = jax.vmap(
                        lambda c, t, i: jax.lax.dynamic_update_slice(
                            c, t, (0, 0, i)))
                    k_all = write(ck.value, kc.astype(ck.value.dtype), idx)
                    v_all = write(cv.value, vc.astype(cv.value.dtype), idx)
                else:
                    k_all = jax.lax.dynamic_update_slice(
                        ck.value, kc.astype(ck.value.dtype), (0, 0, 0, idx))
                    v_all = jax.lax.dynamic_update_slice(
                        cv.value, vc.astype(cv.value.dtype), (0, 0, 0, idx))
                ck.value, cv.value = k_all, v_all
                cache_index.value = idx + s
                k_pos = jnp.arange(k_all.shape[-1])[None]
                new_shared = {"keys": k_all, "values": v_all, "k_pos": k_pos,
                              "k_ok": jnp.ones_like(k_pos, bool)}
                if idx.ndim == 1 and s == 1:
                    from ..ops.pallas import decode_attention
                    new_shared["lengths"] = idx + 1
                    with kernel_precision(k_all):
                        a = from_kernel(decode_attention(
                            kernel_rows(), k_all, v_all, idx + 1,
                            softmax_scale=scale))
                else:
                    a = dense_attend(k_all, v_all, k_pos, new_shared["k_ok"])

        # [b, s, kvh, half, group, 2 d] -> pairs of (a1, a2)
        a = a.astype(jnp.float32)
        a1 = a[:, :, :, 0].reshape(b, s, pairs, 2 * d)
        a2 = a[:, :, :, 1].reshape(b, s, pairs, 2 * d)
        lam_init = 0.8 - 0.6 * math.exp(-0.3 * self.layer_index)
        lvec = lambda name: self.param(
            name, nn.with_logical_partitioning(
                nn.initializers.normal(0.1), (None,)), (d,), jnp.float32)
        lam = jnp.exp(jnp.sum(lvec("lambda_q1") * lvec("lambda_k1"))) \
            - jnp.exp(jnp.sum(lvec("lambda_q2") * lvec("lambda_k2"))) \
            + lam_init
        out = RMSNorm(epsilon=self.norm_epsilon, name="subln")(
            a1 - lam * a2) * (1.0 - lam_init)
        out = dense(self.d_model, ("qkv", "embed"), "out")(
            out.reshape(b, s, pairs * 2 * d))
        return (out, new_shared) if self.kind == "full" else out
