"""GPT-family decoder models (GPT-2 / GPT-Neo / GPT-J layouts).

The flagship training model for the BASELINE configs (GPT-2 125M ZeRO-1,
GPT-2 1.3B ZeRO-2/3). TPU-first choices:

- ``scan_layers``: stack the L transformer blocks into one scanned block
  ([L, ...] params) — compile time O(1) in depth, and gives ZeRO-3 its
  natural per-layer all-gather granularity (the analog of the reference's
  per-submodule fetch in partitioned_param_coordinator.py).
- ``remat``: jax.checkpoint around each block — the analog of the
  reference's activation checkpointing (runtime/activation_checkpointing/).
- params carry logical axis names; the engine binds them to mesh axes.
"""

from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import jax.numpy as jnp
import flax.linen as nn

from .layers import (Block, LayerNorm, QDense, activation_constraint,
                     replicated_constraint)

# jax.checkpoint policies keyed by config string (reference analog: the
# activation_checkpointing config block,
# runtime/activation_checkpointing/config.py:27-43). "offload" is the
# cpu_checkpointing analog: saveable dot outputs are staged to pinned host
# memory instead of HBM (reference: checkpointing.py CPU checkpointing).
REMAT_POLICIES = {
    "none": None,
    "full": jax.checkpoint_policies.nothing_saveable,
    "dots": jax.checkpoint_policies.checkpoint_dots,
    "dots_no_batch": jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
    "offload": jax.checkpoint_policies.offload_dot_with_no_batch_dims(
        "device", "pinned_host"),
    # save ONLY the per-layer attention outputs (named via checkpoint_name
    # in layers.SelfAttention): backward re-runs the MLP matmuls but never
    # the flash-attention kernel — the middle ground between "full"
    # (recompute everything, attention twice) and "dots" (save every
    # matmul output). The knob the perf sweep walks against block sizes.
    "attn_out": jax.checkpoint_policies.save_only_these_names("attn_out"),
}


@dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50257
    max_seq_len: int = 1024
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    d_ff: Optional[int] = None           # default 4*d_model
    dropout_rate: float = 0.0
    attn_dropout_rate: float = 0.0
    dtype: Any = jnp.bfloat16            # activation/compute dtype
    param_dtype: Any = jnp.float32       # master param dtype
    use_bias: bool = True
    ln_epsilon: float = 1e-5
    tie_embeddings: bool = True
    rotary: bool = False                 # GPT-J/NeoX style when True
    rotary_dim: Optional[int] = None     # GPT-J: 64; None = full head_dim
    learned_pos: bool = True             # GPT-2 learned position embeddings
    scan_layers: bool = True
    remat: str = "none"                  # key into REMAT_POLICIES
    activation: str = "gelu"
    attn_backend: Optional[str] = None   # None=auto, "reference", "pallas"
    parallel_residual: bool = False      # GPT-J / GPT-NeoX layout
    shared_parallel_ln: bool = False     # GPT-J (one LN), NeoX uses two
    attn_use_bias: Optional[bool] = None  # GPT-J: False (mlp keeps bias)
    alibi: bool = False                  # BLOOM positioning
    embed_ln: bool = False               # BLOOM word_embeddings_layernorm
    lm_head_bias: bool = False           # GPT-J untied head carries a bias
    seq_parallel: Optional[str] = None   # None=auto, "ulysses", "ring", "none"
    sparsity_config: Any = None          # block-sparse attention pattern
                                         # (train + KV-cache serving)
    offload_params: bool = False         # ZeRO-Infinity: block params live in
                                         # host memory, streamed in per scan
                                         # step (requires scan_layers)

    @property
    def ffn_dim(self):
        return self.d_ff or 4 * self.d_model

    @property
    def head_dim(self):
        return self.d_model // self.n_heads

    def num_params(self):
        """Approximate param count (for capacity planning / flops)."""
        d, f, v, l = self.d_model, self.ffn_dim, self.vocab_size, self.n_layers
        per_layer = 4 * d * d + 2 * d * f + (9 * d + f if self.use_bias else 4 * d)
        emb = v * d + (self.max_seq_len * d if self.learned_pos else 0)
        return l * per_layer + emb + 2 * d


# Presets matching the BASELINE configs (GPT-2 125M / 350M / 1.3B).
GPT2_PRESETS = {
    "gpt2-125m": GPTConfig(d_model=768, n_layers=12, n_heads=12),
    "gpt2-350m": GPTConfig(d_model=1024, n_layers=24, n_heads=16),
    "gpt2-760m": GPTConfig(d_model=1536, n_layers=24, n_heads=16),
    "gpt2-1.3b": GPTConfig(d_model=2048, n_layers=24, n_heads=16),
    "gpt2-2.7b": GPTConfig(d_model=2560, n_layers=32, n_heads=32),
    # GPT-3 6.7B layout — the BLOOM-7B-class serving target (BASELINE #5):
    # bf16 weights (13.4GB) don't fit a 16GB chip beside the KV cache, the
    # int8 weight-only path (6.7GB + bf16 embeddings) does.
    "gpt2-6.7b": GPTConfig(d_model=4096, n_layers=32, n_heads=32),
}


class GPT(nn.Module):
    """Decoder-only LM. __call__ returns logits [batch, seq, vocab]."""
    config: GPTConfig
    # every dense layer is QDense: int8 {"q","scale"} kernel nodes are
    # consumed directly (init_inference direct-quantization gate)
    supports_quantized_kernels = True

    @nn.compact
    def __call__(self, input_ids, *, attention_mask=None, deterministic=True,
                 layer_keep_prob=None, positions=None, decode=False,
                 return_hidden=False):
        """``return_hidden=True`` returns (final_hidden, wte) instead of
        logits so the caller can compute a vocab-CHUNKED cross entropy
        (gpt_chunked_loss_fn) — the full [B,S,V] logits tensor is the HBM
        peak for big-vocab models and never needs to exist at once."""
        cfg = self.config
        b, s = input_ids.shape

        wte = self.param(
            "wte", nn.with_logical_partitioning(
                nn.initializers.normal(0.02), ("vocab", "embed")),
            (cfg.vocab_size, cfg.d_model), cfg.param_dtype)
        h = jnp.take(wte, input_ids, axis=0).astype(cfg.dtype)

        if positions is None:
            positions = jnp.arange(s)
        if cfg.learned_pos:
            wpe = self.param(
                "wpe", nn.with_logical_partitioning(
                    nn.initializers.normal(0.02), ("pos", "embed")),
                (cfg.max_seq_len, cfg.d_model), cfg.param_dtype)
            # gather from the replicated table: a ZeRO-3 embed-dim shard
            # here forces an involuntary-remat reshard (fsdp axis moving
            # from the feature dim onto the batch tile) in fwd AND bwd
            h = h + jnp.take(replicated_constraint(wpe), positions,
                             axis=0).astype(cfg.dtype)

        if cfg.embed_ln:
            h = LayerNorm(epsilon=cfg.ln_epsilon, name="emb_ln")(h)

        if cfg.dropout_rate > 0.0 and not deterministic:
            h = nn.Dropout(rate=cfg.dropout_rate)(h, deterministic=False)
        h = activation_constraint(h, ("batch", "seq", "embed"))

        mask = None
        if attention_mask is not None:
            mask = attention_mask[:, None, None, :].astype(bool)

        bias = None
        block_kwargs = dict(
            n_heads=cfg.n_heads, d_model=cfg.d_model, d_ff=cfg.ffn_dim,
            causal=True, pre_ln=True, dropout_rate=cfg.dropout_rate,
            attn_dropout_rate=cfg.attn_dropout_rate, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, use_bias=cfg.use_bias,
            ln_epsilon=cfg.ln_epsilon, rotary=cfg.rotary,
            rotary_dim=cfg.rotary_dim, activation=cfg.activation,
            attn_backend=cfg.attn_backend,
            parallel_residual=cfg.parallel_residual,
            shared_parallel_ln=cfg.shared_parallel_ln,
            attn_use_bias=cfg.attn_use_bias, alibi=cfg.alibi,
            seq_parallel=cfg.seq_parallel,
            sparsity_config=cfg.sparsity_config,
            sparsity_pattern_len=cfg.max_seq_len)

        block_cls = Block
        policy = REMAT_POLICIES.get(cfg.remat)
        if cfg.offload_params and not cfg.scan_layers:
            raise ValueError("offload_params requires scan_layers (the "
                             "scan step is the fetch granularity)")
        if cfg.remat != "none":
            # all-positional call below; deterministic (4) and decode (6)
            # are python bools and must stay static under remat
            block_cls = nn.remat(
                Block, policy=policy, prevent_cse=not cfg.scan_layers,
                static_argnums=(4, 6))

        if cfg.scan_layers and cfg.offload_params \
                and not self.is_initializing():
            # ZeRO-Infinity param streaming (reference:
            # partitioned_param_coordinator.py per-layer fetch + NVMe
            # prefetch :444): block params live HOST-side as the stacked
            # "h" collection (created by the nn.scan init path below);
            # apply drives an explicit lax.scan whose body fetches each
            # block's slice h2d via stream_in — inside jax.checkpoint, so
            # the backward recompute re-fetches instead of saving device
            # copies. XLA overlaps block k+1's fetch with block k's math
            # (the coordinator's prefetch, scheduled by the compiler).
            #
            # decode=True is the ZeRO-Inference serving mode (reference:
            # DeepSpeedZeRoOffload standalone for inference,
            # parameter_offload.py:166 — weights beyond HBM stream from
            # host per layer): the stacked KV cache rides the same scan
            # as xs (sliced per layer) and ys (updated slices restacked),
            # then is written back to the mutable collection.
            from ..utils.streaming import stream_in_tree
            stacked = self.scope.get_variable("params", "h")
            blk = Block(**block_kwargs, parent=None)
            has_dropout = ((cfg.dropout_rate > 0
                            or cfg.attn_dropout_rate > 0)
                           and not deterministic)
            # per-layer rng: fold the layer index into one base dropout
            # key (the nn.scan path's split_rngs={"dropout": True} analog)
            drop_base = self.make_rng("dropout") if has_dropout else None
            # Only >=3-D stacked leaves (the kernels) live host-side; the
            # engine's placement keeps <3-D leaves (bias/scale, KB-scale)
            # DEVICE-resident — the reference's persistence-threshold
            # semantics (stage3_param_persistence_threshold: small params
            # stay resident). This is also load-bearing for correctness
            # on TPU: host-space scan xs with ndim<3 leaves hit XLA
            # layout bugs (f32 [L,N]: backward re-slice mis-fused losing
            # the S(5) space; bf16 [L,N]: runtime DMA crash; in-jit
            # reshape dodges trip "Only handling bitcasts with majormost
            # dimension of size 1" at scale — all repro'd 2026-07-31 on
            # v5e). stream_in on an already-device leaf is an identity.

            def call(p, x, i):
                rngs = ({"dropout": jax.random.fold_in(drop_base, i)}
                        if has_dropout else None)
                return blk.apply({"params": p}, x, mask, bias,
                                 deterministic, layer_keep_prob, decode,
                                 positions, rngs=rngs)

            if decode:
                if has_dropout:
                    raise NotImplementedError(
                        "offload_params decode with live dropout (MC "
                        "sampling) is unsupported; pass "
                        "deterministic=True or serve without offload")
                cache_in = self.get_variable("cache", "h")

                def step_dec(carry, xs):
                    p, c = xs
                    p = stream_in_tree(p)
                    out, vars_out = blk.apply(
                        {"params": p, "cache": c}, carry, mask, bias,
                        deterministic, layer_keep_prob, decode, positions,
                        mutable=["cache"])
                    return out, vars_out["cache"]

                h, cache_out = jax.lax.scan(
                    step_dec, h, (stacked, cache_in))
                self.put_variable("cache", "h", cache_out)
            else:
                def step(carry, xs):
                    p, i = xs
                    p = stream_in_tree(p)
                    f = (jax.checkpoint(call, policy=policy)
                         if cfg.remat != "none" else call)
                    return f(p, carry, i), None

                h, _ = jax.lax.scan(
                    step, h, (stacked, jnp.arange(cfg.n_layers)))
        elif cfg.scan_layers:
            def body(block, carry):
                x = block(carry, mask, bias, deterministic,
                          layer_keep_prob, decode, positions)
                return x, None

            h, _ = nn.scan(
                body,
                # kv_token: per-layer single-call K/V published for the
                # paged-serving scatter (models/layers.py); the collection
                # only materializes when the caller marks it mutable
                variable_axes={"params": 0, "cache": 0, "kv_token": 0},
                # the paged page pool (inference/cache.py make_paged_view):
                # read-only and the same for every layer, so it crosses
                # the scan whole; the collection exists only in the paged
                # decode program
                variable_broadcast="kv_pool",
                split_rngs={"params": True, "dropout": True},
                length=cfg.n_layers,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )(block_cls(**block_kwargs, name="h"), h)
        else:
            for i in range(cfg.n_layers):
                h = block_cls(**block_kwargs, name=f"h_{i}")(
                    h, mask, bias, deterministic, layer_keep_prob,
                    decode, positions)

        h = LayerNorm(epsilon=cfg.ln_epsilon, name="ln_f")(h)

        if return_hidden:
            if not cfg.tie_embeddings:
                raise ValueError("return_hidden requires tie_embeddings "
                                 "(chunked loss reuses wte as the lm head)")
            return h, wte

        if cfg.tie_embeddings:
            logits = jnp.einsum("bsd,vd->bsv", h, wte.astype(cfg.dtype))
        else:
            logits = QDense(
                features=cfg.vocab_size, use_bias=cfg.lm_head_bias,
                dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                kernel_init=nn.with_logical_partitioning(
                    nn.initializers.normal(0.02), ("embed", "vocab")),
                bias_init=nn.with_logical_partitioning(
                    nn.initializers.zeros, ("vocab",)),
                name="lm_head")(h)
        return logits


def gpt_chunked_loss_fn(hidden, wte, labels, chunk: int = 256,
                        z_loss: float = 0.0):
    """Next-token cross entropy WITHOUT materializing [B, S, V] logits:
    a lax.scan over sequence chunks computes [B, chunk, V] at a time
    (reference analog: none — torch autograd must keep full logits; on
    TPU this is the difference between HBM-bound batch 32 and batch 64+
    for GPT-2-vocab models).

    hidden: [B, S, D] final hidden states (already shifted: pass
    hidden[:, :-1] with labels input_ids[:, 1:]).
    """
    b, s, d = hidden.shape
    if s % chunk != 0:
        chunk = s  # degenerate: single chunk
    n = s // chunk
    hs = hidden.reshape(b, n, chunk, d).transpose(1, 0, 2, 3)
    ls = labels.reshape(b, n, chunk).transpose(1, 0, 2)

    def body(acc, xs):
        hc, lc = xs
        logits = jnp.einsum("bcd,vd->bcv", hc,
                            wte.astype(hc.dtype)).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        nll = logz - ll
        if z_loss > 0.0:
            nll = nll + z_loss * jnp.square(logz)
        return acc + jnp.sum(nll), None

    total, _ = jax.lax.scan(body, jnp.float32(0.0), (hs, ls))
    return total / (b * s)


def gpt_loss_fn(logits, labels, loss_mask=None, z_loss=0.0):
    """Next-token cross entropy in fp32 (labels already shifted by caller,
    or pass input_ids and we shift here when shapes match)."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    label_logits = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - label_logits
    if z_loss > 0.0:
        nll = nll + z_loss * jnp.square(logz)
    if loss_mask is not None:
        nll = nll * loss_mask
        return jnp.sum(nll) / jnp.maximum(jnp.sum(loss_mask), 1.0)
    return jnp.mean(nll)


def build_demo_model(*, vocab_size=256, max_seq_len=256, d_model=64,
                     n_layers=2, n_heads=2, seed=0):
    """Random-init float32 GPT and its params: what a fleet worker
    without a checkpoint serves, and the load harness, the chaos and
    serve CLIs' demo model."""
    cfg = GPTConfig(vocab_size=vocab_size, max_seq_len=max_seq_len,
                    d_model=d_model, n_layers=n_layers, n_heads=n_heads,
                    dtype=jnp.float32)
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.ones((1, 8), jnp.int32))["params"]
    return model, params
