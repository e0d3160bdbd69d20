"""Falcon-H1 (TII, ``tiiuae/Falcon-H1-34B-Instruct``; HF
``modeling_falcon_h1.py``): a decoder whose every block runs a Mamba-2
mixer and grouped-query attention side by side on one normed input and
sums both into the residual, each behind its own muP multipliers, then a
SwiGLU feed-forward. With ``n = RMSNorm(h)``:

    mixer    m = Mamba2Mixer(n * ssm_in_multiplier) * ssm_out_multiplier
             (``models/layers.py Mamba2Mixer`` has the mixer's equations;
             ``ssm_multipliers`` lie over z | x | B | C | dt of its
             in-projection)
    attn     q, k, v = W_q n', W_k n' * key_multiplier, W_v n' with
             n' = n * attention_in_multiplier; fewer K/V heads than query
             heads, a head ``head_dim`` wide (not hidden / heads); RoPE;
             t = W_o Attn(q, k, v) * attention_out_multiplier
             h <- h + m + t
    mlp      f = RMSNorm(h); h <- h + W_down(silu(W_gate f *
             mlp_multipliers[0]) * W_up f) * mlp_multipliers[1]
    model    h_0 = E[ids] * embedding_multiplier; logits = W_head
             RMSNorm(h_L) * lm_head_multiplier, the head untied

No bias but the convolution's. The layers are laid out one by one
(``layers_<i>``), as ``LFM2``'s: a layer's "cache" units are
``SelfAttention``'s (its page pool one 4-D pool a layer) and the mixer's
two states (``conv_state``, ``ssm_state``), which the paged server keeps
a slot, with snapshots of some page ends (``inference/cache.py``).
``FalconH1Config``'s fields are the keys of the architecture's
``config.json``.
"""

from dataclasses import dataclass
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn

from .layers import (GatedMLP, Mamba2Mixer, RMSNorm, SelfAttention,
                     activation_constraint, dot_exact_weights)


@dataclass(frozen=True)
class FalconH1Config:
    vocab_size: int = 261120
    hidden_size: int = 5120
    intermediate_size: int = 21504
    num_hidden_layers: int = 72
    num_attention_heads: int = 20
    num_key_value_heads: int = 4
    head_dim: int = 128
    mamba_d_ssm: int = 4096
    mamba_n_heads: int = 32
    mamba_d_head: int = 128
    mamba_d_state: int = 256
    mamba_n_groups: int = 2
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    mamba_rms_norm: bool = True
    mamba_norm_before_gate: bool = False
    attention_bias: bool = False
    mlp_bias: bool = False
    projectors_bias: bool = False
    rms_norm_eps: float = 1e-5
    rope_theta: float = 100000000000.0
    max_position_embeddings: int = 262144
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: Tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)
    mlp_multipliers: Tuple[float, ...] = (1.0, 1.0)
    ssm_state_dtype: Any = jnp.float32
    dtype: Any = jnp.bfloat16            # activation/compute dtype
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        for key in ("ssm_multipliers", "mlp_multipliers"):
            object.__setattr__(self, key, tuple(getattr(self, key)))
        if self.mamba_d_ssm != self.mamba_n_heads * self.mamba_d_head:
            raise ValueError(
                f"mamba_d_ssm {self.mamba_d_ssm} is not mamba_n_heads x "
                f"mamba_d_head ({self.mamba_n_heads} x {self.mamba_d_head})")
        for key in ("mamba_proj_bias", "attention_bias", "mlp_bias",
                    "projectors_bias"):
            if getattr(self, key):
                raise NotImplementedError(f"{key}: the published models "
                                          "have none")
        if not self.mamba_rms_norm:
            raise NotImplementedError("mamba_rms_norm false: the published "
                                      "models norm the mixer's output")

    @property
    def max_seq_len(self):
        """What the engines ask a model's config for."""
        return self.max_position_embeddings


class FalconH1Layer(nn.Module):
    config: FalconH1Config

    @nn.compact
    def __call__(self, h, decode, positions, token_mask):
        cfg = self.config
        n = RMSNorm(epsilon=cfg.rms_norm_eps, name="input_layernorm")(h)
        m = Mamba2Mixer(
            d_model=cfg.hidden_size, d_ssm=cfg.mamba_d_ssm,
            n_heads=cfg.mamba_n_heads, d_head=cfg.mamba_d_head,
            d_state=cfg.mamba_d_state, n_groups=cfg.mamba_n_groups,
            d_conv=cfg.mamba_d_conv, chunk=cfg.mamba_chunk_size,
            conv_bias=cfg.mamba_conv_bias,
            in_multiplier=cfg.ssm_in_multiplier, mup=cfg.ssm_multipliers,
            norm_epsilon=cfg.rms_norm_eps,
            norm_before_gate=cfg.mamba_norm_before_gate,
            state_dtype=cfg.ssm_state_dtype, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            name="mixer")(n, decode=decode, token_mask=token_mask)
        t = SelfAttention(
            n_heads=cfg.num_attention_heads, d_model=cfg.hidden_size,
            n_kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
            key_multiplier=cfg.key_multiplier, causal=True, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, use_bias=False, rotary=True,
            rotary_base=cfg.rope_theta,
            name="attn")(n * cfg.attention_in_multiplier, decode=decode,
                         positions=positions)
        h = h + m * cfg.ssm_out_multiplier + t * cfg.attention_out_multiplier
        f = RMSNorm(epsilon=cfg.rms_norm_eps, name="pre_ff_layernorm")(h)
        y = GatedMLP(d_model=cfg.hidden_size, d_ff=cfg.intermediate_size,
                     dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                     gate_multiplier=cfg.mlp_multipliers[0],
                     down_multiplier=cfg.mlp_multipliers[1], name="mlp")(f)
        return activation_constraint(h + y, ("batch", "seq", "embed"))


class FalconH1(nn.Module):
    """``__call__`` returns logits ``[batch, seq, vocab]``.

    ``token_mask`` (``[batch, seq]`` bool) names the positions that hold
    a token: one outside it (a server's idle slot, a prefill chunk's
    padding) advances no mixer state."""
    config: FalconH1Config
    # the serving programs hand such a module ``token_mask``
    # (inference/generation.py apply_decode)
    masks_tokens = True

    def __call__(self, input_ids, **kwargs):
        # float32 means float32 arithmetic, on a TPU too (``LFM2``)
        if self.config.dtype == jnp.float32:
            with jax.default_matmul_precision("highest"):
                return self._forward(input_ids, **kwargs)
        return self._forward(input_ids, **kwargs)

    @nn.compact
    def _forward(self, input_ids, *, deterministic=True, decode=False,
                 positions=None, token_mask=None):
        cfg = self.config

        def table(name):
            return self.param(
                name, nn.with_logical_partitioning(
                    nn.initializers.normal(0.02), ("vocab", "embed")),
                (cfg.vocab_size, cfg.hidden_size), cfg.param_dtype)
        # a float32 residual stream whatever ``cfg.dtype`` is: the out
        # multipliers make a block's update a few percent of the stream
        h = jnp.take(table("wte"), input_ids, axis=0).astype(jnp.float32) \
            * cfg.embedding_multiplier
        h = activation_constraint(h, ("batch", "seq", "embed"))
        if positions is None:
            positions = jnp.arange(input_ids.shape[1])
        for i in range(cfg.num_hidden_layers):
            h = FalconH1Layer(cfg, name=f"layers_{i}")(
                h, decode, positions, token_mask)
        h = RMSNorm(epsilon=cfg.rms_norm_eps, name="final_layernorm")(h)
        logits = dot_exact_weights(h.astype(cfg.dtype),
                                   table("lm_head").T)
        return logits * cfg.lm_head_multiplier
