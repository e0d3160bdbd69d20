"""LFM2-MoE (Liquid AI, ``LiquidAI/LFM2-24B-A2B``; HF ``modeling_lfm2_moe.py``):
a decoder whose layers are gated short convolutions with a full-attention
layer among them (``layer_types``), and whose feed-forwards are dense in
the leading ``num_dense_layers`` and a dropless top-k layer of SwiGLU
experts after them. With ``n = RMSNorm_op(x)``:

    conv layer       [B, C, u] = W_in n;  z_t = sum_j w_j (B u)_{t-2+j};
                     h = x + W_out (C z)
    attention layer  q = RMSNorm_q(W_q n), k = RMSNorm_k(W_k n) over each
                     head's values after the heads split, v = W_v n; fewer
                     K/V heads than query heads (query head i reads K/V
                     head i // group); RoPE; h = x + W_o Attn(q, k, v)
    feed-forward     m = RMSNorm_ffn(h); dense: y = h + W_2(silu(W_1 m) *
                     W_3 m); experts: s = sigmoid_float32(W_r m), chosen =
                     topk(s + b), w_e = s_e / (sum_chosen s + 1e-6),
                     y = h + sum_chosen w_e W_2,e(silu(W_1,e m) * W_3,e m)

Token embedding, no position table, no bias anywhere, a final RMSNorm and
the head tied to the embedding.

The layers are not one block scanned ``L`` times: they differ in kind
(conv or attention, dense or experts), so ``__call__`` lays them out one
by one (``layers_<i>``) in the order ``layer_types`` gives, whatever that
order is. What the paged programs expect is kept: an attention layer's
"cache" unit is ``SelfAttention``'s (its page pool one 4-D pool a layer,
which the paged kernel takes as a stack of one: the pool holds the
attention layers only), a conv layer's is ``ShortConv``'s ``conv_state``;
the experts' weights are one ``[L_moe, E, ...]`` stack outside the
layers, as ``OLMoE``'s are, and a layer hands the stack whole to the
grouped matmul with its own index in it; the router's counts come back
``[L_moe, E]``. ``LFM2Config``'s fields are the keys of the
architecture's ``config.json`` (``rope_theta`` is its
``rope_parameters.rope_theta``); the last two say in which dtypes this
program runs it.
"""

from dataclasses import dataclass
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn

from .gpt import gpt_loss_fn
from .layers import (GatedMLP, RMSNorm, SelfAttention, ShortConv,
                     activation_constraint, dot_exact_weights)
from ..moe.layer import DroplessMoE, expert_stack

CONV, ATTENTION = "conv", "full_attention"


@dataclass(frozen=True)
class LFM2Config:
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 11776       # a dense layer's width
    moe_intermediate_size: int = 1536    # ONE expert's width
    num_hidden_layers: int = 40
    layer_types: Tuple[str, ...] = (
        (CONV, CONV) + (ATTENTION, CONV, CONV, CONV) * 10)[:40]
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    conv_L_cache: int = 3                # the convolution's taps
    conv_bias: bool = False
    num_dense_layers: int = 2
    num_experts: int = 64
    num_experts_per_tok: int = 4
    use_expert_bias: bool = True
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    norm_eps: float = 1e-5
    rope_theta: float = 1000000.0
    max_position_embeddings: int = 128000
    dtype: Any = jnp.bfloat16            # activation/compute dtype
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers, "
                f"num_hidden_layers is {self.num_hidden_layers}")
        unknown = set(self.layer_types) - {CONV, ATTENTION}
        if unknown:
            raise ValueError(f"unknown layer types {sorted(unknown)}")
        if self.conv_bias:
            raise NotImplementedError("conv_bias: the published models "
                                      "have none")

    @property
    def max_seq_len(self):
        """What the engines ask a model's config for."""
        return self.max_position_embeddings

    @property
    def num_moe_layers(self):
        return max(0, self.num_hidden_layers - self.num_dense_layers)


class _ExpertStacks(nn.Module):
    """Every expert layer's weights, ``[L_moe, E, ...]`` each."""
    config: LFM2Config

    @nn.compact
    def __call__(self):
        cfg = self.config
        return expert_stack(self, cfg.num_moe_layers, cfg.num_experts,
                            cfg.hidden_size, cfg.moe_intermediate_size,
                            cfg.param_dtype)


class LFM2Layer(nn.Module):
    """One layer: its operator (``kind``: conv or attention) and its
    feed-forward (``moe_index``: its place in the experts' stack, or None
    for a dense layer). Returns ``(y, aux)``, ``aux`` None when dense."""
    config: LFM2Config
    kind: str
    moe_index: Any = None

    @nn.compact
    def __call__(self, x, decode, positions, token_mask, experts):
        cfg = self.config
        n = RMSNorm(epsilon=cfg.norm_eps, name="operator_norm")(x)
        if self.kind == CONV:
            a = ShortConv(d_model=cfg.hidden_size, kernel=cfg.conv_L_cache,
                          dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                          name="conv")(n, decode=decode,
                                       token_mask=token_mask)
        else:
            a = SelfAttention(
                n_heads=cfg.num_attention_heads, d_model=cfg.hidden_size,
                n_kv_heads=cfg.num_key_value_heads, causal=True,
                dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                use_bias=False, rotary=True, rotary_base=cfg.rope_theta,
                qk_norm="head", norm_epsilon=cfg.norm_eps,
                name="attn")(n, decode=decode, positions=positions)
        h = x + a
        m = RMSNorm(epsilon=cfg.norm_eps, name="ffn_norm")(h)
        if self.moe_index is None:
            y = GatedMLP(d_model=cfg.hidden_size, d_ff=cfg.intermediate_size,
                         dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                         name="mlp")(m)
            aux = None
        else:
            y, aux = DroplessMoE(
                num_experts=cfg.num_experts,
                num_experts_per_tok=cfg.num_experts_per_tok,
                norm_topk_prob=cfg.norm_topk_prob, dtype=cfg.dtype,
                score="sigmoid", use_expert_bias=cfg.use_expert_bias,
                routed_scaling_factor=cfg.routed_scaling_factor,
                name="moe")(m, token_mask=token_mask, experts=experts,
                            layer=self.moe_index)
        y = activation_constraint(h + y, ("batch", "seq", "embed"))
        return y, aux


class LFM2(nn.Module):
    """``__call__`` returns logits ``[batch, seq, vocab]``; with
    ``return_router=True`` also ``{"counts"}``: the router's assignments
    per expert layer and expert, ``[L_moe, E]`` int32.

    ``token_mask`` (``[batch, seq]`` bool) names the rows that hold a
    token: a row outside it (a server's idle slot, a prefill chunk's
    padding) is routed to no expert, counted nowhere, and writes no
    convolution state."""
    config: LFM2Config
    # the serving programs hand such a module ``token_mask`` and take its
    # counts back (inference/generation.py apply_decode)
    routes_tokens = True

    def __call__(self, input_ids, **kwargs):
        # ``dtype`` float32 means float32 arithmetic, on a TPU too, where
        # a float32 product is one bfloat16 pass unless told otherwise.
        # This is where this model tells: attention's products, in the
        # kernels as well, are traced under it, and so are the products
        # of a float32 activation with a weight kept in bfloat16, which
        # ``dot_exact_weights`` and ``grouped_matmul`` make exact under
        # this setting and under no other (``layers.exact_weights``)
        if self.config.dtype == jnp.float32:
            with jax.default_matmul_precision("highest"):
                return self._forward(input_ids, **kwargs)
        return self._forward(input_ids, **kwargs)

    @nn.compact
    def _forward(self, input_ids, *, deterministic=True, decode=False,
                 positions=None, token_mask=None, return_router=False):
        cfg = self.config
        wte = self.param(
            "wte", nn.with_logical_partitioning(
                nn.initializers.normal(0.02), ("vocab", "embed")),
            (cfg.vocab_size, cfg.hidden_size), cfg.param_dtype)
        # a float32 residual stream whatever ``cfg.dtype`` is, as
        # ``OLMoE``'s and for its reason: the router reads its input
        # unrounded, and a sigmoid router's near-ties are as close
        h = jnp.take(wte, input_ids, axis=0).astype(jnp.float32)
        h = activation_constraint(h, ("batch", "seq", "embed"))
        if positions is None:
            positions = jnp.arange(input_ids.shape[1])
        stacks = (_ExpertStacks(cfg, name="experts")()
                  if cfg.num_moe_layers else None)
        counts = []
        for i, kind in enumerate(cfg.layer_types):
            moe_index = (None if i < cfg.num_dense_layers
                         else i - cfg.num_dense_layers)
            h, aux = LFM2Layer(cfg, kind, moe_index, name=f"layers_{i}")(
                h, decode, positions, token_mask, stacks)
            if aux is not None:
                counts.append(aux["counts"])
        h = RMSNorm(epsilon=cfg.norm_eps, name="ln_f")(h)
        # the head is the embedding, transposed
        logits = dot_exact_weights(h.astype(cfg.dtype), wte.T)
        if return_router:
            return logits, {"counts": (
                jnp.stack(counts) if counts
                else jnp.zeros((0, cfg.num_experts), jnp.int32))}
        return logits


def lfm2_loss_fn(model, params, batch, rng, train):
    """Next-token cross entropy (engine-compatible signature); the
    published configuration has no auxiliary loss: its ``expert_bias``
    is what balances the experts."""
    ids = batch["input_ids"]
    logits = model.apply(params, ids[:, :-1], deterministic=not train)
    return gpt_loss_fn(logits, ids[:, 1:])
