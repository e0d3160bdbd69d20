"""The DeepSeek-V3 architecture (HF ``modeling_deepseek_v3.py``,
``model_type: deepseek_v3``; served here at the widths of
``kakaocorp/kanana-2-30b-a3b-instruct-2601``): a decoder whose attention
is multi-head LATENT attention and whose feed-forwards are dense in the
leading ``first_k_dense_replace`` layers and, after them, shared experts
beside a dropless top-k of routed SwiGLU experts. With
``n = RMSNorm_in(x)``:

    attention     ``models/layers.py LatentAttention`` (``q_lora_rank``
                  null): h = x + W_o Attn(q, c | k_pe), the cache one
                  compressed vector a token
    feed-forward  m = RMSNorm_post(h). Dense: y = h + W_2(silu(W_1 m) *
                  W_3 m). Experts: s = sigmoid_float32(W_g m), chosen =
                  topk(s + e_score_correction_bias), w = s[chosen] /
                  (sum + 1e-20) * routed_scaling_factor, y = h + sum_chosen
                  w_e SwiGLU_e(m) + SwiGLU_shared(m), the shared MLP
                  ``n_shared_experts`` experts wide and unweighted

Token embedding, no position table, no bias anywhere, a final RMSNorm and
an untied head.

Laid out as ``LFM2`` is and for its reasons: the layers differ in kind,
so ``__call__`` builds them one by one (``layers_<i>``); a layer's cache
unit is ``LatentAttention``'s (a 4-D pool a layer, which the latent
kernel takes as a stack of one); the routed experts' weights are one
``[L_moe, E, ...]`` stack outside the layers, handed whole to the
grouped matmul with the layer's index; the router's counts come back
``[L_moe, E]``. ``DeepseekV3Config``'s fields are the keys of the
architecture's ``config.json``; the last two say in which dtypes this
program runs it.

Not built, and refused by name: a low-rank query path (``q_lora_rank``),
group-limited expert choice (``n_group`` / ``topk_group`` over 1),
long-context rotary scaling (``rope_scaling``), a softmax router
(``scoring_func``).
"""

from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
import flax.linen as nn

from .gpt import gpt_loss_fn
from .layers import (GatedMLP, LatentAttention, QDense, RMSNorm,
                     activation_constraint)
from ..moe.layer import DroplessMoE, expert_stack


@dataclass(frozen=True)
class DeepseekV3Config:
    vocab_size: int = 128256
    hidden_size: int = 2048
    intermediate_size: int = 6144        # a dense layer's width
    moe_intermediate_size: int = 768     # ONE expert's width
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    q_lora_rank: Optional[int] = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    first_k_dense_replace: int = 1
    n_routed_experts: int = 128
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.448
    scoring_func: str = "sigmoid"
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    rope_scaling: Any = None
    rope_interleave: bool = True
    max_position_embeddings: int = 32768
    dtype: Any = jnp.bfloat16            # activation/compute dtype
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        for option, built, why in (
                ("q_lora_rank", self.q_lora_rank is None,
                 "the low-rank query path (a down-projection, a norm and "
                 "an up-projection) is not built: queries are one "
                 "projection"),
                ("n_group", self.n_group == 1 and self.topk_group == 1,
                 "group-limited expert choice (n_group / topk_group over "
                 "1) is not built: experts are chosen among all"),
                ("rope_scaling", self.rope_scaling is None,
                 "long-context rotary scaling (and its mscale on the "
                 "softmax) is not built"),
                ("scoring_func", self.scoring_func == "sigmoid",
                 "the router scores by sigmoid")):
            if not built:
                raise ValueError(
                    f"{option}={getattr(self, option)!r}: {why}")

    @property
    def max_seq_len(self):
        """What the engines ask a model's config for."""
        return self.max_position_embeddings

    @property
    def num_moe_layers(self):
        return max(0, self.num_hidden_layers - self.first_k_dense_replace)


class _ExpertStacks(nn.Module):
    """Every expert layer's routed weights, ``[L_moe, E, ...]`` each."""
    config: DeepseekV3Config

    @nn.compact
    def __call__(self):
        cfg = self.config
        return expert_stack(self, cfg.num_moe_layers, cfg.n_routed_experts,
                            cfg.hidden_size, cfg.moe_intermediate_size,
                            cfg.param_dtype)


class DeepseekV3Layer(nn.Module):
    """One layer: latent attention and its feed-forward (``moe_index``:
    its place in the experts' stack, or None for a dense layer). Returns
    ``(y, aux)``, ``aux`` None when dense."""
    config: DeepseekV3Config
    moe_index: Any = None

    @nn.compact
    def __call__(self, x, decode, positions, token_mask, experts):
        cfg = self.config
        n = RMSNorm(epsilon=cfg.rms_norm_eps, name="input_norm")(x)
        h = x + LatentAttention(
            n_heads=cfg.num_attention_heads, d_model=cfg.hidden_size,
            kv_lora_rank=cfg.kv_lora_rank,
            qk_nope_head_dim=cfg.qk_nope_head_dim,
            qk_rope_head_dim=cfg.qk_rope_head_dim,
            v_head_dim=cfg.v_head_dim, rotary_base=cfg.rope_theta,
            rope_interleave=cfg.rope_interleave,
            norm_epsilon=cfg.rms_norm_eps, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            name="attn")(n, decode=decode, positions=positions)
        m = RMSNorm(epsilon=cfg.rms_norm_eps, name="post_norm")(h)
        if self.moe_index is None:
            y = GatedMLP(d_model=cfg.hidden_size, d_ff=cfg.intermediate_size,
                         dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                         name="mlp")(m)
            aux = None
        else:
            y, aux = DroplessMoE(
                num_experts=cfg.n_routed_experts,
                num_experts_per_tok=cfg.num_experts_per_tok,
                norm_topk_prob=cfg.norm_topk_prob, dtype=cfg.dtype,
                score="sigmoid", use_expert_bias=True,
                routed_scaling_factor=cfg.routed_scaling_factor,
                norm_eps=1e-20,
                shared_width=(cfg.n_shared_experts
                              * cfg.moe_intermediate_size),
                param_dtype=cfg.param_dtype,
                name="moe")(m, token_mask=token_mask, experts=experts,
                            layer=self.moe_index)
        y = activation_constraint(h + y, ("batch", "seq", "embed"))
        return y, aux


class DeepseekV3(nn.Module):
    """``__call__`` returns logits ``[batch, seq, vocab]``; with
    ``return_router=True`` also ``{"counts"}``: the router's assignments
    per expert layer and routed expert, ``[L_moe, E]`` int32.

    ``token_mask`` (``[batch, seq]`` bool) names the rows that hold a
    token: a row outside it (a server's idle slot, a prefill chunk's
    padding) is routed to no expert and counted nowhere."""
    config: DeepseekV3Config
    # the serving programs hand such a module ``token_mask`` and take its
    # counts back (inference/generation.py apply_decode)
    routes_tokens = True

    def shared_expert_rows(self, counts) -> int:
        """Of a dispatch's counts ``[L_moe, E]``: the live rows that went
        through the shared experts, summed over the expert layers — every
        live row of every expert layer takes them once, and is counted
        ``num_experts_per_tok`` times among the routed."""
        if not self.config.n_shared_experts:
            return 0
        return int(counts.sum()) // self.config.num_experts_per_tok

    def __call__(self, input_ids, **kwargs):
        # float32 arithmetic on a TPU has to be asked for (``LFM2`` has
        # why): attention's products, in the latent kernel as well, and
        # the products of a float32 activation with a bfloat16 weight
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
        # ``OLMoE``'s and ``LFM2``'s: the router reads its input unrounded
        h = jnp.take(wte, input_ids, axis=0).astype(jnp.float32)
        h = activation_constraint(h, ("batch", "seq", "embed"))
        if positions is None:
            positions = jnp.arange(input_ids.shape[1])
        stacks = (_ExpertStacks(cfg, name="experts")()
                  if cfg.num_moe_layers else None)
        counts = []
        for i in range(cfg.num_hidden_layers):
            moe_index = (None if i < cfg.first_k_dense_replace
                         else i - cfg.first_k_dense_replace)
            h, aux = DeepseekV3Layer(cfg, moe_index, name=f"layers_{i}")(
                h, decode, positions, token_mask, stacks)
            if aux is not None:
                counts.append(aux["counts"])
        h = RMSNorm(epsilon=cfg.rms_norm_eps, name="ln_f")(h)
        logits = QDense(
            features=cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.normal(0.02), ("embed", "vocab")),
            name="lm_head")(h)
        if return_router:
            return logits, {"counts": (
                jnp.stack(counts) if counts
                else jnp.zeros((0, cfg.n_routed_experts), jnp.int32))}
        return logits


def deepseek_v3_loss_fn(model, params, batch, rng, train):
    """Next-token cross entropy (engine-compatible signature); the
    published configuration balances its experts by
    ``e_score_correction_bias`` and has no auxiliary loss here."""
    ids = batch["input_ids"]
    logits = model.apply(params, ids[:, :-1], deterministic=not train)
    return gpt_loss_fn(logits, ids[:, 1:])
