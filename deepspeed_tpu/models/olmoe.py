"""OLMoE (Muennighoff et al. 2024, arXiv:2409.02060; ``allenai/OLMoE-1B-7B``):
a decoder whose every feed-forward is a dropless top-k layer of SwiGLU
experts.

    h = x + Wo Attn(RoPE(q), RoPE(k), v),   q = RMSNorm_q(Wq n),
                                            k = RMSNorm_k(Wk n), v = Wv n,
                                            n = RMSNorm(x)
    y = h + sum_{e in topk(p)} p_e W_down,e (silu(W_gate,e m) * W_up,e m),
        m = RMSNorm(h),  p = softmax_float32(W_router m)

Token embedding, no position table, no bias anywhere, a final RMSNorm and
an untied head. The two QK norms run over the whole projection before the
heads split; the k weights are not renormalised unless ``norm_topk_prob``.

A module of its own rather than more flags on ``GPT``: the stack is
``Block`` (``models/layers.py``: ``norm="rmsnorm"``, ``qk_norm``, rotary,
an ``mlp_factory`` that builds ``moe.layer.DroplessMoE``) under the same
layer scan ``GPT`` uses, so the paged programs find the stacked leaves and
the collections they expect; what differs from ``GPT.__call__`` is what
this file holds — the live-row mask handed down to the router, the
experts' weights kept as one ``[L, E, ...]`` stack outside the scanned
block (``experts/w_gate|w_up|w_down``: the scan would hand each layer a
slice, and a slice that feeds the grouped matmul is a copy), and the
router's counts and auxiliary loss carried out of the scan — and ``GPT``'s
branches (learned positions, offload streaming, tied chunked loss) have no
part in it. ``OLMoEConfig``'s fields are the keys of the architecture's
``config.json``; the last two say in which dtypes this program runs it.
"""

from dataclasses import dataclass
from typing import Any

import jax.numpy as jnp
import flax.linen as nn

from .gpt import gpt_loss_fn
from .layers import Block, QDense, RMSNorm, activation_constraint
from ..moe.layer import DroplessMoE, expert_stack
from ..moe.sharded_moe import load_balancing_loss


@dataclass(frozen=True)
class OLMoEConfig:
    vocab_size: int = 50304
    hidden_size: int = 2048
    intermediate_size: int = 1024        # ONE expert's width
    num_hidden_layers: int = 16
    num_attention_heads: int = 16
    num_experts: int = 64
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = False
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    max_position_embeddings: int = 4096
    router_aux_loss_coef: float = 0.01
    dtype: Any = jnp.bfloat16            # activation/compute dtype
    param_dtype: Any = jnp.float32

    @property
    def max_seq_len(self):
        """What the engines ask a model's config for."""
        return self.max_position_embeddings


class _ExpertStacks(nn.Module):
    """Every layer's expert weights, ``[L, E, ...]`` each."""
    config: OLMoEConfig

    @nn.compact
    def __call__(self):
        cfg = self.config
        return expert_stack(self, cfg.num_hidden_layers, cfg.num_experts,
                            cfg.hidden_size, cfg.intermediate_size,
                            cfg.param_dtype)


class OLMoE(nn.Module):
    """``__call__`` returns logits ``[batch, seq, vocab]``; with
    ``return_router=True`` also ``{"aux_loss", "counts"}``: the
    load-balancing loss (over all layers' tokens together) and the
    router's assignments per layer and expert, ``[L, E]`` int32.

    ``token_mask`` (``[batch, seq]`` bool) names the rows that hold a
    token: a row outside it (a server's idle slot, a prefill chunk's
    padding) is routed to no expert and counted nowhere."""
    config: OLMoEConfig
    # the serving programs hand such a module ``token_mask`` and take its
    # counts back (inference/generation.py apply_decode)
    routes_tokens = True

    @nn.compact
    def __call__(self, input_ids, *, deterministic=True, decode=False,
                 positions=None, token_mask=None, return_router=False):
        cfg = self.config
        wte = self.param(
            "wte", nn.with_logical_partitioning(
                nn.initializers.normal(0.02), ("vocab", "embed")),
            (cfg.vocab_size, cfg.hidden_size), cfg.param_dtype)
        # the residual stream is float32 whatever ``cfg.dtype`` is: every
        # matmul still takes and gives ``cfg.dtype`` (``QDense`` and the
        # expert layer cast their inputs), but the sum that 2 x L layer
        # outputs are added into is not rounded to 8 bits at each step,
        # the experts' weighted sum joins it unrounded, and the router
        # reads its input unrounded. With the stream in bf16 one run in
        # twenty served a token 0.1265 sigma under the float32
        # reference's best (PERF.md section 6, PR 28)
        h = jnp.take(wte, input_ids, axis=0).astype(jnp.float32)
        h = activation_constraint(h, ("batch", "seq", "embed"))
        if positions is None:
            positions = jnp.arange(input_ids.shape[1])

        def experts(name):
            return DroplessMoE(
                num_experts=cfg.num_experts,
                num_experts_per_tok=cfg.num_experts_per_tok,
                norm_topk_prob=cfg.norm_topk_prob, dtype=cfg.dtype,
                name=name)

        block = Block(
            n_heads=cfg.num_attention_heads, d_model=cfg.hidden_size,
            d_ff=cfg.intermediate_size, causal=True, pre_ln=True,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype, use_bias=False,
            ln_epsilon=cfg.rms_norm_eps, norm="rmsnorm", qk_norm=True,
            rotary=True, rotary_base=cfg.rope_theta, mlp_factory=experts,
            name="h")

        stacks = _ExpertStacks(cfg, name="experts")()

        def body(block, carry, layer):
            return block(carry, None, None, deterministic, None, decode,
                         positions, {"token_mask": token_mask,
                                     "experts": stacks, "layer": layer})

        h, aux = nn.scan(
            body,
            # as GPT's scan: the paged programs' collections
            variable_axes={"params": 0, "cache": 0, "kv_token": 0},
            variable_broadcast="kv_pool",
            split_rngs={"params": True},
            length=cfg.num_hidden_layers,
            metadata_params={nn.PARTITION_NAME: "layers"},
        )(block, h, jnp.arange(cfg.num_hidden_layers))

        h = RMSNorm(epsilon=cfg.rms_norm_eps, name="ln_f")(h)
        logits = QDense(
            features=cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.normal(0.02), ("embed", "vocab")),
            name="lm_head")(h)
        if return_router:
            return logits, {
                "aux_loss": load_balancing_loss(
                    aux["gate_mean"], aux["counts"], cfg.num_experts_per_tok),
                "counts": aux["counts"]}
        return logits


def olmoe_loss_fn(model, params, batch, rng, train):
    """Next-token cross entropy plus ``router_aux_loss_coef`` times the
    load-balancing loss (engine-compatible signature)."""
    ids = batch["input_ids"]
    logits, router = model.apply(params, ids[:, :-1],
                                 deterministic=not train, return_router=True)
    return (gpt_loss_fn(logits, ids[:, 1:])
            + model.config.router_aux_loss_coef * router["aux_loss"])
