"""Phi-4-mini-flash-reasoning (Microsoft; ``model_type: phi4flash``; the
paper is "Decoder-Hybrid-Decoder Architecture for Efficient Reasoning
with Long Generation", arXiv:2507.06607 — SambaY with differential
attention): a self-decoder of Mamba-1 mixers and window attention in
turn, one full attention layer whose keys and values are the only ones
kept for the whole context, and a cross-decoder that keeps nothing — its
layers read the last mixer's scan output through gated memory units, or
the full layer's keys and values with queries of their own. Every layer
is ``h <- h + Mix_i(LayerNorm(h)); h <- h + MLP(LayerNorm(h))`` with a
SwiGLU MLP, and with ``L`` layers (``L / 2`` even: 32 as published) and
``half = L / 2``:

    i < half, even      ``MambaMixer``            conv + SSM state a slot
    i < half, odd       window attention          a K/V ring a slot
    i = half            ``MambaMixer`` that hands on its scan output m
    i = half + 1        full attention            K/V pages: the one paged
                                                  layer
    i > half + 1, even  ``GatedMemoryUnit`` on m  nothing
    i > half + 1, odd   cross attention over layer half + 1's K/V
                                                  nothing

All attention is differential (``models/layers.py
DifferentialAttention``), none has a position encoding. The table is
tied: logits are ``LayerNorm(h) E^T``.

Because the cross-decoder keeps nothing, it has to run only where a
logit is read: ``positions_needed`` (``[batch]`` indices into the
sequence) makes layers ``half + 2 ..`` run on that one position a row,
which is why a prompt's prefill is linear in its length — a server's
chunk program hands the last valid position (``splits_positions``), a
decode step has one position anyway, and training or scoring leaves it
None and gets every position.

The modules are named for what they keep, so that a parameter's or a
pool leaf's path says the mechanism: ``layers_<i>/mixer``,
``/window_attn``, ``/shared_attn``, ``/cross_attn``, ``/gmu``.
``Phi4FlashConfig``'s fields are the keys of the architecture's
``config.json``; the sizes it does not give are Mamba-1's defaults."""

from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
import flax.linen as nn

from .layers import (DifferentialAttention, GatedMemoryUnit, GatedMLP,
                     LayerNorm, MambaMixer, activation_constraint,
                     dot_exact_weights)


@dataclass(frozen=True)
class Phi4FlashConfig:
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    sliding_window: int = 512
    mb_per_layer: int = 2
    layer_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = True
    mlp_bias: bool = False
    lm_head_bias: bool = False
    # not keys of config.json: Mamba-1's defaults
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: Optional[int] = None      # ceil(hidden / 16)
    mamba_bc_gain: float = 1.0               # seeded weights only
    ssm_state_dtype: Any = jnp.float32
    kv_cache_dtype: Any = None               # keys and values as cached
                                             # (None: ``dtype``)
    dtype: Any = jnp.bfloat16                # activation/compute dtype
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        if self.mb_per_layer != 2:
            raise NotImplementedError(
                f"mb_per_layer {self.mb_per_layer}: the published model "
                "alternates a mixer and an attention layer")
        if self.num_hidden_layers % 4:
            raise ValueError(
                f"{self.num_hidden_layers} layers: the two decoders are "
                "each a whole number of (mixer | unit, attention) periods")
        if not self.tie_word_embeddings or self.mlp_bias \
                or self.lm_head_bias:
            raise NotImplementedError(
                "an untied head, an MLP bias or a head bias: the published "
                "model has none")
        if self.num_attention_heads % self.num_key_value_heads or \
                self.num_key_value_heads % 2:
            raise ValueError(
                f"{self.num_attention_heads} query heads over "
                f"{self.num_key_value_heads} K/V heads: differential "
                "attention halves both")

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self):
        return self.mamba_expand * self.hidden_size

    @property
    def dt_rank(self):
        return self.mamba_dt_rank or -(-self.hidden_size // 16)

    @property
    def max_seq_len(self):
        """What the engines ask a model's config for."""
        return self.max_position_embeddings

    def layer_kind(self, i: int) -> str:
        half = self.num_hidden_layers // 2
        if i <= half:
            return "mixer" if i % 2 == 0 else "window_attn"
        if i == half + 1:
            return "shared_attn"
        return "gmu" if i % 2 == 0 else "cross_attn"


class Phi4FlashLayer(nn.Module):
    """One layer; ``carried`` is what the layers before handed on
    (``{"memory": m, "shared": keys and values}``) and comes back with
    what this one adds."""
    config: Phi4FlashConfig
    index: int

    @nn.compact
    def __call__(self, h, decode, positions, token_mask, carried):
        cfg, kind = self.config, self.config.layer_kind(self.index)
        types = dict(dtype=cfg.dtype, param_dtype=cfg.param_dtype)
        n = LayerNorm(epsilon=cfg.layer_norm_eps, name="input_layernorm")(h)
        if kind == "mixer":
            last = self.index == cfg.num_hidden_layers // 2
            mix = MambaMixer(
                d_model=cfg.hidden_size, d_inner=cfg.d_inner,
                dt_rank=cfg.dt_rank, d_state=cfg.mamba_d_state,
                d_conv=cfg.mamba_d_conv, bc_gain=cfg.mamba_bc_gain,
                hand_on=last, state_dtype=cfg.ssm_state_dtype, name="mixer",
                **types)(n, decode=decode, token_mask=token_mask)
            if last:
                mix, memory = mix
                carried = dict(carried, memory=memory)
        elif kind == "gmu":
            mix = GatedMemoryUnit(d_model=cfg.hidden_size,
                                  d_memory=cfg.d_inner, name="gmu",
                                  **types)(n, carried["memory"])
        else:
            attn = DifferentialAttention(
                n_heads=cfg.num_attention_heads,
                n_kv_heads=cfg.num_key_value_heads, d_model=cfg.hidden_size,
                head_dim=cfg.head_dim, layer_index=self.index,
                kind={"window_attn": "window", "shared_attn": "full",
                      "cross_attn": "cross"}[kind],
                window=cfg.sliding_window if kind == "window_attn" else None,
                norm_epsilon=cfg.layer_norm_eps,
                cache_dtype=cfg.kv_cache_dtype, name=kind, **types)
            mix = attn(n, decode=decode, positions=positions,
                       token_mask=token_mask, shared=carried.get("shared"))
            if kind == "shared_attn":
                mix, shared = mix
                carried = dict(carried, shared=shared)
        h = h + mix
        f = LayerNorm(epsilon=cfg.layer_norm_eps,
                      name="post_attention_layernorm")(h)
        h = h + GatedMLP(d_model=cfg.hidden_size, d_ff=cfg.intermediate_size,
                         name="mlp", **types)(f)
        return activation_constraint(h, ("batch", "seq", "embed")), carried


class Phi4Flash(nn.Module):
    """``__call__`` returns logits ``[batch, seq, vocab]``, or ``[batch,
    1, vocab]`` at ``positions_needed`` (``[batch]`` indices into the
    sequence: the cross-decoder then runs on that one position a row).

    ``token_mask`` (``[batch, seq]`` bool) names the positions that hold
    a token: one outside it advances no mixer state and writes no ring."""
    config: Phi4FlashConfig
    # the serving programs hand such a module ``token_mask``
    # (inference/generation.py apply_decode) ...
    masks_tokens = True
    # ... and a chunk program the one position whose logits it reads
    splits_positions = True

    def __call__(self, input_ids, **kwargs):
        # float32 means float32 arithmetic, on a TPU too (``LFM2``)
        if self.config.dtype == jnp.float32:
            with jax.default_matmul_precision("highest"):
                return self._forward(input_ids, **kwargs)
        return self._forward(input_ids, **kwargs)

    @nn.compact
    def _forward(self, input_ids, *, deterministic=True, decode=False,
                 positions=None, token_mask=None, positions_needed=None):
        cfg = self.config
        b, s = input_ids.shape
        table = self.param(
            "wte", nn.with_logical_partitioning(
                nn.initializers.normal(0.02), ("vocab", "embed")),
            (cfg.vocab_size, cfg.hidden_size), cfg.param_dtype)
        # a float32 residual stream whatever ``cfg.dtype`` is
        h = jnp.take(table, input_ids, axis=0).astype(jnp.float32)
        h = activation_constraint(h, ("batch", "seq", "embed"))
        if positions is None:
            positions = jnp.arange(s)
        first_cross = cfg.num_hidden_layers // 2 + 2
        carried = {}
        with jax.named_scope("self_decoder"):
            for i in range(first_cross):
                h, carried = Phi4FlashLayer(cfg, i, name=f"layers_{i}")(
                    h, decode, positions, token_mask, carried)
        if positions_needed is not None:
            # the cross-decoder keeps nothing, so it runs where a logit
            # is read and nowhere else: one position a row
            at = positions_needed[:, None]                      # [b, 1]
            pick = lambda v: jnp.take_along_axis(v, at[..., None], axis=1)
            h = pick(h)
            carried = dict(carried, memory=pick(carried["memory"]))
            positions = jnp.take_along_axis(
                jnp.broadcast_to(positions, (b, s)), at, axis=1)
            if token_mask is not None:
                token_mask = jnp.take_along_axis(token_mask, at, axis=1)
        with jax.named_scope("cross_decoder"):
            for i in range(first_cross, cfg.num_hidden_layers):
                h, carried = Phi4FlashLayer(cfg, i, name=f"layers_{i}")(
                    h, decode, positions, token_mask, carried)
        h = LayerNorm(epsilon=cfg.layer_norm_eps, name="final_layernorm")(h)
        return dot_exact_weights(h.astype(cfg.dtype), table.T)
