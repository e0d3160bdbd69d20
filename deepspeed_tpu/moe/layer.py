"""MoE layer facade.

Reference: deepspeed/moe/layer.py:15 ``MoE`` — wraps TopKGate + Experts +
MOELayer, exposing (output, l_aux, exp_counts). Same surface here as a flax
module; ``ep_size`` is validated against the mesh's expert axis instead of
creating process groups (deepspeed/utils/groups.py).
"""

from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.layers import GatedMLP, QDense

from ..comm.mesh import get_global_mesh
from ..utils.logging import logger
from .sharded_moe import (MOELayer, dropless_experts, mean_gate,
                          topk_routing)


class ExpertMLP(nn.Module):
    """Default expert: the standard FFN (reference: a torch nn.Module the
    user passes; this is the common case)."""
    d_model: int
    d_ff: int
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    activation: str = "gelu"

    @nn.compact
    def __call__(self, x):
        h = QDense(features=self.d_ff, dtype=self.dtype,
                            param_dtype=self.param_dtype,
                            kernel_init=nn.with_logical_partitioning(
                                nn.initializers.variance_scaling(
                                    1.0, "fan_in", "normal"),
                                ("embed", "mlp")),
                            bias_init=nn.with_logical_partitioning(
                                nn.initializers.zeros, ("mlp",)),
                            name="fc_in")(x)
        h = jax.nn.gelu(h, approximate=True) if self.activation == "gelu" \
            else jax.nn.relu(h)
        return QDense(features=self.d_model, dtype=self.dtype,
                               param_dtype=self.param_dtype,
                               kernel_init=nn.with_logical_partitioning(
                                   nn.initializers.variance_scaling(
                                       1.0, "fan_in", "normal"),
                                   ("mlp", "embed")),
                               bias_init=nn.with_logical_partitioning(
                                   nn.initializers.zeros, ("embed",)),
                               name="fc_out")(h)


class MoE(nn.Module):
    """reference: deepspeed/moe/layer.py:15.

    __call__(x) -> (output, l_aux, exp_counts)."""
    hidden_size: int
    num_experts: int = 1
    ep_size: int = 1
    k: int = 1
    capacity_factor: float = 1.0
    eval_capacity_factor: float = 1.0
    min_capacity: int = 4
    d_ff: Optional[int] = None
    expert: Optional[Callable] = None    # factory(name=...) -> nn.Module
    noisy_gate_policy: Optional[str] = None
    drop_tokens: bool = True
    use_rts: bool = True
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def setup(self):
        if self.num_experts % max(self.ep_size, 1) != 0:
            raise ValueError(
                f"num_experts={self.num_experts} must be divisible by "
                f"ep_size={self.ep_size}")
        factory = self.expert or (lambda name: ExpertMLP(
            d_model=self.hidden_size, d_ff=self.d_ff or 4 * self.hidden_size,
            dtype=self.dtype, param_dtype=self.param_dtype, name=name))
        self.moe_layer = MOELayer(
            d_model=self.hidden_size, num_experts=self.num_experts,
            expert_factory=factory, k=self.k,
            capacity_factor=self.capacity_factor,
            eval_capacity_factor=self.eval_capacity_factor,
            min_capacity=self.min_capacity,
            noisy_gate_policy=self.noisy_gate_policy,
            drop_tokens=self.drop_tokens, use_rts=self.use_rts,
            name="deepspeed_moe")

    def __call__(self, x, deterministic=True):
        try:
            ep_axis = get_global_mesh().shape.get("expert", 1)
            if ep_axis > 1 and self.num_experts % ep_axis != 0:
                logger.warning(
                    f"num_experts={self.num_experts} not divisible by mesh "
                    f"expert axis {ep_axis}; experts will replicate")
        except Exception:
            pass
        return self.moe_layer(x, deterministic=deterministic)


def expert_stack(module, n_layers, num_experts, d, f, param_dtype):
    """Every layer's gated experts as params of ``module``, one
    ``[L, E, ...]`` stack a matrix: ``w_gate``, ``w_up`` ``[L, E, d, f]``,
    ``w_down`` ``[L, E, f, d]``, no bias, the ``experts`` logical axis on
    the expert mesh axis as ``MOELayer``'s are (``dropless_experts`` says
    why the layers' weights are kept together; a lone layer is L = 1)."""
    init = nn.initializers.variance_scaling(
        1.0, "fan_in", "normal", in_axis=-2, out_axis=-1, batch_axis=(0, 1))

    def one(name, shape, names):
        return module.param(
            name, nn.with_logical_partitioning(
                init, ("layers", "experts") + names),
            (n_layers, num_experts) + shape, param_dtype)
    return (one("w_gate", (d, f), ("embed", "mlp")),
            one("w_up", (d, f), ("embed", "mlp")),
            one("w_down", (f, d), ("mlp", "embed")))


# the spread of a seeded ``expert_bias``: a twentieth of the spread of a
# seeded router's sigmoid scores (~0.2). A trained model's bias is what
# keeps its experts balanced; a drawn one of 0.1 unbalanced them as no
# trained router is (the largest group five times the mean) and made a
# server's speed the seed's (PERF.md section 6, PR 33). Still not zero:
# a program that put it in the weights, or left it out, reads wrong
EXPERT_BIAS_INIT_STD = 0.01


class DroplessMoE(nn.Module):
    """A dropless top-k expert layer of gated (SwiGLU) experts, as
    OLMoE, Mixtral and their kin publish it:
    ``y = sum_{e in topk(p)} p_e W_down,e (silu(W_gate,e x) * W_up,e x)``,
    ``p = softmax_float32(W_router x)`` over all experts. No capacity, no
    bias (``sharded_moe.dropless_experts``).

    The router is this module's, float32 whatever the model's dtype, and
    its matmul runs at full precision: two gate probabilities that nearly
    tie decide which expert a token gets. The experts' weights are the
    model's (``expert_stack``): it keeps every layer's in one stack and
    hands ``experts=(w_gate, w_up, w_down)`` ``[L, E, ...]`` to each
    layer with its index ``layer``.

    ``__call__(x [b, s, d], token_mask [b, s] or None, experts, layer)
    -> (out, aux)``, ``out`` in ``x``'s dtype (the matmuls run in
    ``dtype``), with ``aux = {"gate_mean": [E], "counts": [E] int32}``:
    each expert's mean gate probability and the assignments counted, rows
    masked out in neither — what ``sharded_moe.load_balancing_loss``
    takes, of one layer or of all a model's layers together."""
    num_experts: int
    num_experts_per_tok: int
    norm_topk_prob: bool = False
    dtype: Any = jnp.bfloat16
    # the sigmoid router of the DeepSeek-V3 line (``topk_routing``): with
    # ``use_expert_bias`` the layer holds ``expert_bias`` [E], added to
    # the scores to choose and never to weigh
    score: str = "softmax"
    use_expert_bias: bool = False
    # what the DeepSeek-V3 line adds to that router: the chosen weights
    # times ``routed_scaling_factor`` (1: no multiply is traced), their
    # sum's ``norm_eps``, and ``shared_width`` > 0 for the shared
    # experts — ONE gated MLP that wide (``n_shared_experts`` times an
    # expert's width) which every token takes, added unweighted to the
    # routed sum; its weights are this module's own (``shared``)
    routed_scaling_factor: float = 1.0
    norm_eps: float = 1e-6
    shared_width: int = 0
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, deterministic=True, token_mask=None, *, experts,
                 layer):
        b, s, d = x.shape
        tokens = x.reshape(b * s, d)
        live = None if token_mask is None else token_mask.reshape(b * s)

        router = self.param("router", nn.with_logical_partitioning(
            nn.initializers.lecun_normal(), ("embed", None)),
            (d, self.num_experts), jnp.float32)
        logits = jnp.dot(tokens.astype(jnp.float32), router,
                         precision=jax.lax.Precision.HIGHEST)
        # a trained model's bias is the balance its training kept, and a
        # seeded one's is drawn, not zero: a program that added it to the
        # weights, or left it out, would otherwise read like one that is
        # right
        bias = self.param(
            "expert_bias", nn.with_logical_partitioning(
                nn.initializers.normal(EXPERT_BIAS_INIT_STD), (None,)),
            (self.num_experts,), jnp.float32) \
            if self.use_expert_bias else None
        probs, weights, chosen = topk_routing(
            logits, self.num_experts_per_tok, self.norm_topk_prob,
            score=self.score, bias=bias, scale=self.routed_scaling_factor,
            norm_eps=self.norm_eps)
        out, counts = dropless_experts(
            tokens.astype(self.dtype), weights, chosen, *experts, layer,
            live=live)
        out = out.reshape(b, s, d).astype(x.dtype)
        if self.shared_width:
            out = out + GatedMLP(
                d_model=d, d_ff=self.shared_width, dtype=self.dtype,
                param_dtype=self.param_dtype, name="shared")(x).astype(
                    x.dtype)
        aux = {"gate_mean": mean_gate(probs, live), "counts": counts}
        return out, aux


def split_params_into_different_moe_groups_for_optimizer(param_groups):
    """API parity with deepspeed/moe/utils.py:61. In the TPU build the
    optimizer shards expert vs dense params differently via the sharding
    rules (zero/sharding.py), so there is nothing to split — returned
    unchanged."""
    return param_groups


def is_moe_param(name_tuple) -> bool:
    """A param is an expert param iff its logical names carry "experts"."""
    return name_tuple is not None and "experts" in name_tuple
