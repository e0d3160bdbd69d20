"""Expert-parallel MoE core: gating + dispatch.

Reference: deepspeed/moe/sharded_moe.py — top1gating (:175), top2gating
(:276) with capacity + load-balancing aux loss + random token selection;
MOELayer.forward (:489): gate -> _AllToAll (:87) -> local experts ->
_AllToAll back -> combine.

TPU-native: dispatch/combine are einsums with sharding constraints over the
"expert" mesh axis — the XLA SPMD partitioner lowers the resharding to the
same all-to-all the reference issues by hand over its expert process group
(created in deepspeed/utils/groups.py:107). Gating math is kept identical.
"""

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import flax.linen as nn

from deepspeed_tpu.models.layers import QDense, exact_weights, split_terms

from ..comm.mesh import get_global_mesh, peek_global_mesh
from ..observability.metrics import get_registry
from ..ops.pallas import grouped_matmul as pallas_grouped
from ..ops.pallas._common import in_manual_region, on_tpu


def _expert_constraint(x, spec_axes):
    """with_sharding_constraint over the expert axis, no-op off-mesh.

    Uses a concrete NamedSharding — a bare PartitionSpec under plain
    ``jit`` has no mesh context and silently fails."""
    from jax.sharding import PartitionSpec as P, NamedSharding
    try:
        from jax.sharding import get_abstract_mesh
        am = get_abstract_mesh()
        if not am.empty and any("Manual" in str(t) for t in am.axis_types):
            return x   # inside shard_map: constraint meshes don't mix
        mesh = get_global_mesh()
        if mesh.shape.get("expert", 1) == 1:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(*spec_axes)))
    except Exception:
        from ..utils.logging import warn_once
        import sys
        warn_once(f"expert sharding constraint skipped: {sys.exc_info()[1]}")
        return x


def _capacity(num_tokens: int, num_experts: int, capacity_factor: float,
              min_capacity: int) -> int:
    """reference: sharded_moe.py _capacity — ceil(T/E * factor), floored at
    min_capacity. Static under jit (token count is a trace-time constant)."""
    cap = math.ceil(num_tokens / num_experts * capacity_factor)
    return max(cap, min_capacity)


def _one_hot(idx, n):
    return jax.nn.one_hot(idx, n, dtype=jnp.float32)


def top1gating(logits, capacity_factor: float, min_capacity: int = 4,
               noisy_gate_policy: Optional[str] = None,
               drop_tokens: bool = True, use_rts: bool = True,
               rng: Optional[jax.Array] = None):
    """Switch-style top-1 gating (reference :175).

    logits: [T, E] fp32. Returns (l_aux, combine [T,E,C], dispatch [T,E,C],
    exp_counts [E])."""
    T, E = logits.shape
    if drop_tokens:
        capacity = _capacity(T, E, capacity_factor, min_capacity)
    else:
        # no-drop needs worst-case capacity T (static shapes under jit);
        # the [T,E,T] dispatch tensors explode quadratically, so refuse
        # beyond a sane budget (reference shrinks dynamically, which XLA
        # static shapes cannot express).
        if T * T * E > 2 ** 26:
            raise ValueError(
                f"drop_tokens=False needs [T,E,T] dispatch tensors; "
                f"T={T}, E={E} exceeds the budget — enable drop_tokens or "
                f"reduce tokens per step")
        capacity = T

    if noisy_gate_policy == "RSample" and rng is not None:
        logits_w_noise = logits + jax.random.gumbel(rng, logits.shape)
    else:
        logits_w_noise = logits
    gates = jax.nn.softmax(logits, axis=-1)

    indices1 = jnp.argmax(logits_w_noise, axis=-1)            # [T]
    mask1 = _one_hot(indices1, E)                             # [T, E]
    exp_counts = jnp.sum(mask1, axis=0)

    # load-balancing loss (reference: l_aux = E * sum(me*ce))
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(mask1, axis=0)
    l_aux = jnp.sum(me * ce) * E

    # position in the expert queue: cumsum of mask in arrival order is the
    # reference's default; random-token-selection re-ranks by uniform
    # noise so truncation under capacity is unbiased (reference :221)
    locations1 = jnp.cumsum(mask1, axis=0) - mask1            # [T, E]
    if use_rts and rng is not None:
        rts = jax.random.uniform(jax.random.fold_in(rng, 1), (T, E))
        priority = mask1 * rts
        order = jnp.argsort(-priority, axis=0)                # [T, E]
        ranks = jnp.argsort(order, axis=0).astype(jnp.float32)
        locations1 = jnp.where(mask1 > 0, ranks, locations1)

    pos_in_expert = jnp.sum(locations1 * mask1, axis=-1)      # [T]
    keep = (pos_in_expert < capacity) & (jnp.sum(mask1, axis=-1) > 0)
    mask1 = mask1 * keep[:, None].astype(mask1.dtype)

    gates1 = jnp.sum(gates * mask1, axis=-1)                  # [T]
    loc_oh = _one_hot(jnp.clip(pos_in_expert, 0, capacity - 1).astype(jnp.int32),
                      capacity)                               # [T, C]
    combine = gates1[:, None, None] * mask1[:, :, None] * loc_oh[:, None, :]
    dispatch = (combine > 0).astype(logits.dtype)
    return l_aux, combine.astype(logits.dtype), dispatch, exp_counts


def top2gating(logits, capacity_factor: float, min_capacity: int = 4,
               rng: Optional[jax.Array] = None):
    """GShard-style top-2 gating (reference :276)."""
    T, E = logits.shape
    capacity = _capacity(T, E, capacity_factor * 2, min_capacity)
    gates = jax.nn.softmax(logits, axis=-1)

    indices1 = jnp.argmax(gates, axis=-1)
    mask1 = _one_hot(indices1, E)
    logits_except1 = jnp.where(mask1 > 0, -jnp.inf, logits)
    indices2 = jnp.argmax(logits_except1, axis=-1)
    mask2 = _one_hot(indices2, E)

    # aux loss on first choice only (reference :300)
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(mask1, axis=0)
    l_aux = jnp.sum(me * ce) * E

    locations1 = jnp.cumsum(mask1, axis=0) - mask1
    locations2 = jnp.cumsum(mask2, axis=0) - mask2 + jnp.sum(mask1, axis=0,
                                                             keepdims=True)
    pos1 = jnp.sum(locations1 * mask1, axis=-1)
    pos2 = jnp.sum(locations2 * mask2, axis=-1)
    mask1 = mask1 * (pos1 < capacity)[:, None].astype(mask1.dtype)
    mask2 = mask2 * (pos2 < capacity)[:, None].astype(mask2.dtype)

    gates1 = jnp.sum(gates * mask1, axis=-1)
    gates2 = jnp.sum(gates * mask2, axis=-1)
    denom = jnp.clip(gates1 + gates2, 1e-9, None)
    gates1, gates2 = gates1 / denom, gates2 / denom

    loc1 = _one_hot(jnp.clip(pos1, 0, capacity - 1).astype(jnp.int32), capacity)
    loc2 = _one_hot(jnp.clip(pos2, 0, capacity - 1).astype(jnp.int32), capacity)
    combine = (gates1[:, None, None] * mask1[:, :, None] * loc1[:, None, :]
               + gates2[:, None, None] * mask2[:, :, None] * loc2[:, None, :])
    dispatch = (combine > 0).astype(logits.dtype)
    exp_counts = jnp.sum(mask1 + mask2, axis=0)
    return l_aux, combine.astype(logits.dtype), dispatch, exp_counts


class TopKGate(nn.Module):
    """Gating network (reference: TopKGate, sharded_moe.py:374)."""
    d_model: int
    num_experts: int
    k: int = 1
    capacity_factor: float = 1.0
    eval_capacity_factor: float = 1.0
    min_capacity: int = 4
    noisy_gate_policy: Optional[str] = None
    drop_tokens: bool = True
    use_rts: bool = True

    @nn.compact
    def __call__(self, x, deterministic=True):
        rng = None
        if not deterministic and (self.use_rts or self.noisy_gate_policy):
            rng = self.make_rng("gating")
        if self.noisy_gate_policy == "Jitter" and rng is not None:
            # reference TopKGate: multiplicative input jitter
            # (multiplicative_jitter, sharded_moe.py — uniform in
            # [1-eps, 1+eps], eps=1e-2) for routing exploration
            eps = 1e-2
            x = x * jax.random.uniform(jax.random.fold_in(rng, 2), x.shape,
                                       x.dtype, 1.0 - eps, 1.0 + eps)
        # gate weights kept fp32 (reference keeps wg in fp32)
        logits = QDense(
            features=self.num_experts, use_bias=False, dtype=jnp.float32,
            param_dtype=jnp.float32, name="wg")(x.astype(jnp.float32))
        factor = (self.capacity_factor if not deterministic
                  else self.eval_capacity_factor)
        if self.k == 1:
            return top1gating(logits, factor, self.min_capacity,
                              self.noisy_gate_policy if not deterministic else None,
                              self.drop_tokens, self.use_rts, rng)
        if self.k == 2:
            return top2gating(logits, factor, self.min_capacity, rng)
        raise ValueError(
            f"TopKGate routes k=1 or k=2 under a capacity (got k={self.k}); "
            "more experts a token take the dropless path: "
            "moe.layer.DroplessMoE (topk_routing + dropless_experts), which "
            "has no capacity and drops no token")


# -- the dropless path ------------------------------------------------------
# No capacity, no [T, E, C] one-hot: a token's output depends on its own
# row and the weights of its k experts, whatever else shares its batch —
# what a server needs, where a capacity gate would let a neighbour's
# routing push a token over an expert's limit.

def topk_routing(logits, k: int, renormalize: bool = False, *,
                 score: str = "softmax", bias=None, scale: float = 1.0,
                 norm_eps: float = 1e-6):
    """Every expert's score in float32, then the k largest.

    logits: [T, E]. Returns (scores [T, E] f32, weights [T, k] f32,
    experts [T, k] int32). ``score="softmax"`` (OLMoE, Mixtral): a
    softmax over ALL experts; ``renormalize`` divides the k weights by
    their sum (a ``config.json``'s ``norm_topk_prob``); off, they are
    used as the softmax gave them.

    ``score="sigmoid"`` (the DeepSeek-V3 line's router, as LFM2's MoE
    publishes it): each expert's score is its own sigmoid. ``bias``
    ``[E]`` (the load-balancing ``expert_bias``) is added to the scores
    to CHOOSE the k experts and never weighs them: the weights are the
    chosen experts' unbiased scores, divided under ``renormalize`` by
    their sum plus ``norm_eps`` (LFM2 publishes ``1e-6``, DeepSeek-V3
    ``1e-20``), then multiplied by ``scale`` (a ``config.json``'s
    ``routed_scaling_factor``; at 1 no multiply is traced)."""
    if score == "softmax":
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        weights, experts = jax.lax.top_k(probs, k)
        if renormalize:
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        if scale != 1.0:
            weights = weights * scale
        return probs, weights, experts.astype(jnp.int32)
    if score != "sigmoid":
        raise ValueError(f"unknown router score {score!r}")
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, experts = jax.lax.top_k(
        scores if bias is None else scores + bias.astype(jnp.float32), k)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if renormalize:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + norm_eps)
    if scale != 1.0:
        weights = weights * scale
    return scores, weights, experts.astype(jnp.int32)


def mean_gate(probs, live=None):
    """The mean gate probability of each expert over the tokens (the
    live ones): ``[T, E] -> [E]``."""
    if live is None:
        return jnp.mean(probs, axis=0)
    n = jnp.maximum(jnp.sum(live.astype(jnp.float32)), 1.0)
    return jnp.sum(jnp.where(live[:, None], probs, 0.0), axis=0) / n


def load_balancing_loss(gate_mean, counts, k: int):
    """The published auxiliary loss (Switch; HF ``load_balancing_loss_func``,
    which OLMoE's ``router_aux_loss_coef`` multiplies): over all tokens
    of all layers together, the mean gate probability of an expert times
    the share of tokens that chose it, summed over experts, times E.

    ``gate_mean`` ``[E]`` (``mean_gate``) and ``counts`` ``[E]`` (the
    assignments ``dropless_experts`` counted) of one layer, or ``[L, E]``
    of a model's layers, each layer having routed the same tokens."""
    n_experts = gate_mean.shape[-1]
    gate_mean = gate_mean.reshape(-1, n_experts).mean(0)
    counts = counts.reshape(-1, n_experts).sum(0).astype(jnp.float32)
    share = k * counts / jnp.maximum(jnp.sum(counts), 1.0)
    return jnp.sum(gate_mean * share) * n_experts


# Rows a ``jax.lax.ragged_dot`` call takes, where the grouped matmul is
# that (``_ragged_matmul``: off the TPU, under differentiation, over more
# than one device, for operands the Pallas kernel refuses). XLA's kernel
# for ``ragged_dot`` makes its row tile as tall as the call has rows (up
# to 512) and multiplies a whole tile for every group that has a row in
# it: at 256 rows over 64 experts that is 63 tiles of 256 rows for 256
# rows of work, and the MXU, not the weights' stream, sets the time.
# Shorter calls waste less and launch more: on a v5e at OLMoE's widths one
# matmul of 256 rows took 0.64 ms whole, 0.49 in calls of 128 rows, 0.52
# of 64, 0.60 of 32 (the weights' stream alone: 0.32; my chip run, PR 28:
# PERF.md section 6). No more than MAX_CALLS calls a matmul, whatever the
# rows: a program's size, and the time to trace it, grow with the calls
# (the page pool's shape-only init traces the model over every token the
# pool holds).
ROW_TILE = 128
MAX_CALLS = 8


def _ragged_matmul(rows, w, groups, **kw):
    """``jax.lax.ragged_dot`` over ``ROW_TILE`` rows at a time (more
    where that would take over ``MAX_CALLS`` calls), each call with the
    sizes of the groups' parts that lie in its rows."""
    m = rows.shape[0]
    tile = max(ROW_TILE, -(-m // MAX_CALLS))
    if m <= tile:
        return jax.lax.ragged_dot(rows, w, groups, **kw)
    ends = jnp.cumsum(groups)
    starts = ends - groups
    parts = []
    for lo in range(0, m, tile):
        hi = min(lo + tile, m)
        inside = jnp.clip(jnp.minimum(ends, hi) - jnp.maximum(starts, lo),
                          0, None)
        parts.append(jax.lax.ragged_dot(rows[lo:hi], w, inside, **kw))
    return jnp.concatenate(parts)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _kernel_matmul(rows, w, groups, out_dtype):
    """The Pallas kernel forward; its gradient is ``_ragged_matmul``'s
    (``ragged_dot``'s own rule: no cell trains through the kernel)."""
    return pallas_grouped.grouped_matmul(rows, w, groups, out_dtype)


def _kernel_matmul_fwd(rows, w, groups, out_dtype):
    return _kernel_matmul(rows, w, groups, out_dtype), (rows, w, groups)


def _kernel_matmul_bwd(out_dtype, saved, ct):
    rows, w, groups = saved
    _, pull = jax.vjp(lambda r, x: _ragged_matmul(
        r, x, groups, preferred_element_type=out_dtype), rows, w)
    return (*pull(ct), None)


_kernel_matmul.defvjp(_kernel_matmul_fwd, _kernel_matmul_bwd)


def _kernel_refusal(rows, w, preferred_element_type):
    """Why this call goes to ``jax.lax.ragged_dot`` and not to the Pallas
    kernel, or None: the platform and the devices first, then the
    kernel's own word on the shapes."""
    if not on_tpu():
        return "not on a TPU"
    mesh = peek_global_mesh()
    if mesh is not None and mesh.size > 1 and not in_manual_region():
        return (f"a program over {mesh.size} devices: a Mosaic call is "
                "not partitioned")
    return pallas_grouped.refusal(rows, w, preferred_element_type)


def grouped_matmul(rows, w, groups, **kw):
    """``rows [m, k]``, sorted by group, times ``w [G, k, n]``: row i by
    its group's matrix; ``groups [G]`` are the groups' sizes, rows past
    their sum are in none. On one TPU a Pallas call a matmul
    (``ops/pallas/grouped_matmul.py``: the weights of the groups that
    hold rows in one pipelined walk), elsewhere ``jax.lax.ragged_dot``
    (``_ragged_matmul``); ``moe/grouped_matmul_traced/kernel`` and
    ``.../xla`` count which, per compile."""
    if exact_weights(rows, w):
        # float32 rows over weights kept in bfloat16 (models/layers.py
        # dot_exact_weights): each row goes as its three bfloat16 terms,
        # side by side in its group, and the three products are summed —
        # the stack of weights is never cast, and is read once
        m, n = rows.shape[0], 3
        terms = split_terms(rows, n).transpose(1, 0, 2).reshape(m * n, -1)
        # (bfloat16 terms have no lower passes to make: a caller's
        # default of HIGHEST would only be refused by the kernel)
        out = grouped_matmul(terms, w, groups * n,
                             preferred_element_type=jnp.float32,
                             precision=jax.lax.Precision.DEFAULT)
        return jnp.sum(out.reshape(m, n, -1), axis=1)
    out_dtype = kw.get("preferred_element_type")
    reason = _kernel_refusal(rows, w, out_dtype)
    get_registry().counter("moe/grouped_matmul_traced/"
                           + ("xla" if reason else "kernel")).inc()
    if reason:
        pallas_grouped.record_fallback(rows, w, reason)
        return _ragged_matmul(rows, w, groups, **kw)
    return _kernel_matmul(rows, w, groups, out_dtype)


def dropless_experts(tokens, weights, experts, w_gate, w_up, w_down, layer,
                     live=None):
    """Every token through each of its k experts, no token dropped:
    sort the T*k assignments by expert, one grouped matmul for the gate
    and one for the up projection, SiLU(gate) * up, one for the down
    projection, and the weighted sum back in token order.

    tokens [T, d]; weights, experts [T, k]; ``live`` [T] bool or None.
    A row that is not live (an idle slot, a chunk's padding) joins no
    group and adds nothing to a count. Returns (out [T, d] float32 — the
    down projection and the weighted sum accumulate in it —, counts [E]
    int32).

    ``w_gate``, ``w_up`` ``[L, E, d, f]`` and ``w_down`` ``[L, E, f, d]``
    hold the experts of every layer of the model (L = 1 for a lone
    layer) and ``layer`` is this call's index: the stacks go to the
    grouped matmul whole, as ``L*E`` groups of which only this layer's
    have rows. A layer's slice of them — what a scan over the layers
    would hand its body — would be a copy: the matmul is a custom call,
    whose operand is a buffer: 805 MB written and read again per layer at
    OLMoE's widths, as much as the matmuls themselves move.

    The grouped matmul (``grouped_matmul``) is on one TPU a Pallas call
    a matmul (``ops/pallas/grouped_matmul.py``, ``%ragged-dot-grouped``
    in a trace) and elsewhere ``jax.lax.ragged_dot``, which XLA lowers
    on a TPU to a Mosaic kernel of its own (``%ragged-dot-none``); either
    walks the groups that have rows and reads no other's weights."""
    n_tokens, k = experts.shape
    n_experts = w_gate.shape[-3]
    flat = experts.reshape(-1)
    if live is not None:
        # expert E does not exist: it sorts last and is in no group
        flat = jnp.where(jnp.repeat(live, k), flat, n_experts)
    order = jnp.argsort(flat)                       # stable: by expert
    counts = jnp.bincount(flat, length=n_experts + 1)[:n_experts].astype(
        jnp.int32)
    n_layers = w_gate.shape[0]
    groups = jax.lax.dynamic_update_slice(
        jnp.zeros((n_layers * n_experts,), jnp.int32), counts,
        (layer * n_experts,))
    w_gate, w_up, w_down = (w.reshape((-1,) + w.shape[2:])
                            for w in (w_gate, w_up, w_down))
    rows = jnp.take(tokens, order // k, axis=0)     # [T*k, d]
    # (exact: float32 rows take bfloat16 weights as they are, grouped_matmul)
    cast = (lambda w: w) if exact_weights(rows, w_gate) \
        else (lambda w: w.astype(rows.dtype))
    g = grouped_matmul(rows, cast(w_gate), groups)
    u = grouped_matmul(rows, cast(w_up), groups)
    h = jax.nn.silu(g) * u
    y = grouped_matmul(h, cast(w_down), groups,
                       preferred_element_type=jnp.float32)
    # rows past the last group belong to no expert
    in_group = jnp.arange(n_tokens * k) < jnp.sum(counts)
    y = jnp.where(in_group[:, None], y, 0.0)
    # back in token order: assignment i sits at rank[i] of the sort
    rank = jnp.argsort(order)
    y = jnp.take(y, rank, axis=0).reshape(n_tokens, k, -1)
    return jnp.sum(y * weights[:, :, None], axis=1), counts


class MOELayer(nn.Module):
    """Gate -> dispatch -> experts -> combine (reference MOELayer :432).

    ``expert_factory(name)`` builds one expert module; experts are stacked
    with nn.vmap and their params carry the "experts" logical axis, which
    the sharding rules map onto the "expert" mesh axis. The dispatch/combine
    einsums carry sharding constraints so GSPMD emits the all-to-all."""
    d_model: int
    num_experts: int
    expert_factory: any
    k: int = 1
    capacity_factor: float = 1.0
    eval_capacity_factor: float = 1.0
    min_capacity: int = 4
    noisy_gate_policy: Optional[str] = None
    drop_tokens: bool = True
    use_rts: bool = True

    @nn.compact
    def __call__(self, x, deterministic=True):
        b, s, d = x.shape
        tokens = x.reshape(b * s, d)

        gate = TopKGate(d_model=self.d_model, num_experts=self.num_experts,
                        k=self.k, capacity_factor=self.capacity_factor,
                        eval_capacity_factor=self.eval_capacity_factor,
                        min_capacity=self.min_capacity,
                        noisy_gate_policy=self.noisy_gate_policy,
                        drop_tokens=self.drop_tokens, use_rts=self.use_rts,
                        name="gate")
        l_aux, combine, dispatch, exp_counts = gate(tokens, deterministic)

        # dispatch: [T,E,C] x [T,d] -> [E,C,d]; the constraint shards E over
        # the expert axis => GSPMD all-to-all (reference _AllToAll :87)
        expert_in = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), tokens)
        expert_in = _expert_constraint(expert_in, ("expert", None, None))

        experts = nn.vmap(
            lambda m, xi: m(xi),
            variable_axes={"params": 0},
            split_rngs={"params": True},
            in_axes=0, out_axes=0,
            metadata_params={nn.PARTITION_NAME: "experts"},
        )(self.expert_factory(name="experts"), expert_in)
        experts = _expert_constraint(experts, ("expert", None, None))

        out = jnp.einsum("tec,ecd->td", combine.astype(x.dtype), experts)
        return out.reshape(b, s, d), l_aux, exp_counts
