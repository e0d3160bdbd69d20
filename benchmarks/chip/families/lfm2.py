"""The LFM2-MoE family (Liquid AI; HF ``modeling_lfm2_moe.py``;
``LiquidAI/LFM2-24B-A2B``): the ``LFM2`` module of
``deepspeed_tpu.models.lfm2`` at a configuration file's sizes under the
keys of its ``config.json``, its training loss, its operation counts, the
kernels it must run, and its plain reference.

The reference is the published forward pass in straightforward
``jax.numpy`` and float32 over the parameter tree the program keeps
(``wte``, ``layers_<i>/*``, ``experts/*`` stacked over the expert layers,
``ln_f``; the head is ``wte`` transposed). It shares no code with
``deepspeed_tpu``: no sort, no grouped matmul, no kernel, no cache, no
chunking. With ``n = RMSNorm_op(x)``, per layer, as published:

    conv layer       [B, C, u] = W_in n (three parts of the width);
                     z_t = sum_{j=0..K-1} w_j * (B*u)_{t-(K-1)+j}, written
                     out as the sum of K shifted products (zeros before
                     the first token), no bias, no activation;
                     h = x + W_out (C * z)
    attention layer  q = RMSNorm_q(W_q n), k = RMSNorm_k(W_k n), each
                     norm over ONE head's values after the heads split;
                     v = W_v n; K and V heads repeated so that query head
                     i reads K/V head i // group; RoPE the half-split
                     rotation over the full head; causal softmax scaled by
                     head^-0.5; h = x + W_o(.); no bias anywhere
    feed-forward     m = RMSNorm_ffn(h). The first ``num_dense_layers``:
                     y = h + W_2(silu(W_1 m) * W_3 m). After them:
                     s = sigmoid(W_r m) over all experts, chosen =
                     top_k(s + b) with b the expert bias (to choose, never
                     to weigh), w_e = s_e / (sum_chosen s + 1e-6) * scale,
                     y = h + sum_chosen w_e W_2,e(silu(W_1,e m) * W_3,e m):
                     every expert is computed for every token, one expert
                     at a time, and the chosen are summed

then a final RMSNorm and the tied head. Departures from the published
model, all of layout only: the program keeps ``W_q | W_k | W_v`` as one
``qkv`` kernel (split here at ``H*hd`` and ``(H + H_kv)*hd``), the
convolution's taps as ``w [K, d]`` (the published ``conv.weight [d, 1,
K]`` transposed), and an expert's three matrices stacked over experts
and expert layers.

A float32 copy of the 10-layer tree at published widths is 21 GB: the
loop below casts one layer at a time, and inside an expert layer one
expert at a time (38 MB).

One thing ``reference_logits`` does beyond the equations, for the
comparison that decides ``correct``: the rows of positions where one of
its own routers chose on a near-tie, and of the few after them that the
convolutions carry the other choice into, come back as zeros — not
judged (``NEAR_TIE`` and ``CARRIED_TIE`` below have why and how many,
``unjudged`` is the rule). ``near_ties="kept"`` gives every row as
computed, ``"gaps"`` also each position's closest router choice."""

from . import MOSAIC_KERNEL
from .. import reference

# the only keys a configuration of this family may name in `reduced`:
# the number of layers, and the list that says which kind each is
DEPTH_KEYS = ("num_hidden_layers", "layer_types")
SIZE_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
             "num_hidden_layers", "layer_types", "num_attention_heads",
             "num_key_value_heads", "conv_L_cache", "num_dense_layers",
             "num_experts", "num_experts_per_tok",
             "max_position_embeddings", "vocab_size")


def sizes(config, rehearse):
    """The configuration's published sizes, or its ``rehearse`` sizes: a
    tiny stand-in that only ever runs on the CPU, under ``--rehearse``."""
    src = config["rehearse"] if rehearse else config
    return {k: src[k] for k in SIZE_KEYS}


def expert_sizes(sizes):
    """What a reader of the expert layer's matmuls needs (``readers/
    lfm2.py``): ONE expert's width and the number of expert layers —
    not ``intermediate_size`` (the dense layers') and not
    ``num_hidden_layers``."""
    return {"hidden_size": sizes["hidden_size"],
            "intermediate_size": sizes["moe_intermediate_size"],
            "num_hidden_layers": (sizes["num_hidden_layers"]
                                  - sizes["num_dense_layers"])}


def build(config, rehearse, **overrides):
    """``LFM2`` at the configuration's sizes; what is not a size comes
    from the file's own keys of ``config.json``."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.lfm2 import LFM2, LFM2Config
    # a rehearsal computes in float32, as OLMoE's does and for its
    # reason: at 8 experts of width 32 one expert is a large share of a
    # layer's output, and a bf16 near-tie in the router moves a served
    # token's logit by whole sigmas, which at published widths it does not
    dtypes = {"compute_dtype": "float32", "param_dtype": "float32"} \
        if rehearse else config
    kw = dict(sizes(config, rehearse),
              conv_bias=config["conv_bias"],
              use_expert_bias=config["use_expert_bias"],
              norm_topk_prob=config["norm_topk_prob"],
              routed_scaling_factor=float(config["routed_scaling_factor"]),
              norm_eps=config["norm_eps"],
              rope_theta=float(config["rope_parameters"]["rope_theta"]),
              dtype=getattr(jnp, dtypes["compute_dtype"]),
              param_dtype=getattr(jnp, dtypes["param_dtype"]))
    kw["layer_types"] = tuple(kw["layer_types"])
    kw.update(overrides)
    return LFM2(LFM2Config(**kw))


def train_loss(config):
    """Next-token cross entropy (the published configuration has no
    auxiliary loss): a batch row holds ``seq + 1`` tokens."""
    from deepspeed_tpu.models.lfm2 import lfm2_loss_fn
    return lfm2_loss_fn


def ops_per_token(sizes, seq):
    """Forward + backward operations per trained token: 6 per weight a
    token multiplies — a conv layer's in and out projections (``4 d^2``)
    and its taps, an attention layer's four projections at its K/V
    width, a dense layer's three matrices, an expert layer's router and
    **k of the E experts** (three matrices each), and the tied head —
    plus the attention layers' two causal ``S x S`` products
    (``families/gpt2.py``)."""
    d = sizes["hidden_size"]
    head = d // sizes["num_attention_heads"]
    kinds = list(sizes["layer_types"])
    n_attn = kinds.count("full_attention")
    n_conv = len(kinds) - n_attn
    n_dense = min(sizes["num_dense_layers"], len(kinds))
    n_moe = len(kinds) - n_dense
    conv = 4 * d * d + sizes["conv_L_cache"] * d
    attn = 2 * d * d + 2 * d * sizes["num_key_value_heads"] * head
    dense = 3 * d * sizes["intermediate_size"]
    moe = (d * sizes["num_experts"] + sizes["num_experts_per_tok"] * 3 * d
           * sizes["moe_intermediate_size"])
    weights = (n_conv * conv + n_attn * attn + n_dense * dense
               + n_moe * moe + sizes["vocab_size"] * d)
    attention = n_attn * 3 * (2 * 2 * seq * d) // 2
    return 6 * weights + attention


def expected_kernels(serving):
    """Serving: the paged decode path and the paged-attention kernel at
    the server's page length — the kernel whose grid walks the pool's
    K/V heads and computes each one's group of query heads (this family
    adds no kernel of its own: the short convolution is three shifted
    products that XLA fuses, and the grouped expert matmul is
    ``jax.lax.ragged_dot``, which leaves no ``tuning.last_dispatch``
    record and which the device trace names)."""
    if serving is None:
        return []
    return [("paged_decode", "path", MOSAIC_KERNEL),
            ("paged_attention", f"page{serving['paging']['page_len']}",
             MOSAIC_KERNEL)]


# -- the plain reference ----------------------------------------------------

def _rms(x, scale, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """``[B, S, H, D]``: the half-split rotation over the full head."""
    import jax.numpy as jnp
    s, d = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[None, :, None]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[None, :, None]
    x1, x2 = jnp.split(x, 2, -1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _conv(n, p):
    """The gated short convolution: the sum of K shifted products."""
    import jax.numpy as jnp
    b, c, u = jnp.split(n @ p["in_proj"]["kernel"], 3, axis=-1)
    bu = b * u
    taps, s = p["w"].shape[0], n.shape[1]
    padded = jnp.pad(bu, ((0, 0), (taps - 1, 0), (0, 0)))
    z = sum(p["w"][j] * padded[:, j:j + s] for j in range(taps))
    return (c * z) @ p["out_proj"]["kernel"]


def _attention(n, p, n_head, n_kv, eps, theta):
    import jax
    import jax.numpy as jnp
    b, s, d = n.shape
    hd = d // n_head
    qkv = n @ p["qkv"]["kernel"]
    q = qkv[..., :d].reshape(b, s, n_head, hd)
    k = qkv[..., d:d + n_kv * hd].reshape(b, s, n_kv, hd)
    v = qkv[..., d + n_kv * hd:].reshape(b, s, n_kv, hd)
    q = _rope(_rms(q, p["q_norm"]["scale"], eps), theta)
    k = _rope(_rms(k, p["k_norm"]["scale"], eps), theta)
    # query head i reads K/V head i // (n_head // n_kv)
    k = jnp.repeat(k, n_head // n_kv, axis=2)
    v = jnp.repeat(v, n_head // n_kv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    return a.reshape(b, s, d) @ p["out"]["kernel"]


def _scores(m, p, config):
    """The sigmoid scores, and the scores the experts are chosen by."""
    import jax
    scores = jax.nn.sigmoid(m @ p["router"])
    return scores, (scores + p["expert_bias"] if config["use_expert_bias"]
                    else scores)


def _route(m, p, top_k, config):
    """Each token's weight for every expert, ``[B, S, E]``: zero for the
    experts not chosen."""
    import jax
    import jax.numpy as jnp
    scores, biased = _scores(m, p, config)
    _, chosen = jax.lax.top_k(biased, top_k)
    weight = jnp.take_along_axis(scores, chosen, axis=-1)
    if config["norm_topk_prob"]:
        weight = weight / (jnp.sum(weight, -1, keepdims=True) + 1e-6)
    weight = weight * config["routed_scaling_factor"]
    every = jnp.sum(jax.nn.one_hot(chosen, scores.shape[-1])
                    * weight[..., None], axis=-2)
    return every


def _choice_gap(m, p, top_k, config):
    """``[B, S]``: how far apart the last expert chosen and the first
    one left out lie in the score they are chosen by."""
    import jax
    best, _ = jax.lax.top_k(_scores(m, p, config)[1], top_k + 1)
    return best[..., top_k - 1] - best[..., top_k]


def _near_tie(m, p, top_k, config):
    """``[B, S]``: whether a token's choice was a near-tie — that gap
    under ``NEAR_TIE``."""
    return _choice_gap(m, p, top_k, config) < NEAR_TIE


def _experts(m, weight, stacks, at, lower):
    """Every expert of expert layer ``at`` on every token, one expert at
    a time, weighted by ``weight [B, S, E]`` and summed. An expert's
    matrices are cut from the stacks one at a time: a layer's slice of
    them would be a copy of 1.2 GB at published widths."""
    import jax
    import jax.numpy as jnp

    def one(total, e):
        w = lower({k: jnp.asarray(v[at, e], jnp.float32)
                   for k, v in stacks.items()})
        y = (jax.nn.silu(m @ w["w_gate"]) * (m @ w["w_up"])) @ w["w_down"]
        return total + y * weight[..., e, None], None

    n_experts = stacks["w_gate"].shape[1]
    return jax.lax.scan(one, jnp.zeros_like(m), jnp.arange(n_experts))[0]


# What a run of the cell cannot judge (PERF.md section 6, PR 33). The
# router's weights are normalised over the four chosen, so where the
# fourth and the fifth of 64 bias-corrected scores are a near-tie the
# model's output hangs on a difference below float32's rounding: the
# program and this reference sum in different orders, their scores differ
# by ~4e-7, and the other choice swaps a quarter of the layer's output
# (the served token then reads 0.2-0.3 sigma under this reference's best:
# 2 of 62 full runs on the chip, each with 2 of 700-900 tokens off and
# every other one the argmax). Such a position's row says nothing of the
# program, nor do the rows the convolutions carry it into, so
# ``reference_logits`` hands them back as zeros, which the comparison
# reads as a gap of 0: not judged. NEAR_TIE is ~25 times the difference
# between program and reference; the 4th and 5th scores lie ~0.03 apart,
# so about 3 choices in 10,000 are near-ties and ~1.3% of positions are
# not judged. A wrong path is wrong at every other position too.
NEAR_TIE = 1e-5
# What a near-tie upstream does to the choices after it (PR 54; PERF.md
# section 2 and ``families/deepseek_v3.py CARRIED_TIE`` have the
# mechanism and every reading). Program and reference settle some of a
# prompt's near-ties differently, every later position attends each such
# position at one part in ~2,300, and a choice that hangs on more than
# NEAR_TIE falls: one served row in ~8,300 read 0.30 sigma where the
# fourth and fifth scores lay 2.8e-5 apart (seed 2154700305, request 1,
# row 39; its neighbours read 6e-4), Kanana's one failing run fell at
# 1.28e-5. Once a near-tie lies upstream, a choice under CARRIED_TIE is
# unjudged too, with the positions the convolutions carry it into: 3.5
# times the larger gap seen to fall; 18% of positions with the four after
# each (1.5-2.7% at NEAR_TIE alone, 43-45% at 3e-4); and the control,
# bf16 activations, keeps 49-55 of its 75-76 tokens of 640 over the limit
# (``tools/near_tie_probe.py``, my chip runs, PR 54). With no near-tie
# upstream such a choice stays judged.
CARRIED_TIE = 1e-4


def _reach(sizes):
    """How many positions after its own a position's choice reaches
    through the convolutions at full size: a layer's ``conv_L_cache - 1``
    next positions, and theirs in a later layer once more (further on it
    is a perturbation of a perturbation)."""
    return 2 * (sizes["conv_L_cache"] - 1)


def unjudged(gap, sizes):
    """``gap [B, S]`` (each position's closest router choice) -> bool:
    the positions no comparison can judge. The near-ties (``NEAR_TIE``);
    once a near-tie lies upstream, the choices under ``CARRIED_TIE``; and
    the ``_reach`` positions after each of either."""
    import jax.numpy as jnp
    near = gap < NEAR_TIE
    upstream = jnp.cumsum(near, axis=1) - near > 0
    near = near | (upstream & (gap < CARRIED_TIE))
    s, reach = gap.shape[1], _reach(sizes)
    carried = jnp.pad(near, ((0, 0), (reach, 0)))
    return sum(carried[:, j:j + s] for j in range(reach + 1)) > 0


def reference_logits(params, ids, sizes, config, lower=None,
                     near_ties="unjudged"):
    """``[B, S] -> [B, S, V]`` float32 logits. ``params`` is the
    program's tree in any dtype. ``lower`` (a control's) takes each
    float32 tree of weights — one layer's, one expert's, the embedding —
    and gives the tree to compute with. ``near_ties``: ``"unjudged"``
    zeroes the rows no comparison can judge (above), ``"kept"`` leaves
    every row as computed (the tools and tests that compare rows, the
    losses), ``"gaps"`` does too and returns ``(logits, gap [B, S])``:
    the closest choice any of a position's routers made, a near-tie
    where it is under ``NEAR_TIE``."""
    import jax
    import jax.numpy as jnp
    lower = lower or (lambda tree: tree)
    f32 = lambda tree: lower(jax.tree.map(
        lambda a: jnp.asarray(a, jnp.float32), tree))
    n_head, n_kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    eps = config["norm_eps"]
    theta = float(config["rope_parameters"]["rope_theta"])
    wte = f32({"wte": params["wte"]})["wte"]
    x = wte[ids]
    gap = jnp.full(ids.shape, jnp.inf)      # the closest choice of any layer
    for i, kind in enumerate(sizes["layer_types"]):
        p = f32(params[f"layers_{i}"])          # one layer at a time
        n = _rms(x, p["operator_norm"]["scale"], eps)
        if kind == "conv":
            h = x + _conv(n, p["conv"])
        else:
            h = x + _attention(n, p["attn"], n_head, n_kv, eps, theta)
        m = _rms(h, p["ffn_norm"]["scale"], eps)
        if i < sizes["num_dense_layers"]:
            w = p["mlp"]
            x = h + (jax.nn.silu(m @ w["w1"]["kernel"])
                     * (m @ w["w3"]["kernel"])) @ w["w2"]["kernel"]
        else:
            at = i - sizes["num_dense_layers"]
            top_k = sizes["num_experts_per_tok"]
            weight = _route(m, p["moe"], top_k, config)
            gap = jnp.minimum(gap, _choice_gap(m, p["moe"], top_k, config))
            x = h + _experts(m, weight, params["experts"], at, lower)
    x = _rms(x, jnp.asarray(params["ln_f"]["scale"], jnp.float32), eps)
    logits = x @ wte.T
    if near_ties == "kept":
        return logits
    if near_ties == "gaps":
        return logits, gap
    return jnp.where(unjudged(gap, sizes)[..., None], 0.0, logits)


def reference_next_token_losses(params, ids, sizes, config):
    """Per-position next-token cross entropy ``[B, S-1]`` of rows of
    ``S`` tokens: position ``i`` predicts token ``i + 1``."""
    return reference.next_token_losses(
        reference_logits(params, ids[:, :-1], sizes, config,
                         near_ties="kept"), ids[:, 1:])


def reference_loss(params, ids, sizes, config):
    """The training loss of rows of ``S`` tokens: the mean next-token
    cross entropy."""
    import jax.numpy as jnp
    return jnp.mean(reference_next_token_losses(params, ids, sizes, config))
