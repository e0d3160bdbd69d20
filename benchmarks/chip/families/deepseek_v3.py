"""The DeepSeek-V3 family (HF ``modeling_deepseek_v3.py``, ``model_type:
deepseek_v3``; run at the widths of
``kakaocorp/kanana-2-30b-a3b-instruct-2601``): the ``DeepseekV3`` module
of ``deepspeed_tpu.models.deepseek_v3`` at a configuration file's sizes
under the keys of its ``config.json``, the kernels it must run, the
latent kernel's operations and bytes, and its plain reference.

The reference is the published forward pass in straightforward
``jax.numpy`` and float32 over the parameter tree the program keeps
(``wte``, ``layers_<i>/*``, ``experts/*`` stacked over the expert layers,
``ln_f``, ``lm_head``). It shares no code with ``deepspeed_tpu``: no
latent cache, no absorbed form, no sort, no grouped matmul, no kernel, no
chunking. With ``n = RMSNorm_in(x)``, per layer, as published
(``q_lora_rank`` null, ``rope_scaling`` null):

    attention     q = W_q n -> [H, nope + rope] = q_nope | q_pe;
                  W_kva n -> c_raw [rank] | k_pe_raw [rope];
                  c = RMSNorm(c_raw); [k_nope | v] = W_kvb c -> [H, nope +
                  v]; q_pe and k_pe (ONE vector for all heads) have their
                  lanes de-interleaved (x0 x1 x2 .. -> x0 x2 .. x1 x3 ..)
                  and are then rotated by halves (``rope_interleave``);
                  score = (q_nope . k_nope + q_pe . k_pe) / sqrt(nope +
                  rope), causal softmax, o = sum p v, h = x + W_o o: keys
                  and values are EXPANDED for every position, which the
                  program never does for a cached one
    feed-forward  m = RMSNorm_post(h). The first ``first_k_dense_replace``
                  layers: y = h + W_2(silu(W_1 m) * W_3 m). After them:
                  s = sigmoid(W_g m) over all experts, chosen = top_k(s +
                  e_score_correction_bias), w = s[chosen] / (sum + 1e-20)
                  * routed_scaling_factor, y = h + sum_chosen w_e
                  SwiGLU_e(m) + SwiGLU_shared(m), the shared MLP
                  ``n_shared_experts`` experts wide and unweighted: every
                  routed expert is computed for every token, one expert at
                  a time, and the chosen are summed

then a final RMSNorm and the untied head. Departures from the published
model, of layout only: an expert's three matrices stacked over experts and
expert layers, ``e_score_correction_bias`` kept as ``moe/expert_bias``.

At the cell's 9,216 positions a head's scores would be 10.9 GB: attention
goes a block of ``QUERY_BLOCK`` queries at a time against all keys (the
same sums, a block's rows at once), an expert layer one expert at a time,
and a layer's weights are cast one layer at a time.

One thing ``reference_logits`` does beyond the equations, for the
comparison that decides ``correct``: the rows of positions where one of
its own routers chose on a near-tie come back as zeros — not judged
(``NEAR_TIE`` and ``CARRIED_TIE`` below; ``unjudged`` is the rule).
``near_ties="kept"`` gives every row as computed, ``"gaps"`` also each
position's closest router choice."""

from . import MOSAIC_KERNEL
from .. import reference

# the only key a configuration of this family may name in `reduced`
DEPTH_KEYS = ("num_hidden_layers",)
SIZE_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
             "num_hidden_layers", "num_attention_heads", "kv_lora_rank",
             "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
             "first_k_dense_replace", "n_routed_experts",
             "n_shared_experts", "num_experts_per_tok",
             "max_position_embeddings", "vocab_size")
QUERY_BLOCK = 256


def sizes(config, rehearse):
    """The configuration's published sizes, or its ``rehearse`` sizes: a
    tiny stand-in that only ever runs on the CPU, under ``--rehearse``."""
    src = config["rehearse"] if rehearse else config
    return {k: src[k] for k in SIZE_KEYS}


def expert_sizes(sizes):
    """What a reader of the routed experts' matmuls needs (``readers/
    latent.py``): ONE routed expert's width and the number of expert
    layers — not ``intermediate_size`` (the dense layer's) and not
    ``num_hidden_layers``."""
    return {"hidden_size": sizes["hidden_size"],
            "intermediate_size": sizes["moe_intermediate_size"],
            "num_hidden_layers": (sizes["num_hidden_layers"]
                                  - sizes["first_k_dense_replace"])}


def latent_width(sizes):
    """What a latent pool keeps a token and layer: the compressed vector
    and the rotated shared key part."""
    return sizes["kv_lora_rank"] + sizes["qk_rope_head_dim"]


def latent_attn_ops(sizes, tokens):
    """Operations of the latent decode kernel over ``tokens`` pooled
    tokens walked (summed over rows and layer calls): every head's score
    over the latent's full width and its values over the compressed
    part; a multiply-add counts as 2."""
    return 2 * sizes["num_attention_heads"] * tokens * (
        latent_width(sizes) + sizes["kv_lora_rank"])


def latent_attn_bytes(sizes, tokens, itemsize):
    """The least the kernel must move: each walked token's latent vector
    once (keys and values are one buffer; queries and outputs are a
    thousandth of it at the cell's lengths and not counted)."""
    return tokens * latent_width(sizes) * itemsize


def build(config, rehearse, **overrides):
    """``DeepseekV3`` at the configuration's sizes; what is not a size
    comes from the file's own keys of ``config.json``."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.deepseek_v3 import DeepseekV3, DeepseekV3Config
    # a rehearsal computes in float32, as OLMoE's and LFM2's do and for
    # their reason: at 8 experts of width 32 one expert is a large share
    # of a layer's output and a bf16 near-tie moves a token by whole sigmas
    dtypes = {"compute_dtype": "float32", "param_dtype": "float32"} \
        if rehearse else config
    kw = dict(sizes(config, rehearse),
              q_lora_rank=config["q_lora_rank"],
              n_group=config["n_group"], topk_group=config["topk_group"],
              norm_topk_prob=config["norm_topk_prob"],
              routed_scaling_factor=float(config["routed_scaling_factor"]),
              scoring_func=config["scoring_func"],
              rms_norm_eps=config["rms_norm_eps"],
              rope_theta=float(config["rope_theta"]),
              rope_scaling=config["rope_scaling"],
              rope_interleave=config["rope_interleave"],
              dtype=getattr(jnp, dtypes["compute_dtype"]),
              param_dtype=getattr(jnp, dtypes["param_dtype"]))
    kw.update(overrides)
    return DeepseekV3(DeepseekV3Config(**kw))


def train_loss(config):
    from deepspeed_tpu.models.deepseek_v3 import deepseek_v3_loss_fn
    return deepseek_v3_loss_fn


def expected_kernels(serving):
    """Serving: the paged decode path and the latent-attention kernel at
    the server's page length (the prefill chunk's attention is plain
    einsums over the slot's gathered latent row, and the grouped expert
    matmul is ``jax.lax.ragged_dot``: neither leaves a
    ``tuning.last_dispatch`` record; the device trace names both)."""
    if serving is None:
        return []
    return [("paged_decode", "path", MOSAIC_KERNEL),
            ("latent_attention", f"page{serving['paging']['page_len']}",
             MOSAIC_KERNEL)]


# -- the plain reference ----------------------------------------------------

def _rms(x, scale, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta, interleave):
    """``[B, S, H, D]``: with ``interleave`` the lanes are de-interleaved
    first (``x0 x1 x2 x3 .. -> x0 x2 .. x1 x3 ..``), then the half-split
    rotation over the ``D`` lanes, as the published code does."""
    import jax.numpy as jnp
    s, d = x.shape[1], x.shape[-1]
    if interleave:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[None, :, None]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[None, :, None]
    x1, x2 = jnp.split(x, 2, -1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _attention(n, p, sizes, config):
    import jax
    import jax.numpy as jnp
    b, s, _ = n.shape
    heads, rank = sizes["num_attention_heads"], sizes["kv_lora_rank"]
    nope, rope, vd = (sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
                      sizes["v_head_dim"])
    theta, weave = float(config["rope_theta"]), config["rope_interleave"]
    q = (n @ p["q_proj"]["kernel"]).reshape(b, s, heads, nope + rope)
    kva = n @ p["kv_a_proj"]["kernel"]
    c = _rms(kva[..., :rank], p["kv_a_norm"]["scale"], config["rms_norm_eps"])
    kv = (c @ p["kv_b_proj"]).reshape(b, s, heads, nope + vd)
    k_pe = _rope(kva[..., rank:][:, :, None, :], theta, weave)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (b, s, heads, rope))], -1)
    q = jnp.concatenate(
        [q[..., :nope], _rope(q[..., nope:], theta, weave)], -1)
    v = kv[..., nope:]
    # a block of queries at a time against every key
    block = min(QUERY_BLOCK, s)
    pad = -s % block
    qb = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
        b, -1, block, heads, nope + rope).swapaxes(0, 1)
    at = jnp.arange(s + pad).reshape(-1, block)

    def one(args):
        qs, rows = args
        scores = jnp.einsum("bqhd,bkhd->bhqk", qs, k) * (nope + rope) ** -0.5
        seen = jnp.arange(s)[None, :] <= rows[:, None]
        scores = jnp.where(seen, scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)

    o = jax.lax.map(one, (qb, at)).swapaxes(0, 1).reshape(
        b, s + pad, heads * vd)[:, :s]
    return o @ p["out"]["kernel"]


def _swiglu(m, w):
    import jax
    return (jax.nn.silu(m @ w["w1"]["kernel"])
            * (m @ w["w3"]["kernel"])) @ w["w2"]["kernel"]


def _scores(m, p):
    """The sigmoid scores, and the scores the experts are chosen by."""
    import jax
    scores = jax.nn.sigmoid(m @ p["router"])
    return scores, scores + p["expert_bias"]


def _route(m, p, top_k, config):
    """Each token's weight for every routed expert, ``[B, S, E]``: zero
    for the experts not chosen."""
    import jax
    import jax.numpy as jnp
    scores, biased = _scores(m, p)
    _, chosen = jax.lax.top_k(biased, top_k)
    weight = jnp.take_along_axis(scores, chosen, axis=-1)
    if config["norm_topk_prob"]:
        weight = weight / (jnp.sum(weight, -1, keepdims=True) + 1e-20)
    weight = weight * config["routed_scaling_factor"]
    return jnp.sum(jax.nn.one_hot(chosen, scores.shape[-1])
                   * weight[..., None], axis=-2)


def _choice_gap(m, p, top_k):
    """``[B, S]``: how far apart the last expert chosen and the first
    one left out lie in the score they are chosen by; a near-tie where
    it is under ``NEAR_TIE``."""
    import jax
    best, _ = jax.lax.top_k(_scores(m, p)[1], top_k + 1)
    return best[..., top_k - 1] - best[..., top_k]


def _experts(m, weight, stacks, at, lower):
    """Every routed expert of expert layer ``at`` on every token, one
    expert at a time (its matrices cut from the stacks one at a time),
    weighted by ``weight [B, S, E]`` and summed."""
    import jax
    import jax.numpy as jnp

    def one(total, e):
        w = lower({k: jnp.asarray(v[at, e], jnp.float32)
                   for k, v in stacks.items()})
        y = (jax.nn.silu(m @ w["w_gate"]) * (m @ w["w_up"])) @ w["w_down"]
        return total + y * weight[..., e, None], None

    n_experts = stacks["w_gate"].shape[1]
    return jax.lax.scan(one, jnp.zeros_like(m), jnp.arange(n_experts))[0]


# What a run of the cell cannot judge, as ``families/lfm2.py`` has it and
# for its reason: the weights of the six chosen are normalised, so where
# the sixth and the seventh of 128 bias-corrected scores are a near-tie
# the layer's output hangs on a difference below float32's rounding
# (program and reference sum in different orders), and the other choice
# swaps about a sixth of the routed sum. Such a position's row says
# nothing of the program and comes back as zeros, which the comparison
# reads as a gap of 0. Attention carries the position's vector on to the
# later ones at a share of one in their context's length: not followed.
# A wrong path is wrong at every other position too.
NEAR_TIE = 1e-5
# What a near-tie upstream does to the choices after it (PR 54; PERF.md
# section 2 has every reading). Two computations of this arithmetic settle
# some of a sequence's near-ties differently - an 8 k document holds ~44 -
# and every later position attends each such position's vector at a share
# of one in its context's length, so its scores differ by ~1e-5 a flip
# where they differed by ~4e-7, and a choice that hangs on more than
# NEAR_TIE falls. The one failure of ``correct`` on record with a seed
# and a position (``serve-kanana-docqa`` seed 2154571472, request 12,
# 0.57 sigma) is this: the row's choice hung on 1.28e-5 after 44
# near-ties, the program and two other compilations of this reference put
# the served token first, and the cell's check, alone, the other choice's;
# served alone outside any window the request reads the same. LFM2's
# replayed rows held one such flip at 2.8e-5. So once a near-tie lies
# upstream, a choice under CARRIED_TIE is unjudged too: 3.5 times the
# larger of the two gaps seen to fall; 4.5% of positions (0.5% at NEAR_TIE
# alone, 13% at 3e-4); and the control, bf16 activations, keeps 74-93 of
# its 84-101 tokens of 512 over the limit (``tools/near_tie_probe.py``,
# my chip runs, PR 54). With no near-tie upstream nothing moves a later
# score by more than rounding, and such a choice stays judged.
CARRIED_TIE = 1e-4


def unjudged(gap, sizes):
    """``gap [B, S]`` (each position's closest router choice) -> bool:
    the positions no comparison can judge. The near-ties (``NEAR_TIE``)
    and, once a near-tie lies upstream, the choices under
    ``CARRIED_TIE``."""
    import jax.numpy as jnp
    near = gap < NEAR_TIE
    upstream = jnp.cumsum(near, axis=1) - near > 0
    return near | (upstream & (gap < CARRIED_TIE))


def reference_logits(params, ids, sizes, config, lower=None,
                     near_ties="unjudged"):
    """``[B, S] -> [B, S, V]`` float32 logits. ``params`` is the
    program's tree in any dtype. ``lower`` (a control's) takes each
    float32 tree of weights — one layer's, one expert's, the embedding,
    the head — and gives the tree to compute with. ``near_ties``:
    ``"unjudged"`` zeroes the rows no comparison can judge (above),
    ``"kept"`` leaves every row as computed, ``"gaps"`` does too and
    returns ``(logits, gap [B, S])``: the closest choice any of a
    position's routers made, a near-tie where it is under ``NEAR_TIE``."""
    import jax
    import jax.numpy as jnp
    eps = config["rms_norm_eps"]
    top_k, dense = sizes["num_experts_per_tok"], sizes["first_k_dense_replace"]
    f32 = lambda tree: jax.tree.map(
        lambda a: jnp.asarray(a, jnp.float32), tree)
    if lower is None:
        lower = lambda tree: tree
        x = jnp.asarray(params["wte"][ids], jnp.float32)   # rows, then cast
    else:
        x = lower(f32({"wte": params["wte"]}))["wte"][ids]
    gap = jnp.full(ids.shape, jnp.inf)      # the closest choice of any layer
    for i in range(sizes["num_hidden_layers"]):
        p = lower(f32(params[f"layers_{i}"]))       # one layer at a time
        n = _rms(x, p["input_norm"]["scale"], eps)
        h = x + _attention(n, p["attn"], sizes, config)
        m = _rms(h, p["post_norm"]["scale"], eps)
        if i < dense:
            x = h + _swiglu(m, p["mlp"])
        else:
            weight = _route(m, p["moe"], top_k, config)
            gap = jnp.minimum(gap, _choice_gap(m, p["moe"], top_k))
            x = h + _experts(m, weight, params["experts"], i - dense, lower)
            if sizes["n_shared_experts"]:
                x = x + _swiglu(m, p["moe"]["shared"])
    x = _rms(x, jnp.asarray(params["ln_f"]["scale"], jnp.float32), eps)
    logits = x @ lower(f32({"lm_head": params["lm_head"]}))[
        "lm_head"]["kernel"]
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
