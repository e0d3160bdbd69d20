"""The OLMoE family (Muennighoff et al. 2024, arXiv:2409.02060; HF
``modeling_olmoe.py``; ``allenai/OLMoE-1B-7B-0125-Instruct``): the ``OLMoE``
module of ``deepspeed_tpu.models.olmoe`` at a configuration file's sizes
under the keys of its ``config.json``, its training loss, its operation
counts, the kernels it must run, and its plain reference.

The reference is the published forward pass in straightforward
``jax.numpy`` and float32 over the parameter tree the program keeps
(``wte``, ``h/*`` and ``experts/*`` stacked over layers, ``ln_f``,
``lm_head``). It shares no
code with ``deepspeed_tpu``: no sort, no grouped matmul, no kernel, no
cache; every expert is computed for every token and the k chosen are
summed. Per layer, as published:

    h = x + Wo Attn(RoPE(q), RoPE(k), v),   q = RMSNorm_q(Wq n),
        k = RMSNorm_k(Wk n), v = Wv n, n = RMSNorm(x); the two QK norms
        over the whole projection before the heads split; RoPE the
        half-split rotation over the full head; causal softmax scaled by
        head^-0.5; no bias anywhere
    y = h + sum_{e in topk(p)} p_e W_down,e (silu(W_gate,e m) * W_up,e m),
        m = RMSNorm(h), p = softmax_float32(W_router m) over all experts,
        the k weights not renormalised (``norm_topk_prob`` false)

then a final RMSNorm and an untied head. Departures from the published
model, both of layout only: the program keeps ``Wq | Wk | Wv`` as one
``qkv`` kernel, split here in thirds, and an expert's three matrices
stacked over experts.

A float32 copy of the 8-layer tree at published widths is 14 GB: the scan
below casts one layer at a time (1.7 GB)."""

from . import MOSAIC_KERNEL
from .. import reference

# the only keys a configuration of this family may name in `reduced`
DEPTH_KEYS = ("num_hidden_layers",)
SIZE_KEYS = ("hidden_size", "intermediate_size", "num_hidden_layers",
             "num_attention_heads", "num_experts", "num_experts_per_tok",
             "max_position_embeddings", "vocab_size")


def sizes(config, rehearse):
    """The configuration's published sizes, or its ``rehearse`` sizes: a
    tiny stand-in that only ever runs on the CPU, under ``--rehearse``."""
    src = config["rehearse"] if rehearse else config
    return {k: src[k] for k in SIZE_KEYS}


def build(config, rehearse, **overrides):
    """``OLMoE`` at the configuration's sizes; what is not a size comes
    from the file's own keys of ``config.json``."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.olmoe import OLMoE, OLMoEConfig
    # a rehearsal computes in float32: at its 64-wide, 8-expert size one
    # expert is a sixth of a layer's output, and a bf16 near-tie in the
    # router that picks another moves a served token's logit by whole
    # sigmas (tests/unit/test_olmoe.py), which at published widths it
    # does not
    dtypes = {"compute_dtype": "float32", "param_dtype": "float32"} \
        if rehearse else config
    kw = dict(sizes(config, rehearse),
              norm_topk_prob=config["norm_topk_prob"],
              rms_norm_eps=config["rms_norm_eps"],
              rope_theta=float(config["rope_theta"]),
              dtype=getattr(jnp, dtypes["compute_dtype"]),
              param_dtype=getattr(jnp, dtypes["param_dtype"]))
    kw.update(overrides)
    return OLMoE(OLMoEConfig(**kw))


def train_loss(config):
    """Next-token cross entropy plus the load-balancing loss at the
    published ``router_aux_loss_coef``: a batch row holds ``seq + 1``
    tokens."""
    from deepspeed_tpu.models.olmoe import olmoe_loss_fn
    return olmoe_loss_fn


def ops_per_token(sizes, seq):
    """Forward + backward operations per trained token: 6 per weight a
    token multiplies — attention's four projections, the router, **k of
    the E experts** (three matrices each) and the untied head — plus
    causal attention's two ``S x S`` products (``families/gpt2.py``)."""
    d, f = sizes["hidden_size"], sizes["intermediate_size"]
    per_layer = (4 * d * d + d * sizes["num_experts"]
                 + sizes["num_experts_per_tok"] * 3 * d * f)
    weights = (sizes["num_hidden_layers"] * per_layer
               + sizes["vocab_size"] * d)
    attention = sizes["num_hidden_layers"] * 3 * (2 * 2 * seq * d) // 2
    return 6 * weights + attention


def expected_kernels(serving):
    """Serving: the paged decode path and the paged-attention kernel at
    the server's page length. The grouped expert matmul is
    ``jax.lax.ragged_dot``, which XLA lowers to a Mosaic kernel of its
    own: it leaves no ``tuning.last_dispatch`` record, and the device
    trace names it (``metrics/serve.expert_mm_share_pct.longgen.json``)."""
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


def _attention(x, p, n_head, eps, theta):
    import jax
    import jax.numpy as jnp
    b, s, d = x.shape
    n = _rms(x, p["ln_1"]["scale"], eps)
    q, k, v = jnp.split(n @ p["attn"]["qkv"]["kernel"], 3, axis=-1)
    q = _rms(q, p["attn"]["q_norm"]["scale"], eps)
    k = _rms(k, p["attn"]["k_norm"]["scale"], eps)
    q, k, v = (t.reshape(b, s, n_head, d // n_head) for t in (q, k, v))
    q, k = _rope(q, theta), _rope(k, theta)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (d // n_head) ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    return x + a.reshape(b, s, d) @ p["attn"]["out"]["kernel"]


def _experts(h, p, top_k, renormalize, eps):
    """Every expert on every token, the k chosen weighted and summed.
    Returns the layer's output and the router's probabilities and picks
    (for the load-balancing loss)."""
    import jax
    import jax.numpy as jnp
    m = _rms(h, p["ln_2"]["scale"], eps)
    probs = jax.nn.softmax(m @ p["mlp"]["router"], -1)         # [B, S, E]
    weight, chosen = jax.lax.top_k(probs, top_k)
    if renormalize:
        weight = weight / jnp.sum(weight, -1, keepdims=True)
    gate = jnp.einsum("bsd,edf->bsef", m, p["experts"]["w_gate"])
    up = jnp.einsum("bsd,edf->bsef", m, p["experts"]["w_up"])
    every = jnp.einsum("bsef,efd->bsed", jax.nn.silu(gate) * up,
                       p["experts"]["w_down"])
    picked = jnp.take_along_axis(every, chosen[..., None], axis=2)
    return h + jnp.sum(picked * weight[..., None], axis=2), (probs, chosen)


def _forward(params, ids, sizes, config, lower=None):
    """Logits ``[B, S, V]`` and, per layer, the router's probabilities
    ``[L, B, S, E]`` and picks ``[L, B, S, k]``. ``lower`` (a control's:
    ``tools/olmoe_check.py``) takes each float32 tree of weights — one
    layer's, or the embedding and the head — and gives the tree to
    compute with."""
    import jax
    import jax.numpy as jnp
    lower = lower or (lambda tree: tree)
    f32 = lambda tree: lower(jax.tree.map(
        lambda a: jnp.asarray(a, jnp.float32), tree))
    n_head, top_k = sizes["num_attention_heads"], sizes["num_experts_per_tok"]
    eps, theta = config["rms_norm_eps"], float(config["rope_theta"])
    renormalize = config["norm_topk_prob"]

    def layer(x, p):
        p = f32(p)                              # one layer at a time
        h = _attention(x, p, n_head, eps, theta)
        return _experts(h, p, top_k, renormalize, eps)

    ends = f32({"wte": params["wte"], "head": params["lm_head"]["kernel"]})
    x = ends["wte"][ids]
    x, routed = jax.lax.scan(
        layer, x, dict(params["h"], experts=params["experts"]))
    x = _rms(x, jnp.asarray(params["ln_f"]["scale"], jnp.float32), eps)
    return x @ ends["head"], routed


def reference_logits(params, ids, sizes, config, lower=None):
    """``[B, S] -> [B, S, V]`` float32 logits. ``params`` is the
    program's tree in any dtype."""
    return _forward(params, ids, sizes, config, lower)[0]


def reference_next_token_losses(params, ids, sizes, config):
    """Per-position next-token cross entropy ``[B, S-1]`` of rows of
    ``S`` tokens: position ``i`` predicts token ``i + 1``."""
    return reference.next_token_losses(
        reference_logits(params, ids[:, :-1], sizes, config), ids[:, 1:])


def reference_loss(params, ids, sizes, config, aux_coef=0.01):
    """The training loss of rows of ``S`` tokens: mean next-token cross
    entropy plus ``aux_coef`` (the published ``router_aux_loss_coef``)
    times the load-balancing loss — over all layers' tokens together,
    the mean gate probability of an expert times the share of tokens
    that chose it, summed over experts and the k choices, times E (HF
    ``load_balancing_loss_func``)."""
    import jax
    import jax.numpy as jnp
    logits, (probs, chosen) = _forward(params, ids[:, :-1], sizes, config)
    n_experts = sizes["num_experts"]
    probs = probs.reshape(-1, n_experts)
    chose = jax.nn.one_hot(chosen.reshape(-1, chosen.shape[-1]), n_experts)
    aux = jnp.sum(jnp.mean(chose, 0) * jnp.mean(probs, 0)[None]) * n_experts
    ce = jnp.mean(reference.next_token_losses(logits, ids[:, 1:]))
    return ce + aux_coef * aux
