"""The Phi-4-mini-flash family (Microsoft; ``model_type: phi4flash``;
``microsoft/Phi-4-mini-flash-reasoning``; arXiv:2507.06607, SambaY with
differential attention): the ``Phi4Flash`` module of
``deepspeed_tpu.models.phi4flash`` at a configuration file's sizes under
the keys of its ``config.json``, the kernels it must run, the bytes its
three kinds of state make a decode step move, and its plain reference.

The reference is the forward pass in straightforward ``jax.numpy`` and
float32 over the parameter tree the program keeps (``wte``,
``layers_<i>/*``, ``final_layernorm``). It shares no code with
``deepspeed_tpu``: no chunks, no cache, no ring, no kernel, and every
layer on every position. With ``L`` layers, ``half = L / 2``, ``n =
LayerNorm(h)`` (bias, eps ``layer_norm_eps``), every layer is ``h <- h +
Mix_i(n); h <- h + W2(silu(W1 f) * W3 f)`` with ``f = LayerNorm(h)`` and:

    mixer (i <= half, even)   [x | z] = W_in n; x = silu(conv1d(x) + b),
              depthwise, causal, zeros before the first token; [delta | B
              | C] = W_x x; dt = softplus(W_dt delta + dt_bias); A =
              -exp(A_log). **The recurrence one token at a time**
              (``lax.scan`` over positions): S_t = exp(dt_t (x) A) S_{t-1}
              + (dt_t x_t) (x) B_t, y_t = S_t C_t + D x_t; out = W_out(y *
              silu(z)). Layer ``half``'s y is the memory m.
    window attention (i < half, odd)   differential attention over the
              positions t - window < s <= t
    full attention (i = half + 1)      differential attention, causal
    gated memory unit (i > half + 1, even)   W2(m_t * silu(W1 n_t))
    cross attention (i > half + 1, odd)  differential attention with
              queries of its own over layer ``half + 1``'s keys and values

Differential attention over heads of ``d = hidden / heads``: q1 = query
heads 0..H/2-1, q2 the rest; k1, v1 = K/V heads 0..G/2-1, k2, v2 the
rest; v = [v1 | v2] (head j beside j + G/2, 2 d wide); pair p reads key
head p // (H / G) of its half; a1 = softmax(q1 k1^T / sqrt(d)) v, a2 =
softmax(q2 k2^T / sqrt(d)) v; lam = exp(lq1 . lk1) - exp(lq2 . lk2) +
lam_init with lam_init = 0.8 - 0.6 exp(-0.3 i); head = RMSNorm(a1 - lam
a2) (1 - lam_init); W_o over the H/2 heads. No position encoding. The
final LayerNorm and the tied table give the logits.

Departures from the published model, of layout only: the program keeps
``W_q | W_k | W_v`` as one ``qkv`` kernel, the convolution's taps as
``conv_w [K, width]``, ``A_log`` as ``[d_state, d_inner]`` (the published
one transposed), the MLP's gate, up and down as ``w1``, ``w3``, ``w2``.
Attention goes by blocks of pairs, so that ``[heads, S, S]`` is never
whole at 4,096 positions."""

import math

from . import MOSAIC_KERNEL
from .. import reference

# nothing of this family is cut but, in a later configuration, its depth
DEPTH_KEYS = ("num_hidden_layers",)
SIZE_KEYS = ("hidden_size", "intermediate_size", "num_hidden_layers",
             "num_attention_heads", "num_key_value_heads", "sliding_window",
             "mb_per_layer", "max_position_embeddings", "vocab_size")
# what ``config.json`` does not give, Mamba-1's defaults (a configuration
# file states them under ``assumed`` and at its top level; ``build`` holds
# the file to them): the state's columns, the convolution's taps, the
# inner width over the hidden one; ``dt_rank`` is ``ceil(hidden / 16)``
D_STATE, D_CONV, EXPAND = 16, 4, 2
PAIR_BLOCK = 4              # pairs of heads whose scores are whole at once


def sizes(config, rehearse):
    """The configuration's published sizes, or its ``rehearse`` sizes: a
    tiny stand-in that only ever runs on the CPU, under ``--rehearse``."""
    src = config["rehearse"] if rehearse else config
    return {k: src[k] for k in SIZE_KEYS}


def build(config, rehearse, **overrides):
    """``Phi4Flash`` at the configuration's sizes; what is not a size
    comes from the file's own keys of ``config.json``."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.phi4flash import Phi4Flash, Phi4FlashConfig
    # a rehearsal computes in float32, as the other families' do: at a
    # hidden size of 64 one bf16 rounding is a large share of a logit
    dtypes = {"compute_dtype": "float32", "param_dtype": "float32",
              "ssm_state_dtype": "float32",
              "kv_cache_dtype": "float32"} if rehearse else config
    assumed = (config["mamba_d_state"], config["mamba_d_conv"],
               config["mamba_expand"])
    if assumed != (D_STATE, D_CONV, EXPAND):
        raise ValueError(f"the file's Mamba-1 sizes {assumed} are not the "
                         f"family's {(D_STATE, D_CONV, EXPAND)}")
    kw = dict(sizes(config, rehearse),
              mamba_d_state=D_STATE, mamba_d_conv=D_CONV,
              mamba_expand=EXPAND, layer_norm_eps=config["layer_norm_eps"],
              tie_word_embeddings=config["tie_word_embeddings"],
              mlp_bias=config["mlp_bias"],
              lm_head_bias=config["lm_head_bias"],
              mamba_bc_gain=config["mamba_bc_gain"],
              ssm_state_dtype=getattr(jnp, dtypes["ssm_state_dtype"]),
              kv_cache_dtype=getattr(jnp, dtypes["kv_cache_dtype"]),
              dtype=getattr(jnp, dtypes["compute_dtype"]),
              param_dtype=getattr(jnp, dtypes["param_dtype"]))
    kw.update(overrides)
    return Phi4Flash(Phi4FlashConfig(**kw))


def layer_kind(sizes, i):
    half = sizes["num_hidden_layers"] // 2
    if i <= half:
        return "mixer" if i % 2 == 0 else "window_attn"
    if i == half + 1:
        return "shared_attn"
    return "gmu" if i % 2 == 0 else "cross_attn"


def layers_of(sizes, kind):
    return [i for i in range(sizes["num_hidden_layers"])
            if layer_kind(sizes, i) == kind]


def expected_kernels(serving):
    """Serving: the paged decode path, the paged-attention kernel at the
    server's page length (the full layer and the layers that read its
    pages) and the contiguous decode kernel on the rings."""
    if serving is None:
        return []
    return [("paged_decode", "path", MOSAIC_KERNEL),
            ("paged_attention", f"page{serving['paging']['page_len']}",
             MOSAIC_KERNEL),
            ("decode_attention", "dma", MOSAIC_KERNEL)]


# -- what a decode step has to move for the three kinds of state ------------

def kv_token_bytes(sizes, itemsize=2):
    """One token's keys and values in one layer: every K/V head, K and
    V."""
    d = sizes["hidden_size"] // sizes["num_attention_heads"]
    return 2 * sizes["num_key_value_heads"] * d * itemsize


def shared_kv_attn_bytes(sizes, context_tokens, itemsize=2):
    """The least ONE call over the shared pages must move: each of the
    ``context_tokens`` (the decoding rows' contexts, summed) read once,
    keys and values. Eight layers make the call a step: the full layer
    and the seven that read its pages."""
    return context_tokens * kv_token_bytes(sizes, itemsize)


def window_attn_bytes(sizes, ring_tokens, itemsize=2):
    """The least one window layer's decode call must move: each row's
    ``min(context, window)`` ring tokens read once."""
    return ring_tokens * kv_token_bytes(sizes, itemsize)


def state_values(sizes):
    return EXPAND * sizes["hidden_size"] * D_STATE


def ssm_update_bytes(sizes, rows, itemsize=4):
    """The least one mixer's decode update must move for ``rows`` rows
    that decode: each row's state read once and written once — the same
    count whatever implements it — and its inputs and output (dt, x and y
    a channel each, B and C a state column each, float32)."""
    d = EXPAND * sizes["hidden_size"]
    small = 4 * (3 * d + 2 * D_STATE)
    return rows * (2 * state_values(sizes) * itemsize + small)


# -- the plain reference ----------------------------------------------------

def _ln(x, p, eps):
    import jax
    import jax.numpy as jnp
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _dense(x, p):
    y = x @ p["kernel"]
    return y + p["bias"] if "bias" in p else y


def _mixer(n, p):
    """``(out, y)``: the mixer and its scan output before the gate. The
    state's columns, the taps and ``dt_rank`` are read off the weights."""
    import jax
    import jax.numpy as jnp
    b, s, _ = n.shape
    ns, taps = p["A_log"].shape[0], p["conv_w"].shape[0]
    rank = p["dt_proj"]["kernel"].shape[0]
    x, z = jnp.split(_dense(n, p["in_proj"]), 2, axis=-1)
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    x = jax.nn.silu(sum(p["conv_w"][j] * padded[:, j:j + s]
                        for j in range(taps)) + p["conv_b"])
    dbc = _dense(x, p["x_proj"])
    delta, bm, cm = dbc[..., :rank], dbc[..., rank:rank + ns], \
        dbc[..., rank + ns:]
    dt = jax.nn.softplus(_dense(delta, p["dt_proj"]) + p["dt_bias"])
    a = -jnp.exp(p["A_log"]).T                               # [d, n]

    def token(state, at):                                    # [b, d, n]
        x_t, b_t, c_t, dt_t = at
        state = jnp.exp(dt_t[..., None] * a) * state \
            + (dt_t * x_t)[..., None] * b_t[:, None, :]
        return state, jnp.sum(state * c_t[:, None, :], -1)

    first = lambda v: jnp.moveaxis(v, 1, 0)
    _, y = jax.lax.scan(token, jnp.zeros((b, x.shape[-1], ns), jnp.float32),
                        tuple(map(first, (x, bm, cm, dt))))
    y = jnp.moveaxis(y, 0, 1) + p["D"] * x
    gated = y * jax.nn.silu(z)
    return _dense(gated, p["out_proj"]), y


def _pairs(q, k, v, sizes):
    """``q [b, s, H, d]``, ``k``, ``v`` ``[b, S, G, d]`` as pairs: ``(q1,
    q2 [b, s, P, d], k1, k2 [b, S, P, d], v [b, S, P, 2 d])`` with ``P =
    H / 2``, the K/V heads repeated so that pair ``p`` reads its own."""
    import jax.numpy as jnp
    pairs = sizes["num_attention_heads"] // 2
    kvh = sizes["num_key_value_heads"] // 2
    rep = lambda t: jnp.repeat(t, pairs // kvh, axis=2)
    both = jnp.concatenate([v[:, :, :kvh], v[:, :, kvh:]], axis=-1)
    return (q[:, :, :pairs], q[:, :, pairs:], rep(k[:, :, :kvh]),
            rep(k[:, :, kvh:]), rep(both))


def _differential(q, k, v, p, sizes, index, window, lam_zero=False):
    """Differential attention of queries ``q [b, s, H, d]`` at positions
    ``0..s-1`` over ``k``, ``v`` ``[b, s, G, d]`` at the same positions,
    by blocks of ``PAIR_BLOCK`` pairs."""
    import jax
    import jax.numpy as jnp
    b, s, heads, d = q.shape
    pairs = heads // 2
    q1, q2, k1, k2, vv = _pairs(q, k, v, sizes)
    rows, cols = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    see = cols <= rows
    if window is not None:
        see &= cols > rows - window

    def attend(qq, kk, vals):                 # [b, s, blk, d] each
        sc = jnp.einsum("bqhd,bkhd->bhqk", qq, kk) / math.sqrt(d)
        sc = jnp.where(see, sc, -jnp.inf)
        return jnp.einsum("bhqk,bkhe->bqhe", jax.nn.softmax(sc, -1), vals)

    def block(at):
        cut = lambda t: jax.lax.dynamic_slice_in_dim(t, at, blk, axis=2)
        return (attend(cut(q1), cut(k1), cut(vv)),
                attend(cut(q2), cut(k2), cut(vv)))

    blk = math.gcd(PAIR_BLOCK, pairs)
    a1, a2 = jax.lax.map(block, jnp.arange(0, pairs, blk))
    join = lambda t: jnp.moveaxis(t, 0, 2).reshape(b, s, pairs, 2 * d)
    a1, a2 = join(a1), join(a2)
    lam_init = 0.8 - 0.6 * math.exp(-0.3 * index)
    lam = jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"])) \
        - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + lam_init
    if lam_zero:
        lam = 0.0
    x = a1 - lam * a2
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5) \
        * p["subln"]["scale"] * (1.0 - lam_init)
    return _dense(x.reshape(b, s, pairs * 2 * d), p["out"])


def _split_qkv(n, p, sizes):
    b, s, _ = n.shape
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    d = sizes["hidden_size"] // heads
    qkv = _dense(n, p["qkv"])
    return (qkv[..., :heads * d].reshape(b, s, heads, d),
            qkv[..., heads * d:(heads + kv) * d].reshape(b, s, kv, d),
            qkv[..., (heads + kv) * d:].reshape(b, s, kv, d))


def reference_logits(params, ids, sizes, config, lower=None, wrong=None):
    """``[B, S] -> [B, S, V]`` float32 logits. ``params`` is the
    program's tree in any dtype. ``lower`` (a control's) takes each
    float32 tree of weights — one layer's, the table — and gives the tree
    to compute with. ``wrong`` names one departure a check has to refuse
    (``tools/phi4flash_check.py``): ``"memory_after_gate"``,
    ``"lambda_zero"``, ``"window_less_one"``, ``"wrong_pairing"``."""
    import jax
    import jax.numpy as jnp
    lower = lower or (lambda tree: tree)
    f32 = lambda tree: lower(jax.tree.map(
        lambda a: jnp.asarray(a, jnp.float32), tree))
    eps = config["layer_norm_eps"]
    heads = sizes["num_attention_heads"]
    d = sizes["hidden_size"] // heads
    window = sizes["sliding_window"] - (wrong == "window_less_one")
    table = f32({"wte": params["wte"]})["wte"]
    x = table[ids]
    memory = shared = None
    for i in range(sizes["num_hidden_layers"]):
        p = f32(params[f"layers_{i}"])          # one layer at a time
        kind = layer_kind(sizes, i)
        n = _ln(x, p["input_layernorm"], eps)
        if kind == "mixer":
            mix, y = _mixer(n, p["mixer"])
            if i == sizes["num_hidden_layers"] // 2:
                memory = y
                if wrong == "memory_after_gate":
                    z = jnp.split(_dense(n, p["mixer"]["in_proj"]), 2, -1)[1]
                    memory = y * jax.nn.silu(z)
        elif kind == "gmu":
            mix = _dense(memory * jax.nn.silu(_dense(n, p["gmu"]["w1"])),
                         p["gmu"]["w2"])
        elif kind == "cross_attn":
            b, s, _ = n.shape
            q = _dense(n, p[kind]["q"]).reshape(b, s, heads, d)
            k, v = shared
            if wrong == "wrong_pairing" and i == sizes["num_hidden_layers"] - 1:
                k = jnp.roll(k, 1, axis=2)
            mix = _differential(q, k, v, p[kind], sizes, i, None,
                                lam_zero=wrong == "lambda_zero")
        else:
            q, k, v = _split_qkv(n, p[kind], sizes)
            if kind == "shared_attn":
                shared = (k, v)
            mix = _differential(
                q, k, v, p[kind], sizes, i,
                window if kind == "window_attn" else None,
                lam_zero=wrong == "lambda_zero")
        x = x + mix
        f = _ln(x, p["post_attention_layernorm"], eps)
        w = p["mlp"]
        x = x + (jax.nn.silu(f @ w["w1"]["kernel"])
                 * (f @ w["w3"]["kernel"])) @ w["w2"]["kernel"]
    x = _ln(x, jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                            params["final_layernorm"]), eps)
    return x @ table.T


def reference_next_token_losses(params, ids, sizes, config):
    """Per-position next-token cross entropy ``[B, S-1]`` of rows of
    ``S`` tokens: position ``i`` predicts token ``i + 1``."""
    return reference.next_token_losses(
        reference_logits(params, ids[:, :-1], sizes, config), ids[:, 1:])
