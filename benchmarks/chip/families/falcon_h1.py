"""The Falcon-H1 family (TII; HF ``modeling_falcon_h1.py``, ``model_type:
falcon_h1``; ``tiiuae/Falcon-H1-34B-Instruct``): the ``FalconH1`` module
of ``deepspeed_tpu.models.falcon_h1`` at a configuration file's sizes
under the keys of its ``config.json``, the kernels it must run, the
operations and bytes of the mixer's state update and chunk scan, and its
plain reference.

The reference is the published forward pass in straightforward
``jax.numpy`` and float32 over the parameter tree the program keeps
(``wte``, ``layers_<i>/*``, ``final_layernorm``, ``lm_head``). It shares
no code with ``deepspeed_tpu``: no chunks, no cache, no kernel. With
``n = RMSNorm_in(h)``, per layer, as published:

    mixer     u = n * ssm_in_multiplier; p = (W_in u) * mup, mup =
              ssm_multipliers[0..4] over the parts z | x | B | C | dt;
              [z | xBC | dt] = p; xBC = silu(conv1d(xBC) + b), depthwise,
              causal, zeros before the first token; [x | B | C] = xBC
              (x: heads x d_head; B, C: groups x d_state, head i reads
              group i // (heads / groups)); dt = softplus(dt + dt_bias);
              a = exp(-exp(A_log) dt) a head. **The recurrence one token
              at a time** (``lax.scan`` over positions): S_t = a_t S_{t-1}
              + dt_t x_t B_t^T, y_t = S_t C_t + D x_t; then y =
              RMSNorm(y * silu(z)), one root-mean-square a group of
              d_ssm / groups values (``mamba_norm_before_gate`` false),
              m = (W_out y) * ssm_out_multiplier
    attention q, k, v = W_q n', W_k n' * key_multiplier, W_v n' with n' =
              n * attention_in_multiplier; K and V heads repeated so that
              query head i reads K/V head i // group; RoPE the half-split
              rotation over the full head; causal softmax scaled by
              head_dim^-0.5; t = W_o(.) * attention_out_multiplier
              h <- h + m + t
    mlp       f = RMSNorm_ff(h); h <- h + W_down(silu(W_gate f *
              mlp_multipliers[0]) * W_up f) * mlp_multipliers[1]

with h_0 = E[ids] * embedding_multiplier before the first layer, and a
final RMSNorm and the untied head times ``lm_head_multiplier`` after the
last. Departures from the published model, of layout only: the program
keeps ``W_q | W_k | W_v`` as one ``qkv`` kernel, the convolution's taps
as ``conv_w [K, width]`` (the published ``conv1d.weight [width, 1, K]``
transposed), the MLP's gate, up and down as ``w1``, ``w3``, ``w2``.

A float32 copy of the 9-layer tree at published widths is 31 GB: the
loop below casts one layer at a time (1.7 GB)."""

from . import MOSAIC_KERNEL
from .. import reference

# the only keys a configuration of this family may name in `reduced`: the
# number of layers, and the rows of the vocabulary one chip of the stated
# deployment holds (model-configs guide, section 4: a sliced vocabulary)
DEPTH_KEYS = ("num_hidden_layers", "vocab_size")
SIZE_KEYS = ("hidden_size", "intermediate_size", "num_hidden_layers",
             "num_attention_heads", "num_key_value_heads", "head_dim",
             "mamba_d_ssm", "mamba_n_heads", "mamba_d_head",
             "mamba_d_state", "mamba_n_groups", "mamba_d_conv",
             "mamba_chunk_size", "max_position_embeddings", "vocab_size")
MULTIPLIER_KEYS = ("embedding_multiplier", "lm_head_multiplier",
                   "attention_in_multiplier", "attention_out_multiplier",
                   "key_multiplier", "ssm_in_multiplier",
                   "ssm_out_multiplier", "ssm_multipliers",
                   "mlp_multipliers")
FLAG_KEYS = ("mamba_conv_bias", "mamba_proj_bias", "mamba_rms_norm",
             "mamba_norm_before_gate", "attention_bias", "mlp_bias",
             "projectors_bias")


def sizes(config, rehearse):
    """The configuration's published sizes, or its ``rehearse`` sizes: a
    tiny stand-in that only ever runs on the CPU, under ``--rehearse``."""
    src = config["rehearse"] if rehearse else config
    return {k: src[k] for k in SIZE_KEYS}


def build(config, rehearse, **overrides):
    """``FalconH1`` at the configuration's sizes; what is not a size
    comes from the file's own keys of ``config.json``."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.falcon_h1 import FalconH1, FalconH1Config
    # a rehearsal computes in float32, as the other families' do: at a
    # hidden size of 64 one bf16 rounding is a large share of a logit
    dtypes = {"compute_dtype": "float32", "param_dtype": "float32",
              "ssm_state_dtype": "float32"} if rehearse else config
    kw = dict(sizes(config, rehearse),
              **{k: config[k] for k in MULTIPLIER_KEYS + FLAG_KEYS},
              rms_norm_eps=config["rms_norm_eps"],
              rope_theta=float(config["rope_theta"]),
              ssm_state_dtype=getattr(jnp, dtypes["ssm_state_dtype"]),
              dtype=getattr(jnp, dtypes["compute_dtype"]),
              param_dtype=getattr(jnp, dtypes["param_dtype"]))
    kw.update(overrides)
    return FalconH1(FalconH1Config(**kw))


def expected_kernels(serving):
    """Serving: the paged decode path, the paged-attention kernel at the
    server's page length and the mixer's state update (the chunk scan is
    plain einsums, which leave no ``tuning.last_dispatch`` record)."""
    if serving is None:
        return []
    return [("paged_decode", "path", MOSAIC_KERNEL),
            ("paged_attention", f"page{serving['paging']['page_len']}",
             MOSAIC_KERNEL),
            ("ssm_update", f"rows{serving['num_slots']}_", MOSAIC_KERNEL)]


# -- what the mixer's two scans must do -------------------------------------

def state_shape(sizes):
    """One sequence's matrix state in one layer: ``[heads, d_state,
    d_head]`` (the program's order; the published one has the last two
    swapped)."""
    return (sizes["mamba_n_heads"], sizes["mamba_d_state"],
            sizes["mamba_d_head"])


def state_values(sizes):
    h, n, p = state_shape(sizes)
    return h * n * p


def ssm_update_ops(sizes, rows):
    """Operations of one layer's decode update over ``rows`` rows that
    decode: a state value is decayed (1), gains its outer-product term
    (2) and is read into y (2)."""
    return 5 * rows * state_values(sizes)


def ssm_update_bytes(sizes, rows, itemsize=4):
    """The least one layer's decode update must move for ``rows`` rows
    that decode: each row's state read once and written once, and its
    inputs and output (dt x, the decay and y a head's width each, B and C
    a group's state width each, float32). Whatever implements the update
    — a kernel that skips the other rows or a program that passes over
    them — this is the work it is held to."""
    h, n, p = state_shape(sizes)
    small = 4 * (3 * h * p + 2 * sizes["mamba_n_groups"] * n)
    return rows * (2 * h * n * p * itemsize + small)


def ssm_chunk_ops(sizes, tokens):
    """Operations of one layer's chunk scan over ``tokens`` positions in
    chunks of ``mamba_chunk_size``: C B^T a group, its product with the
    chunk's inputs a head, what the chunk adds to the state and what the
    carried state gives each position; a multiply-add counts as 2."""
    h, n, p = state_shape(sizes)
    q, g = sizes["mamba_chunk_size"], sizes["mamba_n_groups"]
    return tokens * (2 * q * n * g + 2 * q * p * h + 4 * n * p * h)


def ssm_chunk_bytes(sizes, tokens, itemsize=4):
    """The least one layer's chunk scan must move: the carried state in
    and out, a state at each chunk's end, and the positions' x, B, C, dt
    in and y out."""
    h, n, p = state_shape(sizes)
    q, g = sizes["mamba_chunk_size"], sizes["mamba_n_groups"]
    states = (2 + -(-tokens // q)) * h * n * p * itemsize
    return states + tokens * 4 * (2 * h * p + 2 * g * n + h)


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


def _attention(n, p, sizes, config):
    import jax
    import jax.numpy as jnp
    b, s, _ = n.shape
    n_head, n_kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    hd = sizes["head_dim"]
    qkv = (n * config["attention_in_multiplier"]) @ p["qkv"]["kernel"]
    q = qkv[..., :n_head * hd].reshape(b, s, n_head, hd)
    k = qkv[..., n_head * hd:(n_head + n_kv) * hd].reshape(b, s, n_kv, hd)
    v = qkv[..., (n_head + n_kv) * hd:].reshape(b, s, n_kv, hd)
    theta = float(config["rope_theta"])
    q, k = _rope(q, theta), _rope(k * config["key_multiplier"], theta)
    k = jnp.repeat(k, n_head // n_kv, axis=2)
    v = jnp.repeat(v, n_head // n_kv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    return (a.reshape(b, s, n_head * hd) @ p["out"]["kernel"]) \
        * config["attention_out_multiplier"]


def _mixer(n, p, sizes, config):
    """The mixer, its recurrence one position at a time."""
    import jax
    import jax.numpy as jnp
    b, s, _ = n.shape
    h, pd = sizes["mamba_n_heads"], sizes["mamba_d_head"]
    ns, g = sizes["mamba_d_state"], sizes["mamba_n_groups"]
    d_ssm, taps = sizes["mamba_d_ssm"], sizes["mamba_d_conv"]
    mult = config["ssm_multipliers"]
    proj = (n * config["ssm_in_multiplier"]) @ p["in_proj"]["kernel"]
    mup = jnp.concatenate([jnp.full((w,), m, jnp.float32) for w, m in zip(
        (d_ssm, d_ssm, g * ns, g * ns, h), mult)])
    proj = proj * mup
    z, xbc, dt = (proj[..., :d_ssm], proj[..., d_ssm:-h], proj[..., -h:])
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = sum(p["conv_w"][j] * padded[:, j:j + s] for j in range(taps))
    if config["mamba_conv_bias"]:
        conv = conv + p["conv_b"]
    xbc = jax.nn.silu(conv)
    x = xbc[..., :d_ssm].reshape(b, s, h, pd)
    bm = xbc[..., d_ssm:d_ssm + g * ns].reshape(b, s, g, ns)
    cm = xbc[..., d_ssm + g * ns:].reshape(b, s, g, ns)
    # head i reads group i // (heads / groups)
    bm = jnp.repeat(bm, h // g, axis=2)
    cm = jnp.repeat(cm, h // g, axis=2)
    dt = jax.nn.softplus(dt + p["dt_bias"])                   # [b, s, h]
    a = jnp.exp(-jnp.exp(p["A_log"]) * dt)

    def token(state, at):                    # state [b, h, d_head, d_state]
        x_t, b_t, c_t, dt_t, a_t = at
        state = a_t[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., :, None] * b_t[..., None, :]
        return state, jnp.sum(state * c_t[..., None, :], -1)

    first = lambda v: jnp.moveaxis(v, 1, 0)
    _, y = jax.lax.scan(token, jnp.zeros((b, h, pd, ns), jnp.float32),
                        tuple(map(first, (x, bm, cm, dt, a))))
    y = jnp.moveaxis(y, 0, 1) + p["D"][:, None] * x
    y = y.reshape(b, s, d_ssm)
    gate = jax.nn.silu(z)

    def grouped(v):
        v = v.reshape(b, s, g, d_ssm // g)
        v = v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True)
                              + config["rms_norm_eps"])
        return v.reshape(b, s, d_ssm) * p["norm"]
    y = (grouped(y) * gate if config["mamba_norm_before_gate"]
         else grouped(y * gate))
    return (y @ p["out_proj"]["kernel"]) * config["ssm_out_multiplier"]


def reference_logits(params, ids, sizes, config, lower=None):
    """``[B, S] -> [B, S, V]`` float32 logits. ``params`` is the
    program's tree in any dtype. ``lower`` (a control's) takes each
    float32 tree of weights — one layer's, the embedding, the head — and
    gives the tree to compute with."""
    import jax
    import jax.numpy as jnp
    lower = lower or (lambda tree: tree)
    f32 = lambda tree: lower(jax.tree.map(
        lambda a: jnp.asarray(a, jnp.float32), tree))
    eps = config["rms_norm_eps"]
    gate_mult, down_mult = config["mlp_multipliers"]
    x = f32({"wte": params["wte"]})["wte"][ids] \
        * config["embedding_multiplier"]
    for i in range(sizes["num_hidden_layers"]):
        p = f32(params[f"layers_{i}"])          # one layer at a time
        n = _rms(x, p["input_layernorm"]["scale"], eps)
        x = x + _mixer(n, p["mixer"], sizes, config) \
            + _attention(n, p["attn"], sizes, config)
        f = _rms(x, p["pre_ff_layernorm"]["scale"], eps)
        w = p["mlp"]
        x = x + ((jax.nn.silu((f @ w["w1"]["kernel"]) * gate_mult)
                  * (f @ w["w3"]["kernel"])) @ w["w2"]["kernel"]) * down_mult
    x = _rms(x, jnp.asarray(params["final_layernorm"]["scale"],
                            jnp.float32), eps)
    head = f32({"lm_head": params["lm_head"]})["lm_head"]
    return (x @ head.T) * config["lm_head_multiplier"]


def reference_next_token_losses(params, ids, sizes, config):
    """Per-position next-token cross entropy ``[B, S-1]`` of rows of
    ``S`` tokens: position ``i`` predicts token ``i + 1``."""
    return reference.next_token_losses(
        reference_logits(params, ids[:, :-1], sizes, config), ids[:, 1:])
