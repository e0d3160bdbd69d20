"""The plain reference of the GPT-2 configurations: the published forward
pass in straightforward ``jax.numpy`` and float32 — no kernel, no cache,
no remat, full logits — over the parameter tree the program's ``GPT``
keeps (``wte``, ``wpe``, ``h/*`` stacked over layers, ``ln_f``). It
shares no code with ``deepspeed_tpu``. On a TPU a float32 matmul runs in
bf16 passes unless told otherwise, so every caller runs it under
``jax.default_matmul_precision("highest")`` (``highest()`` below).

GPT-2 as published (Radford et al. 2019; ``openai-community/gpt2``):
token + learned position embeddings; per layer ``x += attn(ln_1(x))``,
``x += mlp(ln_2(x))``; causal softmax attention over ``n_head`` heads of
``n_embd / n_head``, scaled by ``1/sqrt(head)``; a 4x feed-forward with
the tanh GELU; a final LayerNorm; logits against the tied embedding."""


def highest():
    import jax
    return jax.default_matmul_precision("highest")


def _ln(x, p, eps):
    import jax
    import jax.numpy as jnp
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _dense(x, p):
    return x @ p["kernel"] + p["bias"]


def _block(x, p, n_head, eps):
    import jax
    import jax.numpy as jnp
    b, s, d = x.shape
    q, k, v = jnp.split(_dense(_ln(x, p["ln_1"], eps), p["attn"]["qkv"]),
                        3, axis=-1)
    q, k, v = (t.reshape(b, s, n_head, d // n_head) for t in (q, k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (d // n_head) ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    x = x + _dense(a.reshape(b, s, d), p["attn"]["out"])
    h = jax.nn.gelu(_dense(_ln(x, p["ln_2"], eps), p["mlp"]["fc_in"]),
                    approximate=True)
    return x + _dense(h, p["mlp"]["fc_out"])


def logits(params, ids, n_head, eps=1e-5):
    """``[B, S] -> [B, S, V]`` float32 logits. ``params`` is the program's
    tree in any dtype (cast here); layers are scanned so that the program
    compiles in seconds at 24 layers."""
    import jax
    import jax.numpy as jnp
    p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    s = ids.shape[1]
    x = p["wte"][ids] + p["wpe"][:s]
    x, _ = jax.lax.scan(lambda x, lp: (_block(x, lp, n_head, eps), None),
                        x, p["h"])
    return _ln(x, p["ln_f"], eps) @ p["wte"].T


def next_token_losses(params, ids, n_head, eps=1e-5):
    """Per-position next-token cross entropy ``[B, S-1]`` of rows of
    ``S`` tokens: position ``i`` predicts token ``i + 1``."""
    import jax
    import jax.numpy as jnp
    lg = logits(params, ids[:, :-1], n_head, eps)
    logz = jax.nn.logsumexp(lg, -1)
    ll = jnp.take_along_axis(lg, ids[:, 1:, None], -1)[..., 0]
    return logz - ll
