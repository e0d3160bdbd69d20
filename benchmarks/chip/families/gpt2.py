"""The GPT-2 family: the ``GPT`` module of ``deepspeed_tpu.models`` at a
configuration file's sizes (``n_embd``, ``n_layer``, ``n_head``,
``vocab_size``, ``n_positions``: the keys of GPT-2's ``config.json``),
its training loss, its operation count, the kernels it must run, and its
plain reference.

The reference is the published forward pass in straightforward
``jax.numpy`` and float32 — no kernel, no cache, no remat, full logits —
over the parameter tree the program's ``GPT`` keeps (``wte``, ``wpe``,
``h/*`` stacked over layers, ``ln_f``). It shares no code with
``deepspeed_tpu``. GPT-2 as published (Radford et al. 2019;
``openai-community/gpt2``): token + learned position embeddings; per
layer ``x += attn(ln_1(x))``, ``x += mlp(ln_2(x))``; causal softmax
attention over ``n_head`` heads of ``n_embd / n_head``, scaled by
``1/sqrt(head)``; a 4x feed-forward with the tanh GELU; a final
LayerNorm; logits against the tied embedding.

Build and loss recipes copied from ``chip_smoke.py`` (``_gpt``,
``_loss_fn``), which stays the pass/fail start-up proof."""

from . import MOSAIC, MOSAIC_KERNEL
from .. import reference

FFN_MULT = 4
# the only keys a configuration of this family may name in `reduced`
DEPTH_KEYS = ("n_layer",)


def sizes(config, rehearse):
    """The configuration's published sizes, or its ``rehearse`` sizes: a
    tiny stand-in that only ever runs on the CPU, under ``--rehearse``."""
    src = config["rehearse"] if rehearse else config
    return {k: src[k] for k in ("n_embd", "n_layer", "n_head", "vocab_size",
                                "n_positions")}


def build(config, rehearse, **overrides):
    """``GPT`` at the configuration's sizes. GPT-2's published block is
    what ``GPTConfig`` defaults to: learned positions, tied embeddings,
    pre-LN, biases, tanh GELU, LN epsilon 1e-5, 4x feed-forward."""
    import jax.numpy as jnp
    from deepspeed_tpu.models import GPT, GPTConfig
    s = sizes(config, rehearse)
    kw = dict(vocab_size=s["vocab_size"], max_seq_len=s["n_positions"],
              d_model=s["n_embd"], n_layers=s["n_layer"],
              n_heads=s["n_head"], ln_epsilon=config["layer_norm_epsilon"],
              scan_layers=True,
              dtype=getattr(jnp, config["compute_dtype"]),
              param_dtype=getattr(jnp, config["param_dtype"]))
    if "remat" in config:
        kw["remat"] = config["remat"]
    kw.update(overrides)
    return GPT(GPTConfig(**kw))


def train_loss(config):
    """Next-token loss with the chunked vocabulary head. The model runs on
    ``seq`` tokens (128-aligned, so the flash kernel is eligible) and the
    labels are the same window shifted by one: a batch row holds
    ``seq + 1`` tokens, the chunk (``loss_chunk``) divides ``seq``, and
    ``[B, S, V]`` logits never exist at once."""
    chunk = config["loss_chunk"]

    def loss_fn(model, params, batch, rng, train):
        from deepspeed_tpu.models import gpt_chunked_loss_fn
        ids = batch["input_ids"]
        h, wte = model.apply(params, ids[:, :-1], deterministic=not train,
                             return_hidden=True)
        return gpt_chunked_loss_fn(h, wte, ids[:, 1:], chunk=chunk)
    return loss_fn


def ops_per_token(sizes, seq):
    """Forward + backward operations per trained token of a GPT-2 block
    stack with a tied head: 6 per weight that a token multiplies (2
    forward, 4 backward), plus causal attention's two ``S x S`` products
    per layer (scores and values: ``2 * 2 * seq * n_embd`` forward over
    the full square, half of it under the causal mask, times 3 for
    forward + backward). A multiply-add counts as 2 operations;
    recomputed operations do not count (``opcount``)."""
    n_embd, n_layer = sizes["n_embd"], sizes["n_layer"]
    per_layer_weights = (4 + 2 * FFN_MULT) * n_embd * n_embd
    weights = n_layer * per_layer_weights + sizes["vocab_size"] * n_embd
    attention = n_layer * 3 * (2 * 2 * seq * n_embd) // 2
    return 6 * weights + attention


def expected_kernels(serving):
    """Training: attention dispatched the flash kernel, and Mosaic
    compiled it forward and backward. Serving: the paged decode path and
    the paged-attention kernel at the server's page length."""
    if serving is None:
        return [("attention", "backend", {"backend": "pallas"}),
                ("flash_attention", "fwd_", MOSAIC),
                ("flash_attention", "bwd_", MOSAIC)]
    return [("paged_decode", "path", MOSAIC_KERNEL),
            ("paged_attention", f"page{serving['paging']['page_len']}",
             MOSAIC_KERNEL)]


# -- the plain reference ----------------------------------------------------

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


def reference_logits(params, ids, sizes, config):
    """``[B, S] -> [B, S, V]`` float32 logits. ``params`` is the program's
    tree in any dtype (cast here); layers are scanned so that the program
    compiles in seconds at 24 layers."""
    import jax
    import jax.numpy as jnp
    n_head, eps = sizes["n_head"], config["layer_norm_epsilon"]
    p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    s = ids.shape[1]
    x = p["wte"][ids] + p["wpe"][:s]
    x, _ = jax.lax.scan(lambda x, lp: (_block(x, lp, n_head, eps), None),
                        x, p["h"])
    return _ln(x, p["ln_f"], eps) @ p["wte"].T


def reference_next_token_losses(params, ids, sizes, config):
    """Per-position next-token cross entropy ``[B, S-1]`` of rows of
    ``S`` tokens: position ``i`` predicts token ``i + 1``."""
    return reference.next_token_losses(
        reference_logits(params, ids[:, :-1], sizes, config), ids[:, 1:])
