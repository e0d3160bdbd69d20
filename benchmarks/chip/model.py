"""The system under test, built from a configuration file: the GPT module
of ``deepspeed_tpu.models`` at the file's sizes, the training loss handed
to ``ds.initialize``, and seeded weights made on the device.

Recipes copied from ``chip_smoke.py`` (``_gpt``, ``_loss_fn``,
``_seeded_params``), which stays the pass/fail start-up proof."""


def sizes(config, rehearse):
    """The configuration's published sizes, or its ``rehearse`` sizes: a
    tiny stand-in that only ever runs on the CPU, under ``--rehearse``."""
    if rehearse:
        return {**config["rehearse"]}
    return {k: config[k] for k in ("n_embd", "n_layer", "n_head",
                                   "vocab_size", "n_positions")}


def build_gpt(config, rehearse, **overrides):
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
    kw.update(overrides)
    return GPT(GPTConfig(**kw))


def chunked_loss(chunk):
    """Next-token loss with the chunked vocabulary head. The model runs on
    ``seq`` tokens (128-aligned, so the flash kernel is eligible) and the
    labels are the same window shifted by one: a batch row holds
    ``seq + 1`` tokens, the chunk divides ``seq``, and ``[B, S, V]``
    logits never exist at once."""
    def loss_fn(model, params, batch, rng, train):
        from deepspeed_tpu.models import gpt_chunked_loss_fn
        ids = batch["input_ids"]
        h, wte = model.apply(params, ids[:, :-1], deterministic=not train,
                             return_hidden=True)
        return gpt_chunked_loss_fn(h, wte, ids[:, 1:], chunk=chunk)
    return loss_fn


def prng_key(seed):
    """A key from any whole number: the driver's seeds pass 2**31."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31 - 1)),
                              seed // (2 ** 31 - 1))


def seeded_params(model, seed):
    """The model's weights, made on the device by one jitted init, in the
    type the model declares (no array of weights ever lives on the host)."""
    import jax
    import jax.numpy as jnp
    import flax.core.meta as flax_meta
    return jax.jit(lambda r: flax_meta.unbox(model.init(
        r, jnp.ones((1, 8), jnp.int32)))["params"])(prng_key(seed))
