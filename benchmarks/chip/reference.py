"""What every family's plain reference shares. The forward pass itself is
the family's (``families/<family>.py``): straightforward ``jax.numpy``
and float32, no code of ``deepspeed_tpu``. On a TPU a float32 matmul runs
in bf16 passes unless told otherwise, so every caller runs a reference
under ``jax.default_matmul_precision("highest")`` (``highest()``).

``lower_weights`` is the control's arithmetic (``tools/control.py``,
``tests/chip_bench/test_control.py``): the reference put in the program's
place with its weights in the next precision below bf16. No run of the
benchmark calls it."""

# the largest magnitude each 8-bit type holds
_TOP = {"int8": 127.0, "float8_e4m3fn": 448.0}


def highest():
    import jax
    return jax.default_matmul_precision("highest")


def next_token_losses(logits, labels):
    """Cross entropy ``[B, S]`` of float32 ``logits [B, S, V]`` against
    ``labels [B, S]``: the log of the partition sum less the label's
    logit."""
    import jax
    import jax.numpy as jnp
    logz = jax.nn.logsumexp(logits, -1)
    ll = jnp.take_along_axis(logits, labels[:, :, None], -1)[..., 0]
    return logz - ll


def lower_weights(params, kind):
    """The tree with every matrix (two axes or more, stacked layers
    included) rounded to ``kind`` (``int8`` or ``float8_e4m3fn``) and
    back to float32, one scale per channel of the last axis, symmetric:
    weight-only quantization as an inference stack does it. Vectors
    (biases, norms) stay as they are."""
    import jax
    import jax.numpy as jnp

    def one(a):
        a = jnp.asarray(a, jnp.float32)
        if a.ndim < 2:
            return a
        scale = jnp.max(jnp.abs(a), axis=-2, keepdims=True) / _TOP[kind]
        scale = jnp.where(scale == 0, 1.0, scale)
        if kind == "int8":
            return jnp.round(a / scale) * scale
        return (a / scale).astype(jnp.float8_e4m3fn).astype(
            jnp.float32) * scale
    return jax.tree.map(one, params)
