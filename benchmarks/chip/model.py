"""Seeded weights for the system under test, whatever its family: the
module comes from ``families/<family>.py`` (``build``), its weights are
made here on the device from ``--seed``.

Recipe copied from ``chip_smoke.py`` (``_seeded_params``), which stays the
pass/fail start-up proof."""


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
