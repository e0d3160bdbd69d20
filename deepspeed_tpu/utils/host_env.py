"""Process-environment set-up shared by the entry points (``chip_smoke.py``,
``bench.py``, the ``bin/`` CLIs, the fleet workers). Every one of them calls
:func:`configure_compile_cache` before anything that may compile."""

import os

# <checkout>/.jax_cache — a FIXED path (git-ignored): the directory is part
# of JAX's cache key, so a temp dir, a pid or a timestamp would never hit.
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns the directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets nothing in code — the cache can be placed from outside.
    Otherwise the cache lives at the fixed in-checkout path."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR


def assert_not_holding_chip(who: str):
    """A chip belongs to one process at a time: a parent that has
    initialised JAX on a TPU holds it, and the child it is about to
    start would fail or hang reaching the device. Launchers call this
    right before they spawn. Importing jax (as ``deepspeed_tpu`` does) is
    fine; touching ``jax.devices()`` or running a computation is not.
    A parent on the CPU backend holds nothing exclusive and passes."""
    import sys
    if "jax" not in sys.modules:
        return
    import jax
    from jax._src import xla_bridge   # no public spelling of this query
    if xla_bridge.backends_are_initialized() \
            and jax.default_backend() == "tpu":
        raise RuntimeError(
            f"{who} initialised JAX on the TPU before starting its child "
            "process; the child cannot reach the chip this process now "
            "holds. Keep the parent off jax.devices() and computations.")


def force_host_device_count(n: int):
    """Request an ``n``-device virtual CPU backend (the CI/fake mesh).
    Must run before backend init."""
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={int(n)}").strip()
    import jax
    # must not silently degrade: a failed platform switch means the
    # caller would run on the accelerator with the wrong device count
    jax.config.update("jax_platforms", "cpu")
