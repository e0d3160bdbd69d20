"""Host<->device parameter streaming primitives (ZeRO-Infinity analog).

Reference: the ZeRO-3 parameter lifecycle — params live partitioned in
CPU/NVMe and are fetched just-in-time per submodule
(runtime/swap_tensor/partitioned_param_swapper.py:36,
partitioned_param_coordinator.py:444 NVMe prefetch). TPU-native: params
live in the accelerator host's pinned memory; ``stream_in`` is the
just-in-time fetch, applied per scan block inside the jitted step so XLA
overlaps block k+1's h2d with block k's compute (the coordinator's
prefetch, scheduled by the compiler instead of hooks).

Autodiff: the vjp of the h2d fetch moves the parameter cotangent back to
host space, so gradient accumulation buffers for offloaded params live
host-side too — device residency stays bounded by the live block.
"""

import jax


@jax.custom_vjp
def stream_in(x):
    """Host -> device fetch (identity math). Under remat the fetch
    replays in the backward recompute — the reference fetches params
    for the backward walk the same way. The vjp returns the
    cotangent in the PRIMAL's memory space (host params get host
    grads; no-op for device-resident params, e.g. on the CPU test
    backend where memory kinds don't exist)."""
    return jax.device_put(x, jax.memory.Space.Device)


def _stream_in_fwd(x):
    # zero-sized residual carries the primal's memory space (aval-static)
    return stream_in(x), x.ravel()[:0]


def _stream_in_bwd(res, ct):
    space = res.aval.memory_space
    if ct.aval.memory_space == space:
        return (ct,)
    return (jax.device_put(ct, space),)


stream_in.defvjp(_stream_in_fwd, _stream_in_bwd)


def stream_in_tree(tree):
    return jax.tree.map(stream_in, tree)


def double_buffered(items, fetch):
    """Iterate ``(item, fetch(item))`` with item i+1's fetch ISSUED before
    item i is yielded — the classic double buffer, expressed at trace
    time.

    Why issue order matters even though XLA schedules by dataflow: the
    h2d copies this wraps (``jax.device_put`` of pinned-host leaves) are
    what the latency-hiding scheduler overlaps with compute, and it can
    only hoist a copy ahead of the *previous* item's compute if nothing
    artificially sequences them. Emitting fetch N+1 before compute N
    keeps the two dependency chains (transfers, math) interleaved in the
    trace exactly one item ahead — the reference's
    PipelinedOptimizerSwapper read-ahead, with the compiler as the
    executor. Callers that want the prefetch observable (tests) can
    record events inside ``fetch``."""
    items = list(items)
    if not items:
        return
    ahead = fetch(items[0])
    for i, item in enumerate(items):
        current = ahead
        ahead = fetch(items[i + 1]) if i + 1 < len(items) else None
        yield item, current


def to_host_tree(tree):
    """Place a pytree in host memory space (init-time placement)."""
    return jax.tree.map(
        lambda x: jax.device_put(x, jax.memory.Space.Host), tree)


def ensure_streaming_module(module, error_cls=ValueError,
                            context="offload_params"):
    """Validate that ``module`` supports parameter streaming and return
    it with ``config.offload_params=True`` set (rebuilding if needed).

    Shared by the training engine (``offload_param`` block) and the
    inference engine (ZeRO-Inference serving) so the two validation
    paths cannot drift. Streaming needs a scan-over-layers model from
    ``deepspeed_tpu.models``: the scan step is the fetch granularity."""
    mcfg = getattr(module, "config", None)
    if mcfg is None or not hasattr(mcfg, "offload_params"):
        raise error_cls(
            f"{context} needs a model with parameter-streaming support "
            "(models from deepspeed_tpu.models with scan_layers=True)")
    if not getattr(mcfg, "scan_layers", False):
        raise error_cls(
            f"{context} requires scan_layers=True "
            "(the scan step is the fetch granularity)")
    if not getattr(mcfg, "offload_params", False):
        import dataclasses
        module = type(module)(
            dataclasses.replace(mcfg, offload_params=True))
    return module
