"""Numbers from the device trace, through ``xplane``'s reduction."""

import re

from . import reader
from .. import opcount, xplane


def _mean_over_devices(obs, fn):
    t = obs.trace
    if t is None or not t.devices:
        return None
    return sum(fn(t, d) for d in t.devices) / len(t.devices)


@reader("device_idle_pct")
def device_idle_pct(obs):
    t = obs.trace
    if t is None or not t.devices or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - xplane.busy_s(t) / t.window_s)


@reader("op_share_pct")
def op_share_pct(obs, op, module=None):
    """Device time of the operations matching ``op`` (inside the programs
    matching ``module``) / device busy time."""
    busy = _mean_over_devices(obs, xplane.busy_ns)
    if not busy:
        return None
    ns = _mean_over_devices(obs, lambda t, d: sum(
        n for _, n in xplane.op_ns(t, d, op, module)))
    return 100.0 * ns / busy


@reader("module_share_pct")
def module_share_pct(obs, module):
    """Device busy time inside the programs matching ``module`` / device
    busy time (the trace's ``XLA Modules`` line)."""
    busy = _mean_over_devices(obs, xplane.busy_ns)
    if not busy:
        return None
    ns = _mean_over_devices(
        obs, lambda t, d: xplane.module_busy_ns(t, d, module))
    return 100.0 * ns / busy


@reader("collective_exposed_pct")
def collective_exposed_pct(obs):
    t = obs.trace
    if t is None or not t.devices or t.window_s <= 0:
        return None
    ns = _mean_over_devices(obs, xplane.collective_exposed_ns)
    return 100.0 * ns / (t.window_s * 1e9)


_SHAPE = re.compile(r"bf16\[(\d+),(\d+),(\d+),(\d+)\]")


@reader("flash_roofline_pct")
def flash_roofline_pct(obs, op, module=None):
    """Sum over the flash calls of the least time the chip could take
    (the larger of operations / peak FLOP/s and bytes / peak bytes/s,
    from the call's own shapes) / the calls' device time. A call whose
    result holds three ``bf16[B,H,S,D]`` arrays (dq, dk, dv) is a
    backward call."""
    t = obs.trace
    if t is None or not t.devices or obs.peaks is None:
        return None
    least = spent = 0.0
    bounds = {}
    for text, ns in xplane.op_ns(t, t.devices[0], op, module):
        result = text.split(" custom-call(")[0]
        shapes = _SHAPE.findall(result)
        if not shapes:
            continue
        b, h, s, d = map(int, shapes[0])
        backward = len(shapes) >= 3
        sec, bound = opcount.roofline_seconds(
            opcount.flash_ops(b, h, s, d, backward),
            opcount.flash_bytes(b, h, s, d, backward), obs.peaks)
        least += sec
        spent += ns / 1e9
        bounds[bound] = bounds.get(bound, 0) + 1
    if not spent:
        return None
    obs.say(f"flash roofline: {sum(bounds.values())} calls, bounded by "
            f"{bounds}; least {least * 1e3:.3f} ms of {spent * 1e3:.3f} ms")
    return 100.0 * least / spent
