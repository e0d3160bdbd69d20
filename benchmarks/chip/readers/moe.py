"""An expert layer's per-layer metrics: what the program counted of its
routing (the ``moe/*`` counters of the process registry, written by
``ServingMetrics.on_moe_counts`` from the router's ``[L, E]`` counts that
come back with the sampled tokens), and the grouped expert matmuls'
share of their roofline from the device trace, with the functions that
give a call's operations and bytes from the model's sizes
(``hidden_size``, ``intermediate_size``: the keys of a gated-expert
model's ``config.json``). A program that counts no routing (an older
one, or a model without experts) reads as nothing."""

import bisect
import re

from . import reader
from .. import opcount

_METADATA = "ragged-dot-metadata"


def expert_mm_ops(sizes, assignments):
    """Operations of one layer call's three grouped matmuls over
    ``assignments`` token-expert pairs: gate and up ``[d] x [d, f]``,
    down ``[f] x [f, d]``; a multiply-add counts as 2."""
    d, f = sizes["hidden_size"], sizes["intermediate_size"]
    return 2 * 3 * d * f * assignments


def expert_mm_bytes(sizes, assignments, experts_touched, itemsize=2):
    """The least one layer call's three grouped matmuls must move: the
    three matrices of the experts that got at least one row — **not** of
    ``min(E, assignments)`` experts, which over-counts whenever two pairs
    share an expert — plus each matmul's rows in and out (gate and up
    read ``d`` and write ``f``; down reads ``f`` and writes ``d`` in
    float32)."""
    d, f = sizes["hidden_size"], sizes["intermediate_size"]
    weights = experts_touched * 3 * d * f * itemsize
    rows = assignments * (2 * (d + f) * itemsize + f * itemsize + d * 4)
    return weights + rows


def _counters():
    """The registry's ``moe/*`` counters, or None before any routing."""
    from deepspeed_tpu.observability.metrics import get_registry
    reg = get_registry()
    c = {name: reg.counter("moe/" + name).value
         for name in ("assignments", "expert_calls", "experts_touched",
                      "experts_offered", "load_max")}
    return c if c["expert_calls"] and c["assignments"] else None


@reader("moe_load_max_over_mean")
def moe_load_max_over_mean(obs):
    """The largest expert group of a layer call over the mean group,
    over every layer call of the process: 1 is perfect balance."""
    c = _counters()
    if c is None:
        return None
    experts = c["experts_offered"] / c["expert_calls"]
    obs.say(f"moe: {c['assignments']} pairs over {c['expert_calls']} layer "
            f"calls of {experts:g} experts; largest groups sum to "
            f"{c['load_max']}, experts touched {c['experts_touched']}")
    return c["load_max"] * experts / c["assignments"]


def layer_calls(trace, device, pattern, n_layers):
    """(layer calls, matmul events, their nanoseconds) inside the
    window. A run of a program that holds grouped matmuls calls every
    layer once, however many ``ragged_dot`` calls a layer's matmuls are
    split into; a run the window's edge cuts counts by the share of its
    matmuls that lie inside."""
    matmuls = [(s, e) for s, e, text in device.ops
               if pattern.search(text) and _METADATA not in text]
    starts = [s for s, _, _ in device.modules]
    per_run = {}
    for s, e in matmuls:
        i = bisect.bisect_right(starts, s) - 1
        run = per_run.setdefault(i, [0, 0, 0.0])
        run[0] += 1
        inside = min(e, trace.t1) - max(s, trace.t0)
        if inside > 0:
            run[1] += 1
            run[2] += inside
    calls = n_layers * sum(inside / every
                           for every, inside, _ in per_run.values())
    return (calls, sum(r[1] for r in per_run.values()),
            sum(r[2] for r in per_run.values()))


@reader("moe_expert_mm_roofline_pct")
def moe_expert_mm_roofline_pct(obs, op):
    """The least time the chip could take for the grouped matmuls the
    trace holds / their device time (the metric file's ``note`` says how
    the counters' window and the trace's are matched)."""
    t, c = obs.trace, _counters()
    sizes = obs.facts.get("sizes", {})
    if t is None or not t.devices or obs.peaks is None or c is None \
            or not {"hidden_size", "intermediate_size",
                    "num_hidden_layers"} <= set(sizes):
        return None
    calls, events, ns = layer_calls(t, t.devices[0], re.compile(op),
                                    sizes["num_hidden_layers"])
    if not events:
        return None
    pairs = calls * c["assignments"] / c["expert_calls"]
    touched = calls * c["experts_touched"] / c["expert_calls"]
    least, bound = opcount.roofline_seconds(
        expert_mm_ops(sizes, pairs),
        expert_mm_bytes(sizes, pairs, touched), obs.peaks)
    obs.say(f"expert matmul roofline: {events} matmuls in {calls:.1f} "
            f"layer calls, {pairs / calls:.1f} pairs and "
            f"{touched / calls:.2f} experts touched a call (counters), "
            f"bounded by {bound}; least {least * 1e3:.3f} ms of "
            f"{ns / 1e6:.3f} ms")
    return 100.0 * least / (ns / 1e9)
