"""The LFM2 family's expert layer from the device trace. ``readers/moe.py``
takes an expert's width from ``sizes["intermediate_size"]`` and the number
of expert layers from ``sizes["num_hidden_layers"]``; in this family those
are the dense layers' width and all the layers, conv and dense ones
included. This reader asks the family (``families/lfm2.py expert_sizes``)
and keeps its own count of a call's operations and bytes; the trace's
layer calls and the program's ``moe/*`` counters are read as ``moe.py``
reads them. Sizes of another family, a program that counts no routing or
a trace without a grouped matmul read as nothing."""

import re

from . import moe, reader
from .. import opcount
from ..families import lfm2 as family


def expert_mm_ops(d, f, assignments):
    """Operations of one layer call's three grouped matmuls over
    ``assignments`` token-expert pairs: gate and up ``[d] x [d, f]``,
    down ``[f] x [f, d]``; a multiply-add counts as 2."""
    return 2 * 3 * d * f * assignments


def expert_mm_bytes(d, f, assignments, experts_touched, itemsize=2):
    """The least one layer call's three grouped matmuls must move: the
    three matrices of the experts that got at least one row, plus each
    matmul's rows in and out (gate and up read ``d`` and write ``f``;
    down reads ``f`` and writes ``d`` in float32)."""
    weights = experts_touched * 3 * d * f * itemsize
    rows = assignments * (2 * (d + f) * itemsize + f * itemsize + d * 4)
    return weights + rows


@reader("lfm2_expert_mm_roofline_pct")
def lfm2_expert_mm_roofline_pct(obs, op):
    """The least time the chip could take for the grouped matmuls the
    trace holds / their device time."""
    t, c = obs.trace, moe._counters()
    sizes = obs.facts.get("sizes", {})
    if t is None or not t.devices or obs.peaks is None or c is None \
            or not {"hidden_size", "moe_intermediate_size",
                    "num_hidden_layers", "num_dense_layers"} <= set(sizes):
        return None
    experts = family.expert_sizes(sizes)
    d, f = experts["hidden_size"], experts["intermediate_size"]
    calls, events, ns = moe.layer_calls(t, t.devices[0], re.compile(op),
                                        experts["num_hidden_layers"])
    if not events:
        return None
    pairs = calls * c["assignments"] / c["expert_calls"]
    touched = calls * c["experts_touched"] / c["expert_calls"]
    least, bound = opcount.roofline_seconds(
        expert_mm_ops(d, f, pairs), expert_mm_bytes(d, f, pairs, touched),
        obs.peaks)
    obs.say(f"expert matmul roofline: {events} matmuls in {calls:.1f} "
            f"layer calls of experts {f} wide, {pairs / calls:.1f} pairs "
            f"and {touched / calls:.2f} experts touched a call (counters), "
            f"bounded by {bound}; least {least * 1e3:.3f} ms of "
            f"{ns / 1e6:.3f} ms")
    return 100.0 * least / (ns / 1e9)
