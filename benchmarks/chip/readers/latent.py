"""The DeepSeek-V3 family's two layers from the device trace: the latent
decode kernel's share of its roofline, and the routed experts' matmuls'
of theirs at this family's sizes.

The kernel's work comes from the program's own count: every decode
dispatch adds the pooled tokens its rows attended to
``serving/latent_tokens_walked`` (``ServingMetrics.on_latent_walk``), and
each of the model's layers walks them once. The trace gives the calls
and their device time; the kernel's result ``<type>[slots, heads,
rank]`` gives the slots a dispatch offers (so ``serving/
decode_slots_offered / slots`` is the dispatches the counter covers) and
the item size of the pool it read. What a call must do is the family's
(``families/deepseek_v3.py latent_attn_ops``, ``latent_attn_bytes``).

The experts' reader is ``readers/lfm2.py``'s on this family's
``expert_sizes`` (``moe_intermediate_size`` wide, ``num_hidden_layers -
first_k_dense_replace`` layers); the ``moe/*`` counters count the routed
experts only, never the shared ones. A program that writes no such
counter (the parent's), sizes of another family or a trace without the
calls read as nothing."""

import re

from . import lfm2, moe, reader
from .. import opcount, xplane
from ..families import deepseek_v3 as family

_RESULT = re.compile(r"= (f32|bf16)\[(\d+),(\d+),(\d+)\]")
_ITEMSIZE = {"f32": 4, "bf16": 2}


def _is_family(sizes):
    return set(family.SIZE_KEYS) <= set(sizes)


def _counter(name):
    from deepspeed_tpu.observability.metrics import get_registry
    return get_registry().counter(name).value


@reader("latent_attn_roofline_pct")
def latent_attn_roofline_pct(obs, op, module=None):
    """The least time the chip could take for the latent kernel's calls
    in the trace (the larger of their operations / peak FLOP/s and their
    bytes / peak bytes/s) / the calls' device time."""
    t, sizes = obs.trace, obs.facts.get("sizes", {})
    walked = _counter("serving/latent_tokens_walked")
    offered = _counter("serving/decode_slots_offered")
    if t is None or not t.devices or obs.peaks is None or not walked \
            or not offered or not _is_family(sizes):
        return None
    calls = [(_RESULT.search(text), ns)
             for text, ns in xplane.op_ns(t, t.devices[0], op, module)]
    calls = [(m, ns) for m, ns in calls if m]
    if not calls:
        return None
    kind, slots = calls[0][0].group(1), int(calls[0][0].group(2))
    # a layer call of a dispatch walks the dispatch's tokens once
    tokens = len(calls) * walked / (offered / slots)
    least, bound = opcount.roofline_seconds(
        family.latent_attn_ops(sizes, tokens),
        family.latent_attn_bytes(sizes, tokens, _ITEMSIZE[kind]), obs.peaks)
    spent = sum(ns for _, ns in calls) / 1e9
    obs.say(f"latent kernel roofline: {len(calls)} calls of {slots} rows "
            f"over a {kind} pool, {tokens / len(calls):.0f} pooled tokens "
            f"walked a call (counters: {walked} over {offered // slots} "
            f"dispatches), bounded by {bound}; least {least * 1e3:.3f} ms "
            f"of {spent * 1e3:.3f} ms")
    return 100.0 * least / spent


@reader("deepseek_v3_expert_mm_roofline_pct")
def deepseek_v3_expert_mm_roofline_pct(obs, op):
    """The least time the chip could take for the routed experts'
    grouped matmuls the trace holds / their device time."""
    t, c = obs.trace, moe._counters()
    sizes = obs.facts.get("sizes", {})
    if t is None or not t.devices or obs.peaks is None or c is None \
            or not _is_family(sizes):
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
        lfm2.expert_mm_ops(d, f, pairs),
        lfm2.expert_mm_bytes(d, f, pairs, touched), obs.peaks)
    obs.say(f"expert matmul roofline: {events} matmuls in {calls:.1f} "
            f"layer calls of experts {f} wide, {pairs / calls:.1f} pairs "
            f"and {touched / calls:.2f} experts touched a call (counters), "
            f"bounded by {bound}; least {least * 1e3:.3f} ms of "
            f"{ns / 1e6:.3f} ms")
    return 100.0 * least / (ns / 1e9)
