"""The Phi-4-mini-flash family's two decode kernels from the device
trace: the share of its roofline of the paged kernel over the one paged
layer's pages (the full layer's call and the cross layers'), and of the
contiguous decode kernel on the window rings.

What a call must move is the family's (``families/phi4flash.py
shared_kv_attn_bytes``, ``window_attn_bytes``).
The kernels' tokens come from the program's own counts: every decode
dispatch adds what a call over the pages and a call over a ring read to
``serving/shared_kv_tokens_walked`` and ``serving/ring_tokens_read``
(``ServingMetrics.on_ring_walk``); a kernel's result ``<type>[slots, ...]``
gives the slots a dispatch offers, so ``serving/decode_slots_offered /
slots`` is the dispatches the counters cover — the whole process, whose
lead-in holds shorter contexts than the traced window: the share reads a
little low, never high (``readers/latent.py`` has the same). The mixers'
decode update has no roofline share: it has no call of its own, XLA
keeps the slots' states in the chip's second memory space behind
asynchronous copies that overlap other work, and no span of the trace is
the update's alone (the fusions that touch a state leaf read 7 times
over what the bytes allow at HBM's speed: PERF.md section 5, PR 56). A
program that writes no such counter (the parent's), sizes of another
family or a trace without the operations read as nothing."""

import math
import re

from . import reader
from .. import opcount, xplane
from ..families import phi4flash as family

_RESULT = re.compile(r"= (f32|bf16)\[(\d+),")
_ARRAY = re.compile(r"(f32|bf16)\[([\d,]+)\]")
_ITEMSIZE = {"f32": 4, "bf16": 2}


def _is_family(sizes):
    return set(family.SIZE_KEYS) <= set(sizes)


def _counter(name):
    from deepspeed_tpu.observability.metrics import get_registry
    return get_registry().counter(name).value


@reader("kv_attn_roofline_pct")
def kv_attn_roofline_pct(obs, op, counter, module=None):
    """The least time the chip could take for the matching kernel calls
    (their tokens' keys and values once over the memory's speed: a decode
    kernel's operations are a hundredth of that) / the calls' device
    time. ``counter`` names the tokens the process's calls read."""
    t, sizes = obs.trace, obs.facts.get("sizes", {})
    if t is None or not t.devices or obs.peaks is None \
            or not _is_family(sizes):
        return None
    walked = _counter(counter)
    offered = _counter("serving/decode_slots_offered")

    def cached(text):
        """The largest operand: the pages, or the rings."""
        found = list(_ARRAY.finditer(text.split("(", 1)[-1]))
        return max(found, key=lambda m: math.prod(
            map(int, m.group(2).split(","))), default=None)
    calls = [(_RESULT.search(text), cached(text), ns)
             for text, ns in xplane.op_ns(t, t.devices[0], op, module)]
    calls = [c for c in calls if c[0] and c[1]]
    if not calls or not walked or not offered:
        return None
    slots, kind = int(calls[0][0].group(2)), calls[0][1].group(1)
    tokens = len(calls) * walked / (offered / slots)
    d = sizes["hidden_size"] // sizes["num_attention_heads"]
    ops = 2 * 2 * sizes["num_attention_heads"] * 2 * d * tokens
    least, bound = opcount.roofline_seconds(
        ops, family.shared_kv_attn_bytes(sizes, tokens, _ITEMSIZE[kind]),
        obs.peaks)
    spent = sum(ns for _, _, ns in calls) / 1e9
    obs.say(f"{counter.split('/')[1]}: {len(calls)} calls of {slots} rows "
            f"over {kind} keys and values, {tokens / len(calls):.0f} tokens "
            f"read a call (counters: {walked} over {offered // slots} "
            f"dispatches), bounded by {bound}; least {least * 1e3:.3f} ms "
            f"of {spent * 1e3:.3f} ms")
    return 100.0 * least / spent
