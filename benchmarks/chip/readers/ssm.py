"""The Falcon-H1 family's mixer from the device trace: the decode state
update's share of its roofline.

What a call must do is the family's (``families/falcon_h1.py
ssm_update_bytes``, ``ssm_update_ops``): the state of every row that
decodes read once and written once, and its inputs — the same work
whatever implements it, a kernel that skips the other rows or a program
that passes over all of them. The trace gives the calls (one a layer a
decode dispatch) and their device time; the state operand's type
``f32[slots, heads, d_state, d_head]`` in a call's text gives the slots a
dispatch offers; the rows that decode come from the program's own log of
the same window: the rows of ``serving/iterations`` that began under the
tracer (``traced`` 1) and dispatched a decode step, ``rows_decoding`` each
— their mean times the trace's calls, since a dispatch entered just before
the capture runs inside it and the last one's may run after (a call more
or less of ~600). Time and count are of one window: warm-up and the
lead-in's cold phase, whose dispatches hold fewer rows, are in neither.
Sizes of another family, a program without the table, no traced row or a
trace without the calls read as nothing."""

import re

from . import reader
from .. import opcount, xplane
from ..families import falcon_h1 as family
from .iterations import TABLE, rows_read

_STATE = re.compile(r"(f32|bf16)\[(\d+),(\d+),(\d+),(\d+)\]")
_ITEMSIZE = {"f32": 4, "bf16": 2}


def _traced_decode_rows():
    """``rows_decoding`` of every iteration that began under the tracer
    and dispatched a decode step."""
    from deepspeed_tpu.observability.metrics import get_registry
    find = getattr(get_registry(), "table", None)
    table = find(TABLE) if find is not None else None
    if table is None:
        return []
    return [r["rows_decoding"] for r in rows_read(table)[2]
            if r["rows_decoding"] > 0]


@reader("ssm_update_roofline_pct")
def ssm_update_roofline_pct(obs, op, module=None):
    """The least time the chip could take for the trace's state-update
    calls (the larger of their bytes / peak bytes/s and their operations
    / peak FLOP/s) / the calls' device time."""
    t, sizes = obs.trace, obs.facts.get("sizes", {})
    if t is None or not t.devices or obs.peaks is None \
            or not set(family.SIZE_KEYS) <= set(sizes):
        return None
    decoding = _traced_decode_rows()
    if not decoding:
        return None
    shape = family.state_shape(sizes)
    calls = []
    for text, ns in xplane.op_ns(t, t.devices[0], op, module):
        state = next((m for m in _STATE.finditer(text)
                      if tuple(map(int, m.groups()[2:])) == shape), None)
        if state is not None:
            calls.append((state, ns))
    if not calls:
        return None
    kind, slots = calls[0][0].group(1), int(calls[0][0].group(2))
    a_call = sum(decoding) / len(decoding)          # rows that decoded
    rows = len(calls) * a_call
    least, bound = opcount.roofline_seconds(
        family.ssm_update_ops(sizes, rows),
        family.ssm_update_bytes(sizes, rows, _ITEMSIZE[kind]), obs.peaks)
    spent = sum(ns for _, ns in calls) / 1e9
    obs.say(f"state update roofline: {len(calls)} calls over {slots} slots "
            f"of {kind} state, {a_call:.2f} rows decoding a call "
            f"({len(decoding)} traced decode dispatches of {TABLE}), "
            f"bounded by {bound}; least "
            f"{least * 1e3:.3f} ms of {spent * 1e3:.3f} ms")
    return 100.0 * least / spent
