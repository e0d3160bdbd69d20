"""The reduction from a profiler trace (``.xplane.pb``, optionally
gzipped) to numbers: device busy and idle time, time per operation and
per kernel, the share of the window in which only a collective runs, and
the host span that covers each idle gap. One module, read by every
trace-based per-layer metric, and checked on the recorded trace in
``fixtures/``.

What a TPU trace holds (jax 0.9.0, v5e): one plane ``/device:TPU:<n>``
per chip with the lines ``XLA Modules`` (one event per run of a jitted
program, named ``jit_<fn>(<fingerprint>)``) and ``XLA Ops`` (one event
per HLO operation, named by the instruction's whole text; control flow
such as ``%while`` contains its body's events). ``/host:CPU`` holds the
host threads; ``TraceAnnotation``s (the program's spans, the benchmark's
``bench/*``) are events there. Times are nanoseconds on one axis; host
and device agree to within about a millisecond (a device program has
been seen to start 0.6 ms before the host span that launched it)."""

import bisect
import gzip
import re

WINDOW_SPAN = "bench/trace_window"
SPAN_PREFIXES = ("serving/", "train/", "bench/")
SPAN_NAMES = ("fwd_bwd_step", "step", "data", "fwd", "bwd")
SHORT_GAP_NS = 2_000
_COLLECTIVE = re.compile(
    r" (all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast)(-start|-done)?\(")
_OPCODE = re.compile(r"^%(?P<name>[^ ]+) = .*?(?P<shape>[a-z0-9]+\[[0-9,]*\])"
                     r".*? (?P<op>[a-z][a-z0-9-]*)\(")


class Device:
    def __init__(self, name, ops, modules):
        self.name = name
        self.ops = ops              # [(start_ns, end_ns, text)], sorted
        self.modules = modules      # [(start_ns, end_ns, name)], sorted


class Trace:
    def __init__(self, devices, spans):
        self.devices = devices
        self.spans = spans          # [(start_ns, end_ns, name)], host
        win = [s for s in spans if s[2] == WINDOW_SPAN]
        if win:
            self.t0, self.t1 = win[0][0], win[0][1]
        else:
            every = [e for d in devices for e in d.ops]
            self.t0 = min((e[0] for e in every), default=0.0)
            self.t1 = max((e[1] for e in every), default=0.0)

    @property
    def window_s(self):
        return (self.t1 - self.t0) / 1e9


def load(path):
    import jax
    raw = open(path, "rb").read()
    if path.endswith(".gz"):
        raw = gzip.decompress(raw)
    data = jax.profiler.ProfileData.from_serialized_xspace(raw)
    devices, spans = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            devices.append(Device(
                plane.name, _events(lines.get("XLA Ops")),
                _events(lines.get("XLA Modules"))))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                spans.extend(e for e in _events(line) if _is_span(e[2]))
    return Trace(sorted(devices, key=lambda d: d.name), sorted(spans))


def _events(line):
    if line is None:
        return []
    return sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                  for e in line.events)


def _is_span(name):
    return name.startswith(SPAN_PREFIXES) or name in SPAN_NAMES


def clip(events, t0, t1):
    """Events cut to ``[t0, t1]``; those outside are dropped."""
    return [(max(s, t0), min(e, t1), n) for s, e, n in events
            if e > t0 and s < t1]


def union(intervals):
    """Merged ``[(start, end)]`` of possibly overlapping intervals."""
    out = []
    for s, e in sorted((s, e) for s, e, *_ in intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals):
    return sum(e - s for s, e in intervals)


def busy_ns(trace, device):
    """Nanoseconds of the window in which an operation ran on the device."""
    return total(union(clip(device.ops, trace.t0, trace.t1)))


def busy_s(trace):
    """Device busy seconds in the window, averaged over the chips."""
    if not trace.devices:
        return 0.0
    return sum(busy_ns(trace, d) for d in trace.devices) \
        / len(trace.devices) / 1e9


def self_times(events):
    """``[(text, self_ns)]``: each operation's time less the time of the
    operations it contains, so a ``%while`` does not count its body
    twice. ``events`` are sorted by start."""
    out, stack = [], []         # stack of [start, end, text, child_ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            s, e, text, child = stack.pop()
            out.append((text, (e - s) - child))
            if stack:
                stack[-1][3] += e - s

    for s, e, text in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        close(s)
        stack.append([s, e, text, 0.0])
    close(float("inf"))
    return out


def short_name(text):
    """``attn.25 custom-call bf16[32,12,1024,64]`` from an instruction's
    whole text: its name, operation and first result shape."""
    m = _OPCODE.match(text)
    if not m:
        return text[:60]
    return f"{m['name']} {m['op']} {m['shape']}"[:80]


def top_ops(trace, n=10):
    """The operations with most self time in the window on the first
    chip, ``[[name, seconds]]``."""
    if not trace.devices:
        return []
    per = {}
    for text, ns in self_times(clip(trace.devices[0].ops,
                                    trace.t0, trace.t1)):
        key = short_name(text)
        per[key] = per.get(key, 0.0) + ns
    ranked = sorted(per.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def _in_modules(device, module_re, t0, t1):
    pat = re.compile(module_re)
    return [(s, e) for s, e, n in clip(device.modules, t0, t1)
            if pat.search(n)]


def op_ns(trace, device, op_re, module_re=None):
    """Nanoseconds of the operations whose text matches ``op_re``, inside
    runs of the programs whose name matches ``module_re``; with each
    match, so that a caller can read shapes: ``[(text, ns)]``."""
    pat = re.compile(op_re)
    ops = clip(device.ops, trace.t0, trace.t1)
    if module_re is not None:
        # runs of programs on one chip do not overlap: find the last one
        # that starts at or before the operation
        spans = _in_modules(device, module_re, trace.t0, trace.t1)
        starts = [s for s, _ in spans]
        inside = []
        for op in ops:
            i = bisect.bisect_right(starts, op[0]) - 1
            if i >= 0 and op[0] < spans[i][1]:
                inside.append(op)
        ops = inside
    return [(text, e - s) for s, e, text in ops if pat.search(text)]


def module_busy_ns(trace, device, module_re):
    """Busy nanoseconds inside runs of the matching programs."""
    spans = _in_modules(device, module_re, trace.t0, trace.t1)
    busy = union(clip(device.ops, trace.t0, trace.t1))
    return sum(max(0.0, min(e, be) - max(s, bs))
               for s, e in spans for bs, be in busy)


def collective_exposed_ns(trace, device):
    """Nanoseconds in which the core ran a collective operation and so no
    compute: the self time of collective instructions on the ``XLA Ops``
    line (a ``-done`` that waits, or a collective that is not async)."""
    return sum(ns for text, ns in self_times(clip(device.ops,
                                                  trace.t0, trace.t1))
               if _COLLECTIVE.search(text))


def idle_gaps(trace, n=10):
    """The idle time of the first chip by what the host was doing:
    each gap between device operations goes to the shortest host span
    that covers its middle. ``[[span, seconds]]``, most first."""
    if not trace.devices:
        return []
    busy = union(clip(trace.devices[0].ops, trace.t0, trace.t1))
    edges = [trace.t0] + [t for iv in busy for t in iv] + [trace.t1]
    per = {}
    for s, e in zip(edges[::2], edges[1::2]):
        if e <= s:
            continue
        if e - s < SHORT_GAP_NS:
            key = "_gaps_under_2_us_"
        else:
            mid = (s + e) / 2
            cover = [sp for sp in trace.spans if sp[0] <= mid < sp[1]]
            key = (min(cover, key=lambda sp: sp[1] - sp[0])[2]
                   if cover else "_no_span_")
        per[key] = per.get(key, 0.0) + (e - s)
    ranked = sorted(per.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]
