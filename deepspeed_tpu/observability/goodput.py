"""Goodput/badput ledger: classify every wall-clock second of a run.

The PR-5/7 layers record *what happened* (spans, compile events,
resilience transitions); this layer answers the operator question *where
did the time go* by partitioning wall-clock into a fixed taxonomy
(the goodput/badput convention of the TPU-pod scaling literature —
arXiv 1909.09756 frames scale-out wins as accelerator-busy fractions):

- ``compute``            — goodput: the device is doing model work
                           (train dispatch + the wait for its results;
                           serving admit/prefill/decode/harvest)
- ``compile``            — badput: trace + XLA compile wall time
                           (program-registry compile events)
- ``checkpoint_save``    — badput: synchronous checkpoint writes
- ``rollback_recovery``  — badput: divergence rollback restore walks
- ``data_stall``         — badput: host-side batch prep / placement
- ``scheduler_idle``     — badput: everything unaccounted (queue gaps
                           between serving iterations, host bookkeeping,
                           time before/after the measured loop)

Two consumers, one classifier:

1. **Live ledger** (``get_ledger()``): engines wrap the SAME call sites
   their trace spans already wrap with ``timed(category)`` — two
   ``perf_counter`` reads per site, NO device syncs (the TS002 gate and
   the probe-count tests stay green by construction). Compile wall time
   arrives out-of-band from ``TrackedProgram`` via ``note_compile`` and
   is subtracted from the category that contained the compiling dispatch
   (the first ``fwd_bwd_step`` span includes its compile), so the
   fractions partition wall-clock without double counting.
2. **Post-hoc classifier** (``classify_spans``): the same taxonomy over
   a recorded span stream (a ``Tracer`` buffer or a trace.json), for
   tests with synthetic ground truth and for ``ds_tpu_report`` reading
   yesterday's capture.

``breakdown()`` returns seconds + fractions; the fractions sum to 1.0
exactly (``scheduler_idle`` is the remainder), which is the acceptance
invariant the endpoint tests scrape off ``/metrics``.

Stdlib-only (the dependency-free tooling contract of this package).
"""

import time
from typing import Dict, Optional

# the taxonomy; "compute" is goodput, everything else badput
CATEGORIES = ("compute", "compile", "checkpoint_save", "rollback_recovery",
              "data_stall", "scheduler_idle")

GOODPUT_CATEGORIES = ("compute",)

# span name -> category for the post-hoc classifier. Span names are the
# ones the engines already emit (docs/observability.md); prefix match
# handles the per-stage pipe spans.
SPAN_CATEGORIES = {
    "data": "data_stall",
    "fwd_bwd_step": "compute",
    "fwd": "compute",
    "bwd": "compute",
    "step": "compute",
    "pipe/fwd": "compute",
    "pipe/bwd": "compute",
    "pipe/step": "compute",
    "device_probe": "compute",       # blocked draining dispatched work
    "checkpoint_save": "checkpoint_save",
    "rollback_recovery": "rollback_recovery",
    "serving/prefill_chunk": "compute",
    "serving/decode_iter": "compute",
    "serving/harvest": "compute",    # waiting on dispatched decode output
    # residency-manager disk transfers (runtime/tiering/): the blocking
    # waits are I/O stalls. stage_in/stage_out themselves are left
    # uncategorized so the outermost-span rule books only the nested
    # swap waits, not the compute wait stage_out also contains.
    "tiering/swap_in": "data_stall",
    "tiering/swap_out": "data_stall",
}


class _Timed:
    """Tiny reusable timing context (the ledger analog of trace._Span):
    two ``perf_counter`` reads, one dict add. Never touches the device."""

    __slots__ = ("_ledger", "_category", "_t0")

    def __init__(self, ledger, category):
        self._ledger = ledger
        self._category = category
        self._t0 = 0.0

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        self._ledger.note(self._category, time.perf_counter() - self._t0)
        return False


class _NullTimed:
    """Shared no-op: the entire cost of ``timed()`` before any engine
    has started a ledger."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False


_NULL_TIMED = _NullTimed()


class GoodputLedger:
    """Online wall-clock partitioner. ``start()`` pins the epoch (first
    call wins — train and serving engines in one process share one
    ledger, like the memory accountant); ``note``/``timed`` accumulate
    seconds into categories; ``breakdown()`` partitions the elapsed wall
    clock, with the unaccounted remainder as ``scheduler_idle``."""

    def __init__(self):
        self.seconds: Dict[str, float] = {c: 0.0 for c in CATEGORIES}
        self._epoch: Optional[float] = None

    def start(self) -> "GoodputLedger":
        if self._epoch is None:
            self._epoch = time.perf_counter()
        return self

    @property
    def started(self) -> bool:
        return self._epoch is not None

    def reset(self):
        """Drop all accumulated time and re-pin the epoch to now (bench
        harnesses call this so a breakdown covers the measured window,
        not engine construction + warmup)."""
        self.seconds = {c: 0.0 for c in CATEGORIES}
        self._epoch = time.perf_counter()

    def note(self, category: str, seconds: float):
        if category not in self.seconds:
            raise ValueError(f"unknown goodput category {category!r}; "
                             f"known: {CATEGORIES}")
        if seconds > 0:
            self.seconds[category] += seconds

    def note_compile(self, seconds: float):
        """Compile wall time reported by a ``TrackedProgram``. The
        dispatch that compiled ran INSIDE a ``timed("compute")`` site
        (or a prefill/admit span), so the same interval is about to be
        (or was) accumulated as compute: ``breakdown`` re-attributes it
        by moving compile seconds out of compute."""
        if seconds > 0:
            self.seconds["compile"] += seconds

    def timed(self, category: str) -> _Timed:
        return _Timed(self, category)

    def breakdown(self) -> dict:
        """Seconds + fractions over the wall clock since the epoch.
        ``compute`` is reduced by the accumulated compile time (the
        compiling dispatches were timed as compute at their call sites);
        ``scheduler_idle`` absorbs the unaccounted remainder, so the
        fractions sum to 1.0 exactly — the acceptance invariant. Returns
        {} before ``start()``."""
        if self._epoch is None:
            return {}
        wall = time.perf_counter() - self._epoch
        return _finalize(dict(self.seconds), wall)


_LEDGER: Optional[GoodputLedger] = None


def get_ledger() -> GoodputLedger:
    """The process-wide shared ledger (train + serve share one wall
    clock, like ``get_registry()``/``get_accountant()``)."""
    global _LEDGER
    if _LEDGER is None:
        _LEDGER = GoodputLedger()
    return _LEDGER


def reset_ledger():
    """Fresh ledger with a fresh epoch (test isolation / bench windows)."""
    global _LEDGER
    _LEDGER = GoodputLedger()
    _LEDGER.start()
    return _LEDGER


def timed(category: str):
    """Module-level timing context: accumulates into the shared ledger
    when one has been started (an engine exists), else the shared no-op
    — one global load and an attribute check, the span() discipline."""
    ledger = _LEDGER
    if ledger is None or ledger._epoch is None:
        return _NULL_TIMED
    return ledger.timed(category)


def note(category: str, seconds: float):
    """Seconds a caller timed itself, into the shared ledger when one
    has been started (``timed()``'s rule): the serving engine's phase
    clock hands over an iteration's ``compute`` from the stamps it took
    anyway."""
    ledger = _LEDGER
    if ledger is not None and ledger._epoch is not None:
        ledger.note(category, seconds)


def note_compile(seconds: float):
    """Out-of-band compile attribution from ``TrackedProgram`` (dropped
    when no ledger is live — library users without an engine)."""
    ledger = _LEDGER
    if ledger is not None and ledger._epoch is not None:
        ledger.note_compile(seconds)


def _finalize(secs: Dict[str, float], wall: float) -> dict:
    """The one partition rule both consumers share (the live ledger's
    ``breakdown`` and the post-hoc ``classify_spans`` — one
    implementation, the PR-5 percentile-drift lesson): re-attribute
    compile out of the compute that timed it, absorb the unaccounted
    remainder into ``scheduler_idle``, and normalize over
    max(wall, accounted) so clock skew / overlapping sites can never
    push the fraction sum past 1.0."""
    stolen = min(secs["compute"], secs["compile"])
    secs["compute"] -= stolen
    accounted = sum(v for c, v in secs.items() if c != "scheduler_idle")
    denom = max(wall, accounted)
    secs["scheduler_idle"] += max(0.0, denom - accounted
                                  - secs["scheduler_idle"])
    fractions = {c: (secs[c] / denom if denom > 0 else 0.0)
                 for c in CATEGORIES}
    good = sum(fractions[c] for c in GOODPUT_CATEGORIES)
    return {
        "wall_s": wall,
        "seconds": secs,
        "fractions": fractions,
        "goodput_fraction": good,
        "badput_fraction": max(0.0, 1.0 - good),
    }


# ---------------------------------------------------------------------------
# Post-hoc classification of a recorded span stream
# ---------------------------------------------------------------------------

def classify_spans(events, wall_ns: Optional[int] = None) -> dict:
    """Partition a span stream (``Tracer.events`` tuples) into the
    goodput taxonomy. Only OUTERMOST categorized spans count — a
    categorized span fully inside another categorized span on the same
    thread is skipped, so nesting (e.g. a future ``checkpoint_save``
    inside ``rollback_recovery``) never double-counts.

    ``wall_ns`` is the denominator; default = the stream's first-start
    to last-end extent. The remainder lands in ``scheduler_idle`` and
    the returned fractions sum to 1.0 (the same contract as the live
    ledger's ``breakdown``)."""
    spans = [(t0, t0 + dur, name, tid)
             for name, t0, dur, tid, _args in events
             if dur is not None and _category_of(name) is not None]
    spans.sort(key=lambda s: (s[3], s[0], -s[1]))
    secs = {c: 0.0 for c in CATEGORIES}
    first, last = None, None
    cover_end = {}                       # tid -> end of the covering span
    for t0, t1, name, tid in spans:
        first = t0 if first is None else min(first, t0)
        last = t1 if last is None else max(last, t1)
        if t1 <= cover_end.get(tid, -1):
            continue                     # nested inside a counted span
        cover_end[tid] = t1
        secs[_category_of(name)] += (t1 - t0) / 1e9
    if first is None:
        return {}
    wall = (wall_ns if wall_ns is not None else (last - first)) / 1e9
    return _finalize(secs, wall)


def _category_of(name) -> Optional[str]:
    if not isinstance(name, str):
        return None
    return SPAN_CATEGORIES.get(name)


def format_goodput(breakdown: dict) -> str:
    """Render a ``breakdown()`` dict as the goodput/badput text table
    (``ds_tpu_report`` / ``/statusz``). Badput categories print under a
    ``badput/`` prefix so a rollback is visibly attributed."""
    if not breakdown:
        return "(no goodput recorded)"
    lines = [f"wall: {breakdown['wall_s']:.3f}s   goodput "
             f"{breakdown['goodput_fraction']:.1%} / badput "
             f"{breakdown['badput_fraction']:.1%}"]
    for cat in CATEGORIES:
        label = cat if cat in GOODPUT_CATEGORIES else f"badput/{cat}"
        lines.append(f"  {label:<26} {breakdown['seconds'][cat]:>10.3f}s  "
                     f"{breakdown['fractions'][cat]:>7.2%}")
    return "\n".join(lines)
