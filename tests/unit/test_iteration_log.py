"""The server's log of its host loop (ISSUE 41): the registry's table
instrument; the phase clock's partition of one ``advance()`` exit to the
next under a stepped fake clock, and the accounts fed from the same
stamps; the collector's hook; ``ready``; the ``serving/caller`` span."""

import gc
import weakref
from array import array

import jax
import numpy as np
import pytest

from deepspeed_tpu.observability import (MetricsRegistry, Tracer, activate,
                                         deactivate, get_ledger,
                                         get_registry)
from deepspeed_tpu.serving import metrics as metrics_mod
from deepspeed_tpu.serving.metrics import (CALLER, EMPTY, GC,
                                           IN_ADVANCE, ITERATION_COLUMNS,
                                           READBACK, READY, T_ENTRY,
                                           ServingMetrics)

from .test_time_accounting import _inside, _paged_server

COL = {name: i for i, name in enumerate(ITERATION_COLUMNS)}
HOOK = metrics_mod.collector_hook


@pytest.fixture(autouse=True)
def _clean_tracer():
    yield
    deactivate()


# -- the table ---------------------------------------------------------------

def test_table_keeps_the_newest_rows_whole_and_in_column_order():
    reg = MetricsRegistry()
    t = reg.table("log", ("a", "b", "c"), rows=4)
    scratch = array("q", [0, 0, 0])
    for i in range(6):                      # wraps once
        scratch[0], scratch[1], scratch[2] = i, 10 * i, -i
        t.write(scratch)
    assert t.count == 6 and len(t) == 4
    assert t.read() == [(i, 10 * i, -i) for i in (2, 3, 4, 5)]
    t.write(array("q", [7, 70, -7]))
    assert t.read()[-1] == (7, 70, -7) and t.read()[0] == (3, 30, -3)
    with pytest.raises(ValueError, match="3 columns"):
        t.write(array("q", [1, 2]))
    with pytest.raises(TypeError):          # an array('q'), nothing else
        t.write([8, 80, -8])
    assert t.count == 7


def test_table_is_found_again_summarised_and_cleared():
    reg = MetricsRegistry()
    assert reg.table("log") is None         # a reader creates nothing
    assert "log" not in reg.snapshot()["tables"]
    t = reg.table("log", ("a", "b"), rows=2)
    assert reg.table("log") is t and reg.table("log", ("a", "b"), 99) is t
    assert t.rows == 2                      # keeps its first size
    assert reg.snapshot()["tables"]["log"] == {
        "columns": ["a", "b"], "capacity": 2, "count": 0, "retained": 0}
    for i in range(3):
        t.write(array("q", [i, i * i]))
    assert reg.snapshot()["tables"]["log"] == {
        "columns": ["a", "b"], "capacity": 2, "count": 3, "retained": 2,
        "last": {"a": 2, "b": 4}}
    reg.reset()
    assert reg.table("log") is None and reg.snapshot()["tables"] == {}


@pytest.mark.parametrize("other", ["counter", "gauge", "histogram"])
def test_a_table_and_another_instrument_cannot_share_a_name(other):
    reg = MetricsRegistry()
    reg.table("x", ("a",), 1)
    with pytest.raises(ValueError, match="already registered as a table"):
        getattr(reg, other)("x")
    getattr(reg, other)("y")
    with pytest.raises(ValueError, match=f"already registered as a {other}"):
        reg.table("y", ("a",), 1)
    with pytest.raises(ValueError, match="columns"):
        reg.table("x", ("b",), 1)
    with pytest.raises(ValueError, match="distinct"):
        reg.table("z", ("a", "a"), 1)


# -- the partition -----------------------------------------------------------

class SteppedClock:
    """``metrics._now`` for a test: every read moves time on by the next
    of a few unequal steps, so two phases never take the same time and
    nothing but a read moves the clock."""

    STEPS = (1_003, 7, 50_021, 311, 9_973)

    def __init__(self):
        self.t = 1_000_000_000
        self.reads = 0

    def __call__(self):
        self.t += self.STEPS[self.reads % len(self.STEPS)]
        self.reads += 1
        return self.t


class Run:
    """One engine driven under a stepped clock: each ``advance()`` with
    the clock's value before and after it, the row it wrote (or None),
    and the accounts' growth."""

    def __init__(self, prompts, max_new_tokens=3, prefill_only=False):
        self.clock = SteppedClock()
        mp = pytest.MonkeyPatch()
        mp.setattr(metrics_mod, "_now", self.clock)
        try:
            self.eng = eng = _paged_server()
            eng.set_prefill_role(prefill_only)
            table = eng.metrics.clock.table
            reg, ledger = get_registry(), get_ledger()
            hists = [reg.histogram("serving/advance_host_ms"),
                     reg.histogram("serving/advance_readback_ms")]
            self.calls = []
            self.compute0 = ledger.seconds["compute"]
            self.step(table, hists)                     # idle
            for p in prompts:
                eng.submit(p, max_new_tokens=max_new_tokens)
            for _ in range(40):
                if not eng.busy or eng._handoff_ready:
                    break       # done, or (prefill role) staged for export
                self.step(table, hists)
            self.compute = ledger.seconds["compute"] - self.compute0
            eng.close()
        finally:
            mp.undo()

    def step(self, table, hists):
        before, written = self.clock.t, table.count
        busy = self.eng.busy
        counts = [h.count for h in hists]
        self.eng.advance()
        row = table.read()[-1] if table.count > written else None
        assert table.count - written == (1 if busy else 0)
        samples = [h.window[-1] if h.count > c else None
                   for h, c in zip(hists, counts)]
        self.calls.append({"before": before, "after": self.clock.t,
                           "busy_at_entry": busy,
                           "busy_at_exit": self.eng.busy, "row": row,
                           "host_ms": samples[0],
                           "readback_ms": samples[1]})

    @property
    def rows(self):
        return [c for c in self.calls if c["row"] is not None]


def _prompts(*lengths):
    r = np.random.RandomState(5)
    return [r.randint(1, 97, size=n).astype(np.int32) for n in lengths]


@pytest.fixture(scope="module")
def served():
    return Run(_prompts(20, 5, 7))


@pytest.fixture(scope="module")
def prefilled():
    return Run(_prompts(20), prefill_only=True)


def _col(call, name):
    return call["row"][COL[name]]


# each shape of iteration, as a predicate on (call, the one before it)
SHAPES = {
    "idle call": lambda c: not c["busy_at_entry"],
    "admit only (prefill role)":
        lambda c: c["row"] and _col(c, "chunk_pages") > 0
        and _col(c, "decode_dispatch") < 1_000_000
        and _col(c, "rows_decoding") == 0 and _col(c, "readback") == 0,
    "chunk + decode":
        lambda c: c["row"] and _col(c, "chunk_pages") > 0
        and _col(c, "readback") == 0 and c["busy_at_exit"],
    "chunk + decode + harvest":
        lambda c: c["row"] and _col(c, "chunk_pages") > 0
        and _col(c, "readback") > 0,
    "decode + harvest":
        lambda c: c["row"] and _col(c, "chunk_pages") == 0
        and _col(c, "rows_decoding") > 0 and _col(c, "readback") > 0,
    "drain": lambda c: c["row"] and not c["busy_at_exit"],
}


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_every_nanosecond_from_exit_to_exit_has_one_phase(
        shape, served, prefilled):
    run = prefilled if "prefill role" in shape else served
    match = [c for c in run.calls if SHAPES[shape](c)]
    assert match, [c["row"] for c in run.calls]
    if shape == "idle call":
        # writes nothing; its time is the next row's `empty`
        idle, nxt = run.calls[0], run.rows[0]
        assert idle["row"] is None
        assert _col(nxt, "empty") > idle["after"] - idle["before"] >= 0
        assert _col(nxt, "caller") == 0
        return
    prev_exit = {id(c): p["after"] for p, c in zip(run.rows, run.rows[1:])}
    for c in match:
        row = c["row"]
        # the first read of the call is its entry, the last its exit
        assert c["before"] < row[T_ENTRY] <= c["after"]
        assert sum(row[IN_ADVANCE]) == c["after"] - row[T_ENTRY]
        assert all(v >= 0 for v in row)
        if id(c) in prev_exit:
            assert row[CALLER] + row[EMPTY] == row[T_ENTRY] - prev_exit[id(c)]
    if shape == "drain":
        assert _col(match[-1], "rows_decoding") == 0


def test_caller_or_empty_goes_by_what_was_in_flight_at_the_last_exit(served):
    rows = served.rows
    assert rows[0]["row"][CALLER] == 0 and rows[0]["row"][EMPTY] > 0
    for prev, c in zip(rows, rows[1:]):
        assert prev["busy_at_exit"]         # one burst: never empty again
        assert c["row"][EMPTY] == 0 and c["row"][CALLER] > 0
    # a second burst on the same engine would begin with `empty`: the
    # last exit of this one left nothing in flight
    assert not rows[-1]["busy_at_exit"]
    assert served.eng.metrics.clock.phase == EMPTY


def test_the_old_accounts_are_sums_of_the_rows_phases(served):
    """The parent timed ``advance()`` entry -> exit and the blocking
    reads with clock pairs of their own: the two histograms read the
    same intervals from the row's stamps. The goodput ledger's serving
    ``compute`` is the row's two dispatches, its read-back and its
    harvest: wider than the parent's four contexts (the test below)."""
    compute_ns = 0
    for c in served.rows:
        row = c["row"]
        total = c["after"] - row[T_ENTRY]
        assert c["readback_ms"] == row[READBACK] / 1e6
        assert c["host_ms"] == (total - row[READBACK]) / 1e6
        compute_ns += sum(row[COL[n]] for n in (
            "prefill_dispatch", "decode_dispatch", "readback", "harvest"))
    assert served.calls[0]["host_ms"] is None       # the idle call
    assert served.compute == pytest.approx(compute_ns / 1e9, rel=1e-9)
    assert compute_ns > 0
    # counts ride along: every chunk page and every decoding row
    m = served.eng.metrics
    assert sum(_col(c, "chunk_pages") for c in served.rows) \
        == m.prefill_chunk_pages
    assert sum(_col(c, "rows_decoding") for c in served.rows) \
        == m.decode_iterations * 2 - m.wasted_slot_steps
    assert all(_col(c, "traced") == 0 for c in served.rows)


def test_serving_compute_holds_the_parents_sites_and_more():
    """The parent's four ``_goodput("compute")`` contexts sat inside the
    spans ``serving/prefill_chunk``, ``serving/decode_iter`` (or
    ``serving/spec_verify``) and ``serving/harvest``, which are still
    there: on one clock the ledger's ``compute`` now holds every one of
    those intervals and the host's work around the enqueues (a chunk's
    width and padding, the counters after it, speculation's propose),
    and nothing from outside the dispatch, read-back and harvest
    phases."""
    eng = _paged_server()
    ledger, table = get_ledger(), eng.metrics.clock.table
    tracer = Tracer()
    activate(tracer)
    written, before = table.count, ledger.seconds["compute"]
    for p in _prompts(20, 5, 7):
        eng.submit(p, max_new_tokens=3)
    while eng.busy:
        eng.advance()
    deactivate()
    compute = ledger.seconds["compute"] - before
    rows = table.read()[written - table.count:]
    eng.close()
    sites = ("serving/prefill_chunk", "serving/decode_iter",
             "serving/spec_verify", "serving/harvest")
    parents = sum(e[2] for e in tracer.events if e[0] in sites) / 1e9
    phases = sum(r[COL[n]] for r in rows for n in (
        "prefill_dispatch", "decode_dispatch", "readback", "harvest")) / 1e9
    assert compute == pytest.approx(phases, rel=1e-9)
    assert 0 < parents < compute < sum(sum(r[IN_ADVANCE]) for r in rows) / 1e9


def test_a_stall_on_the_hosts_side_is_logged_with_its_phase(monkeypatch):
    m = ServingMetrics(registry=MetricsRegistry())
    clock = m.clock
    now = [0]
    monkeypatch.setattr(metrics_mod, "_now", lambda: now[0])

    def iteration(harvest_ms, readback_ms, ready):
        clock.enter(False)
        outer = clock.switch(metrics_mod.HARVEST)
        inner = clock.begin_readback(ready)
        now[0] += int(readback_ms * 1e6)
        clock.switch(inner)
        now[0] += int(harvest_ms * 1e6)
        clock.switch(outer)
        clock.exit(True)

    iteration(1, 120, ready=False)      # the device's: no stall
    iteration(1, 120, ready=True)       # the host's
    iteration(70, 2, ready=False)
    first, second = m.snapshot()["host_stall_log"]      # and no third
    assert (first["phase"], first["readback_ready"]) == ("readback", True)
    assert (second["phase"], second["phase_ms"]) == ("harvest", 70.0)
    assert [r[READY] for r in clock.table.read()] == [0, 1, 0]


# -- the collector -----------------------------------------------------------

@pytest.fixture
def alone(monkeypatch):
    """No other engine of this worker watches the collector."""
    monkeypatch.setattr(HOOK, "clocks", weakref.WeakSet())
    if HOOK in gc.callbacks:
        gc.callbacks.remove(HOOK)
    yield
    if HOOK in gc.callbacks:
        gc.callbacks.remove(HOOK)


@pytest.mark.parametrize("traced", [False, True], ids=["off", "traced"])
def test_a_collection_inside_a_callback_lands_in_gc(traced, alone):
    eng = _paged_server()
    assert gc.callbacks.count(HOOK) == 1
    reg = get_registry()
    pauses = reg.histogram("serving/gc_pause_ms")
    full = reg.counter("serving/gc_collections/gen2")
    before = (pauses.count, full.value)
    tracer = Tracer()
    if traced:
        activate(tracer)
    eng.submit(_prompts(6)[0], max_new_tokens=3,
               on_token=lambda _r, _t: gc.collect())
    written = eng.metrics.clock.table.count
    while eng.busy:
        eng.advance()
    deactivate()
    rows = eng.metrics.clock.table.read()[written - eng.metrics.clock.
                                          table.count:]
    assert full.value - before[1] == 3              # one a token
    assert pauses.count - before[0] >= 3
    hit = [r for r in rows if r[GC] > 0]
    assert len(hit) >= 2
    # it overlaps the phase it interrupted: the harvest holds it whole
    assert all(r[COL["harvest"]] >= r[GC] for r in hit
               if r[COL["admit"]] < r[GC])
    events = list(tracer.events)
    if traced:
        passes = [e for e in events if e[0] == "serving/gc"]
        assert len(passes) >= 3
        assert {e[4]["generation"] for e in passes} >= {2}
        harvests = [e for e in events if e[0] == "serving/harvest"]
        assert sum(_inside(e, harvests) for e in passes) >= 3
    else:
        assert not events
    eng.close()
    assert HOOK not in gc.callbacks
    assert eng.metrics.clock not in HOOK.clocks
    count = pauses.count
    gc.collect()
    assert pauses.count == count and eng.metrics.clock.gc_ns == 0


def test_one_hook_serves_two_engines_and_goes_with_the_last(alone):
    a, b = _paged_server(), _paged_server()
    assert gc.callbacks.count(HOOK) == 1
    pauses = get_registry().histogram("serving/gc_pause_ms")
    count = pauses.count
    before = (a.metrics.clock.gc_ns, b.metrics.clock.gc_ns)
    gc.collect()
    assert pauses.count == count + 1                # stamped once
    assert a.metrics.clock.gc_ns - before[0] \
        == b.metrics.clock.gc_ns - before[1] > 0
    a.close()
    assert HOOK in gc.callbacks
    b.close()
    assert HOOK not in gc.callbacks


def test_an_engine_dropped_without_close_takes_its_hook_along(alone):
    eng = _paged_server()
    assert HOOK in gc.callbacks
    eng._watchdog = None
    del eng
    gc.collect()            # frees it; the hook finds nobody watching
    gc.collect()
    assert HOOK not in gc.callbacks


# -- ready, and the caller's span --------------------------------------------

def test_ready_says_whether_the_tokens_had_arrived_before_the_read():
    eng = _paged_server()
    table = eng.metrics.clock.table
    eng.submit(_prompts(20)[0], max_new_tokens=10)
    eng.advance()                       # chunk + decode: nothing read yet
    assert table.read()[-1][READY] == 0 and table.read()[-1][READBACK] == 0
    while eng._prefill_tasks or len(eng._pending) > 1:
        eng.advance()                   # to one decode in flight
    for _ in range(3):
        # the dispatch in flight has finished before the next call reads
        # it (and that call's own dispatch is read by the one after)
        jax.block_until_ready([e[-3:-1] for e in eng._pending])
        eng.advance()
        row = table.read()[-1]
        assert row[READY] == 1 and row[READBACK] > 0
    eng.run()
    eng.close()


def test_the_callers_span_opens_only_with_work_in_flight():
    eng = _paged_server()
    tracer = Tracer()
    activate(tracer)
    eng.advance()                               # idle: no span
    assert eng._clock._caller_span is None
    eng.submit(_prompts(6)[0], max_new_tokens=3)
    exits_busy = 0
    while eng.busy:
        eng.advance()
        exits_busy += eng.busy
        assert (eng._clock._caller_span is not None) == eng.busy
    eng.advance()
    names = [e[0] for e in tracer.events]
    assert names.count("serving/caller") == exits_busy > 0
    advances = [e for e in tracer.events if e[0] == "serving/advance"]
    ends = sorted(a[1] + a[2] for a in advances)
    for c in (e for e in tracer.events if e[0] == "serving/caller"):
        # from one call's end to the next one's beginning, and no wider
        assert all(a[1] + a[2] <= c[1] or c[1] + c[2] <= a[1]
                   for a in advances)
        assert any(a[1] + a[2] <= c[1] for a in advances)
        assert not [t for t in ends if c[1] < t < c[1] + c[2]]
    # the table says which rows began under the tracer
    assert all(r[COL["traced"]] == 1
               for r in eng.metrics.clock.table.read()[-exits_busy - 1:])
    eng.close()


@pytest.mark.parametrize("ending", ["close", "cancel", "tracer_off"])
def test_the_callers_span_never_outlives_its_turn(ending):
    eng = _paged_server()
    tracer = Tracer()
    activate(tracer)
    req = eng.submit(_prompts(6)[0], max_new_tokens=8)
    eng.advance()
    assert eng._clock._caller_span is not None
    if ending == "close":
        eng.close()
    elif ending == "cancel":
        eng.cancel(req.request_id)
        eng.advance()                           # an idle call ends it too
    else:
        deactivate()
        eng.advance()
        assert eng._clock._caller_span is None  # and opens none untraced
    assert eng._clock._caller_span is None
    assert [e[0] for e in tracer.events].count("serving/caller") == 1
    eng.close()
