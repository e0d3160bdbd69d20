"""The per-layer metrics that read the server's log of its host loop
(ISSUE 41): the four readers of ``readers/iterations.py`` on a hand-made
``serving/iterations`` table, every metric file against its manifest
entry, and a traced rehearsal of two cells that says a value for each."""

import os
from array import array

import pytest

from benchmarks.chip import manifest as manifest_mod
from benchmarks.chip import readers
from benchmarks.chip.readers import iterations
from deepspeed_tpu.observability import metrics as registry_mod
from deepspeed_tpu.serving.metrics import ITERATION_COLUMNS, ITERATION_ROWS

from ._paths import BENCH, PYTHONPATH, ROOT, RUN, manifest
from .test_program_readers import Say, entry, spec
from .test_rehearse import _last_line, _run

M = manifest()
# the closed-loop cells, less ``serve-kanana-docqa``: its own test holds
# that no metric but PR 37's lists it, until a ``benchmark`` PR says
# otherwise (PERF.md section 7)
CLOSED = ["serve-1p3b-longprompt", "serve-olmoe-longgen", "serve-lfm2-agent"]
CHAT = ["serve-1p3b-chat"]
LOAD, HOST = "load generator", "server host loop"
# ISSUE 41's table: name -> (unit, layer, reader)
ISSUE_41 = {
    "serve.caller_share_pct": ("%", LOAD, "iterations_share_pct"),
    "serve.empty_share_pct": ("%", LOAD, "iterations_share_pct"),
    "serve.prefill_dispatch_ms_p50": ("ms", HOST, "iterations_percentile_ms"),
    "serve.decode_dispatch_ms_p50": ("ms", HOST, "iterations_percentile_ms"),
    "serve.harvest_ms_p50": ("ms", HOST, "iterations_percentile_ms"),
    "serve.host_stall_ms_max": ("ms", HOST, "iterations_max_ms"),
    "serve.host_stalls": ("count", HOST, "iterations_count"),
    "serve.readback_ready_ms_max": ("ms", HOST, "iterations_max_ms"),
    "serve.gc_share_pct": ("%", HOST, "iterations_share_pct"),
}
NAMES = sorted(n + s for n in ISSUE_41 for s in ("", ".chat"))

MS = 1_000_000


def row(**cells):
    """One row in the table's column order, in ns; what is not named
    is 0."""
    assert set(cells) <= set(ITERATION_COLUMNS)
    return array("q", [cells.get(c, 0) for c in ITERATION_COLUMNS])


# warm-up's two runs of one iteration each, a lead-in of four, then the
# profiler: what each reader must and must not see
ROWS = [
    # warm-up's first call compiled: set aside whole
    row(t_entry=0, empty=900 * MS, prefill_dispatch=2000 * MS,
        decode_dispatch=3000 * MS, other=MS, chunk_pages=1, compiles=2),
    # warm-up's second run: a chunk and a decode; nothing read back yet
    row(t_entry=6000 * MS, empty=100 * MS, admit=MS,
        prefill_dispatch=4 * MS, decode_dispatch=MS, other=MS,
        chunk_pages=4, rows_decoding=0),
    # the server was empty a third time: the run proper. Steady decode:
    # the device's wait is no stall of the host's
    row(t_entry=6010 * MS, empty=MS, decode_dispatch=2 * MS,
        readback=120 * MS, harvest=3 * MS, other=MS, rows_decoding=8),
    # a read-back of arrays that were ready took 60 ms: the host's
    row(t_entry=6140 * MS, caller=3 * MS, decode_dispatch=MS,
        readback=60 * MS, harvest=MS, other=MS, rows_decoding=8, ready=1),
    # a full pass of the collector inside a harvest
    row(t_entry=6210 * MS, caller=MS, prefill_dispatch=6 * MS,
        decode_dispatch=3 * MS, readback=10 * MS, harvest=90 * MS,
        other=MS, gc=80 * MS, rows_decoding=7, chunk_pages=1),
    row(t_entry=6330 * MS, caller=5 * MS, decode_dispatch=MS,
        readback=2 * MS, harvest=MS, other=MS, rows_decoding=8),
    # under the profiler, and the drain after it: never read
    row(t_entry=6350 * MS, caller=MS, decode_dispatch=900 * MS,
        readback=MS, harvest=MS, rows_decoding=8, ready=1, traced=1),
    row(t_entry=7300 * MS, caller=700 * MS, decode_dispatch=MS,
        readback=500 * MS, harvest=MS, rows_decoding=8, ready=1),
]


@pytest.fixture
def registry(monkeypatch):
    reg = registry_mod.MetricsRegistry()
    monkeypatch.setattr(registry_mod, "_DEFAULT_REGISTRY", reg)
    return reg


@pytest.fixture
def table(registry):
    t = registry.table(iterations.TABLE, ITERATION_COLUMNS, 64)
    for r in ROWS:
        t.write(r)
    return t


def _read(metric):
    """A metric file's reader with its ``args``, as ``run.py`` calls it."""
    s = spec(metric)
    say = Say()
    obs = readers.Observed(setup={}, series={}, trace=None, peaks=None,
                           facts={}, say=say)
    return readers.load_all()[s["reader"]](obs, **s.get("args", {})), say


def test_the_rows_read_stop_at_the_tracer_and_leave_out_compiles(table):
    rows, compiled, traced = iterations.rows_read(table)
    assert compiled == 1
    # the capture's one row is spoken of, never read; the drain is neither
    assert [r["t_entry"] // MS for r in traced] == [6350]
    assert [r["t_entry"] // MS for r in rows] == [6000, 6010, 6140, 6210,
                                                  6330]
    assert [r["advance"] // MS for r in rows] == [7, 126, 63, 110, 5]
    assert [r["wall"] // MS for r in rows] == [107, 127, 66, 111, 10]
    # a read-back counts against the host only when its arrays were ready
    assert [r["host"] // MS for r in rows] == [7, 6, 63, 100, 3]
    # warm-up's rows are the first two stretches that open on `empty`
    assert [r["part"] for r in rows] == [
        iterations.WARM, iterations.FIRST] + 3 * [iterations.RUN]


# what each metric reads on ROWS, worked by hand
EXPECTED = {
    "serve.caller_share_pct": 100.0 * 9 / (9 + 311),
    "serve.empty_share_pct": 100.0 * 101 / 421,
    "serve.prefill_dispatch_ms_p50": 4.0,       # of 4 and 6
    "serve.decode_dispatch_ms_p50": 1.0,        # of 2, 1, 3, 1
    "serve.harvest_ms_p50": 1.0,                # of 3, 1, 90, 1
    "serve.host_stall_ms_max": 100.0,
    "serve.host_stalls": 2.0,                   # 63 and 100 ms
    "serve.readback_ready_ms_max": 60.0,
    "serve.gc_share_pct": 100.0 * 80 / 421,
}


@pytest.mark.parametrize("name", NAMES)
def test_metric_reads_the_hand_made_table(name, table):
    got, say = _read(name)
    assert got == pytest.approx(EXPECTED[name.removesuffix(".chat")])
    assert say.lines[0] == (
        "serving/iterations: 5 rows read (1 of them warm-up's), 1 that "
        "compiled set aside, of 8 retained and 8 written")
    assert say.lines[1] == (
        "serving/iterations: not read, the 1 rows that began under the "
        "tracer (0.903s): caller 0.11%, empty 0.00%, gc 0.00%, readback "
        "0.11%")
    del say.lines[1]
    if "host_stall_ms_max" in name:
        # it says which row, which phase, and that the collector was in it
        assert "most in harvest (90.000 ms)" in say.lines[1]
        assert "gc 80.000 ms" in say.lines[1]
        assert "0.120s before the last one read" in say.lines[1]
    if "host_stalls" in name:
        assert len(say.lines) == 4
        assert "most in readback (60.000 ms)" in say.lines[1]
        assert "ready=1" in say.lines[1]
        assert say.lines[3] == ("host over 50 ms: 2 rows, 0 of them "
                                "warm-up's, 0 the first after")


@pytest.mark.parametrize("name", NAMES)
def test_metric_reads_nothing_from_an_empty_or_absent_table(name, registry):
    assert _read(name)[0] is None                   # no such table
    assert registry.table(iterations.TABLE) is None     # and made none
    registry.table(iterations.TABLE, ITERATION_COLUMNS, 4)
    assert _read(name)[0] is None                   # no row
    registry.table(iterations.TABLE).write(
        row(t_entry=1, decode_dispatch=MS, traced=1))
    assert _read(name)[0] is None                   # none before the tracer


def test_a_stall_of_warm_ups_is_counted_and_said_to_be_warm_ups(registry):
    """Warm-up's first calls and the lead-in's first admission (a closed
    loop's 32 callers in one call) take 50-130 ms on the chip: they are in
    the count, as ISSUE 41 cut the rows, and the run says whose each was."""
    t = registry.table(iterations.TABLE, ITERATION_COLUMNS, 8)
    t.write(row(t_entry=0, empty=5 * MS, admit=70 * MS, other=MS))
    t.write(row(t_entry=80 * MS, empty=MS, harvest=60 * MS, readback=MS))
    t.write(row(t_entry=150 * MS, empty=MS, admit=90 * MS))   # the lead-in
    t.write(row(t_entry=250 * MS, caller=MS, decode_dispatch=MS))
    got, say = _read("serve.host_stalls")
    assert got == 3.0
    assert [ln.split(": ")[1].split(" that")[0] for ln in say.lines[1:4]] \
        == ["warm-up's row", "warm-up's row", "the first row after warm-up"]
    assert say.lines[4] == ("host over 50 ms: 3 rows, 2 of them warm-up's, "
                            "1 the first after")
    got, say = _read("serve.host_stall_ms_max")
    assert got == 90.0
    assert "largest host: the first row after warm-up that" in say.lines[1]


def test_an_older_program_without_the_instrument_reads_as_nothing(
        monkeypatch):
    class Older:
        """The registry of a program from before ISSUE 41."""

        def counter(self, name):
            raise AssertionError("not asked")

    monkeypatch.setattr(registry_mod, "_DEFAULT_REGISTRY", Older())
    for name in NAMES:
        assert _read(name)[0] is None


def test_no_row_ready_reads_as_zero_and_a_ring_that_wrapped_says_so(registry):
    t = registry.table(iterations.TABLE, ITERATION_COLUMNS, 3)
    for i in range(5):
        t.write(row(t_entry=i * MS, caller=MS, decode_dispatch=MS,
                    readback=MS, rows_decoding=1))
    got, say = _read("serve.readback_ready_ms_max")
    assert got == 0.0
    # (a ring that wrapped no longer holds warm-up's rows: none is marked)
    assert say.lines == ["serving/iterations: 3 rows read (0 of them "
                         "warm-up's), 0 that compiled set aside, of 3 "
                         "retained and 5 written"]
    assert _read("serve.host_stalls")[0] == 0.0
    # the process's whole log fits: over 70 s of the fastest iterations
    assert ITERATION_ROWS * 4.6e-3 > 70


@pytest.mark.parametrize("name", NAMES)
def test_metric_file_is_the_issues_row_and_agrees_with_the_manifest(name):
    s = spec(name)
    unit, layer, reader = ISSUE_41[name.removesuffix(".chat")]
    chat = name.endswith(".chat")
    assert set(s) == {"name", "unit", "better", "source", "layer", "moves",
                      "workloads", "reader", "args", "note"}
    assert (s["name"], s["unit"], s["better"], s["source"], s["layer"],
            s["reader"]) == (name, unit, "lower", "program_span", layer,
                             reader)
    assert s["workloads"] == (CHAT if chat else CLOSED)
    assert s["moves"] == ("itl_p90_ms" if chat else "serve_tokens_per_s")
    assert s["reader"] in readers.load_all()
    assert "before the first that began under the profiler" in s["note"]
    # the manifest's entry is the file less reader, args and note, and it
    # moves an end-to-end metric that each of its cells reports
    assert next(m for m in M["per_layer"] if m["name"] == name) \
        == entry(name)
    for cell in s["workloads"]:
        reported = [m["name"] for m in
                    manifest_mod.Cell(ROOT, M, cell).end_to_end()]
        assert s["moves"] in reported
    # the two files of one metric differ in nothing else
    other = spec(name.removesuffix(".chat") if chat else name + ".chat")
    assert {k: s[k] for k in ("unit", "better", "source", "layer", "reader",
                              "args", "note")} \
        == {k: other[k] for k in ("unit", "better", "source", "layer",
                                  "reader", "args", "note")}


def test_the_manifest_gained_these_entries_at_its_end_and_nothing_else():
    names = [m["name"] for m in M["per_layer"]]
    assert len(names) <= 128
    ours = [n for n in names if n.removesuffix(".chat") in ISSUE_41]
    assert sorted(ours) == NAMES
    assert names[-len(ours):] == ours
    on_disk = {f[:-5] for f in os.listdir(os.path.join(BENCH, "metrics"))
               if spec(f[:-5])["reader"].startswith("iterations_")}
    assert on_disk == set(NAMES)


@pytest.mark.parametrize("cell", ["serve-1p3b-chat", "serve-lfm2-agent"])
def test_a_traced_rehearsal_says_a_value_for_each_metric_of_the_cell(cell):
    proc = _run(RUN, "--workload", cell, "--seed", "11", "--seconds", "1.5",
                "--trace", "1", "--rehearse",
                extra_env={"PYTHONPATH": PYTHONPATH})
    line = _last_line(proc)
    assert line["correct"] is True and line["metrics"] == {}
    said = next(ln for ln in proc.stdout.splitlines()
                if "readers gave a value for" in ln)
    want = [n for n in NAMES if cell in spec(n)["workloads"]]
    assert len(want) == len(ISSUE_41)
    assert all(repr(n) in said for n in want), said
    read = [ln for ln in proc.stdout.splitlines()
            if "serving/iterations:" in ln and "rows read" in ln]
    assert len(read) == 1 and " 0 rows read" not in read[0], read
    # the old histograms say how little of the run they keep
    assert "samples retained of" in proc.stdout
