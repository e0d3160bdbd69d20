"""The per-layer metrics that read what the program counts about itself
(ISSUE 24): the two readers of ``readers/program.py`` on a hand-filled
registry, the eleven metric files row for row, and a traced rehearsal of
each cell that has such a metric.

A metric's manifest entry is its file less ``reader``, ``args`` and
``note`` (``entry`` below). A later PR adds a metric on a ``registry_``
reader as a file and an entry: the eleven are pinned, the set is not."""

import os

import pytest

from benchmarks.chip import manifest as manifest_mod
from benchmarks.chip import readers, stats
from deepspeed_tpu.observability import metrics as registry_mod

from ._paths import BENCH, PYTHONPATH, ROOT, RUN, manifest
from .test_rehearse import _last_line, _run
from .test_manifest import PR23_PER_LAYER

# ISSUE 24's table, letter for letter: name -> (unit, better, layer,
# moves, cells)
CHAT, LONG = ["serve-1p3b-chat"], ["serve-1p3b-longprompt"]
TRAIN = ["train-125m-zero1", "train-1p3b-zero3-4chip"]
HOST, CACHE = "server host loop", "paged KV and prefix cache"
ISSUE_24 = {
    "serve.queue_wait_p90_ms.chat": ("ms", "lower", HOST, "itl_p90_ms", CHAT),
    "serve.prefill_wait_p90_ms.chat": ("ms", "lower", HOST, "itl_p90_ms",
                                       CHAT),
    "serve.batch_occupancy_pct.chat": ("%", "higher", HOST, "itl_p90_ms",
                                       CHAT),
    "serve.prefix_hit_pct.chat": ("%", "higher", CACHE, "itl_p90_ms", CHAT),
    "serve.host_ms_p50.chat": ("ms", "lower", HOST, "itl_p90_ms", CHAT),
    "serve.host_ms_max.chat": ("ms", "lower", HOST, "itl_p90_ms", CHAT),
    "serve.readback_ms_max.chat": ("ms", "lower", HOST, "itl_p90_ms", CHAT),
    "serve.prefill_wait_p50_ms.longprompt": (
        "ms", "lower", HOST, "serve_tokens_per_s", LONG),
    "serve.batch_occupancy_pct.longprompt": (
        "%", "higher", HOST, "serve_tokens_per_s", LONG),
    "serve.host_ms_p50.longprompt": ("ms", "lower", HOST,
                                     "serve_tokens_per_s", LONG),
    "train.host_to_dispatch_ms_p50": ("ms", "lower", "trainer",
                                      "train_tokens_per_s_chip", TRAIN),
}
M = manifest()


def spec(name):
    return manifest_mod.load_json(os.path.join(BENCH, "metrics",
                                               name + ".json"))


def entry(name):
    """The manifest entry of a metric file."""
    return {k: v for k, v in spec(name).items()
            if k not in ("reader", "args", "note")}


class Say:
    def __init__(self):
        self.lines = []

    def __call__(self, line):
        self.lines.append(line)


@pytest.fixture
def registry(monkeypatch):
    """An empty process registry for the readers to find."""
    reg = registry_mod.MetricsRegistry()
    monkeypatch.setattr(registry_mod, "_DEFAULT_REGISTRY", reg)
    return reg


def _read(name, **args):
    say = Say()
    obs = readers.Observed(setup={}, series={}, trace=None, peaks=None,
                           facts={}, say=say)
    return readers.load_all()[name](obs, **args), say.lines


def test_percentile_of_an_empty_or_unknown_histogram_is_nothing(registry):
    assert _read("registry_percentile", histogram="serving/none", q=50) \
        == (None, [])
    registry.histogram("serving/empty")
    assert _read("registry_percentile", histogram="serving/empty", q=90) \
        == (None, [])


@pytest.mark.parametrize("q", [50, 90, 100])
def test_percentile_is_stats_percentile_of_the_retained_samples(registry, q):
    values = [((7 * i) % 41) + i / 1000 for i in range(700)]
    hist = registry.histogram("serving/advance_host_ms")
    for v in values:
        hist.observe(v)
    kept = values[-registry_mod.DEFAULT_HISTOGRAM_WINDOW:]
    got, said = _read("registry_percentile",
                      histogram="serving/advance_host_ms", q=q)
    assert got == stats.percentile(kept, q)
    assert said == ["serving/advance_host_ms: 512 samples retained of 700"]
    if q == 100:
        assert got == max(kept)


def test_ratio_of_counters_and_nothing_over_a_zero_denominator(registry):
    args = {"num": ["serving/prefill_tokens_reused"],
            "den": ["serving/prefill_tokens_reused",
                    "serving/prefill_tokens_computed"]}
    assert _read("registry_ratio_pct", **args) == (None, [])
    registry.counter("serving/prefill_tokens_computed").inc(0)
    assert _read("registry_ratio_pct", **args)[0] is None
    registry.counter("serving/prefill_tokens_reused").inc(256)
    registry.counter("serving/prefill_tokens_computed").inc(768)
    got, said = _read("registry_ratio_pct", **args)
    assert got == 25.0 and len(said) == 1


def _registry_metrics():
    """Every metric file on a ``registry_`` reader, by name."""
    out = {}
    for f in os.listdir(os.path.join(BENCH, "metrics")):
        s = manifest_mod.load_json(os.path.join(BENCH, "metrics", f))
        if s["reader"].startswith("registry_"):
            assert f == s["name"] + ".json"
            out[s["name"]] = s
    return out


def test_the_issues_eleven_metric_files_read_the_registry():
    ours = set(_registry_metrics())
    assert ours >= set(ISSUE_24)
    # the manifest names the eleven, and PR 23's twenty are none of them
    named = {m["name"] for m in M["per_layer"]}
    assert set(ISSUE_24) <= named
    assert PR23_PER_LAYER <= named - ours


@pytest.mark.parametrize("name", sorted(ISSUE_24))
def test_metric_file_is_the_issues_row_and_names_a_registered_reader(name):
    s, (unit, better, layer, moves, cells) = spec(name), ISSUE_24[name]
    assert set(s) == {"name", "unit", "better", "source", "layer", "moves",
                      "workloads", "reader", "args", "note"}
    assert (s["name"], s["unit"], s["better"], s["layer"], s["moves"],
            s["workloads"]) == (name, unit, better, layer, moves, cells)
    assert s["source"] == "program_counter"
    assert s["reader"] in readers.load_all()
    assert "512" in s["note"] or "counters cover the process" in s["note"]
    # it moves an end-to-end metric that each of its cells reports
    for cell in cells:
        reported = [m["name"] for m in
                    manifest_mod.Cell(ROOT, M, cell).end_to_end()]
        assert moves in reported
    if name in {m["name"] for m in M["per_layer"]}:
        assert next(m for m in M["per_layer"] if m["name"] == name) \
            == entry(name)


@pytest.mark.parametrize("cell", sorted(
    {cell for s in _registry_metrics().values() for cell in s["workloads"]}))
def test_a_traced_rehearsal_reads_every_program_metric_of_the_cell(cell):
    proc = _run(RUN, "--workload", cell, "--seed", "11", "--seconds", "1.5",
                "--trace", "1", "--rehearse",
                extra_env={"PYTHONPATH": PYTHONPATH})
    line = _last_line(proc)
    assert line["correct"] is True and line["metrics"] == {}
    said = next(ln for ln in proc.stdout.splitlines()
                if "readers gave a value for" in ln)
    want = [n for n, s in _registry_metrics().items()
            if cell in s["workloads"]]
    assert want and all(repr(n) in said for n in want), said
    assert all(n in want for n, row in ISSUE_24.items() if cell in row[4])
