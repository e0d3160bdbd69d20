"""The reduction from trace to numbers, on the trace recorded on a v5e
chip by ``benchmarks/chip/tools/record_fixture.py`` (three training
steps of a two-layer GPT and six served requests, inside one
``bench/trace_window``) and on hand-made intervals."""

import os

import pytest

from benchmarks.chip import xplane
from benchmarks.chip.readers import Observed, load_all

from ._paths import BENCH

FLASH = r'custom-call\(.*custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def trace():
    return xplane.load(os.path.join(BENCH, "fixtures", "tiny.xplane.pb.gz"))


def test_the_fixture_is_one_chip_with_ops_modules_and_spans(trace):
    assert [d.name for d in trace.devices] == ["/device:TPU:0"]
    assert len(trace.devices[0].ops) == 5614
    assert len(trace.devices[0].modules) == 258
    assert {s[2] for s in trace.spans} >= {
        "bench/trace_window", "bench/train_batch", "fwd_bwd_step", "data",
        "serving/prefill_chunk", "serving/decode_iter", "serving/harvest"}


def test_busy_is_the_union_of_device_operations_in_the_window(trace):
    assert trace.window_s == pytest.approx(0.323444835)
    assert xplane.busy_s(trace) == pytest.approx(0.00128474, rel=1e-6)
    # nested control flow is not counted twice: self times sum to busy
    d = trace.devices[0]
    own = xplane.self_times(xplane.clip(d.ops, trace.t0, trace.t1))
    assert sum(ns for _, ns in own) == pytest.approx(
        xplane.busy_ns(trace, d), rel=1e-5)


@pytest.mark.parametrize("module,calls,kernel_ns,module_ns", [
    (r"^jit_train_step", 18, 117861.0, 451146.0),
    (r"^jit__paged_decode_iter_impl", 30, 86959.0, 489056.0),
    (r"^jit__chunk_prefill_impl", 18, 20586.0, 263753.0),
])
def test_kernel_time_by_program(trace, module, calls, kernel_ns, module_ns):
    d = trace.devices[0]
    found = xplane.op_ns(trace, d, FLASH, module)
    assert len(found) == calls
    assert sum(ns for _, ns in found) == pytest.approx(kernel_ns)
    assert xplane.module_busy_ns(trace, d, module) == pytest.approx(module_ns)


def test_top_operations_are_named_by_instruction_operation_and_shape(trace):
    top = xplane.top_ops(trace, 3)
    assert [name for name, _ in top] == [
        "attn.3 custom-call bf16[4,1,2,64]",
        "attn.25 custom-call bf16[4,2,256,64]",
        "fusion.393 fusion f32[512]"]
    assert top[0][1] == pytest.approx(8.6959e-05)


def test_idle_gaps_go_to_the_host_span_that_covers_them(trace):
    gaps = dict(xplane.idle_gaps(trace))
    assert gaps["serving/prefill_chunk"] == pytest.approx(0.042498337)
    assert gaps["data"] == pytest.approx(0.00498604)
    assert gaps["_gaps_under_2_us_"] == pytest.approx(7.0075e-05)
    idle = trace.window_s - xplane.busy_s(trace)
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-6)


def test_one_chip_has_no_collective(trace):
    assert xplane.collective_exposed_ns(trace, trace.devices[0]) == 0


def test_readers_take_their_numbers_from_the_trace(trace):
    obs = Observed(setup={}, series={}, trace=trace,
                   peaks={"bf16_flops_per_s": 197e12,
                          "hbm_bytes_per_s": 819e9},
                   facts={}, say=lambda msg: None)
    r = load_all()
    assert r["device_idle_pct"](obs) == pytest.approx(
        100 * (1 - 0.00128474 / 0.323444835), rel=1e-6)
    assert r["op_share_pct"](obs, op=FLASH, module="^jit_train_step") \
        == pytest.approx(100 * 117861.0 / 1284740.0, rel=1e-5)
    assert r["module_share_pct"](obs, module="^jit__chunk_prefill_impl") \
        == pytest.approx(100 * 263753.0 / 1284740.0, rel=1e-5)
    roof = r["flash_roofline_pct"](obs, op=FLASH, module="^jit_train_step")
    assert 0 < roof < 100
    assert r["collective_exposed_pct"](obs) == 0.0


def test_readers_return_nothing_where_there_is_nothing_to_read():
    obs = Observed(setup={}, series={}, trace=None, peaks=None, facts={},
                   say=lambda msg: None)
    r = load_all()
    for name, args in (("device_idle_pct", {}), ("mfu_pct", {}),
                       ("op_share_pct", {"op": "x"}), ("peak_hbm_gb", {}),
                       ("series_percentile", {"series": "none", "q": 50}),
                       ("series_tail", {"series": "none", "q": 90}),
                       ("collective_exposed_pct", {}),
                       ("flash_roofline_pct", {"op": "x"})):
        assert r[name](obs, **args) is None, name


# -- hand-made intervals ----------------------------------------------------

def _trace(ops, spans=(), modules=(), window=(0.0, 1000.0)):
    spans = list(spans) + [(window[0], window[1], xplane.WINDOW_SPAN)]
    return xplane.Trace([xplane.Device("/device:TPU:0", sorted(ops),
                                       sorted(modules))], sorted(spans))


def test_union_merges_overlaps_and_clip_cuts_to_the_window():
    assert xplane.union([(0, 10), (5, 20), (30, 40)]) == [(0, 20), (30, 40)]
    assert xplane.clip([(-5, 5, "a"), (990, 1010, "b"), (2000, 2001, "c")],
                       0, 1000) == [(0, 5, "a"), (990, 1000, "b")]


def test_self_time_takes_the_body_out_of_the_loop():
    ops = [(0, 100, "%while = while()"), (10, 40, "%a = f32[2] add()"),
           (50, 90, "%b = f32[2] mul()"), (200, 250, "%c = f32[2] neg()")]
    assert sorted(xplane.self_times(ops)) == sorted([
        ("%while = while()", 30), ("%a = f32[2] add()", 30),
        ("%b = f32[2] mul()", 40), ("%c = f32[2] neg()", 50)])


def test_collective_exposed_is_the_time_the_core_waits_on_one():
    ops = [(0, 100, "%fusion.1 = bf16[8,8] fusion(bf16[8,8] %all-gather.3)"),
           (100, 130, "%all-gather-done.3 = bf16[8,8] all-gather-done(%s)"),
           (130, 170, "%reduce-scatter.2 = f32[2] reduce-scatter(f32[8] %g)"),
           (170, 172, "%all-gather-start.4 = (bf16[2]) all-gather-start(%p)"),
           (200, 300, "%fusion.2 = f32[8] fusion(f32[8] %reduce-scatter.2)")]
    t = _trace(ops)
    assert xplane.collective_exposed_ns(t, t.devices[0]) == 30 + 40 + 2
    obs = Observed(setup={}, series={}, trace=t, peaks=None, facts={},
                   say=lambda msg: None)
    assert load_all()["collective_exposed_pct"](obs) == pytest.approx(
        100 * 72 / 1000)


def test_a_gap_goes_to_the_shortest_span_over_its_middle():
    ops = [(0, 100, "%a = f32[2] add()"), (400, 500, "%b = f32[2] add()"),
           (501, 900, "%c = f32[2] add()")]
    spans = [(90, 450, "bench/advance"), (150, 300, "serving/admit"),
             (920, 960, "serving/harvest")]
    t = _trace([(s * 1000, e * 1000, n) for s, e, n in ops],
               [(s * 1000, e * 1000, n) for s, e, n in spans],
               window=(0.0, 1_000_000.0))
    assert xplane.idle_gaps(t) == [
        ["serving/admit", 300e-6], ["serving/harvest", 100e-6],
        ["_gaps_under_2_us_", 1e-6]]


@pytest.mark.parametrize("name,kept", [
    ("train/prepare", True), ("train/finish", True),
    ("serving/decode_iter", True), ("bench/train_batch", True),
    ("fwd_bwd_step", True), ("training", False), ("PjitFunction(f)", False),
])
def test_spans_are_the_programs_and_the_benchmarks_by_prefix(name, kept):
    assert xplane._is_span(name) is kept


def test_the_trainers_gap_goes_to_its_own_span_inside_the_benchmarks():
    """``train_batch`` entry -> the step program's enqueue is
    ``train/prepare``, inside ``bench/train_batch``: the idle gap under
    it files there and no longer under the outer span."""
    ops = [(0, 100, "%a = f32[2] add()"), (600, 900, "%b = f32[2] add()"),
           (960, 1000, "%c = f32[2] add()")]
    spans = [(50, 950, "bench/train_batch"), (110, 590, "train/prepare"),
             (905, 940, "train/finish")]
    t = _trace([(s * 1000, e * 1000, n) for s, e, n in ops],
               [(s * 1000, e * 1000, n) for s, e, n in spans
                if xplane._is_span(n)], window=(0.0, 1_000_000.0))
    assert xplane.idle_gaps(t) == [["train/prepare", 500e-6],
                                   ["train/finish", 60e-6]]


def test_operations_count_only_inside_the_named_program():
    ops = [(10, 20, '%attn.1 = bf16[1,2,128,64] custom-call(), '
            'custom_call_target="tpu_custom_call"'),
           (110, 140, '%attn.1 = bf16[1,2,128,64] custom-call(), '
            'custom_call_target="tpu_custom_call"')]
    modules = [(0, 50, "jit_train_step(1)"), (100, 150, "jit_other(2)")]
    t = _trace(ops, modules=modules)
    d = t.devices[0]
    assert [ns for _, ns in xplane.op_ns(t, d, FLASH, "^jit_train_step")] \
        == [10]
    assert [ns for _, ns in xplane.op_ns(t, d, FLASH)] == [10, 30]
    assert xplane.module_busy_ns(t, d, "^jit_other") == 30
