"""The cell ``serve-phi4flash-reason``: its place in the manifest, its
traffic, its rehearsal, and its twelve metric files — which operations
each trace pattern counts, and what the readers of the two rooflines
make of the program's counters and a trace's events."""

import os
import re

import pytest

from benchmarks.chip import manifest as manifest_mod
from benchmarks.chip import readers, traffic
from benchmarks.chip.families import phi4flash as family
from deepspeed_tpu.observability import metrics as registry_mod
from deepspeed_tpu.serving.metrics import ITERATION_COLUMNS

from ._paths import BENCH, ROOT, RUN, manifest
from .test_olmoe_cell import FUSION, _Trace, _observed
from .test_rehearse import KEYS, _last_line, _run

CELL = "serve-phi4flash-reason"
M = manifest()
ATT, CROSS = "window and shared-page attention", "cross-decoder"
LAYER_OF = {
    "serve.iter_ms_p50.reason": "server host loop",
    "serve.host_ms_p50.reason": "server host loop",
    "serve.device_idle_pct.reason": "device",
    "serve.batch_occupancy_pct.reason": "server host loop",
    "serve.prefill_share_pct.reason": "model step",
    "serve.ssm_share_pct.reason": "state-space mixer",
    "serve.shared_kv_attn_share_pct.reason": ATT,
    "serve.window_attn_share_pct.reason": ATT,
    "serve.gmu_share_pct.reason": CROSS,
    "serve.shared_kv_attn_roofline_pct.reason": ATT,
    "serve.window_attn_roofline_pct.reason": ATT,
    "serve.cross_decoder_positions_pct.reason": CROSS}
REASON = list(LAYER_OF)
SIZES = family.sizes(manifest_mod.Cell(ROOT, M, CELL).config, False)


def spec(name):
    return manifest_mod.load_json(os.path.join(BENCH, "metrics",
                                               name + ".json"))


def test_the_cell_is_in_the_manifest_as_the_issue_has_it():
    cell = next(w for w in M["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "phi-4-mini-flash-serve", "reason-closed-64", 1)
    conf = next(c for c in M["configs"] if c["name"] == cell["config"])
    assert conf["reduced"] == []                       # nothing is cut
    assert conf["source"] == ("https://huggingface.co/microsoft/Phi-4-mini-"
                              "flash-reasoning/blob/main/config.json")
    assert conf["file"] == "benchmarks/chip/configs/phi-4-mini-flash-serve.json"
    tokens = next(m for m in M["end_to_end"]
                  if m["name"] == "serve_tokens_per_s")
    assert tokens["workloads"][:6] == [
        "serve-1p3b-longprompt", "serve-olmoe-longgen", "serve-lfm2-agent",
        "serve-kanana-docqa", "serve-falconh1-chat", CELL]
    assert tokens["bound"] == 0.03
    named = [m["name"] for m in M["per_layer"] if m["name"] in REASON]
    assert named == REASON                 # appended, in this order
    for name in REASON:
        entry = next(m for m in M["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "serve_tokens_per_s"
    assert [w["name"] for w in M["workloads"]][:9] == [
        "train-125m-zero1", "serve-1p3b-chat", "serve-1p3b-longprompt",
        "train-1p3b-zero3-4chip", "serve-olmoe-longgen", "serve-lfm2-agent",
        "serve-kanana-docqa", "serve-falconh1-chat", CELL]
    assert sum(w["chips"] == 4 for w in M["workloads"][:9]) == 1


def test_the_entries_before_this_cells_are_as_they_were():
    """What ``test_falconh1_cell.py::test_pr_41s_entries_are_one_unbroken_
    run_and_its_files_its_own`` holds beside "nothing after PR 41's but
    h1chat's" (``tests/conftest.py`` marks that case an expected failure:
    this cell's entries follow h1chat's, where the driver's contract puts
    them): PR 41's eighteen one unbroken run, then h1chat's thirteen, then
    this cell's twelve and nothing else."""
    from .test_falconh1_cell import H1CHAT
    from .test_iteration_readers import ISSUE_41, NAMES
    names = [m["name"] for m in M["per_layer"]]
    assert len(names) <= 128
    theirs = [n for n in names if n.removesuffix(".chat") in ISSUE_41]
    assert sorted(theirs) == NAMES
    at = names.index(theirs[0])
    assert names[at:at + len(theirs)] == theirs
    assert names[at + len(theirs):] == H1CHAT + REASON
    on_disk = {f[:-5] for f in os.listdir(os.path.join(BENCH, "metrics"))
               if spec(f[:-5])["reader"].startswith("iterations_")}
    assert on_disk == set(NAMES)


def test_the_mix_is_the_issues_closed_loop_of_64_reasoning_callers():
    mix = traffic.resolve(manifest_mod.Cell(ROOT, M, CELL).traffic, False)
    assert (mix["loop"], mix["clients"], mix["block"], mix["order_seed"]) \
        == ("closed", 64, 64, 1)
    assert "shared_prefix" not in mix                  # nothing shared
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 192,
                                 "sigma": 0.7, "min": 32, "max": 768}
    assert mix["output_len"] == {"dist": "uniform", "min": 1024, "max": 3072}
    assert mix["trace_seconds"] == 2.0
    assert 20 <= mix["lead_in_s"] <= 60
    assert "reasoning model" in mix["why"]
    config = manifest_mod.Cell(ROOT, M, CELL).config
    assert mix["clients"] == config["serving"]["num_slots"]   # one a slot
    stream = traffic.RequestStream(mix, 2 ** 31 + 9, config["vocab_size"])
    block = [stream.take() for _ in range(64)]
    assert all(int(r["prompt"].max()) < config["vocab_size"] for r in block)
    # a slot holds the longest prompt and the longest chain of thought
    assert max(len(r["prompt"]) + r["max_new_tokens"] for r in block) \
        <= 768 + 3072 <= config["serving"]["max_len"]
    # ~3% of the tokens are prompts
    share = sum(len(r["prompt"]) for r in block) / sum(
        len(r["prompt"]) + r["max_new_tokens"] for r in block)
    assert 0.05 < share < 0.15


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses_correct(trace):
    proc = _run(RUN, "--workload", CELL, "--seed", str(2 ** 31 + 56),
                "--seconds", "1.5", "--trace", str(trace), "--rehearse")
    line = _last_line(proc)
    assert set(line) >= KEYS and line["correct"] is True, line
    assert line["check"]["tokens_over"] == {"value": 0, "limit": 0}
    if trace:
        said = proc.stdout
        assert "serving/cross_decoder_positions" in said
        for name in ("serve.cross_decoder_positions_pct.reason",
                     "serve.batch_occupancy_pct.reason"):
            assert name in said, name


@pytest.mark.parametrize("name", REASON)
def test_metric_file_names_its_reader_its_layer_and_its_cell(name):
    s = spec(name)
    assert s["reader"] in readers.load_all()
    assert s["layer"] == LAYER_OF[name]
    assert s["workloads"] == [CELL]
    entry = next(m for m in M["per_layer"] if m["name"] == name)
    assert entry == {k: v for k, v in s.items()
                     if k not in ("reader", "args", "note")}
    if "roofline" in name:
        assert (s["unit"], s["better"], s["source"]) == (
            "%", "higher", "device_trace")


@pytest.fixture
def registry(monkeypatch):
    reg = registry_mod.MetricsRegistry()
    monkeypatch.setattr(registry_mod, "_DEFAULT_REGISTRY", reg)
    return reg


def test_the_readers_find_nothing_in_a_program_without_rings(registry):
    """The parent's program, or another family's sizes: no counter, no
    matching operation, and the metrics are left out of the line — the
    readers do not raise."""
    load = readers.load_all()
    trace = _Trace([(0, 100, FUSION)], [(0, 100, "jit__paged_decode_iter_"
                                         "impl(1)")])
    for name in ("serve.shared_kv_attn_roofline_pct.reason",
                 "serve.window_attn_roofline_pct.reason"):
        s = spec(name)
        for sizes in (SIZES, {"hidden_size": 2048}):
            obs, _ = _observed(trace, sizes=sizes)
            assert load[s["reader"]](obs, **s["args"]) is None
        obs, _ = _observed(None, sizes=SIZES)
        assert load[s["reader"]](obs, **s["args"]) is None
    s = spec("serve.cross_decoder_positions_pct.reason")
    obs, _ = _observed(None, sizes=SIZES)
    assert load[s["reader"]](obs, **s["args"]) is None


# instruction texts as a v5e trace's `XLA Ops` line named them (my chip
# runs, PR 56, shortened): the paged kernel over the one paged layer's
# pages — the full layer's call and a cross layer's, the same two pool
# operands —, the contiguous decode kernel on a ring, the two operations
# of a ring's write (the tile read and selected, keys and values; the
# write-back), the page append of the full layer (NOT a ring's), a
# memory unit's two products, the mixers' decode update on a slot's
# state and the convolution's new columns, and an MLP
SHARED = ('%shared_attn.1 = f32[64,5,8,128]{3,2,1,0:T(8,128)S(1)} custom-call('
          's32[64]{0:T(128)S(1)} %copy-done.300, s32[64,32]{1,0:T(8,128)S(1)}'
          ' %copy-done.163, f32[40]{0:T(128)S(1)} %broadcast.20, s32[1]{0:T('
          '128)} %constant.217, f32[64,5,8,128]{3,2,1,0:T(8,128)S(1)} '
          '%bitcast.1956, bf16[64,5,8,128]{3,2,1,0:T(8,128)(2,1)S(1)} '
          '%bitcast.1981, bf16[64,5,8,128]{3,2,1,0:T(8,128)(2,1)S(1)} '
          '%bitcast.1989, bf16[1,2049,5,2,128,128]{5,4,3,2,1,0:T(8,128)(2,1)}'
          ' %bitcast.278, bf16[1,2049,5,2,128,128]{5,4,3,2,1,0:T(8,128)(2,1)}'
          ' %bitcast.279), custom_call_target="tpu_custom_call"')
CROSS_CALL = SHARED.replace("%shared_attn.1", "%cross_attn.9").replace(
    "%bitcast.1956", "%bitcast.1959")
WINDOW = ('%window_attn.9 = f32[64,5,8,128]{3,2,1,0:T(8,128)S(1)} custom-call('
          's32[64]{0:T(128)S(1)} %get-tuple-element.1448, f32[40]{0:T(128)S(1'
          ')} %broadcast.20, f32[64,5,8,128]{3,2,1,0:T(8,128)S(1)} '
          '%bitcast.1949, bf16[64,5,2,128,512]{4,3,2,1,0:T(8,128)(2,1)} '
          '%bitcast.2045, bf16[64,5,2,128,512]{4,3,2,1,0:T(8,128)(2,1)} '
          '%bitcast.2046), custom_call_target="tpu_custom_call"')
RING_READ = ('%dynamic-slice_select_fusion.25 = (bf16[1,10,128,128]{3,2,1,0:T(8'
             ',128)(2,1)S(1)}, bf16[1,10,128,128]{3,2,1,0:T(8,128)(2,1)S(1)}) '
             'fusion(bf16[64,10,128,512]{3,2,1,0:T(8,128)(2,1)} %get-tuple-'
             'element.2161, s32[]{:T(128)S(6)} %select_n.1017), kind=kLoop, '
             'calls=%fused_computation.31')
RING_WRITE = ('%dynamic_update_slice.108 = bf16[64,10,128,512]{3,2,1,0:T(8,128)'
              '(2,1)} dynamic-update-slice(bf16[64,10,128,512]{3,2,1,0:T(8,128'
              ')(2,1)} %get-tuple-element.2160, bf16[1,10,128,128]{3,2,1,0:T('
              '8,128)(2,1)S(1)} %get-tuple-element.2033, s32[]{:T(128)S(6)} '
              '%select_n.1017, s32[]{:T(128)} %constant.231)')
RING_LOOP = ('%while.33 = (s32[]{:T(128)}, bf16[64,10,128,512]{3,2,1,0:T(8,128)'
             '(2,1)}, bf16[64,10,128,512]{3,2,1,0:T(8,128)(2,1)}) while((s32[]'
             '{:T(128)}) %tuple.9), condition=%cond, body=%body')
PAGE_APPEND = ('%select_dynamic-update-slice_fusion.5 = bf16[2049,10,128,128]{3'
               ',2,1,0:T(8,128)(2,1)} fusion(bf16[2049,10,128,128]{3,2,1,0:T(8'
               ',128)(2,1)} %get-tuple-element.2540, s32[]{:T(128)S(6)} '
               '%select_n.1006, bf16[10,128]{1,0:T(8,128)(2,1)S(1)} %dynamic-'
               'slice_bitcast_fusion.37), kind=kLoop, calls=%fused_computation')
GMU = ('%fusion.1500 = (f32[64]{0:T(128)S(1)}, f32[64,2560]{1,0:T(8,128)S(1)}) '
       'fusion(f32[64,2560]{1,0:T(8,128)S(1)} %get-tuple-element.1558, bf16['
       '5120,2560]{1,0:T(8,128)(2,1)} %params__layers_22____gmu____w2____'
       'kernel__.1, f32[64,1,5120]{2,0,1:T(8,128)S(1)} %bitcast_add_fusion, '
       'bf16[2560,5120]{1,0:T(8,128)(2,1)} %params__layers_22____gmu____w1__'
       '__kernel__.1), kind=kOutput, calls=%fused_computation.2086')
UPDATE = ('%multiply_reduce_fusion.8 = (f32[64,5120]{1,0:T(8,128)S(1)}, f32[64,'
          '16,5120]{2,1,0:T(8,128)}) fusion(f32[64,16]{1,0:T(8,128)S(1)} %get-'
          'tuple-element.1442, f32[64,5120]{1,0:T(8,128)S(1)} %fusion.196, f32['
          '64,16,5120]{2,1,0:T(8,128)} %pool__layers_0____mixer____ssm_state__'
          '.1), kind=kLoop, calls=%fused_computation.8')
CONV_STATE = ('%fusion.8 = f32[64,3,5120]{2,0,1:T(8,128)} fusion(f32[64,4,5120]'
              '{2,0,1:T(8,128)S(1)} %pad_maximum_fusion.5, s32[64]{0:T(128)S(1'
              ')} %fusion.1361), kind=kCustom, calls=%fused_computation.8')
OUT_PROJ = ('%fusion.1472 = (f32[64]{0:T(128)S(1)}, f32[64,2560]{1,0:T(8,128)S('
            '1)}) fusion(f32[64,2560]{1,0:T(8,128)S(1)} %get-tuple-element.1492'
            ', bf16[5120,2560]{1,0:T(8,128)(2,1)} %params__layers_8____mixer__'
            '__out_proj____kernel__.1), kind=kOutput, calls=%fused_computation')
MLP = ('%fusion.1495 = (f32[64]{0:T(128)S(1)}, f32[64,2560]{1,0:T(8,128)S(1)}) '
       'fusion(f32[64,2560]{1,0:T(8,128)S(1)} %get-tuple-element.1548, bf16['
       '10240,2560]{1,0:T(8,128)(2,1)} %params__layers_19____mlp____w2____'
       'kernel__.1), kind=kOutput, calls=%fused_computation.2081')
TEXTS = {"shared": SHARED, "cross": CROSS_CALL, "window": WINDOW,
         "ring_read": RING_READ, "ring_write": RING_WRITE,
         "ring_loop": RING_LOOP, "page_append": PAGE_APPEND, "gmu": GMU,
         "update": UPDATE, "conv_state": CONV_STATE, "out_proj": OUT_PROJ,
         "mlp": MLP, "fusion": FUSION}


def test_the_trace_patterns_tell_the_three_kinds_of_state_apart():
    counted = {name: {k for k, text in TEXTS.items()
                      if re.search(spec(name)["args"]["op"], text)}
               for name in REASON if "op" in spec(name)["args"]}
    assert counted == {
        "serve.ssm_share_pct.reason": {"update", "conv_state"},
        "serve.shared_kv_attn_share_pct.reason": {"shared", "cross"},
        "serve.shared_kv_attn_roofline_pct.reason": {"shared", "cross"},
        "serve.window_attn_share_pct.reason": {"window", "ring_read",
                                               "ring_write"},
        "serve.window_attn_roofline_pct.reason": {"window"},
        "serve.gmu_share_pct.reason": {"gmu"}}
    for name in ("serve.shared_kv_attn_share_pct.reason",
                 "serve.window_attn_share_pct.reason",
                 "serve.shared_kv_attn_roofline_pct.reason",
                 "serve.window_attn_roofline_pct.reason"):
        assert spec(name)["args"]["module"] == "^jit__paged_decode_iter_impl"


def _count(reg, **values):
    for name, v in values.items():
        reg.counter("serving/" + name).inc(v)


def test_the_kernels_rooflines_take_their_tokens_from_the_counters(registry):
    """Two dispatches of 64 slots counted: 2 x 64 x 1,400 pooled tokens
    and 2 x 64 x 512 ring tokens. Eight calls over the pages in the
    trace, four a dispatch's worth each... the reader scales the
    counters' mean a dispatch by the trace's calls: a call over the pages
    reads 89,600 tokens x 5,120 B = 458.8 MB, 560 us at 819 GB/s; a call
    over a ring 32,768 x 5,120 B = 167.8 MB, 205 us."""
    load = readers.load_all()
    _count(registry, decode_slots_offered=128,
           shared_kv_tokens_walked=2 * 64 * 1400,
           ring_tokens_read=2 * 64 * 512)
    run = [(0, 10_000_000, "jit__paged_decode_iter_impl(1)")]
    ops = [(i * 1_000_000, i * 1_000_000 + 800_000, text)
           for i, text in enumerate([SHARED, CROSS_CALL] * 4)]
    ops += [(8_000_000 + i * 400_000, 8_000_000 + i * 400_000 + 410_000 // 2,
             WINDOW) for i in range(4)]
    trace = _Trace(ops, run)
    s = spec("serve.shared_kv_attn_roofline_pct.reason")
    obs, said = _observed(trace, sizes=SIZES)
    got = load[s["reader"]](obs, **s["args"])
    least = 64 * 1400 * 5120 / 819e9
    assert got == pytest.approx(100 * least / 800e-6, rel=1e-6)
    assert 69 < got < 71 and "over bf16 keys and values" in said[0]
    assert "89600 tokens read a call" in said[0]
    s = spec("serve.window_attn_roofline_pct.reason")
    obs, said = _observed(trace, sizes=SIZES)
    got = load[s["reader"]](obs, **s["args"])
    assert got == pytest.approx(100 * (64 * 512 * 5120 / 819e9) / 205e-6,
                                rel=1e-6)
    assert "32768 tokens read a call" in said[0]
