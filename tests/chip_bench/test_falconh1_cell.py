"""The cell ``serve-falconh1-chat``: its place in the manifest, its
traffic, its rehearsal (prefix hits that restore snapshots among its
requests), and its thirteen metric files — which operations each trace
pattern counts, and what the reader of the state update's roofline makes
of the program's counters and a trace's events."""

import os
import re
from array import array

import pytest

from benchmarks.chip import manifest as manifest_mod
from benchmarks.chip import readers, traffic
from benchmarks.chip.families import falcon_h1 as family
from deepspeed_tpu.observability import metrics as registry_mod
from deepspeed_tpu.serving.metrics import ITERATION_COLUMNS

from ._paths import BENCH, PYTHONPATH, ROOT, RUN, manifest
from .test_olmoe_cell import ATTN, FUSION, _Trace, _observed
from .test_rehearse import KEYS, _last_line, _run

CELL = "serve-falconh1-chat"
M = manifest()
H1CHAT = ["serve.ssm_share_pct.h1chat",
          "serve.ssm_update_roofline_pct.h1chat",
          "serve.ssm_chunk_share_pct.h1chat",
          "serve.state_restored_pct.h1chat",
          "serve.state_restore_missed_pct.h1chat",
          "serve.prefix_hit_pct.h1chat", "serve.iter_ms_p50.h1chat",
          "serve.device_idle_pct.h1chat",
          "serve.batch_occupancy_pct.h1chat",
          "serve.paged_attn_share_pct.h1chat",
          "serve.prefill_share_pct.h1chat",
          "serve.host_ms_p50.h1chat", "serve.ttft_p50_ms.h1chat"]
LAYER_OF = {
    "serve.ssm_share_pct.h1chat": "state-space mixer",
    "serve.ssm_update_roofline_pct.h1chat": "state-space mixer",
    "serve.ssm_chunk_share_pct.h1chat": "state-space mixer",
    "serve.state_restored_pct.h1chat": "paged KV and prefix cache",
    "serve.state_restore_missed_pct.h1chat": "paged KV and prefix cache",
    "serve.prefix_hit_pct.h1chat": "paged KV and prefix cache",
    "serve.iter_ms_p50.h1chat": "server host loop",
    "serve.batch_occupancy_pct.h1chat": "server host loop",
    "serve.device_idle_pct.h1chat": "device",
    "serve.paged_attn_share_pct.h1chat": "Pallas kernels",
    "serve.prefill_share_pct.h1chat": "model step",
    "serve.host_ms_p50.h1chat": "server host loop",
    "serve.ttft_p50_ms.h1chat": "server host loop"}

# instruction texts as a v5e trace's `XLA Ops` line named them (my chip
# run, PR 44): the decode update, the paged kernel at this model's heads,
# the convolution's new columns, the chunk scan's products, a restore
# from a snapshot, and what is the mixer's but not its state work (the
# in-projection, whose weights arrive through a prefetch) or not the
# mixer's at all
UPDATE = ('%ssm_update.26 = (f32[64,32,128]{2,1,0:T(8,128)S(1)}, f32[64,32,'
          '256,128]{3,2,1,0:T(8,128)}) custom-call(s32[]{:T(128)} %get-tuple-'
          'element.1171, s32[64]{0:T(128)S(1)} %copy-done.159, s32[1]{0:T(128'
          ')} %bitcast.18, f32[64,32,256,128]{3,2,1,0:T(8,128)} %pool__layers'
          '_8____mixer____ssm_state__.1, f32[64,32,128]{2,1,0:T(8,128)S(1)} '
          '%broadcast_multiply_fusion), custom_call_target="tpu_custom_call"')
PAGED = ('%attn.17 = bf16[64,4,5,128]{3,2,1,0:T(8,128)(2,1)S(1)} custom-call('
         's32[64]{0:T(128)S(1)} %copy-done.133, s32[64,16]{1,0:T(8,128)S(1)} '
         '%copy-done.94, bf16[1025,4,128,128]{3,2,1,0} %param.7), '
         'custom_call_target="tpu_custom_call"')
CONV_STATE = ('%fusion.11 = f32[64,3,5120]{2,0,1:T(8,128)} fusion(f32[64,4,'
              '5120]{2,0,1:T(8,128)S(1)} %pad_maximum_fusion.8, s32[64]{0:T('
              '128)S(1)} %copy-done.4), kind=kCustom, calls=%fused_computation'
              '.11')
SCAN = ['%fusion.184 = f32[1,1,128,2,16,128]{2,5,4,3,1,0:T(8,128)S(1)} fusion('
        'f32[2,16,128,128]{3,2,1,0:T(8,128)S(1)} %fusion.243, f32[2,16,256,128'
        ']{3,2,1,0:T(8,128)S(1)} %bitcast.1075), kind=kOutput, calls=%fused_'
        'computation.427',
        '%fusion.248 = f32[2,16,128,256]{3,2,1,0:T(8,128)S(1)} fusion(f32[128,'
        '2,256,1]{0,2,3,1:T(8,128)S(1)} %bitcast.1241, f32[128,2,16,128]{0,3,'
        '2,1:T(8,128)S(1)} %bitcast.1104), kind=kOutput, calls=%fused_'
        'computation.500',
        '%select_select_fusion = f32[1,32,256,128]{3,2,1,0:T(8,128)} fusion('
        'f32[33,32,256,128]{3,2,1,0:T(8,128)} %pool__layers_8____mixer____'
        'snapshots____ssm_state__.1, s32[]{:T(128)S(6)} %max.24), kind=kLoop, '
        'calls=%fused_computation.127',
        '%select_dynamic-update-slice_fusion.8 = (f32[64,32,256,128]{3,2,1,0:'
        'T(8,128)}, f32[33,32,256,128]{3,2,1,0:T(8,128)}) fusion(f32[64,32,256'
        ',128]{3,2,1,0:T(8,128)} %pool__layers_0____mixer____ssm_state__.1, '
        's32[]{:T(128)} %select_n.283), kind=kLoop, calls=%fused_computation']
IN_PROJ = ('%bitcast_multiply_fusion = bf16[64,1,9248]{2,0,1:T(8,128)(2,1)S(1)'
           '} fusion(bf16[5120,9248]{0,1:T(8,128)(2,1)S(1)} %custom-call.31, '
           'f32[64,5120]{1,0:T(8,128)S(1)} %get-tuple-element.1204), '
           'kind=kOutput, calls=%fused_computation.218')
MLP = ('%convolution_multiply_fusion.1 = bf16[64,21504]{1,0:T(8,128)(2,1)S(1)}'
       ' fusion(bf16[5120,21504]{1,0:T(8,128)(2,1)} %params__layers_8____mlp__'
       '__w3____kernel__.1, bf16[64,21504]{1,0:T(8,128)(2,1)S(1)} %convolution'
       '_multiply_fusion.2), kind=kOutput, calls=%fused_computation.95')
APPEND = ('%while.35 = (s32[]{:T(128)}, bf16[1025,4,128,128]{3,2,1,0:T(8,128)('
          '2,1)}, s32[]{:T(128)}, s32[64]{0:T(128)S(1)}) while((s32[]{:T(128)}'
          ', bf16[1025,4,128,128]{3,2,1,0}) %tuple.9), condition=%cond, '
          'body=%body')


def spec(name):
    return manifest_mod.load_json(os.path.join(BENCH, "metrics",
                                               name + ".json"))


def test_the_cell_is_in_the_manifest_as_the_issue_has_it():
    cell = next(w for w in M["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "falcon-h1-34b-9l-serve", "chat-closed-64", 1)
    assert "8x" in cell["why"]            # a cut in depth: host and idle
    conf = next(c for c in M["configs"] if c["name"] == cell["config"])
    assert conf["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert conf["source"] == ("https://huggingface.co/tiiuae/Falcon-H1-34B-"
                              "Instruct/blob/main/config.json")
    tokens = next(m for m in M["end_to_end"]
                  if m["name"] == "serve_tokens_per_s")
    # appended after the cells that were there (a later cell comes after)
    assert tokens["workloads"][:5] == [
        "serve-1p3b-longprompt", "serve-olmoe-longgen", "serve-lfm2-agent",
        "serve-kanana-docqa", CELL]
    assert tokens["bound"] == 0.03       # 1% until PR 54's check
    named = [m["name"] for m in M["per_layer"] if m["name"] in H1CHAT]
    assert named == H1CHAT                 # appended, in this order
    for name in H1CHAT:
        entry = next(m for m in M["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "serve_tokens_per_s"
    # every cell that was there is still there, first; one in eight on
    # four chips
    assert [w["name"] for w in M["workloads"]][:8] == [
        "train-125m-zero1", "serve-1p3b-chat", "serve-1p3b-longprompt",
        "train-1p3b-zero3-4chip", "serve-olmoe-longgen", "serve-lfm2-agent",
        "serve-kanana-docqa", CELL]
    assert sum(w["chips"] == 4 for w in M["workloads"][:8]) == 1


def test_pr_41s_entries_are_one_unbroken_run_and_its_files_its_own():
    """What ``test_iteration_readers.py::test_the_manifest_gained_these_
    entries_at_its_end_and_nothing_else`` holds beside the list's end
    (``tests/conftest.py`` marks that case an expected failure: this
    cell's entries follow PR 41's, where the driver's contract puts
    them): PR 41's eighteen all there and one unbroken run, nothing after
    them but this cell's, and no file with an ``iterations_*`` reader
    that is not one of them."""
    from .test_iteration_readers import ISSUE_41, NAMES
    names = [m["name"] for m in M["per_layer"]]
    assert len(names) <= 128
    theirs = [n for n in names if n.removesuffix(".chat") in ISSUE_41]
    assert sorted(theirs) == NAMES
    at = names.index(theirs[0])
    assert names[at:at + len(theirs)] == theirs
    assert names[at + len(theirs):] == H1CHAT
    on_disk = {f[:-5] for f in os.listdir(os.path.join(BENCH, "metrics"))
               if spec(f[:-5])["reader"].startswith("iterations_")}
    assert on_disk == set(NAMES)


def test_the_mix_is_the_issues_closed_loop_of_64_chat_users():
    mix = traffic.resolve(manifest_mod.Cell(ROOT, M, CELL).traffic, False)
    assert (mix["loop"], mix["clients"], mix["block"], mix["order_seed"]) \
        == ("closed", 64, 64, 1)
    assert mix["shared_prefix"] == {"share": 1.0, "count": 4, "tokens": 1024}
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 192,
                                 "sigma": 0.8, "min": 16, "max": 1536}
    assert mix["output_len"] == {"dist": "uniform", "min": 128, "max": 512}
    assert mix["trace_seconds"] == 2.0
    # the last cold first token came 29.9 s in on the chip: the window
    # opens at least 8 s after it
    assert 38 <= mix["lead_in_s"] <= 50
    assert "histories do not grow" in mix["why"] and "29.9 s" in mix["why"]
    config = manifest_mod.Cell(ROOT, M, CELL).config
    assert mix["clients"] == config["serving"]["num_slots"]   # one a slot
    stream = traffic.RequestStream(mix, 2 ** 31 + 9, config["vocab_size"])
    block = [stream.take() for _ in range(64)]
    assert all(r["kind"] == "shared_prefix" for r in block)
    assert all(int(r["prompt"].max()) < config["vocab_size"] for r in block)
    # four system prompts of 8 whole pages, every request opens with one
    opens = {tuple(r["prompt"][:1024]) for r in block}
    assert len(opens) == 4
    # prompt + output <= 2,048: test_traffic.py's bound, with no exemption
    assert all(len(r["prompt"]) + r["max_new_tokens"]
               <= config["serving"]["max_len"] == 2048 for r in block)
    lengths = sorted(len(r["prompt"]) for r in block)
    assert lengths[0] >= 1024 + 16 and lengths[-1] == 1536
    assert sum(n == 1536 for n in lengths) in range(5, 10)  # ~1 in 9 cut
    assert 1024 + 170 < lengths[32] < 1024 + 215
    assert 220 < sum(lengths) / 64 - 1024 < 240             # ~230 a turn
    shared = 64 * 1024 / sum(lengths)
    assert 0.80 < shared < 0.84                     # the issue's ~82%
    outs = sorted(r["max_new_tokens"] for r in block)
    assert outs[0] < 135 and outs[-1] > 505
    # the rehearsal keeps a whole shared page
    small = traffic.resolve(manifest_mod.Cell(ROOT, M, CELL).traffic, True)
    assert small["shared_prefix"]["tokens"] \
        == config["rehearse"]["serving"]["paging"]["page_len"]


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses_correct_with_snapshots_restored(trace):
    proc = _run(RUN, "--workload", CELL, "--seed", str(2 ** 31 + 44),
                "--seconds", "2.5", "--trace", str(trace), "--rehearse",
                extra_env={"PYTHONPATH": PYTHONPATH})
    line = _last_line(proc)
    assert set(line) == KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["metrics"] == {}
    assert f"cell {CELL}:" in proc.stdout
    assert "reference check:" in proc.stdout
    if trace:
        # what a CPU run can read: the host's series and the counters
        said = next(ln for ln in proc.stdout.splitlines()
                    if "readers gave a value for" in ln)
        for name in H1CHAT:
            if spec(name)["source"] != "device_trace":
                assert repr(name) in said, (name, said)
        reused = re.search(r"serving/prefill_tokens_reused = (\d+) of",
                           proc.stdout)
        restored = re.search(r"serving/state_snapshots_restored = (\d+) of "
                             r".* = (\d+)", proc.stdout)
        assert int(reused.group(1)) >= 128
        assert 0 < int(restored.group(1)) <= int(restored.group(2))


@pytest.mark.parametrize("name", H1CHAT)
def test_metric_file_names_its_reader_its_layer_and_its_cell(name):
    s = spec(name)
    assert s["reader"] in readers.load_all()
    assert s["workloads"] == [CELL] and s["unit"] and s["source"]
    assert s["layer"] == LAYER_OF[name]
    if s["reader"] == "registry_ratio_pct":
        assert "over the process" in s["note"] \
            or "counters cover the process" in s["note"]
    if name.endswith("_roofline_pct.h1chat"):
        assert (s["unit"], s["better"]) == ("%", "higher")


def test_the_trace_patterns_tell_the_mixers_state_work_from_the_rest():
    mixer = re.compile(spec("serve.ssm_share_pct.h1chat")["args"]["op"])
    chunk = spec("serve.ssm_chunk_share_pct.h1chat")["args"]
    update = re.compile(
        spec("serve.ssm_update_roofline_pct.h1chat")["args"]["op"])
    paged = re.compile(spec("serve.paged_attn_share_pct.h1chat")["args"]["op"])
    assert "module" not in spec("serve.ssm_share_pct.h1chat")["args"]
    assert (chunk["op"], chunk["module"]) == (mixer.pattern,
                                              "^jit__chunk_prefill_impl")
    for name in ("serve.ssm_update_roofline_pct.h1chat",
                 "serve.paged_attn_share_pct.h1chat"):
        assert spec(name)["args"]["module"] == "^jit__paged_decode_iter_impl"
    assert update.search(UPDATE) and mixer.search(UPDATE)
    assert not paged.search(UPDATE)
    for text in (PAGED, ATTN):
        assert paged.search(text)
        assert not update.search(text) and not mixer.search(text)
    for text in [CONV_STATE] + SCAN:
        assert mixer.search(text), text
        assert not update.search(text) and not paged.search(text)
    # not the projections (named by no parameter), not another layer's
    # products, and no loop (its time is its body's)
    for text in (IN_PROJ, MLP, FUSION, APPEND):
        assert not mixer.search(text), text
        assert not update.search(text) and not paged.search(text)


SIZES = family.sizes(manifest_mod.Cell(ROOT, M, CELL).config, False)


@pytest.fixture
def registry(monkeypatch):
    reg = registry_mod.MetricsRegistry()
    monkeypatch.setattr(registry_mod, "_DEFAULT_REGISTRY", reg)
    return reg


def _decode_runs(runs, call_ns, layers=9):
    ops, modules, t = [], [], 0
    for _ in range(runs):
        start = t
        for _ in range(layers):
            ops.append((t, t + call_ns, UPDATE))
            ops.append((t + call_ns, t + call_ns + 1_000, PAGED))
            t += call_ns + 1_000
        modules.append((start, t, "jit__paged_decode_iter_impl(123)"))
        t += 10_000
    return ops, modules


def _iterations(registry, rows):
    """``serving/iterations`` holding ``(rows_decoding, traced)`` a row."""
    table = registry.table("serving/iterations", ITERATION_COLUMNS, 64)
    for i, (decoding, traced) in enumerate(rows):
        cells = {"t_entry": i, "caller": 1, "decode_dispatch": 1,
                 "rows_decoding": decoding, "traced": traced}
        table.write(array("q", [cells.get(c, 0) for c in ITERATION_COLUMNS]))


def test_the_roofline_takes_the_rows_that_decode_from_the_traced_rows(
        registry):
    """Two decode dispatches of nine layers over 64 slots, and the
    server's log says the iterations that began under the tracer advanced
    48 rows each: the least time is 48 rows' state read and written, 8.44
    MB a row at 819 GB/s. At that speed the reader reads 100, whatever
    the other 16 rows would have cost a program that passed over them —
    and whatever the cold phase before the capture held (8 rows a
    dispatch here, and the process's counters with them), a traced
    iteration that dispatched no decode step, or the drain after it."""
    registry.counter("serving/decode_slots_busy").inc(30 * 8)
    registry.counter("serving/decode_slots_offered").inc(30 * 64)
    _iterations(registry, [(8, 0)] * 30 + [(48, 1), (0, 1), (48, 1), (3, 0)])
    args = spec("serve.ssm_update_roofline_pct.h1chat")["args"]
    row = 2 * 32 * 256 * 128 * 4 + 4 * (3 * 4096 + 2 * 512)
    assert family.ssm_update_bytes(SIZES, 48) == 48 * row
    least_ns = round(48 * row / 819e9 * 1e9)
    read = readers.load_all()["ssm_update_roofline_pct"]
    obs, said = _observed(_Trace(*_decode_runs(2, least_ns)), sizes=SIZES)
    assert read(obs, **args) == pytest.approx(100.0, abs=0.01)
    assert "18 calls over 64 slots of f32 state, 48.00 rows" in said[0]
    assert "2 traced decode dispatches" in said[0]
    assert "bounded by memory" in said[0]
    slow = _observed(_Trace(*_decode_runs(2, round(least_ns / 0.65))),
                     sizes=SIZES)[0]
    assert read(slow, **args) == pytest.approx(65.0, abs=0.05)


def test_the_readers_find_nothing_in_a_program_without_a_mixer(registry):
    """The parent's program, or another family's cell: no counter, other
    sizes, no such call — the metrics are left out of the line, and
    nothing raises."""
    load = readers.load_all()
    args = spec("serve.ssm_update_roofline_pct.h1chat")["args"]
    trace = _Trace(*_decode_runs(1, 500_000))
    obs, said = _observed(trace, sizes=SIZES)
    assert load["ssm_update_roofline_pct"](obs, **args) is None  # no table
    _iterations(registry, [(48, 0)] * 4)
    assert load["ssm_update_roofline_pct"](obs, **args) is None  # none traced
    for name in ("serve.state_restored_pct.h1chat",
                 "serve.state_restore_missed_pct.h1chat"):
        assert load["registry_ratio_pct"](obs, **spec(name)["args"]) is None
    registry.table("serving/iterations").write(array("q", [
        {"rows_decoding": 48, "traced": 1}.get(c, 0)
        for c in ITERATION_COLUMNS]))
    lfm2 = {"hidden_size": 2048, "intermediate_size": 11776,
            "num_hidden_layers": 10}
    assert load["ssm_update_roofline_pct"](
        _observed(trace, sizes=lfm2)[0], **args) is None
    assert load["ssm_update_roofline_pct"](
        _observed(None, sizes=SIZES)[0], **args) is None
    assert load["ssm_update_roofline_pct"](
        _observed(_Trace([(0, 10, PAGED)]), sizes=SIZES)[0], **args) is None
    assert said == []
    # restored and missed over all admissions once the program counts them
    registry.counter("serving/state_snapshots_restored").inc(90)
    registry.counter("serving/state_resets").inc(10)
    registry.counter("serving/state_restore_missed").inc(4)
    assert load["registry_ratio_pct"](
        obs, **spec("serve.state_restored_pct.h1chat")["args"]) \
        == pytest.approx(90.0)
    assert load["registry_ratio_pct"](
        obs, **spec("serve.state_restore_missed_pct.h1chat")["args"]) \
        == pytest.approx(4.0)
