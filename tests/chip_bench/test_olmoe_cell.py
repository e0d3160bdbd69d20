"""The cell ``serve-olmoe-longgen``: its rehearsal, its configuration
against the catalog's entry, its traffic, and its nine metric files —
which kernel each trace pattern counts, and what the expert layer's
readers make of the program's counters and a trace's events."""

import json
import os
import re

import pytest

from benchmarks.chip import manifest as manifest_mod
from benchmarks.chip import readers, traffic
from benchmarks.chip.readers import moe
from deepspeed_tpu.observability import metrics as registry_mod

from ._paths import BENCH, PYTHONPATH, ROOT, RUN, manifest
from .test_rehearse import KEYS, _last_line, _run

CELL = "serve-olmoe-longgen"
M = manifest()
# the catalog's `config` of OLMoE-1B-7B-0125-Instruct (model-configs guide,
# architectures.jsonl), key for key
CATALOG = {
    "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 1024,
    "max_position_embeddings": 4096, "model_type": "olmoe",
    "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 16,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "tie_word_embeddings": False, "vocab_size": 50304}
LONGGEN = ["serve.iter_ms_p50.longgen", "serve.device_idle_pct.longgen",
           "serve.paged_attn_share_pct.longgen",
           "serve.batch_occupancy_pct.longgen", "serve.host_ms_p50.longgen",
           "serve.expert_load_max_over_mean.longgen",
           "serve.experts_touched_pct.longgen",
           "serve.expert_mm_share_pct.longgen",
           "serve.expert_mm_roofline_pct.longgen"]

# instruction texts as a v5e trace's `XLA Ops` line names them (my chip
# run, PR 28, shortened): the paged-attention kernel, the three grouped
# expert matmuls and their metadata call — all Mosaic custom calls of the
# decode program — and a fusion
ATTN = ('%attn.7 = bf16[32,2,8,128]{3,2,1,0:T(8,128)(2,1)} custom-call('
        'bf16[32,16,128]{2,1,0} %fusion.1, bf16[8,513,16,128,128]{4,3,2,1,0}'
        ' %param.2), custom_call_target="tpu_custom_call", operand_layout_'
        'constraints={...}')
RAGGED = [
    '%ragged-dot-none.1 = bf16[256,1024]{1,0:T(8,128)(2,1)} custom-call('
    's32[1]{0} %get-tuple-element.4, bf16[256,2048]{1,0} %gather.3, '
    'bf16[512,2048,1024]{2,1,0} %bitcast.9), custom_call_target='
    '"tpu_custom_call", frontend_attributes={ragged_dot_tiling="256,512,512"}',
    '%ragged-dot-none.2 = f32[256,2048]{1,0:T(8,128)} custom-call(s32[1]{0} '
    '%get-tuple-element.4, bf16[256,1024]{1,0} %fusion.7, bf16[512,1024,2048]'
    '{2,1,0} %bitcast.11), custom_call_target="tpu_custom_call"',
    '%ragged-dot-none = bf16[1024,1024]{1,0:T(8,128)(2,1)} custom-call(s32[1]'
    '{0} %gte, bf16[1024,2048]{1,0} %gather, bf16[512,2048,1024]{2,1,0} '
    '%bitcast), custom_call_target="tpu_custom_call"']
METADATA = ('%ragged-dot-metadata = (s32[513]{0}, s32[512]{0}, s32[512]{0}, '
            's32[1]{0}) custom-call(s32[512]{0} %dynamic-update-slice.3), '
            'custom_call_target="tpu_custom_call"')
FUSION = ('%fusion.109 = bf16[32,1,6144]{2,1,0} fusion(bf16[32,1,2048]{2,1,0}'
          ' %x, bf16[2048,6144]{1,0} %w), kind=kOutput, calls=%fused.109')


def spec(name):
    return manifest_mod.load_json(os.path.join(BENCH, "metrics",
                                               name + ".json"))


def test_the_cell_is_in_the_manifest_as_the_issue_has_it():
    cell = next(w for w in M["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "olmoe-1b-7b-8l-serve", "longgen-closed-32", 1)
    conf = next(c for c in M["configs"] if c["name"] == cell["config"])
    assert conf["reduced"] == ["num_hidden_layers"]
    assert conf["source"] == ("https://huggingface.co/allenai/OLMoE-1B-7B-"
                              "0125-Instruct/blob/main/config.json")
    tokens = next(m for m in M["end_to_end"]
                  if m["name"] == "serve_tokens_per_s")
    assert tokens["workloads"] == ["serve-1p3b-longprompt", CELL]
    assert tokens["bound"] == 0.03       # 1% until PR 54's check
    named = [m["name"] for m in M["per_layer"] if m["name"] in LONGGEN]
    assert named == LONGGEN            # appended, in this order
    for name in LONGGEN:
        entry = next(m for m in M["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "serve_tokens_per_s"
    # every cell that was there is still there, first
    assert [w["name"] for w in M["workloads"]][:4] == [
        "train-125m-zero1", "serve-1p3b-chat", "serve-1p3b-longprompt",
        "train-1p3b-zero3-4chip"]


def test_the_configuration_is_the_catalogs_entry_cut_in_depth_only():
    config = manifest_mod.Cell(ROOT, M, CELL).config
    assert config["published"] == CATALOG
    assert {k for k, v in CATALOG.items() if config[k] != v} \
        == {"num_hidden_layers"}
    assert (config["kind"], config["family"]) == ("serve", "olmoe")
    assert config["num_hidden_layers"] == 8
    assert (config["compute_dtype"], config["param_dtype"]) \
        == ("bfloat16", "bfloat16")
    serving = config["serving"]
    assert (serving["num_slots"], serving["max_len"]) == (32, 2048)
    assert serving["paging"] == {"page_len": 128, "kernel": "auto"}
    # the file's arithmetic: a token of KV, the pool, the weights
    layers, width = config["num_hidden_layers"], config["hidden_size"]
    token_bytes = 2 * layers * width * 2
    assert token_bytes == 64 * 1024
    assert 32 * 2048 * token_bytes == 4 * 2 ** 30
    experts = (layers * config["num_experts"] * 3 * width
               * config["intermediate_size"] * 2)
    assert round(experts / 8 / 1e9, 3) == 0.805        # a layer's experts


def test_the_mix_is_the_issues_closed_loop_of_32():
    mix = traffic.resolve(manifest_mod.Cell(ROOT, M, CELL).traffic, False)
    assert (mix["loop"], mix["clients"], mix["block"], mix["order_seed"]) \
        == ("closed", 32, 32, 1)
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 128,
                                 "sigma": 0.6, "min": 32, "max": 512}
    assert mix["output_len"] == {"dist": "uniform", "min": 256, "max": 1024}
    assert mix["trace_seconds"] == 2.0 and "shared_prefix" not in mix
    # all 32 slots decode from 1.23 s on (on the chip); the window opens
    # well after that and before set-up grows past gpt2-1.3b-serve's
    assert 2 * 1.23 <= mix["lead_in_s"] <= 10
    stream = traffic.RequestStream(mix, 2 ** 31 + 9, 50304)
    block = [stream.take() for _ in range(32)]
    # every request fits a slot of 2048, and the block holds the whole mix
    assert all(len(r["prompt"]) + r["max_new_tokens"] <= 2048 for r in block)
    outs = sorted(r["max_new_tokens"] for r in block)
    assert outs[0] < 300 and outs[-1] > 980
    assert 100 < sorted(len(r["prompt"]) for r in block)[16] < 160


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses_correct(trace):
    proc = _run(RUN, "--workload", CELL, "--seed", str(2 ** 31 + 28),
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
        for name in ("serve.iter_ms_p50.longgen",
                     "serve.batch_occupancy_pct.longgen",
                     "serve.host_ms_p50.longgen",
                     "serve.expert_load_max_over_mean.longgen",
                     "serve.experts_touched_pct.longgen"):
            assert repr(name) in said, said
        assert "moe:" in proc.stdout and "pairs over" in proc.stdout


def test_the_logit_row_tool_refuses_both_wrong_paths_and_both_controls(
        tmp_path):
    """``tools/olmoe_check.py`` at the rehearsal's size: the program as
    configured is inside the two limits on a row of logits, and top-(k-1),
    renormalised weights and the reference with fp8 and with int8 weights
    are each outside (at 64 wide an expert is a sixth of a layer: the
    chip's readings, not these, set the limits)."""
    proc = _run(os.path.join(BENCH, "tools", "olmoe_check.py"), "--rehearse",
                "--seeds", "5", "--out", str(tmp_path),
                extra_env={"PYTHONPATH": PYTHONPATH})
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    with open(tmp_path / "5.json") as f:
        out = json.load(f)
    assert out["as_configured"]["within"]
    assert out["as_configured"]["tokens"]["within"]
    assert len(out["as_configured"]["requests"]) == 2
    for arm in ("top_k_minus_1", "renormalised", "float8_e4m3fn", "int8"):
        assert not out[arm]["within"], (arm, out[arm])
        assert out[arm]["row_mean_least"] > 10 * out["as_configured"][
            "row_mean"]
    assert "not refused: none" in proc.stdout


@pytest.mark.parametrize("name", LONGGEN)
def test_metric_file_names_its_reader_its_layer_and_its_cell(name):
    s = spec(name)
    assert s["reader"] in readers.load_all()
    assert s["workloads"] == [CELL] and s["unit"] and s["source"]
    layers = {"serve.iter_ms_p50.longgen": "server host loop",
              "serve.host_ms_p50.longgen": "server host loop",
              "serve.batch_occupancy_pct.longgen": "server host loop",
              "serve.device_idle_pct.longgen": "device",
              "serve.paged_attn_share_pct.longgen": "Pallas kernels"}
    assert s["layer"] == layers.get(name, "expert layer")
    if s["source"] == "program_counter":
        assert "512" in s["note"] or "counters cover the process" in s["note"]


def test_the_two_kernel_patterns_tell_paged_attention_from_the_experts():
    """Both kernels are ``tpu_custom_call``s inside the decode program:
    chat's pattern (every such call) would count the grouped matmuls as
    paged attention. The two ``.longgen`` patterns part them by the
    instruction's name, on a line of each."""
    paged = spec("serve.paged_attn_share_pct.longgen")["args"]
    share = spec("serve.expert_mm_share_pct.longgen")["args"]
    roofline = spec("serve.expert_mm_roofline_pct.longgen")["args"]
    chat = spec("serve.paged_attn_share_pct.chat")["args"]
    assert paged["module"] == chat["module"]
    assert share["op"] == roofline["op"] and "module" not in share
    is_paged, is_expert = re.compile(paged["op"]), re.compile(share["op"])
    assert is_paged.search(ATTN) and not is_expert.search(ATTN)
    for text in RAGGED + [METADATA]:
        assert re.search(chat["op"], text)      # the trap
        assert is_expert.search(text) and not is_paged.search(text)
    assert not is_paged.search(FUSION) and not is_expert.search(FUSION)


class _Trace:
    """As much of an ``xplane.Trace`` as the readers read: one device,
    its operations and program runs, a window."""

    def __init__(self, ops, modules=(), window=None):
        self.t0, self.t1 = window or (0, max(e for _, e, _ in ops))
        device = type("Device", (), {"ops": sorted(ops),
                                     "modules": sorted(modules),
                                     "name": "/device:TPU:0"})()
        self.devices = [device]
        self.window_s = (self.t1 - self.t0) / 1e9


SIZES = {"hidden_size": 2048, "intermediate_size": 1024,
         "num_hidden_layers": 2}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _observed(trace, sizes=SIZES, peaks=PEAKS):
    said = []
    return readers.Observed(setup={}, series={}, trace=trace, peaks=peaks,
                            facts={"sizes": sizes}, say=said.append), said


@pytest.fixture
def registry(monkeypatch):
    reg = registry_mod.MetricsRegistry()
    monkeypatch.setattr(registry_mod, "_DEFAULT_REGISTRY", reg)
    return reg


def _count(reg, **values):
    for name, v in values.items():
        reg.counter("moe/" + name).inc(v)


def test_a_program_that_counts_no_routing_reads_as_nothing(registry):
    """The parent's program, or a model without experts: the counters
    are not there, and the expert layer's metrics are left out of the
    line — the readers do not raise."""
    load = readers.load_all()
    op = spec("serve.expert_mm_roofline_pct.longgen")["args"]["op"]
    trace = _Trace([(0, 500_000, RAGGED[0]), (500_000, 900_000, ATTN)])
    obs, said = _observed(trace)
    assert load["moe_load_max_over_mean"](obs) is None
    assert load["moe_expert_mm_roofline_pct"](obs, op=op) is None
    assert load["registry_ratio_pct"](
        obs, **spec("serve.experts_touched_pct.longgen")["args"]) is None
    assert said == []
    # counters, but a trace without a grouped matmul, or no trace, or
    # sizes of a model without gated experts
    _count(registry, assignments=2048, expert_calls=8, experts_touched=500,
           experts_offered=512, load_max=80)
    assert load["moe_expert_mm_roofline_pct"](
        _observed(_Trace([(0, 10, ATTN)]))[0], op=op) is None
    assert load["moe_expert_mm_roofline_pct"](_observed(None)[0], op=op) \
        is None
    assert load["moe_expert_mm_roofline_pct"](
        _observed(trace, sizes={"n_embd": 2048})[0], op=op) is None


def test_load_and_touched_come_from_the_counters(registry):
    _count(registry, assignments=8 * 256, expert_calls=8,
           experts_touched=8 * 60, experts_offered=8 * 64, load_max=8 * 10)
    load = readers.load_all()
    obs, said = _observed(None)
    # largest group 10 of a mean group of 256 / 64 = 4
    assert load["moe_load_max_over_mean"](obs) == pytest.approx(2.5)
    assert load["registry_ratio_pct"](
        obs, **spec("serve.experts_touched_pct.longgen")["args"]) \
        == pytest.approx(100 * 60 / 64)
    assert any("2048 pairs over 8 layer calls of 64 experts" in s
               for s in said)


def _decode_runs(runs, per_layer_ns, tiles=1):
    """``runs`` runs of a decode program of two layers: per layer a
    metadata call and three grouped matmuls, each split into ``tiles``
    calls of fewer rows that together take ``per_layer_ns / 3``."""
    ops, modules, t = [], [], 0
    for _ in range(runs):
        start = t
        for _ in range(SIZES["num_hidden_layers"]):
            ops.append((t, t + 2_000, METADATA))
            t += 2_000
            for text in RAGGED:
                for _ in range(tiles):
                    ns = round(per_layer_ns / 3 / tiles)
                    ops.append((t, t + ns, text))
                    t += ns
        modules.append((start, t, "jit__paged_decode_iter_impl(123)"))
        t += 10_000
    return ops, modules


def test_the_roofline_counts_the_experts_touched_and_stays_under_100(
        registry):
    """Two runs of a two-layer decode program in the trace, 256 pairs
    and 60 experts touched a layer call by the counters: the least time
    is the bytes of 60 experts' three matrices and the rows, over 819
    GB/s. At the speed of light the share reads 100, whether a layer's
    matmuls are one ``ragged_dot`` call each or split into row tiles
    (the layer calls are counted by program runs, not by matmul events);
    with every expert counted whether touched or not it would read over
    it."""
    _count(registry, assignments=40 * 256, expert_calls=40,
           experts_touched=40 * 60, experts_offered=40 * 64, load_max=400)
    op = spec("serve.expert_mm_roofline_pct.longgen")["args"]["op"]
    weights = 60 * 3 * 2048 * 1024 * 2
    rows = 256 * (2 * (2048 + 1024) * 2 + 1024 * 2 + 2048 * 4)
    assert moe.expert_mm_bytes(SIZES, 256, 60) == weights + rows
    assert moe.expert_mm_ops(SIZES, 256) == 2 * 3 * 2048 * 1024 * 256
    least_ns = (weights + rows) / 819e9 * 1e9           # one call, 0.93 ms
    assert 0.9e6 < least_ns < 1.0e6
    read = readers.load_all()["moe_expert_mm_roofline_pct"]
    for tiles in (1, 2):
        obs, said = _observed(_Trace(*_decode_runs(2, least_ns, tiles)))
        assert read(obs, op=op) == pytest.approx(100.0, abs=0.01)
        assert f"{12 * tiles} matmuls in 4.0 layer calls" in said[0]
        assert "bounded by memory" in said[0]
    assert read(_observed(_Trace(*_decode_runs(2, least_ns / 0.6, 2)))[0],
                op=op) == pytest.approx(60.0, abs=0.05)
    # a window that opens in the middle of the first run, between its
    # two layers: a layer call and a half... the run counts by the share
    # of its matmuls inside, so the reading does not move
    ops, modules = _decode_runs(2, least_ns)
    half = next(s for s, _, text in sorted(ops)[1:] if text == METADATA)
    cut = _Trace(ops, modules, window=(half, max(e for _, e, _ in ops)))
    obs, said = _observed(cut)
    assert read(obs, op=op) == pytest.approx(100.0, abs=0.01)
    assert "6 matmuls in 3.0 layer calls" not in said[0]
    assert "9 matmuls in 3.0 layer calls" in said[0]
    # all 64 experts' weights, touched or not: the count the issue warns of
    over = (64 * 3 * 2048 * 1024 * 2 + rows) / (weights + rows)
    assert over > 1.06
    # compute-bound calls (a chunk of many rows) are bounded by the MXU
    assert moe.expert_mm_ops(SIZES, 10 ** 6) / 197e12 \
        > moe.expert_mm_bytes(SIZES, 10 ** 6, 64) / 819e9


def test_the_family_counts_eight_of_sixty_four_experts_a_token():
    from benchmarks.chip import families
    config = manifest_mod.Cell(ROOT, M, CELL).config
    family = families.load(config)
    sizes = family.sizes(config, False)
    assert family.DEPTH_KEYS == ("num_hidden_layers",)
    d, f = 2048, 1024
    per_layer = 4 * d * d + d * 64 + 8 * 3 * d * f
    weights = 8 * per_layer + 50304 * d
    assert family.ops_per_token(sizes, 0) == 6 * weights
    assert family.ops_per_token(sizes, 2048) - 6 * weights \
        == 8 * 3 * (2 * 2 * 2048 * d) // 2
    assert family.expected_kernels(None) == []
    assert family.expected_kernels(config["serving"]) == [
        ("paged_decode", "path", {"interpret": False, "impl": "kernel"}),
        ("paged_attention", "page128",
         {"interpret": False, "impl": "kernel"})]


def test_the_reference_shares_no_code_with_the_program():
    with open(os.path.join(BENCH, "families", "olmoe.py")) as f:
        lines = f.read().splitlines()
    start = next(i for i, ln in enumerate(lines)
                 if ln.startswith("# -- the plain reference"))
    assert not any("deepspeed_tpu" in ln for ln in lines[start:])
    assert not any(word in ln for ln in lines[start:]
                   for word in ("ragged_dot", "argsort", "pallas"))
