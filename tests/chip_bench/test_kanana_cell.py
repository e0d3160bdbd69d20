"""The cell ``serve-kanana-docqa``: its place in the manifest, its
configuration against the catalog's entry, its traffic, its rehearsal (a
prefix hit on latent pages among its requests), its seventeen metric
files — which operations each trace pattern counts — the family's
counts of the latent kernel's work, and what the two readers of
``readers/latent.py`` make of the program's counters and a trace's
events."""

import json
import os
import re

import pytest

from benchmarks.chip import families, manifest as manifest_mod
from benchmarks.chip import readers, traffic
from benchmarks.chip.families import deepseek_v3 as family
from deepspeed_tpu.observability import metrics as registry_mod

from ._paths import BENCH, PYTHONPATH, ROOT, RUN, manifest
from .test_lfm2_cell import _decode_runs
from .test_olmoe_cell import (ATTN, FUSION, METADATA, PEAKS, RAGGED, _Trace,
                              _count, _observed)
from .test_rehearse import KEYS, _last_line, _run

CELL = "serve-kanana-docqa"
M = manifest()
# the catalog's `config` of kanana-2-30b-a3b-instruct-2601 (model-configs
# guide, architectures.jsonl), key for key
CATALOG = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "kv_lora_rank": 512, "max_position_embeddings": 32768,
    "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
    "n_shared_experts": 2, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 6,
    "num_hidden_layers": 48, "num_key_value_heads": 32, "q_lora_rank": None,
    "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None,
    "rope_theta": 1000000, "routed_scaling_factor": 2.448,
    "scoring_func": "sigmoid", "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 128256}
DOCQA = ["serve.iter_ms_p50.docqa", "serve.host_ms_p50.docqa",
         "serve.device_idle_pct.docqa", "serve.batch_occupancy_pct.docqa",
         "serve.paged_rows_walked_pct.docqa", "serve.prefill_share_pct.docqa",
         "serve.prefill_chunk_width_pct.docqa", "serve.ttft_p50_ms.docqa",
         "serve.prefix_hit_pct.docqa", "serve.expert_mm_share_pct.docqa",
         "serve.expert_mm_roofline_pct.docqa",
         "serve.experts_touched_pct.docqa",
         "serve.expert_load_max_over_mean.docqa",
         "serve.latent_attn_share_pct.docqa",
         "serve.latent_attn_roofline_pct.docqa",
         "serve.chunk_latent_attn_share_pct.docqa",
         "serve.shared_expert_share_pct.docqa"]
LAYER_OF = {
    "serve.iter_ms_p50.docqa": "server host loop",
    "serve.host_ms_p50.docqa": "server host loop",
    "serve.batch_occupancy_pct.docqa": "server host loop",
    "serve.prefill_chunk_width_pct.docqa": "server host loop",
    "serve.ttft_p50_ms.docqa": "server host loop",
    "serve.device_idle_pct.docqa": "device",
    "serve.paged_rows_walked_pct.docqa": "paged KV and prefix cache",
    "serve.prefix_hit_pct.docqa": "paged KV and prefix cache",
    "serve.prefill_share_pct.docqa": "model step",
    "serve.latent_attn_share_pct.docqa": "latent attention",
    "serve.latent_attn_roofline_pct.docqa": "latent attention",
    "serve.chunk_latent_attn_share_pct.docqa": "latent attention"}

# instruction texts as a v5e trace's `XLA Ops` line names them
LATENT = ('%latent_attn.5 = f32[32,32,512]{2,1,0:T(8,128)} custom-call('
          's32[32]{0} %select.1, s32[32,72]{1,0} %param.3, s32[1]{0} '
          '%constant.9, f32[32,32,576]{2,1,0} %fusion.77, f32[32,1,576]{2,1,0}'
          ' %bitcast.4, f32[32,1,512]{2,1,0} %bitcast.5, f32[1025,1,576,128]'
          '{3,2,1,0} %param.12), custom_call_target="tpu_custom_call"')
SCORES = ('%fusion.212 = f32[1,32,128,9216]{3,2,1,0} fusion(f32[1,128,32,576]'
          '{3,2,1,0} %fusion.211, f32[1,576,9216]{2,1,0} %bitcast.88), '
          'kind=kOutput, calls=%fused_computation.212')
VALUES = ('%fusion.215 = f32[1,128,32,512]{3,2,1,0} fusion(f32[1,32,128,9216]'
          '{3,2,1,0} %fusion.214, f32[1,576,9216]{2,1,0} %bitcast.88), '
          'kind=kOutput, calls=%fused_computation.215')
SHARED = ('%fusion.61 = f32[32,1,1536]{2,1,0} fusion(bf16[96,2048]{1,0} '
          '%fusion.60, bf16[2048,1536]{1,0:T(8,128)(2,1)} %params__layers_2'
          '____moe____shared____w1____kernel__.1), kind=kOutput, '
          'calls=%fused_computation.61')


def spec(name):
    return manifest_mod.load_json(os.path.join(BENCH, "metrics",
                                               name + ".json"))


def _config():
    return manifest_mod.Cell(ROOT, M, CELL).config


def test_the_cell_is_in_the_manifest_as_the_issue_has_it():
    cell = next(w for w in M["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kanana-2-30b-a3b-serve", "docqa-closed-32", 1)
    assert "8x" in cell["why"]            # a cut in depth: host and idle
    conf = next(c for c in M["configs"] if c["name"] == cell["config"])
    assert conf["reduced"] == ["num_hidden_layers"]
    assert conf["source"] == ("https://huggingface.co/kakaocorp/kanana-2-30b"
                              "-a3b-instruct-2601/blob/main/config.json")
    tokens = next(m for m in M["end_to_end"]
                  if m["name"] == "serve_tokens_per_s")
    # appended after the cells that were there (a later cell comes after)
    assert tokens["workloads"][:4] == [
        "serve-1p3b-longprompt", "serve-olmoe-longgen", "serve-lfm2-agent",
        CELL]
    assert tokens["bound"] == 0.03       # 1% until PR 54's check
    named = [m["name"] for m in M["per_layer"] if m["name"] in DOCQA]
    assert named == DOCQA                  # appended, in this order
    for name in DOCQA:
        entry = next(m for m in M["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "serve_tokens_per_s"
    # every cell that was there is still there, first; one in seven on
    # four chips
    assert [w["name"] for w in M["workloads"]][:7] == [
        "train-125m-zero1", "serve-1p3b-chat", "serve-1p3b-longprompt",
        "train-1p3b-zero3-4chip", "serve-olmoe-longgen", "serve-lfm2-agent",
        CELL]
    assert sum(w["chips"] == 4 for w in M["workloads"][:7]) == 1
    # no per-layer metric of an earlier cell took this one in
    for m in M["per_layer"]:
        if m["name"] not in DOCQA:
            assert CELL not in m.get("workloads", [])


def test_the_configuration_is_the_catalogs_entry_cut_in_depth_only():
    config = _config()
    assert config["published"] == CATALOG
    assert {k for k, v in CATALOG.items() if config[k] != v} \
        == {"num_hidden_layers"}
    assert (config["kind"], config["family"]) == ("serve", "deepseek_v3")
    # layer 0 (dense) and five expert layers: the floor is one and four
    assert config["num_hidden_layers"] == 6
    assert family.DEPTH_KEYS == ("num_hidden_layers",)
    assert config["param_dtype"] == "bfloat16"
    assert config["compute_dtype"] in ("float32", "bfloat16")
    serving = config["serving"]
    assert (serving["num_slots"], serving["max_len"]) == (32, 9216)
    assert serving["paging"] == {"page_len": 128, "num_pages": 1025,
                                 "kernel": "auto"}
    # the file's arithmetic
    d, heads = 2048, 32
    attention = (d * heads * 192 + d * 576 + 512 * heads * 256
                 + heads * 128 * d)
    assert round(attention / 1e6, 2) == 26.35
    expert = 3 * d * 768
    layer = attention + 128 * expert + 3 * d * 1536 + d * 128 + 2 * d + 512
    assert abs(layer / 1e6 - 640.05) < 0.05    # the file sums rounded parts
    dense = attention + 3 * d * 6144 + 2 * d + 512
    total = 2 * 128256 * d + dense + 5 * layer + d
    assert round(total * 2 / 1e9, 2) == 7.58
    sizes = family.sizes(config, False)
    assert family.latent_width(sizes) == 576
    token = config["num_hidden_layers"] * 576
    assert (token * 2, token * 4) == (6912, 13824)          # 6.9 | 13.8 KB
    assert round(1025 * 128 * token * 4 / 1e9, 2) == 1.81
    assert 9216 * 128256 * 4 == 4728029184                  # the check's logits
    for word in ("stage 0 of eight", "7.58 GB", "4.73 GB", "eight times"):
        assert word in config["deployment"], word
    said = " ".join(config["assumed"])
    for word in ("compute_dtype", "max_len 9216", "num_pages 1025",
                 "e_score_correction_bias", "0.01"):
        assert word in said, word
    assert family.expert_sizes(sizes) == {
        "hidden_size": 2048, "intermediate_size": 768,
        "num_hidden_layers": 5}


def test_the_family_builds_the_program_at_the_files_sizes_and_names_its_kernels():
    config = _config()
    module = families.load(config).build(config, False)
    cfg = module.config
    assert (cfg.num_hidden_layers, cfg.first_k_dense_replace,
            cfg.n_routed_experts, cfg.num_experts_per_tok,
            cfg.n_shared_experts) == (6, 1, 128, 6, 2)
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.q_lora_rank) == (512, 128, 64, 128, None)
    assert cfg.routed_scaling_factor == 2.448 and cfg.rope_interleave
    assert cfg.vocab_size == 128256 and cfg.rope_theta == 1e6
    small = families.load(config).build(config, True).config
    assert (small.hidden_size, small.num_hidden_layers) == (64, 3)
    kernels = family.expected_kernels(config["serving"])
    assert [(k, s) for k, s, _ in kernels] == [
        ("paged_decode", "path"), ("latent_attention", "page128")]
    assert all(f == {"interpret": False, "impl": "kernel"}
               for _, _, f in kernels)
    assert family.expected_kernels(None) == []


def test_the_reference_shares_no_code_with_the_program():
    with open(os.path.join(BENCH, "families", "deepseek_v3.py")) as f:
        source = f.read()
    body = source[source.index("# -- the plain reference"):]
    assert "deepspeed_tpu" not in body
    assert "ragged" not in body and "pallas" not in body


def test_the_mix_is_the_issues_closed_loop_over_four_documents():
    mix = traffic.resolve(manifest_mod.Cell(ROOT, M, CELL).traffic, False)
    assert (mix["loop"], mix["clients"], mix["block"], mix["order_seed"]) \
        == ("closed", 32, 32, 1)
    assert mix["shared_prefix"] == {"share": 1.0, "count": 4, "tokens": 8192}
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 128,
                                 "sigma": 0.6, "min": 32, "max": 8704}
    assert mix["output_len"] == {"dist": "uniform", "min": 64, "max": 192}
    assert mix["trace_seconds"] == 2.0
    # the 32 cold admissions prefill 8.3 k tokens each before any hit
    assert 30 <= mix["lead_in_s"] <= 90
    assert "sessions" in mix["why"]
    stream = traffic.RequestStream(mix, 2 ** 31 + 9, 128256)
    block = [stream.take() for _ in range(32)]
    assert all(r["kind"] == "shared_prefix" for r in block)
    # four documents of 64 whole pages, every request opens with one
    opens = {tuple(r["prompt"][:8192]) for r in block}
    assert len(opens) == 4
    assert all(len(r["prompt"]) + r["max_new_tokens"] <= 9216 for r in block)
    bodies = sorted(len(r["prompt"]) - 8192 for r in block)
    assert bodies[0] >= 32 and bodies[-1] <= 512
    assert 110 < bodies[16] < 150
    shared = 32 * 8192 / sum(len(r["prompt"]) for r in block)
    assert 0.97 < shared < 0.99
    outs = sorted(r["max_new_tokens"] for r in block)
    assert outs[0] < 70 and outs[-1] > 186
    # the rehearsal keeps a whole shared page
    small = traffic.resolve(manifest_mod.Cell(ROOT, M, CELL).traffic, True)
    assert small["shared_prefix"]["tokens"] \
        == _config()["rehearse"]["serving"]["paging"]["page_len"]
    assert (small["clients"], small["shared_prefix"]["count"]) == (3, 2)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses_correct_with_a_prefix_hit(trace):
    proc = _run(RUN, "--workload", CELL, "--seed", str(2 ** 31 + 37),
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
        for name in DOCQA:
            if spec(name)["source"] != "device_trace":
                assert repr(name) in said, (name, said)
        assert "moe:" in proc.stdout and "pairs over" in proc.stdout
        reused = re.search(r"serving/prefill_tokens_reused = (\d+) of",
                           proc.stdout)
        assert int(reused.group(1)) >= 128          # a hit on latent pages


def test_the_logit_row_tool_rehearses_and_refuses_every_control(tmp_path):
    tool = os.path.join(BENCH, "tools", "kanana_check.py")
    proc = _run(tool, "--rehearse", "--new", "7", "--out", str(tmp_path),
                extra_env={"PYTHONPATH": PYTHONPATH})
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "as configured within the limits" in proc.stdout
    assert "not refused: none" in proc.stdout
    out, = [json.load(open(os.path.join(tmp_path, f)))
            for f in os.listdir(tmp_path)]
    assert out["as_configured"]["row_max"] < 1e-4
    assert len(out["as_configured"]["requests"]) == 3
    assert out["as_configured"]["requests"][1]["hit"] is True
    for name in ("prefix_hit_on_another_documents_pages", "scaling_factor_1",
                 "softmax_for_sigmoid", "bias_in_the_weights",
                 "no_shared_expert", "top_5",
                 "rotary_pairs_left_interleaved", "float8_e4m3fn", "int8"):
        assert out[name]["within"] is False, name
        assert out[name]["row_mean_least"] \
            > 100 * out["as_configured"]["row_mean"], name


@pytest.mark.parametrize("name", DOCQA)
def test_metric_file_names_its_reader_its_layer_and_its_cell(name):
    s = spec(name)
    assert s["reader"] in readers.load_all()
    assert s["workloads"] == [CELL] and s["unit"] and s["source"]
    assert s["layer"] == LAYER_OF.get(name, "expert layer")
    if s["source"] == "program_counter":
        assert "512" in s["note"] or "counters cover the process" in s["note"]
    if name.endswith("_roofline_pct.docqa"):
        assert (s["unit"], s["better"]) == ("%", "higher")


def test_the_trace_patterns_tell_the_kernels_and_the_attention_paths_apart():
    latent = re.compile(spec("serve.latent_attn_share_pct.docqa")["args"]["op"])
    expert = re.compile(spec("serve.expert_mm_share_pct.docqa")["args"]["op"])
    chunk = re.compile(
        spec("serve.chunk_latent_attn_share_pct.docqa")["args"]["op"])
    shared = re.compile(
        spec("serve.shared_expert_share_pct.docqa")["args"]["op"])
    assert spec("serve.latent_attn_roofline_pct.docqa")["args"] \
        == spec("serve.latent_attn_share_pct.docqa")["args"]
    assert spec("serve.latent_attn_share_pct.docqa")["args"]["module"] \
        == "^jit__paged_decode_iter_impl"
    assert spec("serve.chunk_latent_attn_share_pct.docqa")["args"]["module"] \
        == "^jit__chunk_prefill_impl"
    assert spec("serve.expert_mm_roofline_pct.docqa")["args"]["op"] \
        == expert.pattern
    assert latent.search(LATENT)
    for text in (ATTN, FUSION, METADATA, SCORES, VALUES, SHARED) + tuple(
            RAGGED):
        assert not latent.search(text), text
    for text in RAGGED + [METADATA]:
        assert expert.search(text)
        assert not chunk.search(text) and not shared.search(text)
    assert chunk.search(SCORES) and chunk.search(VALUES)
    assert shared.search(SHARED)
    for text in (LATENT, ATTN, FUSION):
        assert not expert.search(text) and not chunk.search(text) \
            and not shared.search(text), text
    # no pattern for the paged kernel's `%attn.N` catches the latent call
    assert not re.search(r"^%attn\.", LATENT)


def test_the_familys_counts_of_the_latent_kernels_work():
    sizes = family.sizes(_config(), False)
    # a walked token: 32 heads x (576 for the score + 512 for the values)
    assert family.latent_attn_ops(sizes, 1000) == 2 * 32 * 1000 * (576 + 512)
    assert family.latent_attn_bytes(sizes, 1000, 4) == 1000 * 576 * 4
    assert family.latent_attn_bytes(sizes, 1000, 2) == 1000 * 576 * 2


SIZES = dict(family.sizes(_config(), False), num_hidden_layers=2)


@pytest.fixture
def registry(monkeypatch):
    reg = registry_mod.MetricsRegistry()
    monkeypatch.setattr(registry_mod, "_DEFAULT_REGISTRY", reg)
    return reg


def _latent_runs(runs, call_ns, layers=2):
    ops, modules, t = [], [], 0
    for _ in range(runs):
        start = t
        for _ in range(layers):
            ops.append((t, t + call_ns, LATENT))
            t += call_ns + 1_000
        modules.append((start, t, "jit__paged_decode_iter_impl(123)"))
        t += 10_000
    return ops, modules


def test_the_latent_roofline_takes_the_tokens_walked_from_the_counters(
        registry):
    """Ten dispatches of 32 slots walked 2,000,000 pooled tokens: a layer
    call walks 200,000, which in float32 are 460.8 MB (0.563 ms at 819
    GB/s; its 13.9 GFLOP are 0.071 ms). At that speed the reader reads
    100, at twice the time 50."""
    registry.counter("serving/latent_tokens_walked").inc(2_000_000)
    registry.counter("serving/decode_slots_offered").inc(320)
    args = spec("serve.latent_attn_roofline_pct.docqa")["args"]
    least_ns = 200_000 * 576 * 4 / 819e9 * 1e9
    read = readers.load_all()["latent_attn_roofline_pct"]
    obs, said = _observed(_Trace(*_latent_runs(3, round(least_ns))),
                          sizes=SIZES)
    assert read(obs, **args) == pytest.approx(100.0, abs=0.01)
    assert "6 calls of 32 rows over a f32 pool, 200000 pooled tokens" \
        in said[0]
    assert "bounded by memory" in said[0]
    assert read(_observed(_Trace(*_latent_runs(3, round(2 * least_ns))),
                          sizes=SIZES)[0], **args) \
        == pytest.approx(50.0, abs=0.01)


def test_the_experts_roofline_takes_this_familys_width_and_expert_layers(
        registry):
    """One dense and one expert layer here: 192 pairs and 99 experts
    touched a layer call, experts 768 wide."""
    from benchmarks.chip.readers import lfm2 as lfm2_reader
    _count(registry, assignments=40 * 192, expert_calls=40,
           experts_touched=40 * 99, experts_offered=40 * 128, load_max=400)
    op = spec("serve.expert_mm_roofline_pct.docqa")["args"]["op"]
    least_ns = lfm2_reader.expert_mm_bytes(2048, 768, 192, 99) / 819e9 * 1e9
    read = readers.load_all()["deepseek_v3_expert_mm_roofline_pct"]
    obs, said = _observed(_Trace(*_decode_runs(2, least_ns, layers=1)),
                          sizes=SIZES)
    assert read(obs, op=op) == pytest.approx(100.0, abs=0.01)
    assert "6 matmuls in 2.0 layer calls of experts 768 wide" in said[0]


def test_the_readers_find_nothing_in_a_program_without_a_latent_pool(
        registry):
    """The parent's program, or another family's cell: no counter, other
    sizes — the metrics are left out of the line, and nothing raises."""
    load = readers.load_all()
    args = spec("serve.latent_attn_roofline_pct.docqa")["args"]
    op = spec("serve.expert_mm_roofline_pct.docqa")["args"]["op"]
    trace = _Trace(*_latent_runs(1, 500_000))
    obs, said = _observed(trace, sizes=SIZES)
    assert load["latent_attn_roofline_pct"](obs, **args) is None
    assert load["deepseek_v3_expert_mm_roofline_pct"](obs, op=op) is None
    registry.counter("serving/latent_tokens_walked").inc(1000)
    registry.counter("serving/decode_slots_offered").inc(64)
    _count(registry, assignments=2048, expert_calls=8, experts_touched=500,
           experts_offered=512, load_max=80)
    lfm2_sizes = {"hidden_size": 2048, "intermediate_size": 11776,
                  "moe_intermediate_size": 1536, "num_hidden_layers": 4,
                  "num_dense_layers": 2}
    for sizes, t in ((lfm2_sizes, trace), (SIZES, None),
                     (SIZES, _Trace([(0, 10, ATTN)]))):
        o = _observed(t, sizes=sizes)[0]
        assert load["latent_attn_roofline_pct"](o, **args) is None
        assert load["deepseek_v3_expert_mm_roofline_pct"](o, op=op) is None
    assert said == []
