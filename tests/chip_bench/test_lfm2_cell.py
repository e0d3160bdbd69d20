"""The cell ``serve-lfm2-agent``: its rehearsal (a prefix hit among its
requests), its configuration against the catalog's entry, its traffic,
and its fifteen metric files — which operations each trace pattern
counts, and what the family's own reader of the expert matmuls makes of
the program's counters and a trace's events."""

import json
import os
import re

import pytest

from benchmarks.chip import manifest as manifest_mod
from benchmarks.chip import readers, traffic
from benchmarks.chip.readers import lfm2 as lfm2_reader
from benchmarks.chip.readers import moe
from deepspeed_tpu.observability import metrics as registry_mod

from ._paths import BENCH, PYTHONPATH, ROOT, RUN, manifest
from .test_olmoe_cell import (ATTN, FUSION, METADATA, PEAKS, RAGGED, _Trace,
                              _count, _observed)
from .test_rehearse import KEYS, _last_line, _run

CELL = "serve-lfm2-agent"
M = manifest()
PERIOD = ["full_attention", "conv", "conv", "conv"]
# the catalog's `config` of LFM2-24B-A2B (model-configs guide,
# architectures.jsonl), key for key
CATALOG = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776,
    "layer_types": (["conv", "conv"] + PERIOD * 10)[:40],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
    "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}
AGENT = ["serve.iter_ms_p50.agent", "serve.device_idle_pct.agent",
         "serve.host_ms_p50.agent", "serve.batch_occupancy_pct.agent",
         "serve.paged_rows_walked_pct.agent",
         "serve.paged_attn_share_pct.agent", "serve.prefill_share_pct.agent",
         "serve.ttft_p50_ms.agent", "serve.prefix_hit_pct.agent",
         "serve.experts_touched_pct.agent",
         "serve.expert_load_max_over_mean.agent",
         "serve.expert_mm_share_pct.agent",
         "serve.expert_mm_roofline_pct.agent",
         "serve.state_restored_pct.agent", "serve.conv_share_pct.agent"]
LAYER_OF = {
    "serve.iter_ms_p50.agent": "server host loop",
    "serve.host_ms_p50.agent": "server host loop",
    "serve.batch_occupancy_pct.agent": "server host loop",
    "serve.ttft_p50_ms.agent": "server host loop",
    "serve.device_idle_pct.agent": "device",
    "serve.paged_attn_share_pct.agent": "Pallas kernels",
    "serve.paged_rows_walked_pct.agent": "paged KV and prefix cache",
    "serve.prefix_hit_pct.agent": "paged KV and prefix cache",
    "serve.state_restored_pct.agent": "paged KV and prefix cache",
    "serve.prefill_share_pct.agent": "model step",
    "serve.conv_share_pct.agent": "model step"}

# instruction texts as a v5e trace's `XLA Ops` line names them: a
# fusion's operands that are parameters carry their path in the tree
CONV_IN = ('%fusion.41 = bf16[32,1,6144]{2,1,0:T(8,128)(2,1)} fusion(bf16[32,'
           '1,2048]{2,1,0} %fusion.40, bf16[2048,6144]{1,0:T(8,128)(2,1)} '
           '%params__layers_3____conv____in_proj____kernel__.1), '
           'kind=kOutput, calls=%fused_computation.41')
CONV_SUM = ('%fusion.43 = f32[32,1,2048]{2,1,0} fusion(f32[32,3,2048]{2,1,0} '
            '%concatenate.2, bf16[3,2048]{1,0} %params__layers_3____conv____'
            'w__.1), kind=kLoop, calls=%fused_computation.43')
DENSE = ('%fusion.9 = bf16[32,1,11776]{2,1,0} fusion(bf16[32,1,2048]{2,1,0} '
         '%fusion.8, bf16[2048,11776]{1,0} %params__layers_0____mlp____w1____'
         'kernel__.1), kind=kOutput, calls=%fused_computation.9')


def spec(name):
    return manifest_mod.load_json(os.path.join(BENCH, "metrics",
                                               name + ".json"))


def test_the_cell_is_in_the_manifest_as_the_issue_has_it():
    cell = next(w for w in M["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "lfm2-24b-a2b-10l-serve", "agent-closed-32", 1)
    conf = next(c for c in M["configs"] if c["name"] == cell["config"])
    assert conf["reduced"] == ["num_hidden_layers", "layer_types"]
    assert conf["source"] == ("https://huggingface.co/LiquidAI/LFM2-24B-A2B/"
                              "blob/main/config.json")
    tokens = next(m for m in M["end_to_end"]
                  if m["name"] == "serve_tokens_per_s")
    # appended after the cells that were there (a later cell comes after)
    assert tokens["workloads"][:3] == ["serve-1p3b-longprompt",
                                       "serve-olmoe-longgen", CELL]
    assert tokens["bound"] == 0.03       # 1% until PR 54's check
    named = [m["name"] for m in M["per_layer"] if m["name"] in AGENT]
    assert named == AGENT                  # appended, in this order
    for name in AGENT:
        entry = next(m for m in M["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "serve_tokens_per_s"
    # every cell that was there is still there, first; one in six on
    # four chips
    assert [w["name"] for w in M["workloads"]][:6] == [
        "train-125m-zero1", "serve-1p3b-chat", "serve-1p3b-longprompt",
        "train-1p3b-zero3-4chip", "serve-olmoe-longgen", CELL]
    assert sum(w["chips"] == 4 for w in M["workloads"][:6]) == 1


def test_the_mix_is_the_issues_closed_loop_of_32_agents():
    mix = traffic.resolve(manifest_mod.Cell(ROOT, M, CELL).traffic, False)
    assert (mix["loop"], mix["clients"], mix["block"], mix["order_seed"]) \
        == ("closed", 32, 32, 1)
    assert mix["shared_prefix"] == {"share": 1.0, "count": 4, "tokens": 2048}
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 256,
                                 "sigma": 0.6, "min": 64, "max": 3072}
    assert mix["output_len"] == {"dist": "uniform", "min": 64, "max": 256}
    assert mix["trace_seconds"] == 2.0
    # the first 32 admissions find nothing published and prefill whole:
    # the window opens after them
    # (19.9-20.0 s on the chip: the window opens well after the last)
    assert 22 <= mix["lead_in_s"] <= 30
    assert "sessions" in mix["why"] and "histories do not grow" in mix["why"]
    stream = traffic.RequestStream(mix, 2 ** 31 + 9, 65536)
    block = [stream.take() for _ in range(32)]
    assert all(r["kind"] == "shared_prefix" for r in block)
    # four system prompts of 16 whole pages, every request opens with one
    opens = {tuple(r["prompt"][:2048]) for r in block}
    assert len(opens) == 4
    assert all(len(r["prompt"]) + r["max_new_tokens"] <= 4096 for r in block)
    lengths = sorted(len(r["prompt"]) for r in block)
    assert lengths[0] >= 2048 + 64 and lengths[-1] <= 3072
    assert 2048 + 200 < lengths[16] < 2048 + 320
    shared = 32 * 2048 / sum(lengths)
    assert 0.85 < shared < 0.89                     # the issue's 87%
    outs = sorted(r["max_new_tokens"] for r in block)
    assert outs[0] < 70 and outs[-1] > 250
    # the rehearsal keeps a whole shared page
    small = traffic.resolve(manifest_mod.Cell(ROOT, M, CELL).traffic, True)
    config = manifest_mod.Cell(ROOT, M, CELL).config
    assert small["shared_prefix"]["tokens"] \
        == config["rehearse"]["serving"]["paging"]["page_len"]


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses_correct_with_a_prefix_hit(trace):
    proc = _run(RUN, "--workload", CELL, "--seed", str(2 ** 31 + 33),
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
        for name in AGENT:
            if spec(name)["source"] != "device_trace":
                assert repr(name) in said, (name, said)
        assert "moe:" in proc.stdout and "pairs over" in proc.stdout
        # a prefix hit among its requests, on both kinds of state
        reused = re.search(r"serving/prefill_tokens_reused = (\d+) of",
                           proc.stdout)
        restored = re.search(r"serving/state_snapshots_restored = (\d+) of "
                             r".* = (\d+)", proc.stdout)
        assert int(reused.group(1)) >= 128
        assert 0 < int(restored.group(1)) <= int(restored.group(2))


@pytest.mark.parametrize("name", AGENT)
def test_metric_file_names_its_reader_its_layer_and_its_cell(name):
    s = spec(name)
    assert s["reader"] in readers.load_all()
    assert s["workloads"] == [CELL] and s["unit"] and s["source"]
    assert s["layer"] == LAYER_OF.get(name, "expert layer")
    if s["source"] == "program_counter":
        assert "512" in s["note"] or "counters cover the process" in s["note"]
    if name.endswith("_roofline_pct.agent"):
        assert (s["unit"], s["better"]) == ("%", "higher")


def test_the_trace_patterns_tell_the_kernels_and_the_conv_operators_apart():
    paged = re.compile(spec("serve.paged_attn_share_pct.agent")["args"]["op"])
    expert = re.compile(spec("serve.expert_mm_share_pct.agent")["args"]["op"])
    conv = re.compile(spec("serve.conv_share_pct.agent")["args"]["op"])
    assert spec("serve.expert_mm_roofline_pct.agent")["args"]["op"] \
        == expert.pattern
    assert spec("serve.paged_attn_share_pct.agent")["args"]["module"] \
        == "^jit__paged_decode_iter_impl"
    assert "module" not in spec("serve.conv_share_pct.agent")["args"]
    assert paged.search(ATTN) and not expert.search(ATTN)
    for text in RAGGED + [METADATA]:
        assert expert.search(text) and not paged.search(text)
    for text in (CONV_IN, CONV_SUM):
        assert conv.search(text)
        assert not paged.search(text) and not expert.search(text)
    for text in [ATTN, FUSION, DENSE, METADATA] + RAGGED:
        assert not conv.search(text)


SIZES = {"hidden_size": 2048, "intermediate_size": 11776,
         "moe_intermediate_size": 1536, "num_hidden_layers": 4,
         "num_dense_layers": 2}


@pytest.fixture
def registry(monkeypatch):
    reg = registry_mod.MetricsRegistry()
    monkeypatch.setattr(registry_mod, "_DEFAULT_REGISTRY", reg)
    return reg


def _decode_runs(runs, per_layer_ns, layers=2):
    ops, modules, t = [], [], 0
    for _ in range(runs):
        start = t
        for _ in range(layers):
            ops.append((t, t + 2_000, METADATA))
            t += 2_000
            for text in RAGGED:
                ns = round(per_layer_ns / 3)
                ops.append((t, t + ns, text))
                t += ns
        modules.append((start, t, "jit__paged_decode_iter_impl(123)"))
        t += 10_000
    return ops, modules


def test_the_roofline_takes_an_experts_width_and_the_expert_layers(registry):
    """Two runs of a program of 2 dense + 2 expert layers, 128 pairs and
    55 experts touched a layer call: the least time is the bytes of 55
    experts 1536 wide and the rows. At the speed of light the family's
    reader reads 100; ``readers/moe.py`` on the same sizes would take the
    dense width and four layers and read 15 times that."""
    _count(registry, assignments=40 * 128, expert_calls=40,
           experts_touched=40 * 55, experts_offered=40 * 64, load_max=400)
    op = spec("serve.expert_mm_roofline_pct.agent")["args"]["op"]
    weights = 55 * 3 * 2048 * 1536 * 2
    rows = 128 * (2 * (2048 + 1536) * 2 + 1536 * 2 + 2048 * 4)
    assert lfm2_reader.expert_mm_bytes(2048, 1536, 128, 55) == weights + rows
    assert lfm2_reader.expert_mm_ops(2048, 1536, 128) \
        == 2 * 3 * 2048 * 1536 * 128
    least_ns = (weights + rows) / 819e9 * 1e9
    read = readers.load_all()["lfm2_expert_mm_roofline_pct"]
    obs, said = _observed(_Trace(*_decode_runs(2, least_ns)), sizes=SIZES)
    assert read(obs, op=op) == pytest.approx(100.0, abs=0.01)
    assert "12 matmuls in 4.0 layer calls of experts 1536 wide" in said[0]
    assert "bounded by memory" in said[0]
    assert read(_observed(_Trace(*_decode_runs(2, least_ns / 0.6)),
                          sizes=SIZES)[0], op=op) \
        == pytest.approx(60.0, abs=0.05)
    # the trap: the existing reader on this family's sizes
    wrong = readers.load_all()["moe_expert_mm_roofline_pct"](
        _observed(_Trace(*_decode_runs(2, least_ns)), sizes=SIZES)[0], op=op)
    assert wrong > 1000
    assert moe.expert_mm_bytes(SIZES, 128, 55) > 7.5 * (weights + rows)


def test_the_readers_find_nothing_in_a_program_without_such_state(registry):
    """The parent's program, or another family's cell: no counter, other
    sizes — the metrics are left out of the line, and nothing raises."""
    load = readers.load_all()
    op = spec("serve.expert_mm_roofline_pct.agent")["args"]["op"]
    trace = _Trace(*_decode_runs(1, 500_000))
    obs, said = _observed(trace, sizes=SIZES)
    assert load["lfm2_expert_mm_roofline_pct"](obs, op=op) is None
    assert load["registry_ratio_pct"](
        obs, **spec("serve.state_restored_pct.agent")["args"]) is None
    _count(registry, assignments=2048, expert_calls=8, experts_touched=500,
           experts_offered=512, load_max=80)
    olmoe_sizes = {"hidden_size": 2048, "intermediate_size": 1024,
                   "num_hidden_layers": 2}
    assert load["lfm2_expert_mm_roofline_pct"](
        _observed(trace, sizes=olmoe_sizes)[0], op=op) is None
    assert load["lfm2_expert_mm_roofline_pct"](
        _observed(None, sizes=SIZES)[0], op=op) is None
    assert load["lfm2_expert_mm_roofline_pct"](
        _observed(_Trace([(0, 10, ATTN)]), sizes=SIZES)[0], op=op) is None
    assert said == []
    # restored / (restored + resets) once the program counts them
    registry.counter("serving/state_snapshots_restored").inc(97)
    registry.counter("serving/state_resets").inc(3)
    assert load["registry_ratio_pct"](
        obs, **spec("serve.state_restored_pct.agent")["args"]) \
        == pytest.approx(97.0)


