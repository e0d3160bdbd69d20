"""BENCHMARK.json against the contract's schema, and every name in it
against the files it stands for."""

import json
import os
import re

import pytest

from benchmarks.chip import families, manifest as manifest_mod
from benchmarks.chip import readers

from ._paths import BENCH, ROOT, manifest

M = manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [w["name"] for w in M["workloads"]]
METRICS = M["end_to_end"] + M["per_layer"]
# PR 23's three configurations: GPT-2 small and the GPT-3 1.3B recipe at
# published widths, uncut (n_embd, n_layer, n_head, n_positions)
PR23_WIDTHS = {"gpt2-125m-zero1": (768, 12, 12, 1024),
               "gpt2-1.3b-zero3-fsdp4": (2048, 24, 16, 2048),
               "gpt2-1.3b-serve": (2048, 24, 16, 2048)}
PR23_PER_LAYER = {
    "setup.compile_s", "setup.compiles", "train.step_ms_p50",
    "train.mfu_pct", "train.flash_share_pct", "train.flash_roofline_pct",
    "train.collective_exposed_pct", "train.device_idle_pct",
    "train.peak_hbm_gb", "serve.iter_ms_p50.chat",
    "serve.iter_ms_max.chat", "serve.tpot_p90_ms.chat",
    "serve.ttft_p90_ms.chat", "serve.gen_lag_ms_p90.chat",
    "serve.paged_attn_share_pct.chat",
    "serve.device_idle_pct.chat", "serve.iter_ms_p50.longprompt",
    "serve.prefill_share_pct.longprompt", "serve.ttft_p50_ms.longprompt",
    "serve.device_idle_pct.longprompt"}
PR24_PER_LAYER = {
    "serve.queue_wait_p90_ms.chat", "serve.prefill_wait_p90_ms.chat",
    "serve.batch_occupancy_pct.chat", "serve.prefix_hit_pct.chat",
    "serve.host_ms_p50.chat", "serve.host_ms_max.chat",
    "serve.readback_ms_max.chat", "serve.prefill_wait_p50_ms.longprompt",
    "serve.batch_occupancy_pct.longprompt", "serve.host_ms_p50.longprompt",
    "train.host_to_dispatch_ms_p50"}


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_size():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(M["run_seconds"], int) and 1 <= M["run_seconds"] <= 51
    assert 1 <= len(M["command"]) <= 32 and all(map(_line, M["command"]))


def test_paths_hold_the_benchmark_and_the_command_stays_inside():
    assert 1 <= len(M["paths"]) <= 16
    for p in M["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    for word in M["command"]:
        assert not word.startswith("/") and ".." not in word
        if os.path.exists(os.path.join(ROOT, word)):
            assert any(word.startswith(p + "/") for p in M["paths"])


def test_run_seconds_fits_a_full_check_of_24_cells():
    runs = 2 + 14 * 24
    assert runs * (M["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("conf", M["configs"], ids=lambda c: c["name"])
def test_config_entry(conf):
    assert set(conf) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(conf["name"]) and _line(conf["source"])
    assert _line(conf["why"]) and len(conf["reduced"]) <= 16
    assert any(conf["file"].startswith(p + "/") for p in M["paths"])
    assert any(w["config"] == conf["name"] for w in M["workloads"])
    with open(os.path.join(ROOT, conf["file"])) as f:
        body = json.load(f)
    assert body["kind"] in ("train", "serve")
    assert body["source"] == conf["source"]
    # the sizes the cell runs are the file's own, through its family
    sizes = families.load(body).sizes(body, rehearse=False)
    assert sizes["vocab_size"] > 0
    assert all(body[k] == v for k, v in sizes.items())
    if conf["name"] in PR23_WIDTHS:
        assert (sizes["n_embd"], sizes["n_layer"], sizes["n_head"],
                sizes["n_positions"]) == PR23_WIDTHS[conf["name"]]
        assert sizes["vocab_size"] == 50257 and conf["reduced"] == []
    # published widths, from the file itself: the source's own keys and
    # values; what the file changes is named in `reduced`, and is one of
    # the keys its family declares as depth: nothing else can be cut
    assert "published" in body or conf["name"] in PR23_WIDTHS
    published = body.get("published", {})
    assert set(sizes) <= set(published) or not published
    assert set(conf["reduced"]) <= set(published)
    for key, value in published.items():
        assert (body[key] == value) != (key in conf["reduced"]), key
    assert set(conf["reduced"]) <= set(families.load(body).DEPTH_KEYS)


def test_config_names_and_files_are_distinct():
    assert len({c["name"] for c in M["configs"]}) == len(M["configs"])
    assert len({c["file"] for c in M["configs"]}) == len(M["configs"])
    assert 1 <= len(M["configs"]) <= 24


@pytest.mark.parametrize("cell", M["workloads"], ids=lambda w: w["name"])
def test_workload_entry(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    for key in ("name", "config", "traffic"):
        assert NAME.match(cell[key])
    assert cell["chips"] in (1, 4) and _line(cell["why"])
    assert cell["config"] in {c["name"] for c in M["configs"]}


def test_cells_are_distinct_and_one_in_four_takes_four_chips():
    assert len(set(CELLS)) == len(CELLS) and 1 <= len(CELLS) <= 24
    pairs = {(w["config"], w["traffic"]) for w in M["workloads"]}
    assert len(pairs) == len(CELLS)
    four = [w for w in M["workloads"] if w["chips"] == 4]
    assert 1 <= len(four) <= max(1, len(CELLS) // 4)
    assert "train-1p3b-zero3-4chip" in [w["name"] for w in four]


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    end_to_end = metric in M["end_to_end"]
    keys = {"name", "unit", "better", "source"} | (
        {"bound"} if end_to_end else {"layer", "moves"})
    assert keys <= set(metric) <= keys | {"workloads"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    if end_to_end:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert _line(metric["layer"])
        assert metric["moves"] in {m["name"] for m in M["end_to_end"]}
    for w in metric.get("workloads", []):
        assert w in CELLS
    if metric["name"].endswith("_roofline") or "mfu" in metric["name"]:
        assert metric["unit"] == "%"


def test_metric_names_are_distinct_and_setup_s_is_there():
    names = [m["name"] for m in METRICS]
    assert len(set(names)) == len(names)
    assert 1 <= len(M["end_to_end"]) <= 16 and 1 <= len(M["per_layer"]) <= 128
    setup = [m for m in M["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]
    assert setup[0]["bound"] <= 0.1


def test_the_issues_metrics_letter_for_letter():
    # ISSUE 23's five, less `ttft_p90_ms` (its spread does not repeat
    # from one set of runs to the next) and with `tpot_p90_ms`, a mean per
    # request that one stall of the host moves by half, as the gap over
    # quarter-second stretches, `itl_p90_ms`; both are per-layer metrics
    # (PERF.md)
    assert [m["name"] for m in M["end_to_end"]] == [
        "train_tokens_per_s_chip", "itl_p90_ms", "serve_tokens_per_s",
        "setup_s"]
    # PR 23's twenty and PR 24's eleven stay; later PRs append their own
    assert {m["name"] for m in M["per_layer"]} \
        >= PR23_PER_LAYER | PR24_PER_LAYER


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_its_files_and_reports_enough(name):
    cell = manifest_mod.Cell(ROOT, M, name)
    assert cell.config["kind"] in ("train", "serve")
    assert cell.traffic["kind"] in ("steps", "requests")
    reported = [m["name"] for m in cell.end_to_end()]
    assert "setup_s" in reported and len(reported) >= 2
    assert len(cell.per_layer()) >= 1
    # a per-layer metric moves an end-to-end metric this cell reports
    for m, _ in cell.per_layer():
        assert m["moves"] in reported, (m["name"], m["moves"])


@pytest.mark.parametrize("metric", M["per_layer"], ids=lambda m: m["name"])
def test_metric_file_names_a_reader_and_agrees_with_the_manifest(metric):
    path = os.path.join(BENCH, "metrics", metric["name"] + ".json")
    with open(path) as f:
        spec = json.load(f)
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == metric[key], key
    assert spec.get("workloads") == metric.get("workloads")
    assert spec["reader"] in readers.load_all()


def test_every_file_under_paths_is_named_from_the_allowed_characters():
    for p in M["paths"]:
        for folder, dirs, files in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(folder, f), ROOT)
                assert PATH.match(rel), rel
