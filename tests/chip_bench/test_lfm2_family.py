"""The LFM2 family and its configuration file (the cell that runs them
is ``test_lfm2_cell.py``'s): the configuration
against the catalog's entry, the family's operation counts and kernels,
its reference's independence of the program, and the logit-row tool
that holds the configuration on the chip, rehearsed."""

import json
import os

from benchmarks.chip import families
from benchmarks.chip import manifest as manifest_mod

from ._paths import BENCH, PYTHONPATH
from .test_rehearse import _run

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


def _config():
    return manifest_mod.load_json(os.path.join(
        BENCH, "configs", "lfm2-24b-a2b-10l-serve.json"))


def test_the_configuration_is_the_catalogs_entry_cut_in_depth_only():
    config = _config()
    assert config["published"] == CATALOG
    assert {k for k, v in CATALOG.items() if config[k] != v} \
        == {"num_hidden_layers", "layer_types"}
    assert (config["kind"], config["family"]) == ("serve", "lfm2")
    # layers 0-9: the two leading dense layers and two whole periods
    assert config["num_hidden_layers"] == 10
    assert config["layer_types"] == CATALOG["layer_types"][:10] \
        == ["conv", "conv"] + PERIOD * 2
    # the published bf16 weights under float32 activations (`assumed`
    # says why: bf16 products flip the router's near-ties)
    assert (config["compute_dtype"], config["param_dtype"]) \
        == ("float32", "bfloat16")
    serving = config["serving"]
    assert (serving["num_slots"], serving["max_len"]) == (32, 4096)
    assert serving["paging"] == {"page_len": 128, "kernel": "auto"}
    # the file's arithmetic: a token of K/V, the pool, the state, the
    # weights
    d, kv, head = 2048, config["num_key_value_heads"], 64
    attn_layers = config["layer_types"].count("full_attention")
    conv_layers = config["layer_types"].count("conv")
    assert (attn_layers, conv_layers) == (2, 8)
    token_bytes = attn_layers * 2 * kv * head * 4           # float32 pages
    assert token_bytes == 8 * 1024
    assert 32 * 4096 * token_bytes == 2 ** 30                    # 1.0 GiB
    state = conv_layers * (config["conv_L_cache"] - 1) * d * 4
    assert state == 128 * 1024                      # a slot, and a page
    expert = 3 * d * config["moe_intermediate_size"]
    conv, attn = 4 * d * d + 3 * d, 2 * d * d + 2 * d * kv * head
    dense = 3 * d * config["intermediate_size"]
    moe_layers = 10 - config["num_dense_layers"]
    total = (2 * dense + moe_layers * (64 * expert + d * 64 + 64)
             + attn_layers * attn + conv_layers * conv
             + config["vocab_size"] * d)
    assert round(expert / 1e6, 2) == 9.44 and round(64 * expert / 1e6) == 604
    assert 5.25e9 < total < 5.27e9                  # 10.5 GB in bf16
    for word in ("stage 0", "four pipeline stages", "no layer is divided",
                 "5,259 M"):
        assert word in config["deployment"], word
    said = " ".join(config["assumed"])
    for word in ("tie_word_embeddings", "max_len 4096", "compute_dtype float32",
                 "three bfloat16 terms", "1.017 and 0.9045 sigma",
                 "convolution state", "expert_bias", "standard deviation 0.01"):
        assert word in said, word


def test_the_logit_row_tool_refuses_the_broken_cache_and_the_wrong_routers(
        tmp_path):
    """``tools/lfm2_check.py`` at the rehearsal's size: the program as
    configured is inside the two limits on a row of logits with a prefix
    hit among its requests, and a hit from zero state, a softmax router,
    the bias in the weights and the reference with fp8 and int8 weights
    are each outside (the chip's readings, not these, set the limits)."""
    proc = _run(os.path.join(BENCH, "tools", "lfm2_check.py"), "--rehearse",
                "--seeds", "5", "--new", "9", "--out", str(tmp_path),
                extra_env={"PYTHONPATH": PYTHONPATH})
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    with open(tmp_path / "5.json") as f:
        out = json.load(f)
    assert out["as_configured"]["within"]
    assert [r["hit"] for r in out["as_configured"]["requests"]] \
        == [False, True, False]
    for arm in ("prefix_hit_from_zero_state", "softmax_for_sigmoid",
                "bias_in_the_weights", "float8_e4m3fn", "int8"):
        assert not out[arm]["within"], (arm, out[arm])
        assert out[arm]["row_mean_least"] > 100 * out["as_configured"][
            "row_mean"]
    assert "not refused: none" in proc.stdout


def test_the_family_counts_four_of_sixty_four_experts_and_two_dense_layers():
    config = _config()
    family = families.load(config)
    sizes = family.sizes(config, False)
    assert family.DEPTH_KEYS == ("num_hidden_layers", "layer_types")
    d = 2048
    conv, attn = 4 * d * d + 3 * d, 2 * d * d + 2 * d * 8 * 64
    dense, moe_layer = 3 * d * 11776, d * 64 + 4 * 3 * d * 1536
    weights = 8 * conv + 2 * attn + 2 * dense + 8 * moe_layer + 65536 * d
    assert family.ops_per_token(sizes, 0) == 6 * weights
    assert family.ops_per_token(sizes, 2048) - 6 * weights \
        == 2 * 3 * (2 * 2 * 2048 * d) // 2
    assert family.expected_kernels(None) == []
    assert family.expected_kernels(config["serving"]) == [
        ("paged_decode", "path", {"interpret": False, "impl": "kernel"}),
        ("paged_attention", "page128",
         {"interpret": False, "impl": "kernel"})]


def test_the_reference_shares_no_code_with_the_program():
    with open(os.path.join(BENCH, "families", "lfm2.py")) as f:
        lines = f.read().splitlines()
    start = next(i for i, ln in enumerate(lines)
                 if ln.startswith("# -- the plain reference"))
    assert not any("deepspeed_tpu" in ln for ln in lines[start:])
    assert not any(word in ln for ln in lines[start:]
                   for word in ("ragged_dot", "argsort", "pallas"))
