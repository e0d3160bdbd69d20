"""The Phi-4-mini-flash family and its configuration file (the cell that
runs them is ``test_phi4flash_cell.py``'s): the configuration against the
catalog's entry and its own arithmetic, the family's layer table, byte
counts and kernels, its reference against the module at the rehearsal's
size, its independence of the program, and the logit-row tool that holds
the configuration on the chip, rehearsed."""

import json
import os

import numpy as np

from benchmarks.chip import families
from benchmarks.chip import manifest as manifest_mod

from ._paths import BENCH, PYTHONPATH
from .test_rehearse import _run

# the catalog's `config` of Phi-4-mini-flash-reasoning (model-configs
# guide, architectures.jsonl), key for key
CATALOG = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064}


def _config():
    return manifest_mod.load_json(os.path.join(
        BENCH, "configs", "phi-4-mini-flash-serve.json"))


def test_the_configuration_is_the_catalogs_entry_with_nothing_cut():
    config = _config()
    assert config["published"] == CATALOG
    assert all(config[k] == v for k, v in CATALOG.items())
    assert (config["kind"], config["family"]) == ("serve", "phi4flash")
    assert config["source"] == ("https://huggingface.co/microsoft/Phi-4-mini"
                                "-flash-reasoning/blob/main/config.json")
    # float32 activations over bf16 weights and a bf16 K/V cache: served
    # in bf16 the cell was not correct (the file's `assumed` has the run)
    assert (config["compute_dtype"], config["param_dtype"],
            config["ssm_state_dtype"], config["kv_cache_dtype"]) == (
                "float32", "bfloat16", "float32", "bfloat16")
    assert (config["mamba_d_state"], config["mamba_d_conv"],
            config["mamba_expand"], config["mamba_dt_rank"]) == (
                16, 4, 2, 160 == -(-2560 // 16) and 160)
    serving = config["serving"]
    assert (serving["num_slots"], serving["max_len"]) == (64, 4096)
    assert serving["paging"] == {"page_len": 128, "kernel": "auto",
                                 "enable_prefix_cache": False}
    # the widest chunk the server chooses is the window
    assert 4 * serving["paging"]["page_len"] == config["sliding_window"]
    # the file's arithmetic: each kind of layer, the table, the state
    d, f, inner = 2560, 10240, 5120
    mixer = d * 2 * inner + inner * 192 + 160 * inner + inner * d \
        + 4 * inner + 16 * inner + 3 * inner
    attn = d * 5120 + 5120 + d * d + d + 4 * 64 + 128
    cross = 2 * (d * d + d) + 4 * 64 + 128
    gmu = 2 * d * inner
    mlp = d * 2 * f + f * d
    assert [round(n / 1e6, 1) for n in (mixer, attn, cross, gmu, mlp)] \
        == [41.2, 19.7, 13.1, 26.2, 78.6]
    table = 200064 * d
    assert round(table / 1e6, 1) == 512.2
    weights = 9 * mixer + 9 * attn + 7 * cross + 7 * gmu + 32 * mlp \
        + table + 65 * 2 * d
    assert round(weights / 1e9, 2) == 3.85
    assert round(2 * weights / 1e9, 1) == 7.7
    token = 2 * 20 * 64 * 2
    assert token == 5120
    pages = 2049 * 128 * token
    rings = 64 * 8 * 512 * token
    states = 64 * 9 * (16 * inner * 4 + 3 * inner * 4)
    assert [round(n / 1e9, 2) for n in (pages, rings, states)] \
        == [1.34, 1.34, 0.22]
    assert 0.65 < (2 * weights + pages + rings + states) / 16e9 < 0.67
    for word in ("nothing cut", "all 32 layers", "3.85 B", "7.7 GB",
                 "1.34 GB", "0.22 GB", "66%", "61 GB"):
        assert word in config["deployment"], word
    said = " ".join(config["assumed"])
    for word in ("d_state 16", "dt_rank = ceil(2560 / 16) = 160",
                 "before the gate", "arXiv:2410.05258", "halves",
                 "no rotary", "t - 512 < s <= t", "A_log = log(1..16)",
                 "mamba_bc_gain 2", "ssm_state_dtype float32",
                 "compute_dtype float32", "kv_cache_dtype bfloat16",
                 "0.1465", "max_len 4096",
                 "blocks of positions", "enable_prefix_cache false"):
        assert word in said, word
    rehearse = config["rehearse"]
    assert rehearse["num_hidden_layers"] == 8      # every kind of layer
    assert rehearse["serving"]["paging"]["enable_prefix_cache"] is False


def test_the_family_lays_out_the_layers_counts_the_bytes_and_names_kernels():
    config = _config()
    family = families.load(config)
    assert family.DEPTH_KEYS == ("num_hidden_layers",)
    sizes = family.sizes(config, False)
    assert sizes["vocab_size"] == 200064
    assert set(sizes) <= set(CATALOG)          # published keys, no other
    assert (family.D_STATE, family.D_CONV, family.EXPAND) == (16, 4, 2)
    kinds = [family.layer_kind(sizes, i) for i in range(32)]
    assert kinds[:16] == ["mixer", "window_attn"] * 8
    assert kinds[16:18] == ["mixer", "shared_attn"]
    assert kinds[18:] == ["gmu", "cross_attn"] * 7
    assert family.layers_of(sizes, "shared_attn") == [17]
    # a decode step at 64 rows of context 1,400, as the issue reckons it
    ctx = 64 * 1400
    shared = 8 * family.shared_kv_attn_bytes(sizes, ctx)
    rings = 8 * family.window_attn_bytes(sizes, 64 * 512)
    state = 9 * family.ssm_update_bytes(sizes, 64)
    assert [round(n / 1e9, 1) for n in (shared, rings, state)] \
        == [3.7, 1.3, 0.4]
    assert family.kv_token_bytes(sizes) == 5120
    assert family.state_values(sizes) == 16 * 5120
    mosaic = {"interpret": False, "impl": "kernel"}
    assert family.expected_kernels(config["serving"]) == [
        ("paged_decode", "path", mosaic),
        ("paged_attention", "page128", mosaic),
        ("decode_attention", "dma", mosaic)]
    assert family.expected_kernels(None) == []


def test_the_reference_is_the_module_at_the_rehearsals_size():
    import jax
    import jax.numpy as jnp
    from benchmarks.chip import model, reference
    config = _config()
    family = families.load(config)
    sizes = family.sizes(config, True)
    module = family.build(config, True)
    params = model.seeded_params(module, 2 ** 31 + 5)
    ids = jnp.asarray(np.random.default_rng(0).integers(
        1, sizes["vocab_size"], size=(2, 600), dtype=np.int32))
    with reference.highest():
        got = module.apply({"params": params}, ids)
        want = family.reference_logits(params, ids, sizes, config)
        losses = family.reference_next_token_losses(params, ids, sizes,
                                                    config)
    # float32 both, past the window of 512, sums in another order:
    # test_phi4flash.py's tolerance
    assert float(jnp.abs(got - want).max() / want.std()) < 5e-5
    assert losses.shape == (2, 599) and bool(jnp.all(losses > 0))


def test_the_reference_shares_no_code_with_the_program():
    with open(os.path.join(BENCH, "families", "phi4flash.py")) as f:
        lines = f.read().splitlines()
    start = next(i for i, ln in enumerate(lines)
                 if ln.startswith("# -- the plain reference"))
    assert not any("deepspeed_tpu" in ln for ln in lines[start:])
    assert not any(word in ln for ln in lines[start:]
                   for word in ("associative_scan", "pallas", "ring_key",
                                "cache"))
    assert any("lax.scan(token" in ln for ln in lines[start:])
    assert any("lax.map(block" in ln for ln in lines[start:])


def test_the_logit_row_tool_rehearses_and_refuses_every_control(tmp_path):
    """``tools/phi4flash_check.py`` at the rehearsal's size: the program
    as configured is inside the two limits on a row of logits and
    ``correct`` by the cell's own comparison, with a request whose rings
    wrap and one whose prompt passes the window among its three; a
    zero-state mixer, the memory after the gate, lambda = 0, a window of
    511, the wrong head pairing and fp8 weights are outside (the chip's
    readings, not these, set the limits)."""
    proc = _run(os.path.join(BENCH, "tools", "phi4flash_check.py"),
                "--rehearse", "--seeds", "5", "--out", str(tmp_path),
                extra_env={"PYTHONPATH": PYTHONPATH})
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    with open(tmp_path / "5.json") as f:
        out = json.load(f)
    assert out["as_configured"]["within"]
    assert out["as_configured"]["the_cells_own"]["correct"]
    assert out["requests"] == [[300, 330], [512, 48], [40, 48]]
    sound = out["as_configured"]["row_mean"]
    for arm in ("zero_state", "memory_after_gate", "lambda_zero",
                "window_less_one", "wrong_pairing", "float8_e4m3fn"):
        assert not out[arm]["within"], (arm, out[arm])
        assert out[arm]["row_mean"] > 100 * sound
    assert out["int8"]["row_mean"] > 100 * sound
