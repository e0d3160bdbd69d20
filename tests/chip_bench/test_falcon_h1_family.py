"""The Falcon-H1 family and its configuration file (the cell that runs
them is ``test_falconh1_cell.py``'s): the configuration against the
catalog's entry, the family's counts of the mixer's two scans and its
kernels, its reference against the module at the rehearsal's size, its
independence of the program, and the logit-row tool that holds the
configuration on the chip, rehearsed."""

import json
import os

import numpy as np
import pytest

from benchmarks.chip import families
from benchmarks.chip import manifest as manifest_mod

from ._paths import BENCH, PYTHONPATH
from .test_rehearse import _run

# the catalog's `config` of Falcon-H1-34B-Instruct (model-configs guide,
# architectures.jsonl), key for key
CATALOG = {
    "attention_bias": False, "attention_in_multiplier": 1,
    "attention_out_multiplier": 0.0375, "attn_layer_indices": None,
    "embedding_multiplier": 5.656854249492381, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 21504,
    "key_multiplier": 0.011048543456039804,
    "lm_head_multiplier": 0.0078125, "mamba_chunk_size": 128,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 128,
    "mamba_d_ssm": 4096, "mamba_d_state": 256, "mamba_expand": 2,
    "mamba_n_groups": 2, "mamba_n_heads": 32,
    "mamba_norm_before_gate": False, "mamba_proj_bias": False,
    "mamba_rms_norm": True, "mamba_use_mlp": True,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_expansion_factor": 8,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "model_type": "falcon_h1", "num_attention_heads": 20,
    "num_hidden_layers": 72, "num_key_value_heads": 4,
    "num_logits_to_keep": 1, "projectors_bias": False,
    "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 100000000000, "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "ssm_out_multiplier": 0.08838834764831845,
    "tie_word_embeddings": False, "vocab_size": 261120}


def _config():
    return manifest_mod.load_json(os.path.join(
        BENCH, "configs", "falcon-h1-34b-9l-serve.json"))


def test_the_configuration_is_the_catalogs_entry_cut_in_depth_and_vocabulary():
    config = _config()
    assert config["published"] == CATALOG
    assert {k for k, v in CATALOG.items() if config[k] != v} \
        == {"num_hidden_layers", "vocab_size"}
    assert (config["kind"], config["family"]) == ("serve", "falcon_h1")
    # a stage of nine layers and an eighth of the vocabulary's rows: the
    # guide's floors are four layers and an eighth
    assert config["num_hidden_layers"] == 72 // 8 == 9
    assert config["vocab_size"] == 261120 // 8 == 32640
    assert (config["compute_dtype"], config["param_dtype"],
            config["ssm_state_dtype"]) == ("bfloat16", "bfloat16", "float32")
    serving = config["serving"]
    assert (serving["num_slots"], serving["max_len"]) == (64, 2048)
    assert serving["paging"] == {"page_len": 128, "kernel": "auto",
                                 "state_snapshots": 32}
    assert config["mamba_chunk_size"] == serving["paging"]["page_len"]
    # the file's arithmetic: a layer, the tables, the state, the pages
    d, f = 5120, 21504
    attn = d * 2560 + 2 * d * 512 + 2560 * d
    mixer = d * (4096 + 5120 + 32) + 4096 * d + 4 * 5120 + 5120 + 3 * 32 \
        + 4096
    mlp = 3 * d * f
    assert round(attn / 1e6, 2) == 31.46 and round(mixer / 1e6, 2) == 68.35
    assert round(mlp / 1e6, 2) == 330.30
    layer = attn + mixer + mlp + 2 * d
    assert round(layer / 1e6, 1) == 430.1
    weights = 9 * layer + 2 * 32640 * d + d
    assert round(2 * weights / 1e9, 2) == 8.41
    state = 32 * 256 * 128 * 4
    assert state == 4 * 2 ** 20 and state == 16 * (128 * 2 * 4 * 128 * 2)
    slots = 64 * 9 * (state + 3 * 5120 * 4)
    assert round(slots / 1e9, 2) == 2.45
    pages = 1025 * 128 * 9 * (2 * 4 * 128 * 2)
    assert round(pages / 1e9, 2) == 2.42
    snapshots = 33 * 9 * (state + 3 * 5120 * 4)
    assert round(snapshots / 1e9, 2) == 1.26
    for word in ("stage 0", "eight pipeline stages", "arXiv:2411.05288",
                 "8.41 GB", "2.45 GB", "2.42 GB", "34%"):
        assert word in config["deployment"], word
    said = " ".join(config["assumed"])
    for word in ("gated norm", "group i // ", "A_log", "dt_bias",
                 "ssm_state_dtype float32", "compute_dtype bfloat16",
                 "max_len 2048", "state_snapshots 32",
                 "tie_word_embeddings false"):
        assert word in said, word


def test_the_family_counts_the_mixers_two_scans_and_names_its_kernels():
    config = _config()
    family = families.load(config)
    sizes = family.sizes(config, False)
    assert family.DEPTH_KEYS == ("num_hidden_layers", "vocab_size")
    assert family.state_shape(sizes) == (32, 256, 128)
    state = 32 * 256 * 128
    # a row that decodes: its state read once and written once, float32,
    # and 3 x 4096 + 2 x 512 small float32 values; 5 operations a value
    assert family.ssm_update_bytes(sizes, 1) \
        == 2 * state * 4 + 4 * (3 * 4096 + 2 * 2 * 256)
    assert family.ssm_update_bytes(sizes, 48) \
        == 48 * family.ssm_update_bytes(sizes, 1)
    assert family.ssm_update_ops(sizes, 48) == 5 * 48 * state
    # bound by its bytes, by far: 8.4 MB against 5 M operations a row
    assert family.ssm_update_bytes(sizes, 1) / 819e9 \
        > 50 * family.ssm_update_ops(sizes, 1) / 197e12
    # a page of 128 positions: C B^T a group, its product a head, and
    # the state's two products
    assert family.ssm_chunk_ops(sizes, 128) == 128 * (
        2 * 128 * 256 * 2 + 2 * 128 * 128 * 32 + 4 * 256 * 128 * 32)
    assert family.ssm_chunk_bytes(sizes, 128) == 3 * state * 4 \
        + 128 * 4 * (2 * 4096 + 2 * 512 + 32)
    assert family.expected_kernels(None) == []
    kernel = {"interpret": False, "impl": "kernel"}
    assert family.expected_kernels(config["serving"]) == [
        ("paged_decode", "path", kernel),
        ("paged_attention", "page128", kernel),
        ("ssm_update", "rows64_", kernel)]


def test_the_reference_is_the_module_at_the_rehearsals_size():
    """The module the cell's rehearsal serves, whole forward, against the
    family's per-token reference (``tests/unit/test_falcon_h1.py`` holds
    the paged path and the controls)."""
    import jax.numpy as jnp
    from benchmarks.chip import model, reference
    config = _config()
    family = families.load(config)
    module = family.build(config, rehearse=True)
    assert module.config.mamba_chunk_size == 128
    assert module.config.ssm_multipliers == tuple(CATALOG["ssm_multipliers"])
    params = model.seeded_params(module, 2 ** 31 + 5)
    ids = np.random.default_rng(0).integers(
        1, config["rehearse"]["vocab_size"], size=(2, 150), dtype=np.int32)
    with reference.highest():
        got = module.apply({"params": params}, jnp.asarray(ids))
        want = family.reference_logits(
            params, jnp.asarray(ids), family.sizes(config, True), config)
    # float32 both, sums in another order: test_falcon_h1.py's tolerance
    assert float(jnp.abs(got - want).max() / want.std()) < 3e-5


def test_the_reference_shares_no_code_with_the_program():
    with open(os.path.join(BENCH, "families", "falcon_h1.py")) as f:
        lines = f.read().splitlines()
    start = next(i for i, ln in enumerate(lines)
                 if ln.startswith("# -- the plain reference"))
    assert not any("deepspeed_tpu" in ln for ln in lines[start:])
    assert not any(word in ln for ln in lines[start:]
                   for word in ("cumsum", "pallas", "chunk", "cache"))
    assert any("lax.scan(token" in ln for ln in lines[start:])


def test_the_logit_row_tool_rehearses_every_way_a_request_can_start(
        tmp_path):
    """``tools/falcon_h1_check.py`` at the rehearsal's size: the program
    as configured is inside the two limits on a row of logits with a
    cold request, a hit shortened by a missing snapshot, a hit that
    restores one, a hit at the leaf, an unshared request and one whose
    snapshots were all evicted, each ``correct`` by the cell's own
    comparison too; the wrong models that read whole sigmas at any size
    are outside, and so is B and C of the other group, by the rows and
    by the cell's own comparison (the chip's readings, not these, set
    the limits: at 64 wide, with a state of 16, a hit from zero state
    reads hundredths and a bf16 state ten-thousandths, both far over the
    float32 program's own millionths)."""
    proc = _run(os.path.join(BENCH, "tools", "falcon_h1_check.py"),
                "--rehearse", "--seeds", "5", "--new", "9", "--out",
                str(tmp_path), extra_env={"PYTHONPATH": PYTHONPATH})
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    with open(tmp_path / "5.json") as f:
        out = json.load(f)
    assert out["as_configured"]["within"]
    assert out["as_configured"]["the_cells_own"]["correct"]
    assert [r["kind"] for r in out["as_configured"]["requests"]] == [
        "cold", "hit_shortened", "hit_restored", "hit_at_the_leaf",
        "unshared"]
    assert out["counters"]["state_restore_missed"] == 1
    assert out["counters"]["state_snapshots_restored"] == 2
    assert out["counters"]["state_snapshots_evicted"] == 0
    # more leaves than the pool has entries went through before the first
    # prompt came a third time: nothing was restored for it
    assert out["counters_at_the_end"]["state_snapshots_evicted"] >= 4
    assert out["counters_at_the_end"]["state_snapshots_restored"] == 2
    evicted = out["snapshots_evicted"]
    assert evicted["within"] and evicted["the_cells_own"]["correct"]
    assert [r["kind"] for r in evicted["requests"]] == ["evicted"]
    sound = out["as_configured"]["row_mean"]
    for arm in ("ssm_out_multiplier_dropped", "mlp_down_multiplier_dropped",
                "norm_before_the_gate", "key_multiplier_dropped",
                "float8_e4m3fn", "b_and_c_of_the_other_group"):
        assert not out[arm]["within"], (arm, out[arm])
    assert not out["b_and_c_of_the_other_group"]["the_cells_own"]["correct"]
    for arm in ("hits_from_zero_state", "bfloat16_state", "int8"):
        assert out[arm]["row_mean_least"] > 100 * sound, (arm, out[arm])
    assert [r["kind"] for r in out["hits_from_zero_state"]["requests"]] \
        == ["hit_restored", "hit_at_the_leaf"]
    # the state's own path, one mixer in float32: the program inside its
    # limit, idle rows untouched, and both controls outside
    alone = out["mixer_alone_float32"]
    assert alone["within"] and alone["idle_rows_state_moved"] == 0.0
    assert alone["as_configured"] < alone["limit"] < min(
        alone["state_dropped_before_decode"],
        alone["b_and_c_of_the_other_group"], alone["bfloat16_state"])
