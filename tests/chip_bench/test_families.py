"""Model families found by name: the default, a named one, one that is
not there; what the GPT-2 family gives the runners; and the check of the
kernels a family expects on hand-made dispatch records."""

import os

import pytest

from benchmarks.chip import families, manifest as manifest_mod
from benchmarks.chip.readers import Observed, load_all

from ._paths import BENCH, ROOT, manifest

M = manifest()
CONFIGS = {c["name"]: manifest_mod.load_json(os.path.join(ROOT, c["file"]))
           for c in M["configs"]}


def test_a_configuration_without_a_family_is_gpt2():
    from benchmarks.chip.families import gpt2
    assert families.load({}) is gpt2
    assert families.load({"family": "gpt2"}) is gpt2


def test_an_unknown_family_is_an_error_that_names_the_file():
    with pytest.raises(KeyError, match="families/mamba9.py"):
        families.load({"family": "mamba9"})


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_every_configuration_finds_its_family_and_its_functions(name):
    config = CONFIGS[name]
    family = families.load(config)
    assert os.path.isfile(os.path.join(
        BENCH, "families", config.get("family", families.DEFAULT) + ".py"))
    assert set(family.DEPTH_KEYS) <= set(family.sizes(config, False))
    needed = ["sizes", "build", "reference_logits",
              "reference_next_token_losses", "expected_kernels"]
    if config["kind"] == "train":
        needed += ["train_loss", "ops_per_token"]
    assert all(callable(getattr(family, fn)) for fn in needed)
    # the rehearsal runs the same keys at tiny sizes
    full, tiny = family.sizes(config, False), family.sizes(config, True)
    assert set(full) == set(tiny) and "vocab_size" in full
    assert all(tiny[k] <= full[k] for k in full)


def test_gpt2_expects_flash_when_training_and_the_paged_kernel_serving():
    family = families.load({})
    assert family.expected_kernels(None) == [
        ("attention", "backend", {"backend": "pallas"}),
        ("flash_attention", "fwd_", {"interpret": False}),
        ("flash_attention", "bwd_", {"interpret": False})]
    serving = CONFIGS["gpt2-1.3b-serve"]["serving"]
    assert family.expected_kernels(serving) == [
        ("paged_decode", "path", {"interpret": False, "impl": "kernel"}),
        ("paged_attention", "page128",
         {"interpret": False, "impl": "kernel"})]


MOSAIC = {"interpret": False, "impl": "kernel"}
EXPECTED = [("paged_decode", "path", MOSAIC),
            ("flash_attention", "fwd_", {"interpret": False})]


@pytest.mark.parametrize("records,wrong", [
    ({"paged_decode": {"path": dict(MOSAIC, key="page128")},
      "flash_attention": {"fwd_resident": {"interpret": False},
                          "fwd_streamed": {"interpret": False}}}, None),
    ({"paged_decode": {"path": {"interpret": False, "impl": "gather"}},
      "flash_attention": {"fwd_resident": {"interpret": False}}},
     "paged_decode/path is not"),
    ({"paged_decode": {"path": {"interpret": True, "impl": "kernel"}},
      "flash_attention": {"fwd_resident": {"interpret": False}}},
     "paged_decode/path is not"),
    ({"paged_decode": {"path": {"impl": "kernel"}},
      "flash_attention": {"fwd_resident": {"interpret": False}}},
     "paged_decode/path is not"),
    ({"paged_decode": {"path": dict(MOSAIC)},
      "flash_attention": {"fwd_resident": {"interpret": False},
                          "fwd_streamed": {"interpret": True}}},
     "flash_attention/fwd_streamed is not"),
    ({"paged_decode": {"path": dict(MOSAIC)},
      "flash_attention": {"bwd_resident": {"interpret": False}}},
     r"flash_attention/fwd_\* was not dispatched"),
    ({}, r"paged_decode/path\* was not dispatched"),
])
def test_check_kernels_on_hand_made_dispatch_records(records, wrong):
    def last_dispatch(kernel):
        return records.get(kernel, {})
    if wrong is None:
        families.check_kernels(last_dispatch, EXPECTED)
    else:
        with pytest.raises(RuntimeError, match=wrong):
            families.check_kernels(last_dispatch, EXPECTED)


def test_mfu_reads_the_familys_count_from_the_runners_facts():
    peaks = {"bf16_flops_per_s": 197e12}
    facts = {"tokens_per_s_chip": 94_800.0, "ops_per_token": 797_815_296}
    mfu = load_all()["mfu_pct"]

    def obs(facts, peaks=peaks):
        return Observed(setup={}, series={}, trace=None, peaks=peaks,
                        facts=facts, say=lambda msg: None)
    assert mfu(obs(facts)) == pytest.approx(
        100 * 797_815_296 * 94_800.0 / 197e12)
    # a runner that states no count, or no chip's peaks: nothing to read
    assert mfu(obs({"tokens_per_s_chip": 94_800.0})) is None
    assert mfu(obs(facts, peaks=None)) is None


def test_the_reference_of_gpt2_is_the_programs_forward_pass_in_float32():
    """The family's reference against the module it builds, both in
    float32 on the CPU at the rehearsal's sizes: the same logits to
    rounding, and the losses are their cross entropy."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.chip import model as bench_model
    from benchmarks.chip import reference
    config = CONFIGS["gpt2-125m-zero1"]
    family = families.load(config)
    sizes = family.sizes(config, True)
    module = family.build(config, True, dtype=jnp.float32, remat="none")
    params = bench_model.seeded_params(module, 2 ** 31 + 3)
    ids = np.random.default_rng(3).integers(
        0, sizes["vocab_size"], size=(2, 33), dtype=np.int32)
    with reference.highest():
        want = family.reference_logits(params, jnp.asarray(ids), sizes,
                                       config)
        got = module.apply({"params": params}, jnp.asarray(ids))
        losses = family.reference_next_token_losses(
            params, jnp.asarray(ids), sizes, config)
    assert want.shape == (2, 33, sizes["vocab_size"])
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4
    assert losses.shape == (2, 32)
    logp = jax.nn.log_softmax(want[:, :-1], -1)
    picked = jnp.take_along_axis(logp, jnp.asarray(ids)[:, 1:, None], -1)
    assert float(jnp.max(jnp.abs(losses + picked[..., 0]))) < 1e-5
