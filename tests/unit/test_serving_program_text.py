"""The served programs, letter for letter.

A host-side change to who calls ``serving/paged_decode``,
``serving/chunk_prefill`` and ``serving/spec_verify_iter`` (the manager
of ``serving/paging/manager.py`` since PR 52, the engine before it) must
leave what is compiled alone: the same argument list in the same order
at the same types, the same traced function, the same result tuple. For a
tiny configuration of every served family a ``ServingEngine`` serves one
short request while every dispatch of a tracked program — through the
jit or through an executable compiled ahead — has its arguments written
down as shapes; each program is then lowered from exactly those and the
SHA-256 of its text (StableHLO, no locations) is held to the constant
computed by this same file on the parent of PR 52, commit ``1c9ce11``.

A change that means to move a served program records the new text's
hash here, and says so.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.observability.programs import TrackedProgram
from deepspeed_tpu.serving import ServingEngine

PAGE = 16
DECODE, CHUNK, VERIFY = ("serving/paged_decode", "serving/chunk_prefill",
                         "serving/spec_verify_iter")


def _gpt():
    from deepspeed_tpu.models import GPT, GPTConfig
    return GPT(GPTConfig(vocab_size=128, max_seq_len=64, d_model=32,
                         n_layers=2, n_heads=2, dtype=jnp.float32))


def _olmoe():
    from deepspeed_tpu.models.olmoe import OLMoE, OLMoEConfig
    return OLMoE(OLMoEConfig(
        vocab_size=128, hidden_size=32, intermediate_size=16,
        num_hidden_layers=2, num_attention_heads=2, num_experts=4,
        num_experts_per_tok=2, max_position_embeddings=64,
        dtype=jnp.float32))


def _lfm2():
    from deepspeed_tpu.models.lfm2 import LFM2, LFM2Config
    return LFM2(LFM2Config(
        vocab_size=128, hidden_size=32, intermediate_size=48,
        moe_intermediate_size=16, num_hidden_layers=3,
        layer_types=("conv", "full_attention", "conv"),
        num_attention_heads=2, num_key_value_heads=1, num_dense_layers=1,
        num_experts=4, num_experts_per_tok=2, max_position_embeddings=64,
        dtype=jnp.float32))


def _deepseek_v3():
    from deepspeed_tpu.models.deepseek_v3 import (DeepseekV3,
                                                  DeepseekV3Config)
    return DeepseekV3(DeepseekV3Config(
        vocab_size=128, hidden_size=32, intermediate_size=48,
        moe_intermediate_size=16, num_hidden_layers=2,
        num_attention_heads=2, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=8, first_k_dense_replace=1,
        n_routed_experts=4, n_shared_experts=1, num_experts_per_tok=2,
        max_position_embeddings=64, dtype=jnp.float32))


def _falcon_h1():
    from deepspeed_tpu.models.falcon_h1 import FalconH1, FalconH1Config
    return FalconH1(FalconH1Config(
        vocab_size=128, hidden_size=32, intermediate_size=48,
        num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=1,
        head_dim=8, mamba_d_ssm=32, mamba_n_heads=4, mamba_d_head=8,
        mamba_d_state=8, mamba_n_groups=2, mamba_chunk_size=PAGE,
        max_position_embeddings=64, dtype=jnp.float32))


FAMILIES = {"gpt": _gpt, "olmoe": _olmoe, "lfm2": _lfm2,
            "deepseek_v3": _deepseek_v3, "falcon_h1": _falcon_h1}

# sha256 of each program's lowered text on the parent commit 1c9ce11,
# computed by ``python tests/unit/test_serving_program_text.py`` there
PARENT_TEXT = {
    "gpt:serving/chunk_prefill":
        "fe967a30ab141136438009fa73adcaff7ba1e153b421cbc2f7de916334e9490b",
    "gpt:serving/paged_decode":
        "a00aa9f5c88dd73d3227415defc6049d14a95792f4ce9a51e3471269cdad6bac",
    "gpt:serving/spec_verify_iter":
        "a30aa489ae78618c1c5b320bb5087b4ffe510367caf9010c4771afad079fa914",
    "olmoe:serving/chunk_prefill":
        "ee861d9c53b02a75c64d18dab042475ba0e628dcde7179af27ec97ae96b78584",
    "olmoe:serving/paged_decode":
        "bb9490539ad806a0aefa77aa028302d4caf444ae10c406f5ea575ea69a9da840",
    "lfm2:serving/chunk_prefill":
        "74d2ef7e8b8637973ddb2ad1211f67c88df7f4b01652f517b153130f504166f1",
    "lfm2:serving/paged_decode":
        "eab1176657ca55564b75c5c2e4726ce724bf207565255c57dc579d919d762f79",
    "deepseek_v3:serving/chunk_prefill":
        "89cff7684947e53b8543f4f82798926f3f331c171df9597d6e260cd5e52f3952",
    "deepseek_v3:serving/paged_decode":
        "b3f72acff893996ab8e03eaef73523b7645551c0aad9e2dbbe9d3ba6ee827105",
    "falcon_h1:serving/chunk_prefill":
        "874a242522e7e2d086109f023ce4f507fb39b301e37b0253e3d38f742fbf8f9d",
    "falcon_h1:serving/paged_decode":
        "7f171aea563b1593d469d4f7234903817f794fdf19d1193b0d2cfae08227f777",
}


def _shapes(args):
    """The arguments of one dispatch as the lowering sees them: arrays
    as shapes (a weak type kept), statics as they are."""
    def shape(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return jax.ShapeDtypeStruct(
                x.shape, x.dtype, weak_type=getattr(x, "weak_type", False))
        return x
    return jax.tree.map(shape, args)


def served_texts(family, monkeypatch):
    """``{program name: sha256 of its lowered text}`` for the programs a
    server of ``family`` dispatched while it served one request (GPT's
    speculates: its prompt repeats)."""
    module = FAMILIES[family]()
    params = module.init(jax.random.PRNGKey(0),
                         jnp.zeros((1, 8), jnp.int32))["params"]
    seen = {}
    call, ahead = TrackedProgram.__call__, TrackedProgram.compile_ahead

    def note(program, args):
        seen.setdefault(program.record.name, (program, _shapes(args)))

    def noted_call(program, *args, **kwargs):
        assert not kwargs
        note(program, args)
        return call(program, *args)

    def noted_ahead(program, *args, **kwargs):
        note(program, args)
        return ahead(program, *args, **kwargs)

    monkeypatch.setattr(TrackedProgram, "__call__", noted_call)
    monkeypatch.setattr(TrackedProgram, "compile_ahead", noted_ahead)
    config = {"num_slots": 2, "max_len": 48,
              "paging": {"page_len": PAGE, "prefill_chunk": PAGE}}
    if family == "gpt":
        config["speculation"] = {"max_spec_tokens": 2}
    if family == "falcon_h1":
        config["paging"]["state_snapshots"] = 4
    srv = ServingEngine(module, params, config)
    try:
        prompt = np.tile(np.asarray([5, 6, 7], np.int32), 6)
        handle = srv.submit(prompt, max_new_tokens=6)
        srv.run()
        assert len(handle.output_tokens) == 6
    finally:
        srv.close()
    return {name: hashlib.sha256(
                program._fn.lower(*args).as_text().encode()).hexdigest()
            for name, (program, args) in seen.items()
            if name.startswith("serving/")}


CASES = [(family, program) for family in FAMILIES
         for program in (DECODE, CHUNK) + ((VERIFY,) if family == "gpt"
                                           else ())]


@pytest.fixture(scope="module")
def texts():
    """Each family served once, whichever of its programs is asked for
    first."""
    cache = {}

    def get(family):
        if family not in cache:
            with pytest.MonkeyPatch.context() as patch:
                cache[family] = served_texts(family, patch)
        return cache[family]
    return get


@pytest.mark.parametrize("family,program", CASES)
def test_program_lowers_to_the_parents_text(texts, family, program):
    assert texts(family)[program] == PARENT_TEXT[f"{family}:{program}"]


if __name__ == "__main__":
    with pytest.MonkeyPatch.context() as patch:
        for family in FAMILIES:
            for name, digest in sorted(served_texts(family, patch).items()):
                print(f'    "{family}:{name}":\n        "{digest}",')
