"""The controls of ``correct``, at a size a test holds: the plain
reference put in the program's place with its weights in the next
precision down, judged by the runs' own arithmetic under the runs' own
limits. A training run's comparison refuses fp8 weights; a serving run's
refuses another model's tokens and, as on the chip, not int8 weights
(PERF.md section 2). And ``tools/control.py``, which reads the same on the
chip at the cells' sizes, holds together."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks.chip import model as bench_model
from benchmarks.chip import reference, serve_runner, train_runner
from benchmarks.chip.families import gpt2

from ._paths import BENCH, PYTHONPATH, ROOT

# GPT-2's vocabulary (the near-ties are the vocabulary's) on a body a test
# can hold
CONFIG = {"n_embd": 128, "n_layer": 2, "n_head": 4, "vocab_size": 50257,
          "n_positions": 128, "layer_norm_epsilon": 1e-5,
          "compute_dtype": "float32", "param_dtype": "float32"}
SEEDS = [2 ** 31 + 12, 2 ** 31 + 13]


class _Served:
    """A finished request as ``serve_runner._reference_check`` reads it."""

    def __init__(self, prompt, output):
        self.spec = {"prompt": prompt}
        self.handle = type("Handle", (), {"output_tokens": output})()


def _weights(seed):
    sizes = gpt2.sizes(CONFIG, False)
    return sizes, bench_model.seeded_params(gpt2.build(CONFIG, False), seed)


def _greedy(params, prompt, steps, sizes):
    """What a server with these weights serves: the argmax, token by
    token, of the reference on them."""
    import jax
    import jax.numpy as jnp
    width = len(prompt) + steps
    with reference.highest():
        forward = jax.jit(lambda p, ids: gpt2.reference_logits(
            p, ids, sizes, CONFIG))
        ids = np.zeros((1, width), np.int32)
        ids[0, :len(prompt)] = prompt
        for n in range(len(prompt), width):
            ids[0, n] = int(jnp.argmax(forward(params, jnp.asarray(ids))[
                0, n - 1]))
    return ids[0, len(prompt):]


@pytest.mark.parametrize("kind", ["int8", "float8_e4m3fn"])
def test_lower_weights_rounds_matrices_per_channel_and_leaves_vectors(kind):
    rng = np.random.default_rng(5)
    tree = {"kernel": rng.normal(size=(3, 64, 32)).astype(np.float32),
            "bias": rng.normal(size=(32,)).astype(np.float32)}
    low = reference.lower_weights(tree, kind)
    assert np.array_equal(np.asarray(low["bias"]), tree["bias"])
    got, want = np.asarray(low["kernel"]), tree["kernel"]
    top = np.abs(want).max(axis=-2, keepdims=True)
    assert got.shape == want.shape and not np.array_equal(got, want)
    # each channel's largest weight is kept, no weight moves by more than
    # half a step of int8, or a sixteenth of itself in e4m3's 3 bits
    assert np.allclose(np.abs(got).max(axis=-2, keepdims=True), top)
    room = top / 127 / 2 if kind == "int8" else np.maximum(
        np.abs(want) / 16, top / 448 / 64)
    assert np.all(np.abs(got - want) <= room * 1.001)
    if kind == "int8":
        assert len(np.unique(np.round(got / (top / 127)))) <= 255


@pytest.mark.parametrize("seed", SEEDS)
def test_the_serving_comparison_refuses_another_models_tokens(seed):
    """The run's own comparison on tokens served from the weights as they
    are (every token the reference's argmax: 0), from other weights (a
    wrong path: whole sigmas, refused) and from int8 weights: read, some
    near-ties flipped, and under the limit — what PERF.md section 2 says
    of the chip's readings: the served tokens alone do not tell int8
    weights from bf16 arithmetic."""
    sizes, params = _weights(seed)
    _, other = _weights(seed + 100)
    rng = np.random.default_rng(seed)
    served = {"sound": [], "int8": [], "other": []}
    weights = {"sound": params, "other": other,
               "int8": reference.lower_weights(params, "int8")}
    for _ in range(6):
        prompt = rng.integers(1, sizes["vocab_size"], size=16, dtype=np.int32)
        for name, tree in weights.items():
            served[name].append(_Served(prompt, _greedy(tree, prompt, 24,
                                                        sizes)))
    check = {name: serve_runner._reference_check(
        gpt2, params, reqs, sizes, CONFIG, CONFIG["n_positions"])
        for name, reqs in served.items()}
    assert all(c["tokens"] == 6 * 24 for c in check.values())
    assert check["sound"]["max"] == check["sound"]["mean"] == 0.0
    assert check["sound"]["exact"] == check["sound"]["tokens"]
    assert check["other"]["max"] > 10 * serve_runner.LOGIT_TOL_SIGMA
    assert 0.0 < check["int8"]["max"] < check["other"]["max"]
    assert check["int8"]["exact"] < check["int8"]["tokens"]
    assert [len(g) for g in check["int8"]["gaps"]] == [24] * 6


@pytest.mark.parametrize("seed", SEEDS)
def test_the_training_comparison_refuses_fp8_weights(seed):
    """The reference with fp8 weights in the engine's place, judged by
    the run's own comparison (the rms over the check's groups of rows)
    at the 125M cell's rows of 1024 tokens: over the run's limit."""
    sizes, params = _weights(seed)
    groups = train_runner.check_rows(sizes, 1024, 1, seed)
    assert groups.shape == (train_runner.CHECK_GROUPS, 2, 1025)
    # the body holds 128 positions: each row of 1024 as eight of them
    groups = groups[:, :, :1024].reshape(train_runner.CHECK_GROUPS, 16, 128)
    want = train_runner.reference_losses(gpt2, params, groups, sizes, CONFIG)
    got = train_runner.reference_losses(
        gpt2, reference.lower_weights(params, "float8_e4m3fn"), groups,
        sizes, CONFIG)
    assert train_runner.rms(want - want) == 0.0
    assert train_runner.rms(got - want) > train_runner.LOSS_RMS_TOL


@pytest.mark.parametrize("cell,arms,stat", [
    ("serve-1p3b-chat", ("as_configured", "int8"), "max"),
    ("train-125m-zero1", ("engine", "int8", "float8_e4m3fn"), "rms"),
])
def test_the_control_tool_rehearses(cell, arms, stat):
    """``tools/control.py`` at the rehearsal's size: every arm reads a
    number on the same requests or rows. At 64 wide nothing separates
    them: the test holds the tool together, not a limit."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=PYTHONPATH)
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "tools", "control.py"),
         "--workload", cell, "--seeds", "1", "--requests", "3", "--steps",
         "2", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    row = json.loads(next(ln for ln in proc.stdout.splitlines()
                          if ln.startswith("{")))
    assert row["seed"] > 2 ** 31
    assert all(row[arm][stat] >= 0.0 for arm in arms)
    for arm in arms:
        assert f"{arm} {stat}: smallest" in proc.stdout
