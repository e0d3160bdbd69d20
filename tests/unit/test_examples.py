"""The examples/ scripts must actually run (they are living docs —
reference analog: DeepSpeedExamples smoke coverage). Each runs as a
subprocess on the CPU backend with DS_TPU_EXAMPLE_SMOKE=1 (tiny model,
2 steps)."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _run_example(script, n_devices=8, extra_env=None, timeout=600):
    env = dict(os.environ)
    env.update({
        # examples stay backend-agnostic; the runner picks the CPU mesh
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={n_devices}",
        "DS_TPU_EXAMPLE_SMOKE": "1",
    })
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, script], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("script,expect", [
    pytest.param("examples/train_gpt2_zero3.py", "final loss",
                 marks=pytest.mark.slow),
    pytest.param("examples/train_long_context_sp.py", "final loss",
                 marks=pytest.mark.slow),
    pytest.param("examples/train_moe_ep.py", "final loss",
                 marks=pytest.mark.slow),
    ("examples/train_pipeline.py", "final loss"),
    pytest.param("examples/serve_hf_model.py", "smoke generated ids",
                 marks=pytest.mark.slow),
    pytest.param("examples/autotune_gpt2.py", "AUTOTUNE_RESULT",
                 marks=pytest.mark.slow),
])
def test_example_runs(script, expect, tmp_path):
    extra = {}
    if "zero3" in script:
        extra["DS_TPU_EXAMPLE_CKPT_DIR"] = str(tmp_path / "ckpt")
    r = _run_example(os.path.join(REPO, script), extra_env=extra)
    assert r.returncode == 0, (
        f"{script} failed\nstdout:\n{r.stdout[-2000:]}\n"
        f"stderr:\n{r.stderr[-2000:]}")
    assert expect in r.stdout, r.stdout[-2000:]
