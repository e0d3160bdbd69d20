"""Test harness: 8-device virtual CPU mesh.

The reference forks N processes with NCCL over localhost
(tests/unit/common.py:63 distributed_test). The TPU-native equivalent is
single-process SPMD over a virtual multi-device CPU backend — XLA's
``--xla_force_host_platform_device_count`` gives 8 fake devices so every
collective/sharding path runs in CI without TPU hardware.

Env vars MUST be set before jax is imported anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # the suite runs on the CPU mesh wherever it is started
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (xla_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_global_mesh():
    """Each test starts with no global mesh so MeshSpec tests don't leak."""
    yield
    from deepspeed_tpu.comm import mesh as mesh_mod
    mesh_mod._GLOBAL_MESH = None
