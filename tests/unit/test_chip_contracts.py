"""Contracts of the bring-up on the chip: nothing may hide the device.

The platform is ``tpu`` or it is not; a choice that leaves a Pallas kernel
for a jnp path is recorded where ``chip_smoke.py`` reads it; an explicit
request that cannot be honoured raises; a parent that holds the chip does
not start children that need it; the compile cache can be placed from
outside. All light enough for tier-1.
"""

import os
import subprocess
import sys
import types

import numpy as np
import pytest
import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _run(args, **env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run([sys.executable] + args, cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=120)


# ---------------------------------------------------------------------------
# compile cache
# ---------------------------------------------------------------------------

class TestCompileCache:
    @pytest.fixture(autouse=True)
    def _restore(self):
        before = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", before)

    def test_env_set_means_code_sets_nothing(self, monkeypatch, tmp_path):
        from deepspeed_tpu.utils import host_env
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", None)
        assert host_env.configure_compile_cache() == str(tmp_path)
        # JAX reads the variable itself; the helper touched no config
        assert jax.config.jax_compilation_cache_dir is None

    def test_unset_means_the_fixed_in_checkout_path(self, monkeypatch):
        from deepspeed_tpu.utils import host_env
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        got = host_env.configure_compile_cache()
        assert got == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
        # fixed: the directory is part of the cache key
        assert host_env.configure_compile_cache() == got


# ---------------------------------------------------------------------------
# platform is tpu or it is not
# ---------------------------------------------------------------------------

def _broken_backend():
    raise RuntimeError("Unable to initialize backend 'tpu'")


class TestPlatform:
    def test_on_tpu_propagates_a_backend_error(self, monkeypatch):
        from deepspeed_tpu.ops.pallas import _common
        monkeypatch.setattr(jax, "default_backend", _broken_backend)
        with pytest.raises(RuntimeError, match="Unable to initialize"):
            _common.on_tpu()
        # ...so a failed init can never read as "interpret everything"
        with pytest.raises(RuntimeError, match="Unable to initialize"):
            _common.interpret_mode()

    def test_only_tpu_is_tpu(self, monkeypatch):
        from deepspeed_tpu.ops.pallas import _common
        for name, want in (("tpu", True), ("cpu", False), ("gpu", False)):
            monkeypatch.setattr(jax, "default_backend", lambda n=name: n)
            assert _common.on_tpu() is want
            assert _common.interpret_mode() is (not want)

    @staticmethod
    def _device(platform, kind):
        return [types.SimpleNamespace(platform=platform, device_kind=kind)]

    def test_detect_chip_raises_on_unknown_tpu_kind(self, monkeypatch):
        from deepspeed_tpu.observability import perf
        monkeypatch.setattr(jax, "local_devices",
                            lambda: self._device("tpu", "TPU v9 ultra"))
        with pytest.raises(ValueError, match="unknown TPU device kind"):
            perf.detect_chip()
        with pytest.raises(ValueError, match="unknown TPU device kind"):
            perf.resolve_peak_flops(types.SimpleNamespace())

    def test_detect_chip_known_kinds_and_cpu(self, monkeypatch):
        from deepspeed_tpu.observability import perf
        monkeypatch.setattr(jax, "local_devices",
                            lambda: self._device("tpu", "TPU v5 lite"))
        assert perf.detect_chip() == "tpu-v5e"
        assert perf.resolve_peak_flops(types.SimpleNamespace()) == 197e12
        # off-accelerator there is no peak: MFU is "not measured"
        monkeypatch.setattr(jax, "local_devices",
                            lambda: self._device("cpu", "cpu"))
        assert perf.detect_chip() is None
        assert perf.resolve_peak_flops(types.SimpleNamespace()) is None


# ---------------------------------------------------------------------------
# entry points fail without a chip
# ---------------------------------------------------------------------------

class TestEntryPointsNeedTheChip:
    def test_chip_smoke_on_cpu_exits_nonzero_naming_the_platform(self):
        r = _run(["chip_smoke.py"])
        assert r.returncode != 0
        assert "platform is 'cpu'" in r.stderr
        assert "platform=cpu" in r.stdout
        assert '"ok"' not in r.stdout          # no result line

    def test_bench_on_cpu_exits_nonzero_at_once(self):
        r = _run(["bench.py"])
        assert r.returncode != 0
        assert "not 'tpu'" in r.stderr
        assert r.stdout.strip() == ""          # nothing was measured


class TestOffloadThatCannotOffload:
    def test_param_offload_is_refused_by_name_on_tpu(self, monkeypatch):
        """On the chip the step would abort inside XLA's host-offload
        pass, where no Python error can be caught: refuse first."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from deepspeed_tpu.comm import MeshSpec, build_mesh
        from deepspeed_tpu.runtime import engine
        from deepspeed_tpu.runtime.config_utils import DeepSpeedConfigError
        sh = NamedSharding(build_mesh(MeshSpec()), P())
        assert engine._host_kind(sh) is sh     # CPU: device memory IS host
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(engine.ParamOffloadUnsupportedError,
                           match="offload_optimizer"):
            engine._host_kind(sh)
        assert issubclass(engine.ParamOffloadUnsupportedError,
                          DeepSpeedConfigError)

    def test_optimizer_offload_placement_raises_instead_of_warning(self):
        """A backend that cannot express pinned_host is an error: the
        moments must not quietly stay in device memory."""
        from deepspeed_tpu.runtime.zero import offload_optimizer as oo

        class NoHostMemory:
            def with_memory_kind(self, kind):
                raise ValueError(f"memory kind {kind!r} unsupported")
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(jax, "default_backend", lambda: "gpu")
            mp.setattr(oo, "NamedSharding", NoHostMemory)
            with pytest.raises(ValueError, match="pinned_host"):
                oo._with_host_memory_tree({"w": NoHostMemory()})


# ---------------------------------------------------------------------------
# one process per chip
# ---------------------------------------------------------------------------

class TestOneProcessPerChip:
    def test_process_replica_refuses_under_a_tpu_parent(self, monkeypatch):
        from deepspeed_tpu.serving.fleet import replica

        def no_spawn(*a, **k):
            raise AssertionError("spawned a worker under a TPU parent")
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(replica.subprocess, "Popen", no_spawn)
        with pytest.raises(ValueError, match="backend='inprocess'"):
            replica.ProcessReplica(0, "mixed", {})

    def test_process_replica_passes_the_platform_explicitly(
            self, monkeypatch):
        from deepspeed_tpu.serving.fleet import replica
        seen = {}

        class Spawned(Exception):
            pass

        def fake_popen(argv, env=None, **k):
            seen.update(env)
            raise Spawned
        monkeypatch.setattr(replica.subprocess, "Popen", fake_popen)
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        with pytest.raises(Spawned):
            replica.ProcessReplica(0, "mixed", {})
        assert seen["JAX_PLATFORMS"] == "cpu"  # the parent's, not a default

    def test_a_parent_holding_the_chip_may_not_spawn(self, monkeypatch):
        from deepspeed_tpu.utils.host_env import assert_not_holding_chip
        jax.devices()                          # this process holds a backend
        assert_not_holding_chip("a CPU parent")   # nothing exclusive: fine
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(RuntimeError, match="holds"):
            assert_not_holding_chip("the launcher")

    def test_launcher_and_autotuner_imports_initialise_no_backend(self):
        r = _run(["-c",
                  "import deepspeed_tpu.launcher.launch, "
                  "deepspeed_tpu.launcher.runner, "
                  "deepspeed_tpu.autotuning.runner\n"
                  "from deepspeed_tpu.utils.host_env import "
                  "assert_not_holding_chip\n"
                  "from jax._src import xla_bridge\n"
                  "assert not xla_bridge.backends_are_initialized()\n"
                  "assert_not_holding_chip('launcher')\n"])
        assert r.returncode == 0, r.stderr[-800:]


# ---------------------------------------------------------------------------
# every choice away from a kernel is in the dispatch record
# ---------------------------------------------------------------------------

class TestDispatchRecords:
    def test_paged_path_choice_is_recorded_with_its_reason(self):
        from deepspeed_tpu.ops.pallas import tuning
        from deepspeed_tpu.serving.paging.manager import PagedKVManager
        mgr = types.SimpleNamespace(page_len=128)
        tuning.clear_last_dispatch()
        assert PagedKVManager._resolve_kernel(mgr, "auto") is False
        rec = tuning.last_dispatch("paged_decode")["path"]
        assert rec["impl"] == "gather"
        assert rec["reason"] == "platform is not tpu"
        assert PagedKVManager._resolve_kernel(mgr, "on") is True
        rec = tuning.last_dispatch("paged_decode")["path"]
        assert rec["impl"] == "kernel" and rec["reason"] is None
        assert rec["interpret"] is True        # CPU: not a Mosaic kernel

    def test_decode_dense_path_is_recorded_with_its_reason(self):
        from deepspeed_tpu.ops.pallas import decode_attention, tuning
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.standard_normal((2, 1, 4, 16)), jnp.float32)
        k, v = (jnp.asarray(rng.standard_normal((2, 4, 16, 96)),
                            jnp.float32) for _ in range(2))
        tuning.clear_last_dispatch()
        decode_attention(q, k, v, jnp.asarray([5, 90]))
        rec = tuning.last_dispatch("decode_attention")["dma"]
        assert rec["impl"] == "dense" and "96" in rec["reason"]

    def test_ragged_int8_weight_is_recorded_dense(self):
        from deepspeed_tpu.ops.pallas import tuning
        from deepspeed_tpu.ops.pallas.wo_int8_matmul import wo_int8_matmul
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((1, 4096)), jnp.float32)
        # a vocab-sized ragged dim: pick_block can only take it whole
        q = jnp.zeros((4096, 50257), jnp.int8)
        tuning.clear_last_dispatch()
        wo_int8_matmul(x, q, jnp.ones((50257,), jnp.float32))
        rec = tuning.last_dispatch("wo_int8_matmul")["m1"]
        assert rec["impl"] == "dense" and "50257" in rec["reason"]


# ---------------------------------------------------------------------------
# kernel repairs the chip asked for
# ---------------------------------------------------------------------------

class TestKernelTiling:
    def test_head_block_is_a_size_mosaic_compiles(self):
        from deepspeed_tpu.ops.pallas._common import pick_head_block
        # 12 aborted Mosaic on the chip (PR 21: ``limits[i] <= dim(i)``).
        # Those are the words of the float32 arm's one-row cut of its
        # boolean mask past the eighth row (PR 49: a float32 pool at 12
        # still aborts compiling for a described v5e, a bf16 pool
        # compiles); whether PR 21's one-armed kernel died of that cut
        # is not known. The chip has run 1, 2, 4 and 8 since PR 21 and
        # 16 over a bf16 pool since PR 51 (the paged kernel's sweep and
        # the sixteen-head serving cells); 16 is answered only to a
        # caller that asks for it, and 12 to nobody (5 and 10, over a
        # bf16 cache alone since PR 58: the next test)
        assert pick_head_block(12, 8) == 4     # GPT-2
        assert pick_head_block(16, 8) == 8
        assert pick_head_block(20, 8) == 4
        assert pick_head_block(16, 16) == 16   # the three bf16 cells
        assert pick_head_block(32, 16) == 16
        assert pick_head_block(24, 16) == 8
        assert pick_head_block(8, 16) == 8     # 16 heads at mp_size=2
        assert pick_head_block(36, 12) == 4    # never 12
        assert pick_head_block(4, 8) == 4      # Falcon-H1's K/V heads
        assert pick_head_block(6, 8) == 2      # 12 heads at mp_size=2
        assert pick_head_block(3, 8) == 1      # ... and at mp_size=4

    @pytest.mark.parametrize("heads,want,narrow,block", [
        # ten cached heads (Phi-4-mini-flash's pool and rings, PR 58): ten
        # or five a step to a bf16 cache whose entry or caller asks for
        # them, two at every want that was answered before
        (10, 10, True, 10), (10, 5, True, 5), (10, 20, True, 10),
        (10, 8, True, 2), (10, 16, True, 2), (10, 4, True, 2),
        (5, 10, True, 5), (5, 5, True, 5), (5, 8, True, 1),
        (20, 10, True, 10), (20, 8, True, 4), (15, 10, True, 5),
        # a want of five or ten changes nothing else: sixteen and four heads
        (16, 10, True, 2), (4, 10, True, 2), (16, 16, True, 16),
        # the float32 arm is never answered five or ten
        (10, 10, False, 2), (10, 5, False, 1), (5, 5, False, 1),
        (20, 10, False, 2),
        # twelve stays unchosen on both arms
        (12, 12, True, 4), (36, 12, True, 4), (24, 12, True, 4),
        (60, 60, True, 10), (60, 60, False, 4)])
    def test_five_and_ten_heads_a_step_on_the_narrow_arm_alone(
            self, heads, want, narrow, block):
        from deepspeed_tpu.ops.pallas._common import pick_head_block
        assert pick_head_block(heads, want, narrow) == block
        if want % 5:
            # no answer that was given before PR 58 moved
            assert block == pick_head_block(heads, want)

    @pytest.mark.parametrize("dtype,group,want,block", [
        ("bfloat16", 4, 10, 10), ("bfloat16", 4, 5, 5), ("bfloat16", 4, 8, 2),
        ("bfloat16", 1, 10, 10), ("float32", 4, 10, 2), ("float32", 4, 5, 2),
        ("float32", 1, 10, 2), ("int8", 4, 10, 2), ("int8", 1, 10, 2),
        ("float16", 4, 10, 10)])
    def test_a_ten_head_cache_by_its_type(self, dtype, group, want, block):
        """``step_head_block``: only a cache whose products run narrower
        than float32 is answered five or ten; a float32 or int8 pool of
        ten heads keeps two a step whatever is asked."""
        from deepspeed_tpu.ops.pallas.paged_attention import step_head_block
        assert step_head_block(10, group, jnp.dtype(dtype), want) == block

    def test_decode_kernels_take_twelve_heads(self):
        """12 heads -> head block 4: the per-token operands ride 4-D so
        the tile is legal; results match the dense forms."""
        from deepspeed_tpu.ops.pallas import (decode_attention,
                                              paged_attention, tuning)
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.standard_normal((2, 1, 12, 16)), jnp.float32)
        k, v = (jnp.asarray(rng.standard_normal((2, 12, 16, 128)),
                            jnp.float32) for _ in range(2))
        lengths = jnp.asarray([9, 128])
        from deepspeed_tpu.ops.pallas.decode_attention import _decode_dense
        tuning.clear_last_dispatch()
        got = decode_attention(q, k, v, lengths)
        assert tuning.last_dispatch("decode_attention")["dma"][
            "head_block"] == 4
        want = _decode_dense(q[:, 0], k, v, lengths, jnp.zeros((12,)),
                             scale=0.25, alibi=False)
        np.testing.assert_allclose(np.asarray(got[:, 0]), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        kp, vp = (jnp.asarray(rng.standard_normal((5, 12, 16, 32)),
                              jnp.float32) for _ in range(2))
        kn, vn = (jnp.asarray(rng.standard_normal((2, 12, 16, 1)),
                              jnp.float32) for _ in range(2))
        ptab = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
        plen = jnp.asarray([0, 50])
        got = paged_attention(q, kp, vp, ptab, plen, kn, vn, impl="kernel")
        want = paged_attention(q, kp, vp, ptab, plen, kn, vn, impl="dense")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_block_sparse_builder_survives_a_second_trace(self):
        """The cached builder used to make jnp constants under whichever
        jit traced it first — a leaked tracer on the next trace."""
        from deepspeed_tpu.ops.sparse_attention import (
            FixedSparsityConfig, sparse_attention)
        cfg = FixedSparsityConfig(num_heads=2, block=16,
                                  num_local_blocks=4, num_global_blocks=1)
        rng = np.random.default_rng(0)
        q, k, v = (jnp.asarray(rng.standard_normal((1, 128, 2, 16)),
                               jnp.float32) for _ in range(3))
        f = lambda q, k, v: sparse_attention(q, k, v, cfg,
                                             backend="pallas").sum()
        jax.jit(f)(q, k, v)
        g = jax.jit(jax.grad(f))(q, k, v)      # second, different trace
        assert np.isfinite(np.asarray(g)).all()


# ---------------------------------------------------------------------------
# Pallas calls under a real mesh are mapped by hand
# ---------------------------------------------------------------------------

class TestKernelsUnderAMesh:
    def test_decode_kernel_maps_over_the_model_axis(self):
        from deepspeed_tpu.comm import MeshSpec, build_mesh
        from deepspeed_tpu.ops.pallas import decode_attention, tuning
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.standard_normal((2, 1, 4, 16)), jnp.float32)
        k, v = (jnp.asarray(rng.standard_normal((2, 4, 16, 128)),
                            jnp.float32) for _ in range(2))
        lengths = jnp.asarray([7, 100])
        want = decode_attention(q, k, v, lengths)
        mesh = build_mesh(MeshSpec(model=2, data=4))
        tuning.clear_last_dispatch()
        got = jax.jit(lambda *a: decode_attention(*a, mesh=mesh))(
            q, k, v, lengths)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        rec = tuning.last_dispatch("decode_attention")["dma"]
        assert rec["model_shards"] == 2 and rec["impl"] == "kernel"
        # the kernel takes its mesh from the caller: a global mesh some
        # other engine built does not reach it
        jax.jit(decode_attention)(q, k, v, lengths)
        assert tuning.last_dispatch("decode_attention")["dma"][
            "model_shards"] == 1

    def test_flash_kernel_sees_one_devices_share_of_the_batch(self):
        from deepspeed_tpu.comm import MeshSpec, build_mesh
        from deepspeed_tpu.ops.pallas import tuning
        from deepspeed_tpu.ops.transformer.attention import attention
        rng = np.random.default_rng(0)
        q, k, v = (jnp.asarray(rng.standard_normal((8, 128, 4, 16)),
                               jnp.float32) for _ in range(3))
        f = jax.jit(lambda q, k, v, backend: attention(
            q, k, v, causal=True, backend=backend, seq_parallel="none"),
            static_argnums=3)
        want = f(q, k, v, "reference")
        build_mesh(MeshSpec(data=4, model=2))
        tuning.clear_last_dispatch()
        got = f(q, k, v, "pallas")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        rec = tuning.last_dispatch("attention")["backend"]
        assert (rec["batch"], rec["heads"]) == (2, 2)   # 8/4 rows, 4/2 heads

    def test_init_inference_shards_caller_params_over_mp_size(self):
        import deepspeed_tpu as ds
        from deepspeed_tpu.models.gpt import GPT, GPTConfig
        m = GPT(GPTConfig(vocab_size=64, max_seq_len=32, d_model=32,
                          n_layers=1, n_heads=2, dtype=jnp.float32))
        params = m.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 8), jnp.int32))["params"]
        eng = ds.init_inference(m, params=params, dtype=jnp.float32,
                                mp_size=2)
        qkv = eng.params["h"]["attn"]["qkv"]["kernel"]
        assert "model" in str(qkv.sharding.spec)
        assert qkv.addressable_shards[0].data.nbytes * 2 == qkv.nbytes


# ---------------------------------------------------------------------------
# the global mesh and the activation rules have an owner
# ---------------------------------------------------------------------------

class TestEnginesOwnTheirTraceState:
    @staticmethod
    def _trainer():
        import deepspeed_tpu as ds
        from deepspeed_tpu.models.gpt import GPT, GPTConfig
        model = GPT(GPTConfig(vocab_size=64, max_seq_len=32, d_model=32,
                              n_layers=2, n_heads=2, dtype=jnp.float32,
                              scan_layers=True))
        engine, *_ = ds.initialize(
            model=model, rng=jax.random.PRNGKey(0),
            loss_fn=lambda m, p, b, rng, train: jnp.mean(
                m.apply(p, b["input_ids"]) ** 2),
            sample_batch={"input_ids": np.ones((1, 8), np.int32)},
            config={"train_batch_size": 8,
                    "train_micro_batch_size_per_gpu": 1,
                    "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                    "zero_optimization": {"stage": 1},
                    "steps_per_print": 1000})
        return model, engine

    @staticmethod
    def _host_streamed_generate(model):
        import deepspeed_tpu as ds
        from flax.core import meta
        params = meta.unbox(model.init(
            jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32)))["params"]
        eng = ds.init_inference(model, params=params, dtype=jnp.float32,
                                offload_params=True)
        return eng, eng.generate(np.ones((1, 4), np.int32),
                                 max_new_tokens=2)

    def test_destroy_clears_what_the_trainer_installed(self):
        """trainer -> destroy -> ZeRO-Inference in one process: the
        trainer's rules used to outlive it and constrain the next
        engine's device-0 leaves to the whole mesh (incompatible
        devices)."""
        from deepspeed_tpu.comm.mesh import peek_global_mesh
        from deepspeed_tpu.models import layers
        model, engine = self._trainer()
        assert layers._ACTIVATION_RULES and peek_global_mesh() is engine.mesh
        engine.destroy()
        assert layers._ACTIVATION_RULES == {} and peek_global_mesh() is None
        _, out = self._host_streamed_generate(model)
        assert np.asarray(out).shape == (1, 6)

    def test_inference_traces_under_its_own_mesh_and_no_rules(self):
        """With the trainer still alive the inference engine neither
        inherits its rules nor clears them."""
        from deepspeed_tpu.comm.mesh import peek_global_mesh
        from deepspeed_tpu.models import layers
        model, engine = self._trainer()
        try:
            rules = layers._ACTIVATION_RULES
            eng, out = self._host_streamed_generate(model)
            assert np.asarray(out).shape == (1, 6)
            with eng._own_trace_state():
                assert layers._ACTIVATION_RULES == {}
                assert peek_global_mesh() is eng.mesh
            assert layers._ACTIVATION_RULES is rules      # the trainer's
            # a later engine's rules are not this one's to clear
            other = layers.set_activation_rules({"batch": "data"})
            engine.destroy()
            assert layers._ACTIVATION_RULES is other
        finally:
            layers.set_activation_rules({})

