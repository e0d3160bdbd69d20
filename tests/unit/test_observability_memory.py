"""HBM accountant + compiled-program registry (ISSUE 7).

The acceptance contract: the static memory estimate lands within 2x of
XLA's ``memory_analysis()`` on the gpt2/gptj/bloom reference configs
(CPU backend — the FLOPs-estimator test pattern), the serving engine's
``decode_gather_transient_bytes`` is derived by the accountant instead
of hand arithmetic, every registered jit site shows up in one queryable
program table, and none of it adds a per-step host sync (probe-count
assertions here; TS002 statically in CI).
"""

import json
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import deepspeed_tpu as ds
from deepspeed_tpu.models import GPT, GPTConfig, gpt_loss_fn
from deepspeed_tpu.observability import (
    MemoryAccountant, MemoryConfig, ObservabilityConfig, Tracer, activate,
    chrome_trace_events, deactivate, estimate_forward_memory_bytes,
    format_memory_report, format_program_table, format_summary,
    get_accountant, get_program_registry, get_registry, is_oom_error,
    oom_forensics, summarize, track_program, tree_bytes, write_oom_forensics)
from deepspeed_tpu.observability.metrics import MetricsRegistry

VOCAB, SEQ = 64, 16
MODEL_CFG = GPTConfig(vocab_size=VOCAB, max_seq_len=SEQ, d_model=32,
                      n_layers=2, n_heads=4, dtype=jnp.float32)


def loss_fn(model, params, batch, rng, train):
    logits = model.apply(params, batch["input_ids"], deterministic=not train)
    return gpt_loss_fn(logits[:, :-1], batch["input_ids"][:, 1:])


def make_batch(n, seed=0):
    rng = np.random.default_rng(seed)
    return {"input_ids": rng.integers(0, VOCAB, size=(n, SEQ),
                                      dtype=np.int32)}


def make_engine(observability=None, **extra):
    cfg = {
        "train_batch_size": 16,
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "steps_per_print": 1000,
        **extra,
    }
    if observability is not None:
        cfg["observability"] = observability
    eng, _, _, _ = ds.initialize(
        model=GPT(MODEL_CFG), config=cfg, loss_fn=loss_fn,
        sample_batch=make_batch(1))
    return eng


@pytest.fixture(autouse=True)
def _clean_global_state():
    """Accountant and tracer state must not leak between tests (the
    program registry is deliberately long-lived — module-level jits
    register once at import — so it is NOT reset here)."""
    yield
    deactivate()
    get_accountant().reset()


# ---------------------------------------------------------------------------
# shape walker + accountant
# ---------------------------------------------------------------------------

class TestAccountant:
    def test_tree_bytes_concrete_and_abstract(self):
        tree = {"a": jnp.zeros((4, 8), jnp.float32),
                "b": {"c": jnp.zeros((3,), jnp.int32), "d": None}}
        assert tree_bytes(tree) == 4 * 8 * 4 + 3 * 4
        abstract = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            {"a": jnp.zeros((4, 8), jnp.float32)})
        assert tree_bytes(abstract) == 4 * 8 * 4

    def test_account_replaces_not_accumulates(self):
        reg = MetricsRegistry()
        acct = MemoryAccountant(registry=reg)
        acct.account("sub", num_bytes=100)
        acct.account("sub", num_bytes=250)          # same (sub, name)
        assert acct.subsystem_bytes("sub") == 250
        assert reg.snapshot()["gauges"]["mem/by_subsystem/sub"] == 250
        acct.account("sub", num_bytes=50, name="other")
        assert acct.subsystem_bytes("sub") == 300
        assert acct.static_total() == 300

    def test_discard_zeroes_gauge(self):
        reg = MetricsRegistry()
        acct = MemoryAccountant(registry=reg)
        acct.account("gone", num_bytes=10)
        acct.discard("gone")
        assert acct.subsystem_bytes("gone") == 0
        assert reg.snapshot()["gauges"]["mem/by_subsystem/gone"] == 0

    def test_top_buffers_sorted(self):
        acct = MemoryAccountant(registry=MetricsRegistry())
        acct.account("a", num_bytes=10, name="small")
        acct.account("b", num_bytes=1000, name="big")
        acct.account("c", num_bytes=100, name="mid")
        top = acct.top_buffers(2)
        assert [r["bytes"] for r in top] == [1000, 100]

    def test_live_sampling_unsupported_on_cpu_detected_once(self):
        acct = MemoryAccountant(registry=MetricsRegistry())
        assert acct.sample_live(step=1) is None   # CPU: no memory_stats
        assert acct._live_unsupported
        assert acct.live_samples == 0
        assert acct.sample_live(step=2) is None   # cheap no-op now

    def test_report_and_format(self):
        acct = MemoryAccountant(registry=MetricsRegistry())
        acct.account("train/params", num_bytes=4096)
        rep = acct.report()
        assert rep["by_subsystem"]["train/params"]["bytes"] == 4096
        assert rep["static_total_bytes"] == 4096
        text = format_memory_report(rep)
        assert "train/params" in text and "4.10KB" in text
        assert "live: unavailable" in text

    def test_memory_config_validation(self):
        with pytest.raises(ValueError, match="poll_interval"):
            MemoryConfig(poll_interval=-1)
        with pytest.raises(ValueError, match="top_buffers"):
            MemoryConfig(top_buffers=0)

    def test_config_block_parses_nested_dict(self):
        cfg = ObservabilityConfig(enabled=True,
                                  memory={"poll_interval": 7,
                                          "oom_forensics": False})
        assert cfg.memory.poll_interval == 7
        assert not cfg.memory.oom_forensics


# ---------------------------------------------------------------------------
# static estimator vs XLA memory_analysis (the 2x acceptance bound)
# ---------------------------------------------------------------------------

class TestEstimatorVsXla:
    @pytest.mark.parametrize("variant", [
        {},                                                        # gpt2
        dict(rotary=True, learned_pos=False, parallel_residual=True,
             shared_parallel_ln=True, attn_use_bias=False,
             tie_embeddings=False, lm_head_bias=True),             # gptj
        dict(alibi=True, learned_pos=False, embed_ln=True),        # bloom
    ], ids=["gpt2", "gptj", "bloom"])
    def test_estimate_within_2x_of_memory_analysis(self, variant):
        """Static working-set estimate vs the compiler's own accounting
        (argument + output + temp bytes) on the three reference model
        families — the FLOPs-estimator-within-2x pattern applied to
        memory."""
        cfg = GPTConfig(vocab_size=128, max_seq_len=32, d_model=64,
                        n_layers=2, n_heads=4, dtype=jnp.float32, **variant)
        model = GPT(cfg)
        ids = jnp.zeros((2, 32), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), ids)
        compiled = jax.jit(
            lambda p, i: model.apply(p, i, deterministic=True)
        ).lower(params, ids).compile()
        ma = compiled.memory_analysis()
        assert ma is not None, "CPU backend must expose memory_analysis"
        xla_total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                     + ma.temp_size_in_bytes)
        n_params = sum(int(x.size) for x in jax.tree.leaves(params))
        est = estimate_forward_memory_bytes(
            n_params, batch=2, seq=32, d_model=cfg.d_model,
            n_heads=cfg.n_heads, vocab_size=cfg.vocab_size, dtype_bytes=4)
        assert xla_total > 0
        ratio = est / xla_total
        assert 0.5 < ratio < 2.0, (est, xla_total)


# ---------------------------------------------------------------------------
# compiled-program registry
# ---------------------------------------------------------------------------

class TestProgramRegistry:
    def test_track_counts_calls_and_compiles(self):
        tracked = track_program("test/add_one",
                                jax.jit(lambda x: x + 1), subsystem="test")
        x = jnp.zeros((4,), jnp.float32)
        tracked(x)                       # compile 1
        tracked(x)                       # cache hit
        tracked(jnp.zeros((8,), jnp.float32))   # new shape -> compile 2
        rec = tracked.record
        assert rec.calls == 3
        assert rec.compiles == 2
        assert rec.compile_wall_s > 0
        assert rec.arg_bytes == 8 * 4    # last-compiled input tree
        # the registry table carries the same record
        table = get_program_registry().table()
        assert table["test/add_one"]["compiles"] == 2

    def test_attribute_passthrough(self):
        tracked = track_program("test/passthrough", jax.jit(lambda x: x * 2))
        tracked(jnp.ones((2,)))
        # the compile-once tests' probe keeps working on the wrapper
        assert tracked._cache_size() == 1

    def test_analyze_pulls_memory_analysis(self):
        tracked = track_program("test/matmul",
                                jax.jit(lambda a, b: a @ b))
        a = jnp.ones((16, 16), jnp.float32)
        tracked(a, a)
        info = tracked.analyze()
        assert info is not None
        assert info["argument_bytes"] == 2 * 16 * 16 * 4
        assert info["flops"] > 0
        table = get_program_registry().table()
        assert table["test/matmul"]["analysis"]["argument_bytes"] \
            == 2 * 16 * 16 * 4
        assert "test/matmul" in format_program_table(table)

    def test_analyze_before_any_compile_is_none(self):
        tracked = track_program("test/nevercalled", jax.jit(lambda x: x))
        assert tracked.analyze() is None

    def test_compile_events_bump_registry(self):
        before = get_registry().counter("programs/compiles_total").value
        tracked = track_program("test/bump", jax.jit(lambda x: x - 1))
        tracked(jnp.zeros((3,)))
        assert get_registry().counter("programs/compiles_total").value \
            == before + 1

    def test_module_jit_sites_registered(self):
        """The serving/paging/inference jit sites register at import —
        the one queryable table the ISSUE asks for."""
        import deepspeed_tpu.serving.engine          # noqa: F401
        import deepspeed_tpu.serving.paging.manager  # noqa: F401
        import deepspeed_tpu.inference.generation    # noqa: F401
        names = set(get_program_registry().table())
        assert {"serving/paged_decode", "serving/chunk_prefill",
                "serving/spec_verify_iter",
                "inference/prefill", "inference/decode_loop"} <= names
        assert not {"serving/admit", "serving/decode_iter"} & names


# ---------------------------------------------------------------------------
# snapshot stamps + dropped-span counter + counter tracks
# ---------------------------------------------------------------------------

class TestSatellites:
    def test_snapshot_meta_stamps_monotonic(self):
        reg = MetricsRegistry()
        s1 = reg.snapshot()
        s2 = reg.snapshot()
        assert s1["meta"]["capture_seq"] == 1
        assert s2["meta"]["capture_seq"] == 2
        assert (s2["meta"]["captured_at_monotonic_s"]
                >= s1["meta"]["captured_at_monotonic_s"])
        assert s2["meta"]["captured_at_unix"] > 0

    def test_dropped_spans_counter_and_summary_footer(self):
        from deepspeed_tpu.observability import Observability, span
        obs = Observability(ObservabilityConfig(
            enabled=True, trace_buffer_events=4),
            registry=MetricsRegistry())
        activate(obs.tracer)
        for i in range(10):
            with span(f"s{i}"):
                pass
        deactivate()
        snap = obs.snapshot()
        assert snap["registry"]["counters"]["trace/spans_dropped_total"] == 6
        assert snap["trace"]["events_dropped"] == 6
        # re-snapshot: the counter is a delta export, not double-counted
        assert obs.snapshot()["registry"]["counters"][
            "trace/spans_dropped_total"] == 6
        footer = format_summary(summarize(obs.tracer.events), 6)
        assert "6 spans dropped" in footer

    def test_counter_track_exports_as_chrome_counter_event(self):
        t = Tracer()
        activate(t)
        from deepspeed_tpu.observability import span
        with span("work"):
            pass
        t.record_counter("mem/hbm_used", 12345)
        deactivate()
        events = chrome_trace_events(t.events)
        counters = [e for e in events if e["ph"] == "C"]
        assert len(counters) == 1
        assert counters[0]["name"] == "mem/hbm_used"
        assert counters[0]["args"]["value"] == 12345
        # summaries skip counter samples (they have no duration)
        assert set(summarize(t.events)) == {"work"}


# ---------------------------------------------------------------------------
# OOM forensics
# ---------------------------------------------------------------------------

class TestOomForensics:
    def test_is_oom_error_markers(self):
        assert is_oom_error(RuntimeError(
            "RESOURCE_EXHAUSTED: Out of memory allocating 16g"))
        assert not is_oom_error(ValueError("shape mismatch"))

    def test_forensics_report_and_dump(self, tmp_path):
        acct = get_accountant()
        acct.account("train/params", num_bytes=2 ** 20)
        report = oom_forensics(reason="test failure")
        assert report["reason"] == "test failure"
        assert report["memory"]["by_subsystem"]["train/params"]["bytes"] \
            == 2 ** 20
        assert isinstance(report["programs"], dict)
        path = write_oom_forensics(str(tmp_path / "oom.json"), report)
        loaded = json.loads(open(path).read())
        assert loaded["memory"]["static_total_bytes"] == 2 ** 20

    def test_engine_dispatch_failure_hook(self, tmp_path):
        dump = tmp_path / "forensics.json"
        eng = make_engine(observability={
            "enabled": True, "trace": False,
            "memory": {"oom_dump_path": str(dump)}})
        before = get_registry().counter(
            "resilience/oom_forensics/total").value
        eng._note_dispatch_failure(ValueError("not an oom"))
        assert not dump.exists()
        eng._note_dispatch_failure(RuntimeError(
            "RESOURCE_EXHAUSTED: out of memory while allocating"))
        assert dump.exists()
        loaded = json.loads(dump.read_text())
        assert "train/params" in loaded["memory"]["by_subsystem"]
        assert isinstance(loaded["programs"], dict)
        # no resilience configured: the counter must not have moved
        assert get_registry().counter(
            "resilience/oom_forensics/total").value == before
        eng.destroy()

    def test_forensics_honors_top_buffers(self):
        acct = get_accountant()
        for i in range(4):
            acct.account("train/params", num_bytes=1000 + i, name=f"b{i}")
        report = oom_forensics(reason="x", top=2)
        assert len(report["memory"]["top_buffers"]) == 2
        assert report["memory"]["top_buffers"][0]["bytes"] == 1003

    def test_memory_disabled_skips_attribution_and_forensics(self, tmp_path):
        """observability.memory.enabled=false turns off the whole layer:
        no static attribution, no grad-buffer tagging, no OOM dump."""
        dump = tmp_path / "forensics.json"
        eng = make_engine(observability={
            "enabled": True, "trace": False,
            "memory": {"enabled": False, "oom_dump_path": str(dump)}})
        assert get_accountant().subsystem_bytes("train/params") == 0
        eng.forward(make_batch(16))
        eng.backward()
        eng.step()
        assert get_accountant().subsystem_bytes(
            "train/gradient_buffers") == 0
        eng._note_dispatch_failure(RuntimeError(
            "RESOURCE_EXHAUSTED: out of memory while allocating"))
        assert not dump.exists()
        eng.destroy()


# ---------------------------------------------------------------------------
# engine integration (train + serving), zero new per-step syncs
# ---------------------------------------------------------------------------

class TestEngineIntegration:
    def test_train_engine_accounts_and_registers(self):
        eng = make_engine(observability={
            "enabled": True, "trace": False, "probe_interval": 3,
            "peak_tflops": 0.001})
        batch = make_batch(16)
        for _ in range(8):
            eng.train_batch(batch)
        # probe discipline unchanged: interval 3 over 8 steps -> 2 syncs,
        # and the memory layer added none (CPU backend: live sampling
        # detects unsupported without any device sync)
        assert eng.observability.probe.host_reads == 2
        snap = eng.observability.snapshot()
        mem = snap["memory"]["by_subsystem"]
        assert mem["train/params"]["bytes"] > 0
        assert mem["train/optimizer_state"]["bytes"] > 0
        progs = snap["programs"]
        assert progs["train/train_step"]["compiles"] == 1
        assert progs["train/train_step"]["calls"] == 8
        assert progs["train/train_step"]["compile_wall_s"] > 0
        gauges = snap["registry"]["gauges"]
        assert gauges["mem/by_subsystem/train/params"] \
            == mem["train/params"]["bytes"]
        eng.destroy()
        # destroy releases the attribution
        assert get_accountant().subsystem_bytes("train/params") == 0

    def test_parity_path_accounts_gradient_buffers(self):
        eng = make_engine(observability={"enabled": True, "trace": False})
        batch = make_batch(16)
        eng.forward(batch)
        eng.backward()
        eng.step()
        assert get_accountant().subsystem_bytes("train/gradient_buffers") > 0
        eng.destroy()

    @pytest.mark.slow
    def test_train_step_analysis_memory_on_cpu(self):
        """The registered fused train step re-lowers from its stored
        avals and yields a real XLA memory analysis (the ds_tpu_trace
        --memory path)."""
        eng = make_engine(observability={"enabled": True, "trace": False})
        eng.train_batch(make_batch(16))
        tracked = get_program_registry().get("train/train_step")
        info = tracked.analyze()
        assert info is not None and info["argument_bytes"] > 0
        eng.destroy()

    def test_serving_engine_memory_attribution(self):
        from deepspeed_tpu.serving import ServingConfig
        from deepspeed_tpu.serving.engine import ServingEngine
        cfg = GPTConfig(vocab_size=61, max_seq_len=64, d_model=32,
                        n_layers=1, n_heads=2, dtype=jnp.float32)
        m = GPT(cfg)
        params = m.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 8), jnp.int32))["params"]
        eng = ServingEngine(m, params, ServingConfig(
            num_slots=2, max_len=64, seed=0))
        acct = get_accountant()
        kv = acct.subsystem_bytes("serving/kv_pool")
        assert kv == (eng._paged.pool_bytes()
                      + tree_bytes(eng._paged.page_table))
        assert acct.subsystem_bytes("serving/params") == tree_bytes(params)
        report = eng.memory_report()
        assert report["kv_pool_resident_bytes"] == kv
        assert report["kv_page_dtype"] == "float32"
        assert not report["paged_kernel"]               # CPU: gather path
        assert get_registry().gauge("mem/kv_pool_resident").value == kv
        # close() is the serving mirror of destroy(): attribution released
        eng.close()
        assert acct.subsystem_bytes("serving/kv_pool") == 0
        assert acct.subsystem_bytes("serving/params") == 0
        assert acct.subsystem_bytes("serving/state") == 0
        assert get_registry().gauge("mem/kv_pool_resident").value == 0
        eng.close()                                          # idempotent

    def test_paged_serving_transient_derived_not_hand_computed(self):
        """The acceptance check: decode_gather_transient_bytes comes
        from the accountant walk over the pool's leaf shapes and equals
        the independent slots×cache_len arithmetic."""
        from deepspeed_tpu.serving import ServingConfig
        from deepspeed_tpu.serving.engine import ServingEngine
        from deepspeed_tpu.serving.paging import PagingConfig
        cfg = GPTConfig(vocab_size=61, max_seq_len=64, d_model=32,
                        n_layers=2, n_heads=2, dtype=jnp.float32)
        m = GPT(cfg)
        params = m.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 8), jnp.int32))["params"]
        eng = ServingEngine(m, params, ServingConfig(
            num_slots=3, max_len=64, seed=0,
            paging=PagingConfig(page_len=16)))
        mgr = eng._paged
        derived = mgr.decode_gather_transient_bytes()
        # independent cross-check (the PR-6 hand arithmetic)
        bytes_per_token = mgr.pool_bytes() / (mgr.num_pages * mgr.page_len)
        assert derived == int(bytes_per_token * 3 * eng.config.cache_len)
        report = eng.memory_report()
        assert report["decode_gather_transient_bytes"] == derived
        assert get_registry().gauge(
            "mem/decode_gather_transient").value == derived
        # generation still runs end-to-end with tracked programs
        rng = np.random.default_rng(0)
        for i in range(3):
            eng.submit(rng.integers(1, 60, size=5), max_new_tokens=3,
                       request_id=i)
        eng.run()
        table = get_program_registry().table()
        assert table["serving/paged_decode"]["compiles"] >= 1
        assert table["serving/chunk_prefill"]["compiles"] >= 1

    def test_serving_spans_carry_request_labels(self):
        from deepspeed_tpu.serving import ServingConfig
        from deepspeed_tpu.serving.engine import ServingEngine
        cfg = GPTConfig(vocab_size=61, max_seq_len=64, d_model=32,
                        n_layers=1, n_heads=2, dtype=jnp.float32)
        m = GPT(cfg)
        params = m.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 8), jnp.int32))["params"]
        eng = ServingEngine(m, params, ServingConfig(
            num_slots=2, max_len=64, seed=0))
        t = Tracer()
        activate(t)
        rng = np.random.default_rng(0)
        for i in range(3):
            eng.submit(rng.integers(1, 60, size=5), max_new_tokens=3,
                       request_id=100 + i)
        eng.run()
        deactivate()
        by_name = {}
        for name, _t0, _dur, _tid, args in t.events:
            by_name.setdefault(name, []).append(args)
        chunk_ids = {a["request_id"]
                     for a in by_name["serving/prefill_chunk"]}
        assert chunk_ids == {100, 101, 102}
        assert all("active_requests" in a and "iteration" in a
                   for a in by_name["serving/decode_iter"])
        assert max(a["active_requests"]
                   for a in by_name["serving/decode_iter"]) >= 1
        assert all(a["kind"] in ("admit", "decode")
                   for a in by_name["serving/harvest"])
