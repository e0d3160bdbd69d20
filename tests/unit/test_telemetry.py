"""Production telemetry plane (ISSUE 9): goodput ledger, collective
accounting, live /metrics + /statusz endpoint.

Acceptance contract: a CPU-backend train run and a serving run each
expose a scrapeable /metrics endpoint whose goodput fractions sum to
1.0 +- eps; a chaos-injected rollback is visibly attributed to
badput/rollback_recovery; the probe-count discipline proves zero new
per-step host syncs; comm-span byte accounting matches hand-computed
payload sizes.
"""

import json
import os
import re
import time
import urllib.request

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import deepspeed_tpu as ds
import deepspeed_tpu.comm as dist
from deepspeed_tpu.comm import MeshSpec, build_mesh
from deepspeed_tpu.models import GPT, GPTConfig, gpt_loss_fn
from deepspeed_tpu.observability import (
    GoodputLedger, TelemetryServer, build_statusz, classify_spans,
    diff_snapshots, format_goodput, format_snapshot_diff, get_ledger,
    get_registry, prometheus_name, render_prometheus, reset_ledger)
from deepspeed_tpu.observability.goodput import CATEGORIES
from deepspeed_tpu.observability.metrics import MetricsRegistry
from deepspeed_tpu.utils.jax_compat import shard_map

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

VOCAB, SEQ = 128, 16
MODEL_CFG = GPTConfig(vocab_size=VOCAB, max_seq_len=SEQ, d_model=32,
                      n_layers=2, n_heads=4, dtype=jnp.float32,
                      scan_layers=True)


def loss_fn(model, params, batch, rng, train):
    logits = model.apply(params, batch["input_ids"], deterministic=not train)
    return gpt_loss_fn(logits[:, :-1], batch["input_ids"][:, 1:])


def make_batch(n, seed=0):
    rng = np.random.default_rng(seed)
    return {"input_ids": rng.integers(0, VOCAB, size=(n, SEQ),
                                      dtype=np.int32)}


def make_engine(observability=None, ckpt_dir=None, resilience=None):
    cfg = {
        "train_batch_size": 16,
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "steps_per_print": 1000,
    }
    if observability is not None:
        cfg["observability"] = observability
    if resilience is not None:
        res = dict(resilience)
        if ckpt_dir is not None:
            res.setdefault("checkpoint_dir", str(ckpt_dir))
        cfg["resilience"] = res
    eng, _, _, _ = ds.initialize(
        model=GPT(MODEL_CFG), config=cfg, loss_fn=loss_fn,
        sample_batch=make_batch(1), rng=jax.random.PRNGKey(42))
    return eng


def scrape(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.status, r.read().decode()


def goodput_fractions_from_metrics(text):
    out = {}
    for line in text.splitlines():
        if line.startswith("ds_tpu_goodput_fraction{"):
            cat = line.split('category="')[1].split('"')[0]
            out[cat] = float(line.rsplit(" ", 1)[1])
    return out


@pytest.fixture(autouse=True)
def _fresh_ledger():
    """The ledger is process-global (train + serve share a wall clock);
    each test gets a fresh epoch so fractions reflect only its run."""
    reset_ledger()
    yield


# ---------------------------------------------------------------------------
# goodput ledger
# ---------------------------------------------------------------------------

class TestGoodputLedger:
    def test_fractions_partition_wall_clock(self):
        led = GoodputLedger().start()
        with led.timed("compute"):
            time.sleep(0.02)
        with led.timed("data_stall"):
            time.sleep(0.005)
        b = led.breakdown()
        assert set(b["fractions"]) == set(CATEGORIES)
        assert sum(b["fractions"].values()) == pytest.approx(1.0, abs=1e-9)
        assert b["seconds"]["compute"] >= 0.02
        assert b["seconds"]["data_stall"] >= 0.005
        assert b["fractions"]["compute"] > b["fractions"]["data_stall"]
        assert b["goodput_fraction"] == b["fractions"]["compute"]
        assert b["badput_fraction"] == pytest.approx(
            1.0 - b["goodput_fraction"])

    def test_compile_reattributed_out_of_compute(self):
        led = GoodputLedger().start()
        led.note("compute", 1.0)
        led.note_compile(0.4)        # the compiling dispatch WAS the
        b = led.breakdown()          # compute site's 1.0s, partly
        assert b["seconds"]["compile"] == pytest.approx(0.4)
        assert b["seconds"]["compute"] == pytest.approx(0.6)

    def test_unknown_category_raises(self):
        led = GoodputLedger().start()
        with pytest.raises(ValueError, match="unknown goodput category"):
            led.note("coffee_break", 1.0)

    def test_unstarted_ledger_and_module_timed_noop(self):
        assert GoodputLedger().breakdown() == {}
        from deepspeed_tpu.observability import goodput as gp
        saved = gp._LEDGER
        gp._LEDGER = None
        try:
            with gp.timed("compute"):
                pass                 # must not raise, must not record
        finally:
            gp._LEDGER = saved

    def test_observability_snapshot_follows_ledger_reset(self):
        """reset_ledger() (bench measurement windows) rebinds the module
        global; an Observability bundle must snapshot the CURRENT ledger
        — the one timed() feeds — not a cached pre-reset object."""
        from deepspeed_tpu.observability import (Observability,
                                                 ObservabilityConfig)
        from deepspeed_tpu.observability import goodput as gp
        obs = Observability(ObservabilityConfig(enabled=True))
        reset_ledger()
        with gp.timed("compute"):
            time.sleep(0.005)
        snap = obs.snapshot()
        assert snap["goodput"]["seconds"]["compute"] >= 0.005

    def test_format_goodput_marks_badput(self):
        led = GoodputLedger().start()
        led.note("rollback_recovery", 0.5)
        text = format_goodput(led.breakdown())
        assert "badput/rollback_recovery" in text
        assert "compute" in text


class TestGoodputClassifier:
    """classify_spans against a synthetic span stream with known ground
    truth — the post-hoc half of the taxonomy."""

    @staticmethod
    def _ev(name, t0_ms, dur_ms, tid=1):
        return (name, int(t0_ms * 1e6), int(dur_ms * 1e6), tid, None)

    def test_known_ground_truth(self):
        # 100ms wall: 40 compute + 10 data + 20 checkpoint + 30 idle
        events = [
            self._ev("data", 0, 10),
            self._ev("fwd_bwd_step", 10, 40),
            self._ev("checkpoint_save", 60, 20),
        ]
        b = classify_spans(events, wall_ns=int(100e6))
        assert b["seconds"]["data_stall"] == pytest.approx(0.010)
        assert b["seconds"]["compute"] == pytest.approx(0.040)
        assert b["seconds"]["checkpoint_save"] == pytest.approx(0.020)
        assert b["seconds"]["scheduler_idle"] == pytest.approx(0.030)
        assert sum(b["fractions"].values()) == pytest.approx(1.0)
        assert b["goodput_fraction"] == pytest.approx(0.40)

    def test_nested_categorized_span_not_double_counted(self):
        # checkpoint_save INSIDE rollback_recovery: only the outer counts
        events = [
            self._ev("rollback_recovery", 0, 50),
            self._ev("checkpoint_save", 10, 20),
        ]
        b = classify_spans(events, wall_ns=int(50e6))
        assert b["seconds"]["rollback_recovery"] == pytest.approx(0.050)
        assert b["seconds"]["checkpoint_save"] == 0.0
        assert sum(b["fractions"].values()) == pytest.approx(1.0)

    def test_uncategorized_spans_ignored(self):
        events = [self._ev("monitor_flush", 0, 10),
                  self._ev("fwd", 10, 10)]
        b = classify_spans(events, wall_ns=int(20e6))
        assert b["seconds"]["compute"] == pytest.approx(0.010)
        assert b["seconds"]["scheduler_idle"] == pytest.approx(0.010)

    def test_empty_stream(self):
        assert classify_spans([]) == {}


# ---------------------------------------------------------------------------
# collective accounting
# ---------------------------------------------------------------------------

class TestCollectiveAccounting:
    def test_all_reduce_bytes_match_hand_computed(self):
        mesh = build_mesh(MeshSpec(data=8))
        reg = get_registry()
        before_b = reg.counter("comm/traced_bytes/all_reduce:data").value
        before_c = reg.counter("comm/traced_calls/all_reduce:data").value
        x = jnp.ones((8, 6), jnp.float32)
        f = shard_map(lambda t: dist.all_reduce(t, group="data"),
                      mesh, (P("data"),), P("data"))
        np.asarray(jax.jit(f)(x))
        # per-shard payload: [1, 6] fp32 = 24 bytes, traced exactly once
        assert reg.counter("comm/traced_bytes/all_reduce:data").value \
            - before_b == 24
        assert reg.counter("comm/traced_calls/all_reduce:data").value \
            - before_c == 1

    def test_ppermute_and_all_gather_accounted(self):
        mesh = build_mesh(MeshSpec(data=8))
        reg = get_registry()
        b_pp = reg.counter("comm/traced_bytes/ppermute:data").value
        b_ag = reg.counter("comm/traced_bytes/all_gather:data").value
        x = jnp.ones((8, 2), jnp.bfloat16)

        def f(t):
            t = dist.send_recv_next(t, group="data")       # ppermute
            return dist.all_gather(t, group="data")
        np.asarray(jax.jit(shard_map(f, mesh, (P("data"),), P(None)))(x))
        # per-shard [1, 2] bf16 = 4 bytes for each collective
        assert reg.counter("comm/traced_bytes/ppermute:data").value \
            - b_pp == 4
        assert reg.counter("comm/traced_bytes/all_gather:data").value \
            - b_ag == 4

    def test_compressed_allreduce_records_wire_bytes(self):
        """The quantized collective records its WIRE payload (bf16 signs
        + one fp32 scalar), not the logical fp32 tensor — the 1-bit
        compression is visible in the accounting."""
        from deepspeed_tpu.runtime.comm_compression import \
            compressed_allreduce
        mesh = build_mesh(MeshSpec(data=8))
        reg = get_registry()
        key = "comm/traced_bytes/compressed_allreduce:data"
        before = reg.counter(key).value
        x = jnp.ones((8, 10), jnp.float32)
        e = jnp.zeros((8, 10), jnp.float32)

        def f(t, err):
            out, _ = compressed_allreduce(t, err, "data")
            return out
        np.asarray(jax.jit(shard_map(
            f, mesh, (P("data"), P("data")), P("data")))(x, e))
        # per-shard signs [1, 10] bf16 = 20 bytes + 4 (fp32 scale) = 24;
        # the fp32 payload would have been 40
        assert reg.counter(key).value - before == 24

    def test_program_registry_attributes_collective_bytes(self):
        """TrackedProgram diffs the trace tally around its compile: the
        per-call bytes-moved estimate lands on the record and the
        executed-traffic counter accumulates per dispatch."""
        from deepspeed_tpu.observability.programs import track_program
        mesh = build_mesh(MeshSpec(data=8))
        reg = get_registry()
        before = reg.counter("comm/program_bytes_total").value
        x = jnp.ones((8, 16), jnp.float32)
        prog = track_program("test/telemetry_psum", jax.jit(shard_map(
            lambda t: dist.all_reduce(t, group="data"),
            mesh, (P("data"),), P("data"))))
        for _ in range(3):
            np.asarray(prog(x))
        rec = prog.record
        assert rec.collective_bytes == {"all_reduce:data": 64}  # [1,16]f32
        assert rec.collective_bytes_per_call == 64
        assert rec.to_dict()["collective_bytes_per_call"] == 64
        assert reg.counter("comm/program_bytes_total").value \
            - before == 3 * 64

    def test_rejected_reduce_op_does_not_pollute_tally(self):
        build_mesh(MeshSpec(data=8))
        from deepspeed_tpu.observability.metrics import collective_tally
        before = collective_tally()
        with pytest.raises(ValueError, match="Unsupported reduce op"):
            dist.all_reduce(jnp.ones((4,)), op=dist.ReduceOp.UNUSED,
                            group="data")
        assert collective_tally() == before

    def test_host_path_records_achieved_bandwidth(self):
        build_mesh(MeshSpec(data=8))
        dist.configure(enabled=True)
        try:
            reg = get_registry()
            before = reg.counter("comm/host_bytes_total").value
            hist = reg.histogram("comm/host_bytes_per_s")
            count_before = hist.count
            x = jnp.ones((64,), jnp.float32)
            dist.timed_host_op("all_reduce", dist.all_reduce_host, x,
                               group="data")
            assert reg.counter("comm/host_bytes_total").value \
                - before == 64 * 4
            assert hist.count == count_before + 1
        finally:
            dist.configure(enabled=False)

    def test_comm_span_carries_payload_record(self):
        """The payload record of one traced collective is the counter
        pair — with a tracer active too, and no ``comm/<op>`` span: under
        jit its duration was Python tracing time."""
        from deepspeed_tpu.observability import Tracer, activate, deactivate
        mesh = build_mesh(MeshSpec(data=8))
        reg = get_registry()
        calls = reg.counter("comm/traced_calls/all_reduce:data")
        nbytes = reg.counter("comm/traced_bytes/all_reduce:data")
        before = calls.value, nbytes.value
        t = Tracer()
        activate(t)
        try:
            x = jnp.ones((8, 3), jnp.float32)
            np.asarray(jax.jit(shard_map(
                lambda v: dist.all_reduce(v, group="data"),
                mesh, (P("data"),), P("data")))(x))
        finally:
            deactivate()
        assert calls.value - before[0] == 1
        assert nbytes.value - before[1] == 12    # [1, 3] fp32 per shard
        assert not [e[0] for e in t.events if e[0].startswith("comm/")]


# ---------------------------------------------------------------------------
# Prometheus rendering + endpoint
# ---------------------------------------------------------------------------

PROM_LINE = re.compile(
    r"^(# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]* .+"
    r"|[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? [-+0-9.eEinfa]+)$")


class TestPrometheusFormat:
    def test_name_sanitization(self):
        assert prometheus_name("serving/queue_depth") \
            == "ds_tpu_serving_queue_depth"
        assert prometheus_name("comm/traced_bytes/all_reduce:data") \
            == "ds_tpu_comm_traced_bytes_all_reduce:data"
        assert prometheus_name("1weird name!") == "ds_tpu__1weird_name_"

    def test_render_full_snapshot(self):
        reg = MetricsRegistry()
        reg.counter("train/steps_total").inc(5)
        reg.gauge("serving/queue_depth").set(3)
        reg.histogram("lat").observe(1.0)
        reg.histogram("lat").observe(3.0)
        reg.register_collector("serving", lambda: {"tokens": 7,
                                                   "skip_me": "str"})
        led = GoodputLedger().start()
        led.note("compute", 1.0)
        snap = {"registry": reg.snapshot(), "goodput": led.breakdown(),
                "perf": {"mfu": 0.5},
                "probe": {"host_reads": 2}}
        text = render_prometheus(snap)
        for line in text.strip().splitlines():
            assert PROM_LINE.match(line), line
        assert "ds_tpu_train_steps_total 5.0" in text
        assert "ds_tpu_serving_queue_depth 3.0" in text
        assert 'ds_tpu_lat{quantile="0.5"}' in text
        assert "ds_tpu_lat_count 2" in text
        assert "ds_tpu_serving_tokens 7.0" in text
        assert "skip_me" not in text          # non-numeric dropped
        assert "ds_tpu_perf_mfu 0.5" in text
        assert 'category="compute",kind="goodput"' in text
        assert 'category="rollback_recovery",kind="badput"' in text
        assert "ds_tpu_probe_host_reads 2.0" in text

    def test_render_parse_roundtrip_preserves_every_family(self):
        """The satellite acceptance: ``render -> parse`` preserves
        every counter/gauge family — sanitized names, labeled series,
        histogram summaries, collected numerics — value-exactly."""
        from deepspeed_tpu.observability.export import parse_prometheus
        reg = MetricsRegistry()
        reg.counter("train/steps_total").inc(5)
        reg.counter("comm/traced_bytes/all_reduce:data").inc(4096)
        reg.counter("1weird name!").inc(2)        # sanitized name
        reg.gauge("serving/queue_depth").set(3)
        reg.gauge("mem/hbm_used").set(1.25e9)
        reg.gauge("flag").set(True)               # bool -> 1
        reg.histogram("lat").observe(1.0)
        reg.histogram("lat").observe(3.0)
        reg.register_collector("serving", lambda: {"tokens": 7,
                                                   "frac": 0.5})
        led = GoodputLedger().start()
        led.note("compute", 2.0)
        led.note("compile", 1.0)
        snap = {"registry": reg.snapshot(), "goodput": led.breakdown(),
                "perf": {"mfu": 0.5}}
        parsed = parse_prometheus(render_prometheus(snap))
        # every counter family survives, sanitized, value-exact
        assert parsed["ds_tpu_train_steps_total"] == 5.0
        assert parsed["ds_tpu_comm_traced_bytes_all_reduce:data"] \
            == 4096.0
        assert parsed["ds_tpu__1weird_name_"] == 2.0
        # every gauge family (incl. bool coercion + big floats)
        assert parsed["ds_tpu_serving_queue_depth"] == 3.0
        assert parsed["ds_tpu_mem_hbm_used"] == 1.25e9
        assert parsed["ds_tpu_flag"] == 1.0
        # histogram summaries: quantile series + count + sum
        assert parsed['ds_tpu_lat{quantile="0.5"}'] == 1.0
        assert parsed['ds_tpu_lat{quantile="0.95"}'] == 3.0
        assert parsed["ds_tpu_lat_count"] == 2.0
        assert parsed["ds_tpu_lat_sum"] == 4.0
        # collected numerics + perf + labeled goodput series
        assert parsed["ds_tpu_serving_tokens"] == 7.0
        assert parsed["ds_tpu_serving_frac"] == 0.5
        assert parsed["ds_tpu_perf_mfu"] == 0.5
        compute = parsed['ds_tpu_goodput_seconds{category="compute",'
                         'kind="goodput"}']
        assert compute == 1.0   # compile re-attributed out of compute
        # nothing in the rendered text failed to parse back: every
        # non-comment line's value is accounted for
        rendered_samples = [
            line for line in render_prometheus(snap).splitlines()
            if line and not line.startswith("#")]
        assert len(rendered_samples) == len(parsed)

    def test_label_values_escape_roundtrip(self):
        """Label values with quotes/backslashes/newlines used to mangle
        the sample line (an unescaped ``"`` ends the label early);
        render now escapes them and the value still parses back."""
        from deepspeed_tpu.observability.export import parse_prometheus
        text = render_prometheus({
            "registry": {"counters": {}, "gauges": {}, "histograms": {}},
            "goodput": {"fractions": {'we"ird\\cat': 1.0},
                        "seconds": {'we"ird\\cat': 2.0}},
        })
        assert '\\"' in text and "\\\\" in text
        assert "\n" == text[-1]                   # no raw newlines mid-line
        parsed = parse_prometheus(text)
        labeled = [k for k in parsed
                   if k.startswith("ds_tpu_goodput_fraction{")]
        assert labeled and parsed[labeled[0]] == 1.0

    def test_statusz_sections(self):
        snap = {"registry": {"meta": {"capture_seq": 1},
                             "counters": {"c": 1}, "gauges": {},
                             "collected": {"serving": {"queue_depth": 2}}},
                "goodput": {"fractions": {}},
                "programs": {"p": {"calls": 1}},
                "memory": {"by_subsystem": {}}}
        st = build_statusz(snap)
        assert st["serving"] == {"queue_depth": 2}
        assert st["programs"] == {"p": {"calls": 1}}
        assert st["meta"]["capture_seq"] == 1


class TestTelemetryServer:
    def test_endpoint_smoke(self):
        reg = MetricsRegistry()
        reg.counter("hits").inc(2)
        srv = TelemetryServer(lambda: {"registry": reg.snapshot()},
                              port=0).start()
        try:
            assert srv.running and srv.port > 0
            code, body = scrape(srv.url("/healthz"))
            assert (code, body) == (200, "ok\n")
            code, body = scrape(srv.url("/metrics"))
            assert code == 200
            for line in body.strip().splitlines():
                assert PROM_LINE.match(line), line
            assert "ds_tpu_hits 2.0" in body
            code, body = scrape(srv.url("/statusz"))
            assert code == 200
            assert json.loads(body)["counters"] == {"hits": 2}
            with pytest.raises(urllib.error.HTTPError) as e:
                scrape(srv.url("/nope"))
            assert e.value.code == 404
        finally:
            srv.stop()
        assert not srv.running

    def test_snapshot_failure_is_503_not_crash(self):
        def bad():
            raise ValueError("boom")
        srv = TelemetryServer(bad, port=0).start()
        try:
            with pytest.raises(urllib.error.HTTPError) as e:
                scrape(srv.url("/metrics"))
            assert e.value.code == 503
            # the server thread survived the failed scrape
            assert scrape(srv.url("/healthz"))[0] == 200
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# engine integration: the acceptance criteria
# ---------------------------------------------------------------------------

class TestTrainEndpoint:
    @pytest.mark.slow
    def test_train_run_scrapeable_goodput_sums_to_one(self):
        """CPU train run with the export block: /metrics scrapes live,
        goodput fractions sum to 1.0 +- eps, and the probe counter shows
        the endpoint added ZERO host syncs (2 reads = interval-3 cadence
        over 8 steps, identical to the PR-5 baseline test)."""
        eng = make_engine(observability={
            "enabled": True, "probe_interval": 3, "metrics_interval": 4,
            "peak_tflops": 0.001, "export": {"enabled": True, "port": 0}})
        try:
            assert eng.telemetry is not None and eng.telemetry.running
            batch = make_batch(16)
            for _ in range(8):
                eng.train_batch(batch)
            code, text = scrape(eng.telemetry.url("/metrics"))
            assert code == 200
            fr = goodput_fractions_from_metrics(text)
            assert set(fr) == set(CATEGORIES)
            assert sum(fr.values()) == pytest.approx(1.0, abs=1e-6)
            assert fr["compute"] > 0
            # compile happened (first dispatch) and was attributed
            assert fr["compile"] > 0
            # train gauges flushed through the registry reach /metrics
            assert "ds_tpu_train_global_steps 8.0" in text
            # probe-count discipline: scraping added no syncs
            assert eng.observability.probe.host_reads == 2
            code, body = scrape(eng.telemetry.url("/statusz"))
            st = json.loads(body)
            assert "train/train_step" in st["programs"]
            assert st["goodput"]["fractions"]["compute"] > 0
        finally:
            eng.destroy()
        assert eng.telemetry is None

    @pytest.mark.slow
    def test_destroy_stops_endpoint(self):
        eng = make_engine(observability={
            "enabled": True, "export": {"enabled": True, "port": 0}})
        url = eng.telemetry.url("/healthz")
        assert scrape(url)[0] == 200
        eng.destroy()
        with pytest.raises((urllib.error.URLError, ConnectionError,
                            OSError)):
            urllib.request.urlopen(url, timeout=2)

    @pytest.mark.slow
    def test_snapshot_carries_goodput_without_observability_block(self):
        eng = make_engine()
        try:
            eng.train_batch(make_batch(16))
            snap = eng.metrics_snapshot()
            assert sum(snap["goodput"]["fractions"].values()) \
                == pytest.approx(1.0, abs=1e-6)
            assert snap["goodput"]["seconds"]["compute"] > 0
        finally:
            eng.destroy()


class TestRollbackAttribution:
    @pytest.mark.slow
    def test_chaos_rollback_attributed_to_badput(self, tmp_path):
        """The acceptance chaos leg: a NaN-injected divergence rollback
        shows up in the goodput breakdown under rollback_recovery (and
        the fractions still partition to 1.0)."""
        from deepspeed_tpu.runtime.resilience.faults import Fault, injected
        eng = make_engine(ckpt_dir=tmp_path, resilience={
            "divergence": {"check_interval": 1, "patience": 1,
                           "max_rollbacks": 2}})
        try:
            batch = make_batch(16)
            for _ in range(2):
                eng.train_batch(batch)
            eng.save_checkpoint(str(tmp_path))
            with injected([Fault("nan_grads", step=3)]):
                for _ in range(4):
                    eng.train_batch(batch)
                    if eng.resilience.rollbacks:
                        break
            assert eng.resilience.rollbacks == 1
            b = get_ledger().breakdown()
            assert b["seconds"]["rollback_recovery"] > 0
            assert b["fractions"]["rollback_recovery"] > 0
            assert b["seconds"]["checkpoint_save"] > 0
            assert sum(b["fractions"].values()) == pytest.approx(
                1.0, abs=1e-6)
            # post-hoc classification of the recorded spans agrees that
            # recovery time exists (the trace side of the attribution)
            text = format_goodput(b)
            assert "badput/rollback_recovery" in text
        finally:
            eng.destroy()


class TestServingEndpoint:
    def _serving_engine(self):
        from deepspeed_tpu.serving import ServingConfig
        from deepspeed_tpu.serving.engine import ServingEngine
        cfg = GPTConfig(vocab_size=61, max_seq_len=64, d_model=32,
                        n_layers=1, n_heads=2, dtype=jnp.float32)
        m = GPT(cfg)
        params = m.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 8), jnp.int32))["params"]
        return ServingEngine(m, params, ServingConfig(
            num_slots=2, max_len=64, seed=0))

    @pytest.mark.slow
    def test_serving_run_scrapeable_with_queue_gauges(self):
        eng = self._serving_engine()
        srv = eng.start_telemetry(port=0)
        try:
            rng = np.random.default_rng(0)
            for i in range(4):
                eng.submit(rng.integers(1, 60, size=5), max_new_tokens=3,
                           request_id=i)
            eng.run()
            code, text = scrape(srv.url("/metrics"))
            assert code == 200
            # satellite: scheduler state is now live registry gauges
            assert "ds_tpu_serving_queue_depth" in text
            assert "ds_tpu_serving_active_slots" in text
            fr = goodput_fractions_from_metrics(text)
            assert sum(fr.values()) == pytest.approx(1.0, abs=1e-6)
            assert fr["compute"] > 0
            st = json.loads(scrape(srv.url("/statusz"))[1])
            assert st["serving"]["requests_finished"] == 4
            assert "serving/decode_iter" in st["programs"]
        finally:
            eng.close()
        assert eng.telemetry is None

    def test_registry_gauges_track_scheduler_state(self):
        from deepspeed_tpu.serving.metrics import ServingMetrics
        reg = MetricsRegistry()
        sm = ServingMetrics(registry=reg)
        sm.sample(queue_depth=5, busy_slots=3, num_slots=4, iteration=1)
        snap = reg.snapshot()
        assert snap["gauges"]["serving/queue_depth"] == 5
        assert snap["gauges"]["serving/active_slots"] == 3


# ---------------------------------------------------------------------------
# snapshot diffing (ds_tpu_report --diff)
# ---------------------------------------------------------------------------

class TestSnapshotDiff:
    def _two_snaps(self):
        reg = MetricsRegistry()
        reg.counter("requests").inc(3)
        reg.gauge("depth").set(1)
        reg.histogram("lat").observe(1.0)
        a = {"registry": reg.snapshot()}
        reg.counter("requests").inc(4)
        reg.gauge("depth").set(9)
        reg.histogram("lat").observe(2.0)
        b = {"registry": reg.snapshot()}
        return a, b

    def test_counters_as_deltas_gauges_before_after(self):
        a, b = self._two_snaps()
        d = diff_snapshots(a, b)
        assert d["counters"]["requests"]["delta"] == 4
        assert d["counters"]["requests"]["before"] == 3
        assert d["gauges"]["depth"] == {"before": 1, "after": 9}
        assert d["histograms"]["lat"]["count_delta"] == 1
        assert not d["meta"]["swapped_inputs"]
        assert d["meta"]["elapsed_s"] >= 0
        text = format_snapshot_diff(d)
        assert "requests: +4" in text
        assert "depth: 1 -> 9" in text

    def test_reversed_inputs_swapped_by_capture_stamps(self):
        a, b = self._two_snaps()
        d = diff_snapshots(b, a)      # newest first: meta stamps fix it
        assert d["meta"]["swapped_inputs"]
        assert d["counters"]["requests"]["delta"] == 4

    def test_cross_process_snapshots_order_by_wall_clock(self):
        """A restarted run's capture_seq starts over at 1 and its
        monotonic clock shares no epoch: ordering must come from the
        unix stamp and elapsed from the unix delta — never a negated
        diff or a garbage monotonic rate."""
        run_a = {"registry": {        # older run, high seq, high mono
            "meta": {"capture_seq": 5, "captured_at_unix": 1000.0,
                     "captured_at_monotonic_s": 99999.0},
            "counters": {"steps": 10}, "gauges": {}, "histograms": {}}}
        run_b = {"registry": {        # newer run, restarted process
            "meta": {"capture_seq": 1, "captured_at_unix": 1060.0,
                     "captured_at_monotonic_s": 3.0},
            "counters": {"steps": 25}, "gauges": {}, "histograms": {}}}
        d = diff_snapshots(run_a, run_b)
        assert not d["meta"]["swapped_inputs"]    # unix order wins
        assert d["counters"]["steps"]["delta"] == 15
        assert d["meta"]["elapsed_s"] == pytest.approx(60.0)


# ---------------------------------------------------------------------------
# lint gate (satellite): the new modules + touched comm files ship clean
# ---------------------------------------------------------------------------

class TestLintGate:
    def test_telemetry_plane_lints_clean(self):
        from deepspeed_tpu.analysis.cli import main as lint_main
        assert lint_main([
            os.path.join(REPO_ROOT, "deepspeed_tpu", "observability",
                         "goodput.py"),
            os.path.join(REPO_ROOT, "deepspeed_tpu", "observability",
                         "export.py"),
            os.path.join(REPO_ROOT, "deepspeed_tpu", "comm"),
            os.path.join(REPO_ROOT, "deepspeed_tpu", "runtime",
                         "comm_compression.py"),
            "-q"]) == 0
