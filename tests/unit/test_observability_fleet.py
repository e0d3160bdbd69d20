"""Fleet-wide request tracing + telemetry aggregation
(deepspeed_tpu/observability/fleet.py + the serving/fleet wiring).

Acceptance surface:

- trace_id lifecycle: deterministic ids stamped at submit, propagated
  through engine spans, the worker line-JSON protocol, and the handoff
  wire format (v2; v1 payloads still load);
- per-request waterfall: queue -> prefill -> handoff -> decode stage
  sums telescope EXACTLY to each request's end-to-end steps on the
  fleet clock, whatever marks are missing;
- stitched Chrome traces: one process lane per replica, spans joined
  across lanes by ``args.trace_id`` (the disaggregated 2-replica
  process-backend acceptance run lives here, marked slow);
- flight recorder: bounded, JSON-able, riding every snapshot (incl.
  the crash-path partial snapshot);
- telemetry aggregator: merged totals equal the sum of the per-replica
  scrapes; per-replica up/staleness distinguishes a dead replica from
  one dropped scrape; the hardened scrape client retries one transient
  failure and stamps ``last_success_unix``.

Unique vocab sizes per engine-building test (repo convention): jit
caches are process-global, so distinct shapes keep compile-once probes
honest across tests.
"""

import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

from deepspeed_tpu.observability.export import (MetricsScrapeClient,
                                                TelemetryServer,
                                                parse_prometheus,
                                                render_prometheus)
from deepspeed_tpu.observability.fleet import (STAGES,
                                               FleetTelemetryAggregator,
                                               FlightRecorder,
                                               breakdown_from_trace,
                                               format_waterfall,
                                               make_trace_id,
                                               merge_numeric,
                                               per_request_breakdown,
                                               stitch_chrome_traces)

# ---------------------------------------------------------------------------
# trace ids + flight recorder (pure host, no jax)
# ---------------------------------------------------------------------------


class TestTraceIds:
    def test_deterministic_and_distinct(self):
        assert make_trace_id("req-7", 3) == make_trace_id("req-7", 3)
        assert make_trace_id("req-7", 3) != make_trace_id("req-7", 4)
        assert make_trace_id("a", 0) != make_trace_id("b", 0)
        # int and str ids both work and never collide by repr
        assert make_trace_id(7, 0) != make_trace_id("7", 0)


class TestFlightRecorder:
    def test_bounded_ring_counts_evictions(self):
        fr = FlightRecorder(3)
        for i in range(5):
            fr.record("submit", request_id=i, trace_id=f"t{i}",
                      iteration=i)
        assert len(fr.events) == 3
        assert fr.recorded == 5 and fr.dropped == 2
        snap = fr.snapshot()
        assert snap["dropped"] == 2 and len(snap["events"]) == 3
        json.dumps(snap)                       # JSON-able contract
        assert snap["events"][0]["request_id"] == 2   # oldest evicted

    def test_capacity_zero_disables(self):
        fr = FlightRecorder(0)
        fr.record("submit", request_id=1)
        assert not fr.events and fr.recorded == 0

    def test_extra_fields_ride_along(self):
        fr = FlightRecorder(8)
        fr.record("shed", request_id="r", trace_id="t", iteration=4,
                  reason="slo")
        ev = fr.events[0]
        assert ev["reason"] == "slo" and ev["unix_ts"] > 0


# ---------------------------------------------------------------------------
# per-request waterfall
# ---------------------------------------------------------------------------

def _ev(event, tid, it, **kw):
    return {"event": event, "trace_id": tid, "request_id": tid,
            "iteration": it, **kw}


class TestWaterfall:
    def test_full_chain_telescopes(self):
        events = [_ev("submit", "A", 0), _ev("admit", "A", 2),
                  _ev("first_token", "A", 5),
                  _ev("handoff_export", "A", 6),
                  _ev("handoff_inject", "A", 7),
                  _ev("finished", "A", 20)]
        row = per_request_breakdown(events)["requests"]["A"]
        assert (row["queue"], row["prefill"], row["handoff"],
                row["wire"], row["decode"]) == (2, 3, 1, 1, 13)
        assert sum(row[s] for s in STAGES) == row["total_steps"] == 20

    def test_missing_export_mark_folds_into_wire(self):
        # a legacy recorder stream (no handoff_export event): handoff
        # clamps to zero, wire absorbs the export->inject gap, and the
        # telescoping invariant holds untouched
        events = [_ev("submit", "A", 0), _ev("admit", "A", 2),
                  _ev("first_token", "A", 5),
                  _ev("handoff_inject", "A", 7),
                  _ev("finished", "A", 20)]
        row = per_request_breakdown(events)["requests"]["A"]
        assert (row["handoff"], row["wire"]) == (0, 2)
        assert sum(row[s] for s in STAGES) == row["total_steps"] == 20

    def test_missing_marks_collapse_not_break(self):
        # no admit, no handoff (single-replica request): the stage sums
        # must STILL equal end-to-end steps
        events = [_ev("submit", "B", 1), _ev("first_token", "B", 3),
                  _ev("finished", "B", 9)]
        row = per_request_breakdown(events)["requests"]["B"]
        assert sum(row[s] for s in STAGES) == row["total_steps"] == 8
        assert row["handoff"] == 0

    def test_out_of_order_marks_clamped_monotone(self):
        # an inject mark recorded before first_token (same-step races)
        # must not produce a negative stage
        events = [_ev("submit", "C", 0), _ev("handoff_inject", "C", 2),
                  _ev("first_token", "C", 4), _ev("finished", "C", 6)]
        row = per_request_breakdown(events)["requests"]["C"]
        assert all(row[s] >= 0 for s in STAGES)
        assert sum(row[s] for s in STAGES) == row["total_steps"] == 6

    def test_in_flight_and_shed_requests(self):
        events = [_ev("submit", "D", 0),               # never finished
                  _ev("submit", "E", 0), _ev("shed", "E", 3)]
        out = per_request_breakdown(events)
        assert "D" not in out["requests"]
        assert out["requests"]["E"]["status"] == "shed"
        assert out["requests"]["E"]["total_steps"] == 3

    def test_stage_percentiles_and_rendering(self):
        events = []
        for i, tid in enumerate(("X", "Y", "Z")):
            events += [_ev("submit", tid, 0), _ev("admit", tid, i),
                       _ev("first_token", tid, i + 2),
                       _ev("finished", tid, i + 10)]
        out = per_request_breakdown(events, include_requests=False)
        assert "requests" not in out
        assert out["stages"]["queue"]["count"] == 3
        assert out["stages"]["prefill"]["p50"] == 2
        table = format_waterfall(out)
        assert "queue" in table and "p95" in table
        assert "3 requests completed" in table
        assert "(no completed traced requests)" in format_waterfall(
            {"stages": {}})

    def test_breakdown_from_trace_spans(self):
        def span(name, tid, dur_us, pid=0):
            return {"name": name, "ph": "X", "ts": 0.0, "dur": dur_us,
                    "pid": pid, "tid": 0, "args": {"trace_id": tid}}
        trace = {"traceEvents": [
            span("serving/queue_wait", "A", 1000.0, pid=0),
            span("serving/prefill_chunk", "A", 2000.0, pid=0),
            span("serving/prefill_chunk", "A", 2000.0, pid=0),
            span("serving/handoff_export", "A", 300.0, pid=0),
            span("serving/handoff_inject", "A", 500.0, pid=1),
            span("serving/decode_residency", "A", 4000.0, pid=1),
            span("serving/decode_iter", "A", 9.0, pid=1),  # unstaged
            {"name": "x", "ph": "M", "pid": 0},            # metadata
        ]}
        out = breakdown_from_trace(trace)
        row = out["requests"]["A"]
        assert row["queue"] == pytest.approx(1.0)
        assert row["prefill"] == pytest.approx(4.0)
        assert row["handoff"] == pytest.approx(0.3)
        assert row["wire"] == pytest.approx(0.5)
        assert row["decode"] == pytest.approx(4.0)
        assert row["lanes"] == 2        # crossed a replica boundary
        assert out["unit"] == "ms"


# ---------------------------------------------------------------------------
# Chrome-trace stitching
# ---------------------------------------------------------------------------

class TestStitcher:
    def test_lanes_metadata_and_normalization(self):
        a = [{"name": "s", "ph": "X", "ts": 500.0, "dur": 5.0, "pid": 9,
              "tid": 0, "args": {"trace_id": "T"}}]
        b = {"traceEvents": [{"name": "s2", "ph": "X", "ts": 9000.0,
                              "dur": 2.0, "pid": 4, "tid": 1}]}
        out = stitch_chrome_traces([("prefill", a), ("decode", b)])
        events = out["traceEvents"]
        names = {e["args"]["name"] for e in events
                 if e.get("ph") == "M" and e["name"] == "process_name"}
        assert names == {"prefill", "decode"}
        xs = [e for e in events if e.get("ph") == "X"]
        assert [e["pid"] for e in xs] == [0, 1]     # lanes reassigned
        assert all(e["ts"] == 0.0 for e in xs)      # per-lane rebase
        assert xs[0]["args"]["trace_id"] == "T"     # join key intact
        json.dumps(out)

    def test_no_normalize_keeps_timestamps(self):
        a = [{"name": "s", "ph": "X", "ts": 500.0, "dur": 5.0, "pid": 0,
              "tid": 0}]
        out = stitch_chrome_traces([("only", a)], normalize=False)
        xs = [e for e in out["traceEvents"] if e.get("ph") == "X"]
        assert xs[0]["ts"] == 500.0


# ---------------------------------------------------------------------------
# telemetry aggregation + the hardened scrape client
# ---------------------------------------------------------------------------

class TestMergeNumeric:
    def test_sums_numeric_skips_junk_normalizes_prefix(self):
        merged = merge_numeric({
            0: {"requests": 3, "ds_tpu_requests": 2, "note": "str",
                "flag": True},
            1: {"requests": 4, "nested": {"x": 1}},
            2: None,
        })
        # ds_tpu_ prefix strips onto the same key space; bools and
        # non-numerics never merge
        assert merged == {"requests": 9}

    def test_non_additive_statistics_never_sum(self):
        """Summing two replicas' p50s would fabricate a latency no
        replica ever saw: percentiles/means/rates/capacities stay OUT
        of the merged totals."""
        merged = merge_numeric({
            0: {"ttft_s_p50": 3.0, "latency_s_mean": 1.0,
                "page_utilization": 0.4, "shed_rate": 0.1,
                'lat{quantile="0.5"}': 2.0, "tokens_generated": 5},
            1: {"ttft_s_p50": 5.0, "tokens_generated": 7},
        })
        assert merged == {"tokens_generated": 12}


class TestAggregator:
    def test_direct_sources_merge_and_liveness(self):
        agg = FleetTelemetryAggregator(stale_after_s=60.0)
        agg.add_direct(0, lambda: {"requests_finished": 3, "x": 1.5})
        agg.add_direct(1, lambda: {"requests_finished": 4, "x": 0.5})
        snap = agg.poll()
        assert snap["merged"] == {"requests_finished": 7, "x": 2.0}
        assert all(r["up"] and not r["stale"]
                   for r in snap["replicas"].values())
        gauges = agg.gauges()
        assert gauges["fleet/replica/0/up"] == 1
        assert gauges["fleet/merged/requests_finished"] == 7

    def test_failure_keeps_last_sample_marks_down(self):
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] > 1:
                return None
            return {"requests_finished": 5}
        agg = FleetTelemetryAggregator()
        agg.add_direct(0, flaky)
        agg.poll()
        snap = agg.poll()                      # source went dark
        rep = snap["replicas"]["0"]
        assert rep["up"] is False and rep["scrapes_failed"] == 1
        # the work it served must not vanish from the merged view
        assert snap["merged"] == {"requests_finished": 5}

    def test_mark_dead_stops_polling(self):
        calls = {"n": 0}

        def src():
            calls["n"] += 1
            return {"v": 1}
        agg = FleetTelemetryAggregator()
        agg.add_direct(0, src)
        agg.poll()
        agg.mark_dead(0)
        agg.poll()
        assert calls["n"] == 1
        assert agg.snapshot()["replicas"]["0"]["up"] is False

    def test_scrape_merge_equals_sum_of_per_replica_scrapes(self):
        """THE merged-/metrics acceptance: totals served from the
        aggregated view equal the sum of what each replica's endpoint
        individually scrapes to."""
        def snap_fn(n):
            return lambda: {"registry": {
                "counters": {"serving/requests_finished": n,
                             "serving/tokens_generated": 10 * n},
                "gauges": {"serving/queue_depth": n + 1},
                "histograms": {}}}
        servers = [TelemetryServer(snap_fn(3)).start(),
                   TelemetryServer(snap_fn(4)).start()]
        try:
            agg = FleetTelemetryAggregator()
            per_replica = []
            for rid, srv in enumerate(servers):
                agg.add_scrape(rid, f"http://127.0.0.1:{srv.port}")
                per_replica.append(MetricsScrapeClient(
                    f"http://127.0.0.1:{srv.port}").gauges())
            snap = agg.poll()
            merged = snap["merged"]
            for key in ("serving_requests_finished",
                        "serving_tokens_generated",
                        "serving_queue_depth"):
                expected = sum(s[f"ds_tpu_{key}"] for s in per_replica)
                assert merged[key] == expected, (key, merged)
            assert all(r["up"] and r["last_success_unix"] is not None
                       for r in snap["replicas"].values())
        finally:
            for srv in servers:
                srv.stop()

    def test_dead_endpoint_reads_down_not_crash(self):
        agg = FleetTelemetryAggregator()
        agg.add_scrape(0, "http://127.0.0.1:1",   # nothing listens here
                       timeout_s=0.2)
        snap = agg.poll()
        rep = snap["replicas"]["0"]
        assert rep["up"] is False and rep["stale"] is True
        assert snap["merged"] == {}


class _FlakyHandler(BaseHTTPRequestHandler):
    """Drops the FIRST connection (simulated transient failure), serves
    a one-sample /metrics page afterwards."""
    failures_left = 1

    def log_message(self, fmt, *args):
        pass

    def do_GET(self):
        cls = type(self)
        if cls.failures_left > 0:
            cls.failures_left -= 1
            # close without a response: urllib sees a protocol error
            self.connection.close()
            return
        body = b"ds_tpu_up 1.0\n"
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)


class TestScrapeClientHardening:
    def _serve_flaky(self, failures=1):
        handler = type("H", (_FlakyHandler,), {"failures_left": failures})
        httpd = ThreadingHTTPServer(("127.0.0.1", 0), handler)
        t = threading.Thread(target=httpd.serve_forever, daemon=True)
        t.start()
        return httpd

    def test_one_transient_failure_retried(self):
        httpd = self._serve_flaky(failures=1)
        try:
            client = MetricsScrapeClient(
                f"http://127.0.0.1:{httpd.server_address[1]}",
                timeout_s=2.0)
            assert client.last_success_unix is None
            gauges = client.gauges()            # first try fails, retry
            assert gauges == {"ds_tpu_up": 1.0}
            assert client.last_success_unix is not None
            assert client.staleness_s() >= 0.0
        finally:
            httpd.shutdown()
            httpd.server_close()

    def test_two_failures_degrade_to_none(self):
        httpd = self._serve_flaky(failures=4)
        try:
            client = MetricsScrapeClient(
                f"http://127.0.0.1:{httpd.server_address[1]}",
                timeout_s=2.0)
            assert client.gauges() is None      # try + one retry both die
            assert client.last_success_unix is None
        finally:
            httpd.shutdown()
            httpd.server_close()

    def test_retries_zero_restores_single_shot(self):
        httpd = self._serve_flaky(failures=1)
        try:
            client = MetricsScrapeClient(
                f"http://127.0.0.1:{httpd.server_address[1]}",
                timeout_s=2.0, retries=0)
            assert client.gauges() is None
        finally:
            httpd.shutdown()
            httpd.server_close()


class TestDiffAggregatedSnapshots:
    def test_diff_works_on_two_aggregated_snapshots(self):
        """``ds_tpu_report --diff`` on two fleet ``metrics_snapshot``
        payloads: the aggregator's merged gauges diff before->after and
        registry counters diff as deltas — the fleet section rides
        along without breaking the registry-shaped differ."""
        from deepspeed_tpu.observability.metrics import (
            diff_snapshots, format_snapshot_diff)

        def snap(seq, finished):
            return {"registry": {
                        "meta": {"capture_seq": seq,
                                 "captured_at_unix": 100.0 + seq,
                                 "captured_at_monotonic_s": 10.0 + seq},
                        "counters": {"serving/requests_shed": seq},
                        "gauges": {
                            "fleet/merged/requests_finished": finished,
                            "fleet/replica/0/up": 1},
                        "histograms": {}},
                    "fleet": {"iteration": seq * 4,
                              "replicas": {"0": {"alive": True}}}}
        diff = diff_snapshots(snap(1, 3), snap(2, 9))
        assert diff["counters"]["serving/requests_shed"]["delta"] == 1
        merged = diff["gauges"]["fleet/merged/requests_finished"]
        assert (merged["before"], merged["after"]) == (3, 9)
        text = format_snapshot_diff(diff)
        assert "fleet/merged/requests_finished: 3 -> 9" in text


# ---------------------------------------------------------------------------
# handoff wire format v2 (trace_id travels; v1 still loads)
# ---------------------------------------------------------------------------

def _wire_payload(version=3, with_trace=True):
    request = {"request_id": "r0", "prompt": np.arange(5, dtype=np.int32),
               "generated": [7], "max_new_tokens": 4, "priority": 1}
    if with_trace:
        request["trace_id"] = make_trace_id("r0", 0)
    return {"version": version, "page_len": 16, "kv_quant": None,
            "prefill_len": 5, "n_pages_filled": 1,
            "kv": [{"k": np.ones((1, 2, 2, 16), np.float32),
                    "v": np.zeros((1, 2, 2, 16), np.float32)}],
            "state": {"last_token": 7, "remaining": 3},
            "request": request}


class TestHandoffWireV2:
    def test_roundtrip_carries_trace_id(self):
        from deepspeed_tpu.serving.fleet.handoff import (
            HANDOFF_VERSION, deserialize_handoff, serialize_handoff)
        assert HANDOFF_VERSION == 3   # v3: federation socket blob framing
        payload = _wire_payload()
        out = deserialize_handoff(serialize_handoff(payload))
        assert out["version"] == 3
        assert out["request"]["trace_id"] == payload["request"]["trace_id"]
        np.testing.assert_array_equal(out["kv"][0]["k"],
                                      payload["kv"][0]["k"])

    def test_v2_payload_still_loads(self):
        from deepspeed_tpu.serving.fleet.handoff import (
            deserialize_handoff, serialize_handoff)
        blob = serialize_handoff(_wire_payload(version=2))
        out = deserialize_handoff(blob)
        assert out["version"] == 2
        assert out["request"]["trace_id"] is not None

    def test_v1_payload_still_loads(self):
        from deepspeed_tpu.serving.fleet.handoff import (
            deserialize_handoff, serialize_handoff)
        blob = serialize_handoff(_wire_payload(version=1,
                                               with_trace=False))
        out = deserialize_handoff(blob)
        assert out["version"] == 1
        assert "trace_id" not in out["request"]

    def test_unknown_version_refused_loudly(self):
        from deepspeed_tpu.serving.fleet.handoff import (
            deserialize_handoff, serialize_handoff)
        blob = serialize_handoff(_wire_payload(version=99))
        with pytest.raises(ValueError, match="handoff wire version"):
            deserialize_handoff(blob)


# ---------------------------------------------------------------------------
# engine-level tracing (one small contiguous engine; in-lane)
# ---------------------------------------------------------------------------

def _model(vocab, max_seq_len=64, d_model=32, n_layers=1, n_heads=2):
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt import GPT, GPTConfig
    cfg = GPTConfig(vocab_size=vocab, max_seq_len=max_seq_len,
                    d_model=d_model, n_layers=n_layers, n_heads=n_heads,
                    dtype=jnp.float32)
    m = GPT(cfg)
    params = m.init(jax.random.PRNGKey(0),
                    jnp.ones((1, 8), jnp.int32))["params"]
    return m, params


class TestEngineTracing:
    # tier-1 note (ROADMAP): the in-lane budget is ~zero, so the one
    # jit-compiling engine test here rides the slow lane; the pure-host
    # tests above keep the in-lane coverage of every new mechanism
    @pytest.mark.slow
    def test_trace_ids_spans_and_flight_recorder(self):
        from deepspeed_tpu.observability.trace import (Tracer, activate,
                                                       deactivate)
        from deepspeed_tpu.serving import ServingConfig
        from deepspeed_tpu.serving.engine import ServingEngine
        m, params = _model(vocab=151)
        tracer = Tracer()
        activate(tracer)
        try:
            eng = ServingEngine(m, params, ServingConfig(
                num_slots=2, max_len=64))
            r = np.random.RandomState(0)
            reqs = [eng.submit(r.randint(1, 151, size=6), 3)
                    for _ in range(3)]
            eng.run(max_iterations=200)
        finally:
            deactivate()
        assert all(q.status == "finished" for q in reqs)
        tids = {q.trace_id for q in reqs}
        assert len(tids) == 3 and None not in tids
        by_name = {}
        for name, _t0, _dur, _tid, args in tracer.events:
            if args and args.get("trace_id"):
                by_name.setdefault(name, set()).add(args["trace_id"])
        # the per-request span chain is tagged end to end
        for span in ("serving/queue_wait", "serving/admit",
                     "serving/decode_residency"):
            assert tids <= by_name.get(span, set()), (span, by_name)
        assert by_name.get("serving/harvest")     # first-token harvests
        # flight recorder rode the snapshot with a complete chain
        snap = eng.metrics.snapshot()
        recorder = snap["flight_recorder"]
        kinds = {e["event"] for e in recorder["events"]}
        assert {"submit", "admit", "first_token", "finished"} <= kinds
        # stage sums telescope on the ENGINE clock too
        bd = per_request_breakdown(recorder["events"])
        for q in reqs:
            row = bd["requests"][q.trace_id]
            assert sum(row[s] for s in STAGES) == row["total_steps"] \
                == q.finished_iteration - q.submitted_iteration
        eng.close()

    def test_recorder_disabled_by_config(self):
        from deepspeed_tpu.serving.metrics import ServingMetrics
        metrics = ServingMetrics(registry=False, flight_recorder_events=0)
        from deepspeed_tpu.serving.request import Request
        req = Request(np.arange(3, dtype=np.int32), 2, "x",
                      trace_id="t")
        metrics.on_submit(req)
        assert "flight_recorder" not in metrics.snapshot()


# ---------------------------------------------------------------------------
# fleet integration (slow: engine fleets with jit compiles)
# ---------------------------------------------------------------------------

def _paged_fleet_cfg(fleet, num_slots=2, max_len=128, page_len=16):
    from deepspeed_tpu.serving import PagingConfig, ServingConfig
    return ServingConfig(num_slots=num_slots, max_len=max_len,
                         paging=PagingConfig(page_len=page_len),
                         fleet=fleet)


@pytest.mark.slow
class TestFleetTracingInprocess:
    def test_disaggregated_trace_waterfall_and_aggregation(self):
        import jax.numpy as jnp  # noqa: F401  (jax presence gate)
        from deepspeed_tpu.inference.generation import generate
        from deepspeed_tpu.observability.export import build_statusz
        from deepspeed_tpu.observability.trace import (Tracer, activate,
                                                       deactivate)
        from deepspeed_tpu.serving.fleet.config import FleetConfig
        from deepspeed_tpu.serving.fleet.manager import ServingFleet
        m, params = _model(vocab=157, max_seq_len=128, n_layers=2)
        cfg = _paged_fleet_cfg(FleetConfig(
            replicas=2, disaggregate=True, prefill_replicas=1,
            aggregate_every_steps=2))
        activate(Tracer())
        try:
            fleet = ServingFleet(m, params, cfg)
            r = np.random.RandomState(0)
            prompts = [r.randint(1, 157, size=int(r.randint(5, 30)))
                       for _ in range(4)]
            handles = [fleet.submit(p, max_new_tokens=8)
                       for p in prompts]
            fleet.run(max_iterations=500)
            # token-exact across the handoff, trace identity intact
            for h, p in zip(handles, prompts):
                assert h.status == "finished"
                ref = np.asarray(generate(
                    m, params, np.asarray(p)[None], max_new_tokens=8,
                    temperature=0.0, max_len=128))[0, len(p):]
                np.testing.assert_array_equal(np.asarray(h.tokens), ref)
                assert h.trace_id is not None and h.handoffs == 1
            # THE waterfall acceptance: stage sums == end-to-end steps
            bd = fleet.per_request_breakdown()
            for h in handles:
                row = bd["requests"][h.trace_id]
                assert sum(row[s] for s in STAGES) \
                    == row["total_steps"] \
                    == h.finished_iteration - h.submitted_iteration
                assert row["handoff"] >= 0
            snap = fleet.snapshot()
            kinds = {e["event"]
                     for e in snap["flight_recorder"]["events"]}
            assert {"submit", "admit", "first_token", "handoff_export",
                    "handoff_inject", "finished"} <= kinds
            # handoff events carry the SAME trace_id on both sides
            per_tid = {}
            for ev in snap["flight_recorder"]["events"]:
                if ev["event"].startswith("handoff"):
                    per_tid.setdefault(ev["trace_id"],
                                       set()).add(ev["event"])
            assert all({"handoff_export", "handoff_inject"} <= v
                       for v in per_tid.values())
            # aggregated telemetry merged == sum of per-replica samples
            # (one synchronous poll: the cadenced polls run off-thread)
            fleet._aggregator.poll()
            snap = fleet.snapshot()
            tel = snap["telemetry"]
            # direct samples share the scrape key space (serving_*)
            expected = sum(
                (rep["sample"] or {}).get("serving_requests_finished", 0)
                for rep in tel["replicas"].values())
            assert tel["merged"]["serving_requests_finished"] == expected
            assert expected > 0
            # /statusz carries the fleet section with all three blocks
            statusz = build_statusz(fleet.metrics_snapshot())
            assert statusz["fleet"]["per_request_breakdown"]["stages"]
            assert statusz["fleet"]["telemetry"]["replicas"]
            assert statusz["fleet"]["flight_recorder"]["events"]
            # merged totals ride the router /metrics rendering
            text = render_prometheus(fleet.metrics_snapshot())
            parsed = parse_prometheus(text)
            assert any(k.startswith("ds_tpu_fleet_merged_")
                       for k in parsed)
            assert parsed["ds_tpu_fleet_replica_0_up"] == 1.0
            fleet.close()
        finally:
            deactivate()

    def test_dead_replica_reads_down_in_aggregated_view(self):
        from deepspeed_tpu.serving.fleet.config import FleetConfig
        from deepspeed_tpu.serving.fleet.manager import ServingFleet
        m, params = _model(vocab=163, max_seq_len=128, n_layers=1)
        cfg = _paged_fleet_cfg(FleetConfig(
            replicas=2, aggregate_every_steps=1))
        fleet = ServingFleet(m, params, cfg)
        r = np.random.RandomState(3)
        handles = [fleet.submit(r.randint(1, 163, size=8),
                                max_new_tokens=4) for _ in range(3)]
        for _ in range(2):
            fleet.advance()
        fleet.kill_replica(1)
        fleet.run(max_iterations=300)
        assert all(h.status == "finished" for h in handles)
        fleet._aggregator.poll()     # deterministic final sample
        tel = fleet.snapshot()["telemetry"]
        assert tel["replicas"]["1"]["up"] is False
        assert tel["replicas"]["0"]["up"] is True
        kinds = {e["event"]
                 for e in fleet.recorder.snapshot()["events"]}
        assert "replica_dead" in kinds
        fleet.close()


@pytest.mark.slow
class TestFleetTracingProcessBackend:
    def test_stitched_trace_spans_two_lanes_one_trace_id(self):
        """The PR acceptance: a disaggregated 2-replica PROCESS-backend
        run produces ONE stitched Chrome trace where a single request's
        queue->prefill->handoff->decode spans share a trace_id across
        both replica lanes, stage sums match end-to-end steps, and the
        merged /metrics equals the sum of per-replica scrapes."""
        import dataclasses
        from deepspeed_tpu.serving.fleet.config import FleetConfig
        from deepspeed_tpu.serving.fleet.manager import ServingFleet
        cfg = _paged_fleet_cfg(FleetConfig(
            replicas=2, backend="process", disaggregate=True,
            prefill_replicas=1, replica_trace=True,
            aggregate_every_steps=2))
        spec = {"serving": dataclasses.asdict(
                    dataclasses.replace(cfg, fleet=None)),
                "model": {"vocab_size": 167, "max_seq_len": 128,
                          "d_model": 32, "n_layers": 2, "n_heads": 2,
                          "seed": 0}}
        fleet = ServingFleet(None, None, cfg, spec=spec)
        try:
            r = np.random.RandomState(1)
            prompts = [r.randint(1, 167, size=int(r.randint(5, 30)))
                       for _ in range(3)]
            handles = [fleet.submit(p, max_new_tokens=6)
                       for p in prompts]
            fleet.run(max_iterations=400)
            assert all(h.status == "finished" for h in handles)
            # waterfall telescopes on the fleet clock across processes
            bd = fleet.per_request_breakdown()
            for h in handles:
                row = bd["requests"][h.trace_id]
                assert sum(row[s] for s in STAGES) \
                    == row["total_steps"] \
                    == h.finished_iteration - h.submitted_iteration
            # ONE stitched trace, a lane per replica, trace_id joined
            trace = fleet.stitched_trace()
            lanes = {e["args"]["name"] for e in trace["traceEvents"]
                     if e.get("ph") == "M"
                     and e["name"] == "process_name"}
            assert {"replica0:prefill", "replica1:decode"} <= lanes
            tid = handles[0].trace_id
            spans_by_lane = {}
            for ev in trace["traceEvents"]:
                if ev.get("ph") == "X" \
                        and (ev.get("args") or {}).get("trace_id") == tid:
                    spans_by_lane.setdefault(ev["pid"],
                                             set()).add(ev["name"])
            assert len(spans_by_lane) >= 2, spans_by_lane
            all_spans = set().union(*spans_by_lane.values())
            assert {"serving/queue_wait", "serving/prefill_chunk",
                    "serving/handoff_export", "serving/handoff_inject",
                    "serving/decode_residency"} <= all_spans
            # the trace-file waterfall sees the same request cross lanes
            td = breakdown_from_trace(trace)
            assert td["requests"][tid]["lanes"] >= 2
            # merged /metrics totals == sum of per-replica scrapes
            fleet._aggregator.poll()
            tel = fleet._aggregator.snapshot()
            scraped = []
            for rep in fleet._replicas.values():
                sample = MetricsScrapeClient(
                    f"http://127.0.0.1:{rep.telemetry_port}").gauges()
                scraped.append(sample or {})
            key = "ds_tpu_serving_requests_finished"
            assert tel["merged"]["serving_requests_finished"] \
                == sum(s.get(key, 0) for s in scraped)
        finally:
            fleet.close()


# ---------------------------------------------------------------------------
# lint gate: the new module ships clean (no baseline, no suppressions)
# ---------------------------------------------------------------------------

class TestLintGate:
    def test_fleet_observability_lints_clean(self):
        import os
        from deepspeed_tpu.analysis.cli import main as lint_main
        repo = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        assert lint_main([
            os.path.join(repo, "deepspeed_tpu", "observability",
                         "fleet.py"),
            os.path.join(repo, "deepspeed_tpu", "serving", "fleet"),
        ]) == 0
