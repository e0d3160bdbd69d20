"""Smoke tests for every bin/ CLI entry (reference analogs: bin/deepspeed,
ds_report, ds_elastic, ds_ssh, ds_bench + the checkpoint converter).
Each runs as a real subprocess — catches import breakage, argparse
regressions and sys.path wiring that in-process tests cannot."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BIN = os.path.join(REPO, "bin")


def _run(args, timeout=120, env_extra=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    if env_extra:
        env.update(env_extra)
    return subprocess.run([sys.executable] + args, capture_output=True,
                          text=True, timeout=timeout, env=env)


@pytest.mark.parametrize("script", [
    "ds_tpu", "ds_tpu_bench", "ds_tpu_elastic", "ds_tpu_ssh",
    "ds_tpu_to_universal", "ds_tpu_lint", "ds_tpu_serve", "ds_tpu_chaos",
    "ds_tpu_trace"])
def test_help_exits_zero(script):
    r = _run([os.path.join(BIN, script), "--help"])
    assert r.returncode == 0, r.stderr[-300:]
    assert "usage" in r.stdout.lower()


def test_lint_gate_subprocess():
    """The CI gate invocation, as a real subprocess — with the accelerator
    stack genuinely blocked (an import hook installed before the script
    runs raises on jax/numpy/flax), proving the lint job needs no
    dependency install."""
    script = os.path.join(BIN, "ds_tpu_lint")
    shim = (
        "import sys, importlib.abc, runpy\n"
        "class _B(importlib.abc.MetaPathFinder):\n"
        "    def find_spec(self, fullname, path=None, target=None):\n"
        "        if fullname.split('.')[0] in ('jax', 'jaxlib', 'numpy',\n"
        "                                      'flax', 'optax', 'torch'):\n"
        "            raise ImportError('blocked by test: ' + fullname)\n"
        "sys.meta_path.insert(0, _B())\n"
        f"sys.argv = [{script!r}] + sys.argv[1:]\n"
        f"runpy.run_path({script!r}, run_name='__main__')\n")
    r = _run(["-c", shim,
              os.path.join(REPO, "deepspeed_tpu"),
              "--baseline", os.path.join(REPO, ".ds_tpu_lint_baseline.json"),
              "-q"])
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-500:]
    assert "0 new" in r.stdout


def test_lint_flags_seeded_violation(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import jax\n\ndef f(x):\n"
                   "    return jax.lax.psum(x, 'dataa')\n")
    r = _run([os.path.join(BIN, "ds_tpu_lint"), str(bad)])
    assert r.returncode == 1
    assert "SC001" in r.stdout


def test_report_runs():
    # ds_tpu_report has no flags: it prints the env + op matrix directly
    r = _run([os.path.join(BIN, "ds_tpu_report")], timeout=300)
    assert r.returncode == 0, r.stderr[-300:]
    assert "environment info" in r.stdout


def test_elastic_resolves_config(tmp_path):
    cfg = {"elasticity": {"enabled": True, "max_train_batch_size": 1024,
                          "micro_batch_sizes": [2, 4], "min_gpus": 1,
                          "max_gpus": 8, "min_time": 0,
                          "prefer_larger_batch": True, "version": 0.1}}
    p = tmp_path / "ds.json"
    p.write_text(json.dumps(cfg))
    r = _run([os.path.join(BIN, "ds_tpu_elastic"), "-c", str(p),
              "-w", "4"])
    assert r.returncode == 0, r.stderr[-300:]
    assert "batch" in r.stdout.lower()


def test_to_universal_rejects_bad_mesh(tmp_path):
    r = _run([os.path.join(BIN, "ds_tpu_to_universal"), str(tmp_path),
              str(tmp_path / "out"), "--target-mesh", "bogus=2"])
    assert r.returncode != 0
    assert "axis" in r.stderr


def test_serve_synthetic_demo(tmp_path):
    """End-to-end serving CLI: tiny synthetic workload, metrics JSON."""
    out = tmp_path / "metrics.json"
    r = _run([os.path.join(BIN, "ds_tpu_serve"), "--synthetic", "3",
              "--num-slots", "2", "--max-len", "48",
              "--max-new-tokens", "3", "--d-model", "32",
              "--n-layers", "1", "--vocab-size", "64", "--quiet",
              "--metrics-out", str(out)], timeout=300)
    assert r.returncode == 0, r.stderr[-800:]
    snap = json.loads(out.read_text())
    assert snap["requests_finished"] == 3
    assert snap["tokens_generated"] >= 3


@pytest.mark.slow
def test_serve_metrics_port_endpoint(tmp_path):
    """--metrics-port: the serving CLI announces its live telemetry
    endpoint and still completes the workload (the endpoint itself is
    scraped in-process by test_telemetry.py — a subprocess race against
    a 3-request run would flake)."""
    r = _run([os.path.join(BIN, "ds_tpu_serve"), "--synthetic", "3",
              "--num-slots", "2", "--max-len", "48",
              "--max-new-tokens", "3", "--d-model", "32",
              "--n-layers", "1", "--vocab-size", "64", "--quiet",
              "--metrics-port", "0"], timeout=300)
    assert r.returncode == 0, r.stderr[-800:]
    telemetry = [l for l in r.stdout.splitlines()
                 if l.startswith("# telemetry: http://127.0.0.1:")]
    assert telemetry, r.stdout[-800:]
    assert telemetry[0].endswith("/metrics")


@pytest.mark.slow
def test_serve_qos_smoke(tmp_path):
    """A qos-enabled serve run completes and announces the shed/preempt
    counters plus the per-class breakdown on stdout (the operator-facing
    QoS summary line), with the per-class keys in the metrics JSON."""
    out = tmp_path / "metrics.json"
    r = _run([os.path.join(BIN, "ds_tpu_serve"), "--synthetic", "5",
              "--qos", "--num-slots", "2", "--max-len", "48",
              "--max-new-tokens", "3",
              "--d-model", "32", "--n-layers", "1", "--vocab-size", "64",
              "--quiet", "--metrics-out", str(out)], timeout=300)
    assert r.returncode == 0, r.stderr[-800:]
    qos_lines = [l for l in r.stdout.splitlines() if l.startswith("qos:")]
    assert qos_lines, r.stdout[-800:]
    assert "shed=" in qos_lines[0] and "preempted=" in qos_lines[0]
    snap = json.loads(out.read_text())
    assert "requests_shed" in snap and "requests_preempted" in snap
    assert any(k.startswith("class/") for k in snap)


@pytest.mark.slow
def test_serve_crash_leaves_partial_snapshot_and_exits_nonzero(tmp_path):
    """The fault-containment satellite: a serving loop that dies mid-run
    (chaos hook --inject-crash-at) exits NONZERO and still leaves the
    partial metrics snapshot — stdout JSON + the sidecar file (a crash
    used to leave nothing)."""
    out = tmp_path / "metrics.json"
    r = _run([os.path.join(BIN, "ds_tpu_serve"), "--synthetic", "4",
              "--num-slots", "2", "--max-len", "48",
              "--max-new-tokens", "4", "--d-model", "32",
              "--n-layers", "1", "--vocab-size", "64", "--quiet",
              "--inject-crash-at", "2", "--metrics-out", str(out)],
             timeout=300)
    assert r.returncode != 0
    artifact = json.loads(out.read_text())
    assert artifact["failed"] is True
    assert "injected crash" in artifact["reason"]
    # whatever the engine accumulated before dying rode along
    assert artifact["serving"].get("requests_submitted") == 4


FLEET_ARGS = ["--num-slots", "2", "--max-len", "48",
              "--max-new-tokens", "3", "--d-model", "32",
              "--n-layers", "1", "--vocab-size", "64",
              "--page-len", "16", "--quiet"]


@pytest.mark.slow
def test_serve_fleet_summary_line(tmp_path):
    """--replicas 2: the fleet serve path completes the workload and
    prints the stable ``fleet:`` exit summary (replica/finished/router/
    handoff/failover counters) plus the fleet snapshot JSON."""
    out = tmp_path / "fleet.json"
    r = _run([os.path.join(BIN, "ds_tpu_serve"), "--synthetic", "4",
              "--replicas", "2", *FLEET_ARGS, "--metrics-out", str(out)],
             timeout=300)
    assert r.returncode == 0, r.stderr[-800:]
    fleet_lines = [l for l in r.stdout.splitlines()
                   if l.startswith("fleet: ")]
    assert fleet_lines, r.stdout[-800:]
    assert "2 replicas (2 alive), 4/4 finished" in fleet_lines[0]
    assert "router=prefix_affinity" in fleet_lines[0]
    snap = json.loads(out.read_text())
    assert snap["requests_finished"] == 4
    assert set(snap["replicas"]) == {"0", "1"}


@pytest.mark.slow
def test_serve_fleet_replica_crash_unsupervised_partial_snapshot(tmp_path):
    """With --no-supervise an injected in-process replica crash is
    fatal (the pre-supervision contract): nonzero exit AND the partial
    fleet snapshot — stdout JSON + sidecar — recording which replica
    died."""
    out = tmp_path / "fleet.json"
    r = _run([os.path.join(BIN, "ds_tpu_serve"), "--synthetic", "4",
              "--replicas", "2", *FLEET_ARGS, "--no-supervise",
              "--inject-replica-crash-at", "1",
              "--metrics-out", str(out)], timeout=300)
    assert r.returncode != 0
    artifact = json.loads(out.read_text())
    assert artifact["failed"] is True
    assert "crashed at iteration" in artifact["reason"]
    assert artifact["serving"]["replicas"]["1"]["alive"] is False


@pytest.mark.slow
def test_serve_fleet_replica_crash_supervised_recovers(tmp_path):
    """Default (supervised) semantics: the SAME injected crash is
    contained — failover finishes the workload, exit 0, and the
    summary/snapshot record the death (and the restart when the
    backoff elapses before the run drains)."""
    out = tmp_path / "fleet.json"
    r = _run([os.path.join(BIN, "ds_tpu_serve"), "--synthetic", "4",
              "--replicas", "2", *FLEET_ARGS,
              "--inject-replica-crash-at", "1",
              "--metrics-out", str(out)], timeout=300)
    assert r.returncode == 0, r.stderr[-800:]
    fleet_lines = [l for l in r.stdout.splitlines()
                   if l.startswith("fleet: ")]
    assert fleet_lines and "4/4 finished" in fleet_lines[0]
    assert "dead=1" in fleet_lines[0]
    snap = json.loads(out.read_text())
    assert snap["requests_finished"] == 4
    assert snap["dead_replicas"] == 1


@pytest.mark.slow
def test_chaos_fleet_scenario_pack():
    """The seeded fleet chaos pack (worker kill, crash loop, prefill
    wipe, truncated handoff, hung worker, partitioned federation
    network) recovers end to end: exit 0 and every sub-scenario
    reports ok."""
    r = _run([os.path.join(BIN, "ds_tpu_chaos"), "--scenario", "fleet"],
             timeout=570)
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-800:])
    assert "[chaos] all scenarios recovered" in r.stdout
    for sub in ("crash_loop", "prefill_wipe", "truncated_handoff",
                "worker_kill", "hung_worker", "partitioned_network"):
        assert f"fleet/{sub}: RECOVERED" in r.stdout


@pytest.mark.slow
def test_serve_fleet_kill_replica_failover(tmp_path):
    """The contained-death path: a DETECTED replica kill mid-run fails
    its requests over — everything still finishes, exit 0, the summary
    line records the death."""
    out = tmp_path / "fleet.json"
    r = _run([os.path.join(BIN, "ds_tpu_serve"), "--synthetic", "4",
              "--replicas", "2", *FLEET_ARGS, "--kill-replica-at", "1",
              "--metrics-out", str(out)], timeout=300)
    assert r.returncode == 0, r.stderr[-800:]
    fleet_lines = [l for l in r.stdout.splitlines()
                   if l.startswith("fleet: ")]
    assert fleet_lines and "4/4 finished" in fleet_lines[0]
    assert "dead=1" in fleet_lines[0]


def test_report_diff_two_snapshots(tmp_path):
    """ds_tpu_report --diff: counters as deltas, gauges before->after,
    ordered by the meta capture stamps (stdlib path, no jax needed)."""
    a = {"registry": {
        "meta": {"capture_seq": 1, "captured_at_monotonic_s": 10.0},
        "counters": {"serving/requests": 3}, "gauges": {"depth": 1},
        "histograms": {}}}
    b = {"registry": {
        "meta": {"capture_seq": 2, "captured_at_monotonic_s": 12.5},
        "counters": {"serving/requests": 8}, "gauges": {"depth": 4},
        "histograms": {}}}
    pa, pb = tmp_path / "a.json", tmp_path / "b.json"
    pa.write_text(json.dumps(a))
    pb.write_text(json.dumps(b))
    r = _run([os.path.join(BIN, "ds_tpu_report"), "--diff", str(pa),
              str(pb)])
    assert r.returncode == 0, r.stderr[-500:]
    assert "serving/requests: +5" in r.stdout
    assert "depth: 1 -> 4" in r.stdout
    assert "over 2.500s" in r.stdout
    # missing file is a readable exit 2, not a traceback
    r2 = _run([os.path.join(BIN, "ds_tpu_report"), "--diff", str(pa),
               str(tmp_path / "missing.json")])
    assert r2.returncode == 2
    assert "no such snapshot" in r2.stderr


def test_report_fleet_snapshot_and_trace(tmp_path):
    """ds_tpu_report --fleet: renders per-replica health + aggregated
    totals + the per-request waterfall from a fleet snapshot, and the
    wall-ms waterfall from a stitched trace (stdlib path, no jax)."""
    snap = {"iteration": 12, "backend": "inprocess",
            "replicas": {"0": {"role": "full", "alive": True,
                               "queue_depth": 0, "active_slots": 1,
                               "num_slots": 2}},
            "router": {"policy": "prefix_affinity"},
            "handoffs_completed": 1, "failovers": 0, "dead_replicas": 0,
            "requests_submitted": 2, "requests_finished": 2,
            "telemetry": {"replicas": {"0": {"up": True,
                                             "staleness_s": 0.5}},
                          "merged": {"requests_finished": 2}},
            "flight_recorder": {"dropped": 0, "events": [
                {"event": "submit", "request_id": "r", "trace_id": "t",
                 "iteration": 0, "replica_id": 0},
                {"event": "admit", "request_id": "r", "trace_id": "t",
                 "iteration": 1, "replica_id": 0},
                {"event": "first_token", "request_id": "r",
                 "trace_id": "t", "iteration": 3, "replica_id": 0},
                {"event": "finished", "request_id": "r", "trace_id": "t",
                 "iteration": 9, "replica_id": 0}]}}
    path = tmp_path / "fleet.json"
    path.write_text(json.dumps(snap))
    r = _run([os.path.join(BIN, "ds_tpu_report"), "--fleet", str(path)])
    assert r.returncode == 0, r.stderr[-500:]
    assert "replica 0 [full]" in r.stdout and "up" in r.stdout
    assert "requests_finished: 2" in r.stdout
    assert "per-request waterfall (fleet steps)" in r.stdout
    assert "queue" in r.stdout and "decode" in r.stdout
    assert "flight recorder" in r.stdout
    # stitched-trace form: the wall-ms waterfall
    trace = {"traceEvents": [
        {"name": "process_name", "ph": "M", "pid": 0,
         "args": {"name": "replica0:prefill"}},
        {"name": "serving/queue_wait", "ph": "X", "ts": 0.0,
         "dur": 1500.0, "pid": 0, "tid": 0, "args": {"trace_id": "t"}},
        {"name": "serving/decode_residency", "ph": "X", "ts": 0.0,
         "dur": 4000.0, "pid": 1, "tid": 0, "args": {"trace_id": "t"}}]}
    tpath = tmp_path / "trace.json"
    tpath.write_text(json.dumps(trace))
    r2 = _run([os.path.join(BIN, "ds_tpu_report"), "--fleet",
               str(tpath)])
    assert r2.returncode == 0, r2.stderr[-500:]
    assert "wall ms" in r2.stdout and "replica0:prefill" in r2.stdout
    # missing file: readable exit 2, not a traceback
    r3 = _run([os.path.join(BIN, "ds_tpu_report"), "--fleet",
               str(tmp_path / "nope.json")])
    assert r3.returncode == 2 and "no such fleet artifact" in r3.stderr


@pytest.mark.slow
def test_serve_fleet_trace_out_stitched(tmp_path):
    """--trace-out on a disaggregated fleet run writes ONE stitched
    Chrome trace and prints the waterfall in the exit summary."""
    out = tmp_path / "fleet_trace.json"
    r = _run([os.path.join(BIN, "ds_tpu_serve"), "--synthetic", "3",
              "--replicas", "2", "--disaggregate", *FLEET_ARGS,
              "--trace-out", str(out)], timeout=300)
    assert r.returncode == 0, r.stderr[-800:]
    assert "per-request waterfall (fleet steps)" in r.stdout
    assert "# stitched fleet trace:" in r.stdout
    trace = json.loads(out.read_text())
    names = {e["name"] for e in trace["traceEvents"]
             if e.get("ph") == "X"}
    assert "serving/handoff_inject" in names
    tagged = [e for e in trace["traceEvents"]
              if e.get("ph") == "X"
              and (e.get("args") or {}).get("trace_id")]
    assert tagged, "spans must carry trace ids"


@pytest.mark.slow
def test_chaos_smoke_torn_scenario(tmp_path):
    """Fast chaos smoke (tier-1): the torn-save scenario must recover —
    the CLI exits 0 only when the fallback restored a verified tag —
    and the report JSON records the recovery evidence."""
    out = tmp_path / "chaos.json"
    r = _run([os.path.join(BIN, "ds_tpu_chaos"), "--scenario", "torn",
              "--seed", "0", "--json-out", str(out)], timeout=300)
    assert r.returncode == 0, r.stdout[-1200:] + r.stderr[-800:]
    report = json.loads(out.read_text())["scenarios"]["torn"]
    assert report["ok"] and report["torn_detected"]
    assert report["fallback_path"].endswith("good")


@pytest.mark.slow
def test_bench_serving_writes_artifact(tmp_path):
    """`ds_tpu_bench serving` replays the seeded trace and writes the
    BENCH_serving JSON artifact."""
    out = tmp_path / "BENCH_serving.json"
    r = _run([os.path.join(BIN, "ds_tpu_bench"), "serving",
              "--num-requests", "4", "--num-slots", "2", "--max-len", "48",
              "--min-prompt", "3", "--max-prompt",
              "8", "--min-output", "2", "--max-output", "3", "--d-model",
              "32", "--n-layers", "1", "--vocab-size", "64",
              "--out", str(out)], timeout=300)
    assert r.returncode == 0, r.stderr[-800:]
    assert "BENCH_serving" in r.stdout
    art = json.loads(out.read_text())
    assert art["bench"] == "serving"
    assert art["aggregate"]["requests_finished"] == 4
    assert len(art["per_request"]) == 4
    assert all(p["ttft_steps"] is not None for p in art["per_request"])


@pytest.mark.slow
def test_bench_serving_paged_prefix_adversarial(tmp_path):
    """`ds_tpu_bench serving --scenario prefix-adversarial`: the
    engine serves the shared-prefix + long-prompt trace and the
    artifact embeds the paging accounting block (page utilization,
    prefix hit rate, TTFT-under-load, density vs full-length rows)."""
    out = tmp_path / "BENCH_serving.json"
    r = _run([os.path.join(BIN, "ds_tpu_bench"), "serving",
              "--page-len", "16", "--prefill-chunk", "16",
              "--scenario", "prefix-adversarial",
              "--shared-prefix-len", "32", "--long-prompt-len", "64",
              "--num-requests", "8", "--num-slots", "3", "--max-len", "96",
              "--min-prompt", "3", "--max-prompt",
              "8", "--min-output", "2", "--max-output", "4", "--d-model",
              "32", "--n-layers", "1", "--vocab-size", "64",
              "--out", str(out)], timeout=300)
    assert r.returncode == 0, r.stderr[-800:]
    art = json.loads(out.read_text())
    assert art["aggregate"]["requests_finished"] == 8
    assert art["config"]["paging"]["page_len"] == 16
    assert art["trace"]["scenario"] == "prefix-adversarial"
    pg = art["paging"]
    for key in ("page_utilization", "prefix_hit_rate", "pool_bytes",
                "contiguous_bytes_equivalent", "concurrent_requests_peak",
                "density_gain_vs_full_rows",
                "prefill_recompute_skipped_frac"):
        assert key in pg, key
    assert pg["prefix_hits"] >= 1              # the shared prefix got reused
    kinds = {p["kind"] for p in art["per_request"]}
    assert "shared_prefix" in kinds and "long" in kinds
    # the memory block rides next to perf, and the gather-transient
    # figure is the accountant-derived one (same value both places)
    mem = art["memory"]
    assert mem["kv_pool_resident_bytes"] > 0
    assert mem["decode_gather_transient_bytes"] \
        == pg["decode_gather_transient_bytes"] > 0
    assert "serving/kv_pool" in mem["by_subsystem"]


@pytest.mark.slow
def test_trace_windowed_capture(tmp_path):
    """`ds_tpu_trace` runs a short training loop and writes a valid
    Chrome-trace JSON (windowed capture) + the metrics snapshot."""
    trace = tmp_path / "trace.json"
    metrics = tmp_path / "metrics.json"
    r = _run([os.path.join(BIN, "ds_tpu_trace"), "--steps", "6",
              "--start-step", "2", "--window", "3", "--probe-interval", "2",
              "--batch-size", "4", "--seq-len", "16", "--vocab-size", "64",
              "--d-model", "32", "--n-layers", "1", "--quiet",
              "--out", str(trace), "--metrics-out", str(metrics),
              "--cpu", "1"], timeout=300)
    assert r.returncode == 0, r.stdout[-800:] + r.stderr[-800:]
    payload = json.loads(trace.read_text())
    names = [e["name"] for e in payload["traceEvents"]]
    # 3-step window, split convention: each captured step records the
    # iteration phases as complete ("X") events
    for phase in ("train_iteration", "data", "fwd", "bwd", "step"):
        assert names.count(phase) == 3, (phase, names)
    assert all(e["ph"] == "X" and "ts" in e and "dur" in e
               for e in payload["traceEvents"])
    snap = json.loads(metrics.read_text())
    assert "train/tokens_per_sec" in snap["registry"]["gauges"]
    assert snap["perf"]["steps_measured"] >= 1
    assert "trace_summary" in snap
    # the metrics snapshot embeds the memory + program blocks and the
    # diffable capture stamp (ISSUE 7 satellites)
    assert snap["registry"]["meta"]["capture_seq"] >= 1
    assert snap["memory"]["by_subsystem"]["train/params"]["bytes"] > 0
    # split mode drives the parity-path programs
    assert snap["programs"]["train/fwd_grads"]["compiles"] == 1


@pytest.mark.slow
def test_trace_memory_sections(tmp_path):
    """`ds_tpu_trace --memory` prints the ds_tpu_mem attribution +
    compiled-program tables with per-program XLA analysis."""
    r = _run([os.path.join(BIN, "ds_tpu_trace"), "--steps", "4",
              "--mode", "fused",
              "--batch-size", "4", "--seq-len", "16", "--vocab-size", "64",
              "--d-model", "32", "--n-layers", "1", "--quiet", "--memory",
              "--out", str(tmp_path / "trace.json"), "--cpu", "1"],
             timeout=300)
    assert r.returncode == 0, r.stdout[-800:] + r.stderr[-800:]
    assert "ds_tpu_mem: memory attribution" in r.stdout
    assert "train/params" in r.stdout
    assert "ds_tpu_mem: compiled programs" in r.stdout
    assert "train/train_step" in r.stdout


@pytest.mark.slow
def test_bench_trace_attaches_capture(tmp_path):
    """`ds_tpu_bench serving --trace` attaches the span capture to the
    bench run and dumps serving-phase spans as Chrome-trace JSON."""
    trace = tmp_path / "bench_trace.json"
    out = tmp_path / "BENCH_serving.json"
    r = _run([os.path.join(BIN, "ds_tpu_bench"), "serving",
              "--trace", str(trace),
              "--num-requests", "3", "--num-slots", "2", "--max-len", "48",
              "--min-prompt", "3", "--max-prompt",
              "8", "--min-output", "2", "--max-output", "3", "--d-model",
              "32", "--n-layers", "1", "--vocab-size", "64",
              "--out", str(out)], timeout=300)
    assert r.returncode == 0, r.stderr[-800:]
    names = {e["name"]
             for e in json.loads(trace.read_text())["traceEvents"]}
    assert {"serving/prefill_chunk", "serving/decode_iter",
            "serving/harvest"} <= names, names
    # the artifact also embeds the static-estimator perf block
    perf = json.loads(out.read_text())["perf"]
    assert perf["n_params"] > 0 and perf["flops_per_token_fwd"] > 0


def test_launcher_single_host_exec(tmp_path):
    script = tmp_path / "hello.py"
    script.write_text("print('LAUNCHED_OK')\n")
    r = _run([os.path.join(BIN, "ds_tpu"), str(script)])
    assert r.returncode == 0, r.stderr[-300:]
    assert "LAUNCHED_OK" in r.stdout
