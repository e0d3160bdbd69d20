"""Federation observability (PR 20): the wire accountant's exact byte
reconciliation at the FrameConnection seams, the SLO watch's fire/clear
hysteresis and bit-exact replay, and the slow fleet-level scenarios —
a socket-only 2-"host" fleet producing ONE stitched trace with a
telescoping wire stage, and a chaos-induced corrupt-handoff SLO breach
that fires exactly one incident and clears after recovery.

Wire-accountant and SLO units are stdlib-only (no jax, no engines);
the fleet scenarios build real engines and are marked slow.
"""

import json
import socket

import numpy as np
import pytest

from deepspeed_tpu.observability.metrics import get_registry
from deepspeed_tpu.observability.slo import (SloConfig, SloWatch,
                                             rules_from_config)
from deepspeed_tpu.serving.fleet.config import FleetConfig
from deepspeed_tpu.serving.fleet.federation.frames import (KIND_BLOB,
                                                           FrameError,
                                                           encode_frame)
from deepspeed_tpu.serving.fleet.federation.transport import FrameConnection


# ---------------------------------------------------------------------------
# wire accountant: byte-exact reconciliation at the FrameConnection seams
# ---------------------------------------------------------------------------

def _pair(peer_a=None, peer_b=None):
    sa, sb = socket.socketpair()
    ca, cb = FrameConnection(sa), FrameConnection(sb)
    ca.peer, cb.peer = peer_a, peer_b
    return ca, cb


class TestWireAccountant:
    def test_byte_reconciliation_exact(self):
        """tx/rx byte counters reconcile EXACTLY with encode_frame
        output sizes, per kind, on both ends of the wire — the
        accountant never estimates."""
        reg = get_registry()
        ca, cb = _pair("wa_tx_end", "wa_rx_end")
        try:
            ca.negotiate(2)                  # DSF2 (crc32) on the wire
            blob = bytes(range(256)) * 4
            expect_json = expect_blob = blobs = 0
            for i in range(5):
                msg = {"op": "noise", "i": i}
                head = dict(msg)
                with_blob = i % 2 == 0
                if with_blob:
                    head["_blob"] = True
                    expect_blob += len(encode_frame(blob, KIND_BLOB,
                                                    rev=2))
                    blobs += 1
                expect_json += len(encode_frame(
                    json.dumps(head, default=float).encode("utf-8"),
                    rev=2))
                ca.send_msg(msg, blob=blob if with_blob else None)
                got, got_blob = cb.recv_msg(timeout_s=5.0)
                assert got == msg
                assert got_blob == (blob if with_blob else None)
            for peer, family in (("wa_tx_end", "tx"),
                                 ("wa_rx_end", "rx")):
                assert reg.counter(
                    f"wire/{family}_frames/json/{peer}").value == 5
                assert reg.counter(
                    f"wire/{family}_bytes/json/{peer}").value \
                    == expect_json
                assert reg.counter(
                    f"wire/{family}_frames/blob/{peer}").value == blobs
                assert reg.counter(
                    f"wire/{family}_bytes/blob/{peer}").value \
                    == expect_blob
        finally:
            ca.close()
            cb.close()

    def test_corrupt_frame_is_fault_not_rx_bytes(self):
        """A crc-failing frame lands in wire/faults, never in the rx
        byte tally — clean-traffic reconciliation stays exact across
        the damage."""
        sa, sb = socket.socketpair()
        cb = FrameConnection(sb)
        cb.peer = "wa_corrupt_end"
        try:
            bad = bytearray(encode_frame(b'{"op": "x"}', rev=2))
            bad[-1] ^= 0xFF          # flip one payload bit: crc catches
            clean = encode_frame(json.dumps({"op": "y"}).encode("utf-8"),
                                 rev=2)
            sa.sendall(bytes(bad) + clean)
            with pytest.raises(FrameError) as ei:
                cb.recv_msg(timeout_s=5.0)
            assert ei.value.kind == "corrupt"
            msg, got_blob = cb.recv_msg(timeout_s=5.0)
            assert msg == {"op": "y"} and got_blob is None
            reg = get_registry()
            assert reg.counter(
                "wire/faults/corrupt/wa_corrupt_end").value == 1
            assert reg.counter(
                "wire/rx_frames/json/wa_corrupt_end").value == 1
            assert reg.counter(
                "wire/rx_bytes/json/wa_corrupt_end").value == len(clean)
        finally:
            sa.close()
            cb.close()

    def test_unaccounted_connection_stays_silent(self):
        """peer=None (codec tests, pre-handshake dials) must not mint
        any wire/ series."""
        before = set(get_registry()._counters)
        ca, cb = _pair()                       # both peers unset
        try:
            ca.send_msg({"op": "quiet"})
            msg, _ = cb.recv_msg(timeout_s=5.0)
            assert msg == {"op": "quiet"}
        finally:
            ca.close()
            cb.close()
        fresh = set(get_registry()._counters) - before
        assert not {n for n in fresh if n.startswith("wire/")}


# ---------------------------------------------------------------------------
# SLO watch: hysteresis, config plumbing, bit-exact replay
# ---------------------------------------------------------------------------

class TestSloWatch:
    def _watch(self, **kw):
        kw.setdefault("enabled", True)
        kw.setdefault("shed_rate", 0.25)
        kw.setdefault("replica_up_fraction", 0.0)   # only shed armed
        kw.setdefault("fire_streak", 3)
        kw.setdefault("clear_streak", 2)
        return SloWatch.from_config(SloConfig(**kw))

    def test_flapping_never_fires(self):
        w = self._watch()
        for step in range(20):
            sample = {"shed_rate": 0.9 if step % 2 == 0 else 0.0}
            assert w.evaluate(sample, step) == []
        assert w.incidents_opened == 0 and not w.open_incidents

    def test_fire_once_then_clear(self):
        w = self._watch()
        breaches_before = get_registry().counter("slo/breaches").value
        trans = []
        for step in range(6):            # 6 consecutive breaches
            trans += w.evaluate({"shed_rate": 0.9}, step)
        # fires EXACTLY once, on the fire_streak'th breach, and holds
        assert [t["event"] for t in trans] == ["incident_open"]
        assert trans[0]["rule"] == "shed_rate" and trans[0]["step"] == 2
        assert get_registry().counter("slo/breaches").value \
            == breaches_before + 1
        assert get_registry().gauge("slo/incidents_open").value == 1
        # one clean tick is not enough to clear (clear_streak=2)
        assert w.evaluate({"shed_rate": 0.0}, 6) == []
        assert w.open_incidents
        cleared = w.evaluate({"shed_rate": 0.0}, 7)
        assert [t["event"] for t in cleared] == ["incident_clear"]
        assert cleared[0]["opened_step"] == 2
        assert cleared[0]["duration_steps"] == 5
        assert not w.open_incidents
        assert get_registry().gauge("slo/incidents_open").value == 0
        snap = w.snapshot()
        assert snap["incidents_opened"] == 1
        assert snap["incidents_cleared"] == 1
        assert [e["event"] for e in snap["incident_log"]["events"]] \
            == ["incident_open", "incident_clear"]

    def test_missing_key_and_below_direction(self):
        w = SloWatch.from_config(SloConfig(
            enabled=True, shed_rate=0.0, replica_up_fraction=0.5,
            fire_streak=1, clear_streak=1))
        assert [r.name for r in w.rules] == ["replica_up_fraction"]
        assert w.evaluate({}, 0) == []          # absent sample is ok
        recs = w.evaluate({"replica_up_fraction": 0.25}, 1)
        assert recs and recs[0]["rule"] == "replica_up_fraction"
        assert recs[0]["direction"] == "below"

    def test_zero_threshold_disables_rule(self):
        assert rules_from_config(SloConfig(
            shed_rate=0.0, replica_up_fraction=0.0)) == []

    def test_config_validation_names_the_knob(self):
        with pytest.raises(ValueError,
                           match="serving.fleet.slo.fire_streak"):
            SloConfig(fire_streak=0).validate()
        with pytest.raises(ValueError,
                           match="serving.fleet.slo.shed_rate"):
            SloConfig(shed_rate=1.5).validate()

    def test_fleet_config_lifts_slo_dict(self):
        fcfg = FleetConfig(replicas=1,
                           slo={"enabled": True, "shed_rate": 0.1})
        assert isinstance(fcfg.slo, SloConfig)
        assert fcfg.slo.enabled and fcfg.slo.shed_rate == 0.1
        with pytest.raises(ValueError, match="serving.fleet.slo"):
            FleetConfig(replicas=1, slo={"fire_streak": 0}).validate()

    def test_replay_bit_identical(self):
        """The determinism contract: the same sample sequence replays
        to a bit-identical snapshot — no wall clock anywhere in the
        evaluation or the incident records."""
        cfg = SloConfig(enabled=True, shed_rate=0.2,
                        replica_up_fraction=0.5, wire_rtt_p95_ms=50.0,
                        fire_streak=2, clear_streak=2)
        rng = np.random.RandomState(33)
        samples = [{"shed_rate": float(rng.rand() * 0.5),
                    "replica_up_fraction": float(rng.choice([0.25, 1.0])),
                    "wire_rtt_p95_ms": float(rng.rand() * 100.0)}
                   for _ in range(40)]
        snaps = []
        for _ in range(2):
            w = SloWatch.from_config(cfg)
            for step, s in enumerate(samples):
                w.evaluate(s, step)
            snaps.append(w.snapshot())
        assert snaps[0] == snaps[1]
        json.dumps(snaps[0])                   # JSON-able contract
        assert snaps[0]["evaluations"] == 40
        assert snaps[0]["incidents_opened"] >= 1   # the seed breaches


# ---------------------------------------------------------------------------
# fleet scenarios (slow: engine fleets, federation worker subprocesses)
# ---------------------------------------------------------------------------

def _paged_fleet_cfg(fleet, num_slots=2, max_len=128, page_len=16):
    from deepspeed_tpu.serving import PagingConfig, ServingConfig
    return ServingConfig(num_slots=num_slots, max_len=max_len,
                         paging=PagingConfig(page_len=page_len),
                         fleet=fleet)


def _model(vocab, max_seq_len=128, d_model=32, n_layers=2, n_heads=2):
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


def _start_worker(port=0):
    import subprocess
    import sys
    from deepspeed_tpu.serving.fleet.federation.worker import READY_BANNER
    proc = subprocess.Popen(
        [sys.executable, "-m",
         "deepspeed_tpu.serving.fleet.federation.worker",
         "--listen", f"127.0.0.1:{port}"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    while True:
        line = proc.stdout.readline()
        if not line:
            raise RuntimeError("federation worker died before its banner")
        if READY_BANNER in line:
            return proc, line.split(READY_BANNER, 1)[1].strip()


@pytest.mark.slow
class TestFederatedObservabilityEndToEnd:
    def test_socket_fleet_stitched_trace_wire_stage_and_metrics(self):
        """The PR acceptance scenario: a socket-only 2-'host'
        disaggregated fleet produces ONE stitched Chrome trace where
        the remote replicas' own span lanes (pulled over the wire via
        trace_dump frames) join the router lane by trace_id, the
        waterfall telescopes with the wire stage included, and the
        /metrics registry carries per-peer wire counters and RTT
        histograms for both peers."""
        import dataclasses
        from deepspeed_tpu.observability.fleet import STAGES
        from deepspeed_tpu.serving.fleet.manager import ServingFleet
        model_spec = {"vocab_size": 1619, "max_seq_len": 128,
                      "d_model": 32, "n_layers": 2, "n_heads": 2,
                      "seed": 0}
        p0, addr0 = _start_worker()
        p1, addr1 = _start_worker()
        fleet = None
        try:
            fcfg = FleetConfig(
                replicas=2, disaggregate=True, prefill_replicas=1,
                replica_trace=True, aggregate_every_steps=4,
                federation={"peers": [addr0, addr1]},
                slo={"enabled": True, "corrupt_handoff_rate": 0.3,
                     "shed_rate": 0.0, "replica_up_fraction": 0.0})
            cfg = _paged_fleet_cfg(fcfg)
            spec = {"serving": dataclasses.asdict(
                        dataclasses.replace(cfg, fleet=None)),
                    "model": model_spec}
            fleet = ServingFleet(None, None, cfg, spec=spec)
            assert all(r.backend == "remote"
                       for r in fleet._replicas.values())
            r = np.random.RandomState(5)
            prompts = [r.randint(1, 1619, size=int(r.randint(5, 30)))
                       for _ in range(3)]
            handles = [fleet.submit(p, max_new_tokens=6)
                       for p in prompts]
            fleet.run(max_iterations=800)
            assert all(h.status == "finished" for h in handles)
            assert fleet.handoffs_completed >= 3

            # the waterfall telescopes on the fleet clock WITH the
            # wire stage — pages crossed a real TCP hop, so the
            # export->inject gap is attributed, never lost
            bd = fleet.per_request_breakdown()
            for h in handles:
                row = bd["requests"][h.trace_id]
                assert sum(row[s] for s in STAGES) \
                    == row["total_steps"] \
                    == h.finished_iteration - h.submitted_iteration
                assert row["wire"] >= 0
            assert "wire" in bd["stages"]

            # ONE stitched trace: remote workers' own lanes (pulled
            # over trace_dump frames), joined to the router lane by
            # trace_id
            trace = fleet.stitched_trace()
            lanes = {e["args"]["name"] for e in trace["traceEvents"]
                     if e.get("ph") == "M"
                     and e["name"] == "process_name"}
            assert {"replica0:prefill", "replica1:decode"} <= lanes
            tid = handles[0].trace_id
            pids = {ev["pid"] for ev in trace["traceEvents"]
                    if ev.get("ph") == "X"
                    and (ev.get("args") or {}).get("trace_id") == tid}
            assert len(pids) >= 2       # same request, multiple lanes

            # per-peer wire accounting reached the process registry:
            # every peer shows framed traffic both ways plus a
            # dispatch->reply RTT window
            reg = get_registry()
            snap = reg.snapshot()
            for rid in (0, 1):
                peer = f"replica{rid}"
                assert reg.counter(
                    f"wire/tx_frames/json/{peer}").value > 0
                assert reg.counter(
                    f"wire/rx_frames/json/{peer}").value > 0
                assert reg.counter(
                    f"wire/tx_bytes/json/{peer}").value > 0
                assert snap["histograms"][f"wire/rtt_ms/{peer}"][
                    "count"] > 0
            # the KV handoff blob crossed the wire as raw blob frames:
            # received FROM the prefill peer (export reply), sent TO
            # the decode peer (injection)
            assert reg.counter(
                "wire/rx_frames/blob/replica0").value > 0
            assert reg.counter(
                "wire/tx_frames/blob/replica1").value > 0

            # the SLO watch evaluated on the aggregation cadence and
            # stayed quiet (clean run), riding the fleet snapshot
            fsnap = fleet.snapshot()
            assert fsnap["slo"]["evaluations"] > 0
            assert fsnap["slo"]["incidents_opened"] == 0
            json.dumps(fsnap["slo"])
        finally:
            if fleet is not None:
                fleet.close()
            for proc in (p0, p1):
                if proc.poll() is None:
                    proc.kill()
                proc.wait()

    def test_corrupt_handoff_slo_breach_fires_once_and_clears(self):
        """A chaos-flipped handoff drives corrupt_handoff_rate over
        its threshold: the incident fires EXACTLY once (hysteresis
        holds while the cumulative rate stays high), clears after
        enough clean handoffs dilute the rate, and the recorded sample
        sequence replays through a fresh watch to a bit-identical
        incident log."""
        from deepspeed_tpu.serving.fleet.manager import ServingFleet
        m, params = _model(vocab=1621)
        slo_cfg = {"enabled": True, "corrupt_handoff_rate": 0.3,
                   "shed_rate": 0.0, "replica_up_fraction": 0.0,
                   "fire_streak": 2, "clear_streak": 2}
        cfg = _paged_fleet_cfg(FleetConfig(
            replicas=2, disaggregate=True, prefill_replicas=1,
            aggregate_every_steps=2, slo=dict(slo_cfg)))
        fleet = ServingFleet(m, params, cfg)
        # record every (sample, step) the watch judges so the replay
        # check below re-derives the incident log from the same stream
        recorded = []
        orig_sample = fleet.slo_sample

        def _sampling():
            s = orig_sample()
            recorded.append((dict(s), fleet._iteration))
            return s

        fleet.slo_sample = _sampling
        try:
            r = np.random.RandomState(9)

            def _submit(n):
                prompts = [r.randint(1, 1621,
                                     size=int(r.randint(5, 20)))
                           for _ in range(n)]
                return [fleet.submit(p, max_new_tokens=4)
                        for p in prompts]

            # clean warm-up traffic
            a = _submit(2)
            fleet.run(max_iterations=400)
            assert all(h.status == "finished" for h in a)
            assert fleet.slo_watch.incidents_opened == 0

            # one flipped-bit handoff: the digest gate rejects every
            # injection attempt, the cumulative corrupt rate breaches,
            # and after fire_streak evaluations ONE incident opens
            fleet.chaos_flip_handoff_bits = 1
            b = _submit(1)
            fleet.run(max_iterations=600)
            assert all(h.status == "finished" for h in b)  # failover
            assert fleet.handoffs_rejected_corrupt >= 1
            # idle ticks: the cumulative rate stays breached, so the
            # watch keeps evaluating on cadence until the fire streak
            # is satisfied — the incident opens exactly once
            for _ in range(8):
                fleet.advance()
            assert fleet.slo_watch.incidents_opened == 1
            assert "corrupt_handoff_rate" in fleet.slo_watch.open_incidents

            # recovery: clean handoffs dilute the cumulative rate
            # below threshold, and after clear_streak evaluations the
            # incident clears — exactly one open, exactly one clear
            c = _submit(10)
            fleet.run(max_iterations=1200)
            assert all(h.status == "finished" for h in c)
            for _ in range(8):          # let the clear streak complete
                fleet.advance()
            snap = fleet.slo_watch.snapshot()
            assert snap["incidents_opened"] == 1
            assert snap["incidents_cleared"] == 1
            assert not snap["open_incidents"]
            events = snap["incident_log"]["events"]
            assert [e["event"] for e in events] \
                == ["incident_open", "incident_clear"]
            assert events[0]["rule"] == "corrupt_handoff_rate"

            # the fleet recorder carries the transitions for the
            # crash path / ds_tpu_report timeline
            kinds = [e["event"] for e in fleet.recorder.events
                     if e["event"].startswith("slo_")]
            assert kinds == ["slo_incident_open", "slo_incident_clear"]

            # bit-exact replay: the same sample sequence through a
            # fresh watch reproduces the incident log byte for byte
            replay = SloWatch.from_config(SloConfig(**slo_cfg))
            for sample, step in recorded:
                replay.evaluate(sample, step)
            assert replay.snapshot() == snap
        finally:
            fleet.close()
