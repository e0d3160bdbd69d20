"""Federation: socket transport, remote replicas, HTTP front-end,
rolling updates (serving/fleet/federation/).

Acceptance surface of the federation PR:

- frame codec: every torn/short/oversize/garbage wire condition maps to
  a NAMED ``FrameError`` kind (malformed/truncated/oversize/timeout) —
  no silent drops, no raw struct errors (no jax, no sockets);
- transport: JSON + companion-blob frames round-trip over a real
  socket; read deadlines surface as the ``timeout`` kind; a clean
  disconnect is ``PeerGone``, a mid-frame one is ``truncated``;
- ``RemoteReplica`` containment: every wire fault lands on PR 15's
  ``WorkerProtocolError`` taxonomy with the replica id attached (a
  scripted in-thread stub peer — no engine, no jax);
- two-"host" fleet (slow lane): a socket-only DISAGGREGATED fleet over
  two federation worker subprocesses serves token-exact vs the
  single-engine reference, including a mid-trace zero-downtime rolling
  update — N/N requests finish, each parity-checked against the
  reference for ITS stamped weights version;
- rolling updates in-process (slow lane): drain -> swap -> rejoin on
  the fleet step clock, one replica out of dispatch at a time, zero
  dropped requests, per-version parity;
- HTTP front-end (slow lane): POST /v1/submit + GET /v1/result +
  /v1/stream round-trip while the dispatch thread stays deterministic.

Unique vocab sizes per engine-building test (repo convention):
1601/1607/1613.
"""

import base64
import json
import socket
import struct
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deepspeed_tpu.serving.fleet.config import FleetConfig
from deepspeed_tpu.serving.fleet.federation.config import FederationConfig
from deepspeed_tpu.serving.fleet.federation.frames import (
    DEFAULT_MAX_FRAME_BYTES, KIND_BLOB, KIND_JSON, MAGIC,
    FrameDecoder, FrameError, encode_frame)
from deepspeed_tpu.serving.fleet.federation.transport import (
    FrameConnection, PeerGone, parse_address)
from deepspeed_tpu.serving.fleet.handoff import serialize_handoff
from deepspeed_tpu.serving.fleet.replica import (ReplicaDead,
                                                 WorkerProtocolError)


# ---------------------------------------------------------------------------
# frame codec units (no jax, no sockets)
# ---------------------------------------------------------------------------

class TestFrameCodec:
    def test_json_and_blob_frames_roundtrip(self):
        dec = FrameDecoder()
        dec.feed(encode_frame(b'{"op": "ready"}', KIND_JSON))
        dec.feed(encode_frame(b"\x00\x01raw", KIND_BLOB))
        assert dec.next_frame() == (KIND_JSON, b'{"op": "ready"}')
        assert dec.next_frame() == (KIND_BLOB, b"\x00\x01raw")
        assert dec.next_frame() is None
        assert dec.eof() is None          # clean close between frames

    def test_incremental_feed_yields_nothing_until_complete(self):
        frame = encode_frame(b"payload")
        dec = FrameDecoder()
        for byte in frame[:-1]:
            dec.feed(bytes([byte]))
            assert dec.next_frame() is None
        dec.feed(frame[-1:])
        assert dec.next_frame() == (KIND_JSON, b"payload")

    def test_bad_magic_is_malformed(self):
        dec = FrameDecoder()
        dec.feed(b"NOPE" + encode_frame(b"x")[4:])
        with pytest.raises(FrameError) as e:
            dec.next_frame()
        assert e.value.kind == "malformed"

    def test_unknown_kind_byte_is_malformed(self):
        dec = FrameDecoder()
        dec.feed(struct.pack(">4sBI", MAGIC, 7, 1) + b"x")
        with pytest.raises(FrameError) as e:
            dec.next_frame()
        assert e.value.kind == "malformed"

    def test_declared_length_over_cap_is_oversize(self):
        dec = FrameDecoder(max_frame_bytes=64)
        dec.feed(struct.pack(">4sBI", MAGIC, KIND_JSON, 65))
        with pytest.raises(FrameError) as e:
            dec.next_frame()
        assert e.value.kind == "oversize"

    def test_eof_mid_frame_is_truncated(self):
        dec = FrameDecoder()
        dec.feed(encode_frame(b"torn in transit")[:-3])
        assert dec.next_frame() is None   # still waiting for bytes...
        with pytest.raises(FrameError) as e:
            dec.eof()                     # ...that will never come
        assert e.value.kind == "truncated"
        assert dec.pending > 0

    def test_encode_rejects_unknown_kind(self):
        with pytest.raises(ValueError):
            encode_frame(b"x", kind=9)


class TestParseAddress:
    def test_host_port(self):
        assert parse_address("10.0.0.7:7077") == ("10.0.0.7", 7077)
        assert parse_address("localhost:0") == ("localhost", 0)

    def test_rejects_garbage(self):
        for bad in ("nohost", ":7077", "h:", "h:notaport"):
            with pytest.raises(ValueError):
                parse_address(bad)


# ---------------------------------------------------------------------------
# transport over a real (local) socket pair
# ---------------------------------------------------------------------------

def _pair():
    a, b = socket.socketpair()
    return FrameConnection(a), FrameConnection(b)


class TestFrameConnection:
    def test_msg_with_companion_blob_roundtrips(self):
        tx, rx = _pair()
        try:
            tx.send_msg({"op": "payload", "id": 3}, blob=b"\x00" * 1000)
            msg, blob = rx.recv_msg(timeout_s=5.0)
            assert msg == {"op": "payload", "id": 3}   # _blob flag eaten
            assert blob == b"\x00" * 1000
            tx.send_msg({"op": "advance"})
            msg, blob = rx.recv_msg(timeout_s=5.0)
            assert msg == {"op": "advance"} and blob is None
        finally:
            tx.close()
            rx.close()

    def test_read_deadline_is_the_timeout_kind(self):
        tx, rx = _pair()
        try:
            with pytest.raises(FrameError) as e:
                rx.recv_msg(timeout_s=0.05)
            assert e.value.kind == "timeout"
        finally:
            tx.close()
            rx.close()

    def test_clean_close_is_peer_gone(self):
        tx, rx = _pair()
        tx.close()
        try:
            with pytest.raises(PeerGone):
                rx.recv_msg(timeout_s=5.0)
        finally:
            rx.close()

    def test_mid_frame_close_is_truncated(self):
        a, b = socket.socketpair()
        rx = FrameConnection(b)
        a.sendall(encode_frame(b'{"op": "ready"}')[:6])
        a.close()
        try:
            with pytest.raises(FrameError) as e:
                rx.recv_msg(timeout_s=5.0)
            assert e.value.kind == "truncated"
        finally:
            rx.close()

    def test_non_object_json_is_malformed(self):
        a, b = socket.socketpair()
        rx = FrameConnection(b)
        a.sendall(encode_frame(b"[1, 2]"))
        try:
            with pytest.raises(FrameError) as e:
                rx.recv_msg(timeout_s=5.0)
            assert e.value.kind == "malformed"
        finally:
            a.close()
            rx.close()


# ---------------------------------------------------------------------------
# federation config + plumbing
# ---------------------------------------------------------------------------

class TestFederationConfig:
    def test_defaults_validate(self):
        cfg = FederationConfig()
        cfg.validate()
        assert cfg.peers == [] and cfg.rolling_verify
        assert cfg.max_frame_bytes == DEFAULT_MAX_FRAME_BYTES

    def test_named_validation_errors(self):
        with pytest.raises(ValueError, match="federation.peers"):
            FederationConfig(peers=["nohost"]).validate()
        with pytest.raises(ValueError, match="connect_timeout_s"):
            FederationConfig(connect_timeout_s=0).validate()
        with pytest.raises(ValueError, match="reply_timeout_s"):
            FederationConfig(reply_timeout_s=-1).validate()
        with pytest.raises(ValueError, match="max_frame_bytes"):
            FederationConfig(max_frame_bytes=16).validate()
        with pytest.raises(ValueError, match="http_port"):
            FederationConfig(http_port=70000).validate()
        with pytest.raises(ValueError, match="rolling_drain_slot_cap"):
            FederationConfig(rolling_drain_slot_cap=0).validate()

    def test_fleet_block_lifts_nested_dict(self):
        cfg = FleetConfig(
            replicas=2,
            federation={"peers": ["10.0.0.7:7077"],
                        "reply_timeout_s": 12.0}).validate()
        assert isinstance(cfg.federation, FederationConfig)
        assert cfg.federation.peers == ["10.0.0.7:7077"]
        assert cfg.federation.reply_timeout_s == 12.0
        # absent sub-block stays None: single-host fleets carry no
        # federation state at all
        assert FleetConfig().validate().federation is None

    def test_more_peers_than_replicas_refused(self):
        with pytest.raises(ValueError, match="peers"):
            FleetConfig(
                replicas=1,
                federation={"peers": ["a:1", "b:2"]}).validate()


# ---------------------------------------------------------------------------
# RemoteReplica protocol containment (scripted stub peer — no engine)
# ---------------------------------------------------------------------------

_READY = {"op": "ready", "telemetry_port": None}


class _StubPeer:
    """A scripted federation 'worker': accepts ONE connection, answers
    init with ready, then hands the connection to ``script``."""

    def __init__(self, script=None):
        self._listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._listener.bind(("127.0.0.1", 0))
        self._listener.listen(1)
        self.port = self._listener.getsockname()[1]
        self.address = f"127.0.0.1:{self.port}"
        self.init_msg = None
        self._script = script
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        sock, _ = self._listener.accept()
        conn = FrameConnection(sock)
        try:
            self.init_msg, _ = conn.recv_msg(timeout_s=10.0)
            conn.send_msg(_READY)
            if self._script is not None:
                self._script(conn)
        finally:
            conn.close()
            self._listener.close()

    def join(self):
        self._thread.join(timeout=10.0)


def _remote(peer, **kw):
    from deepspeed_tpu.serving.fleet.federation.remote import RemoteReplica
    kw.setdefault("reply_timeout_s", 2.0)
    return RemoteReplica(0, "full", peer.address, {"serving": {}}, **kw)


class TestRemoteReplicaContainment:
    def test_dial_failure_is_replica_dead(self):
        from deepspeed_tpu.serving.fleet.federation.remote import (
            RemoteReplica)
        probe = socket.socket()
        probe.bind(("127.0.0.1", 0))
        port = probe.getsockname()[1]
        probe.close()                      # nothing listens here now
        with pytest.raises(ReplicaDead):
            RemoteReplica(4, "full", f"127.0.0.1:{port}", {},
                          connect_timeout_s=1.0)

    def test_init_carries_spec_and_ready_is_consumed(self):
        peer = _StubPeer()
        rep = _remote(peer)
        peer.join()
        assert peer.init_msg["op"] == "init"
        assert peer.init_msg["replica_id"] == 0
        assert rep.alive and rep.backend == "remote"
        assert rep.telemetry_host == "127.0.0.1"
        rep.kill()

    def test_reply_timeout_is_named_protocol_error(self):
        peer = _StubPeer(script=lambda conn: time.sleep(4.0))
        rep = _remote(peer, reply_timeout_s=0.2)
        with pytest.raises(WorkerProtocolError) as e:
            rep.advance()
        assert e.value.kind == "timeout" and e.value.replica_id == 0
        assert not rep.alive and rep.protocol_errors == 1

    def test_torn_reply_is_truncated(self):
        def script(conn):
            conn.recv_msg(timeout_s=10.0)           # the advance op
            conn._sock.sendall(encode_frame(b'{"op": "stepped"}')[:6])
        peer = _StubPeer(script=script)
        rep = _remote(peer)
        with pytest.raises(WorkerProtocolError) as e:
            rep.advance()
        assert e.value.kind == "truncated"

    def test_garbage_bytes_are_malformed(self):
        def script(conn):
            conn.recv_msg(timeout_s=10.0)
            conn._sock.sendall(b"HTTP/1.1 200 OK\r\n\r\n")
        peer = _StubPeer(script=script)
        rep = _remote(peer)
        with pytest.raises(WorkerProtocolError) as e:
            rep.advance()
        assert e.value.kind == "malformed"

    def test_oversize_frame_maps_to_malformed(self):
        def script(conn):
            conn.recv_msg(timeout_s=10.0)
            conn._sock.sendall(
                struct.pack(">4sBI", MAGIC, KIND_JSON, 1 << 20))
        peer = _StubPeer(script=script)
        rep = _remote(peer, max_frame_bytes=4096)
        with pytest.raises(WorkerProtocolError) as e:
            rep.advance()
        assert e.value.kind == "malformed"

    def test_clean_disconnect_is_replica_dead_not_protocol(self):
        def script(conn):
            conn.recv_msg(timeout_s=10.0)
            conn.close()                  # clean EOF between frames
        peer = _StubPeer(script=script)
        rep = _remote(peer)
        with pytest.raises(ReplicaDead) as e:
            rep.advance()
        assert not isinstance(e.value, WorkerProtocolError)
        assert not rep.alive and not rep.healthy()

    def test_export_accepts_blob_frame_and_base64_fallback(self):
        payload = {"version": 3, "page_len": 4, "kv_quant": "none",
                   "prefill_len": 5, "n_pages_filled": 2,
                   "kv": [{"k": np.arange(8, dtype=np.float32)}],
                   "state": {"last_token": 7, "remaining": 3},
                   "request": {"prompt": np.arange(5, dtype=np.int32),
                               "id": "r1"}}
        blob = serialize_handoff(payload)

        def script(conn):
            msg, _ = conn.recv_msg(timeout_s=10.0)   # blob-frame export
            conn.send_msg({"op": "payload", "id": msg["id"]}, blob=blob)
            msg, _ = conn.recv_msg(timeout_s=10.0)   # pipe-dialect export
            conn.send_msg({"op": "payload", "id": msg["id"],
                           "blob": base64.b64encode(blob).decode("ascii")})
        peer = _StubPeer(script=script)
        rep = _remote(peer)
        for _ in range(2):                # framed first, then base64
            out = rep.export_handoff_by_id("r1")
            assert out["prefill_len"] == 5
            np.testing.assert_array_equal(out["kv"][0]["k"],
                                          payload["kv"][0]["k"])
        rep.kill()

    def test_inject_ships_payload_as_raw_blob_frame(self):
        got = {}

        def script(conn):
            msg, blob = conn.recv_msg(timeout_s=10.0)
            got["op"], got["blob"] = msg["op"], blob
            conn.send_msg({"op": "injected", "accepted": True})
        peer = _StubPeer(script=script)
        rep = _remote(peer)
        payload = {"version": 3, "page_len": 4, "kv_quant": "none",
                   "prefill_len": 3, "n_pages_filled": 1,
                   "kv": [{"k": np.zeros(4, np.float32)}],
                   "state": {}, "request": {"prompt": np.arange(3)}}
        assert rep.inject_handoff(payload) is True
        peer.join()
        assert got["op"] == "inject"
        assert isinstance(got["blob"], bytes) and len(got["blob"]) > 0
        rep.kill()


# ---------------------------------------------------------------------------
# rolling-update policy units (no engine)
# ---------------------------------------------------------------------------

class TestRollingUpdatePolicy:
    def test_unverifiable_checkpoint_refused_by_name(self, tmp_path):
        from deepspeed_tpu.serving.fleet.federation.rolling import (
            RollingUpdateError, _verify_checkpoint)
        with pytest.raises(RollingUpdateError,
                           match="rolling update refused"):
            _verify_checkpoint(str(tmp_path))   # empty dir: no manifest


# ---------------------------------------------------------------------------
# engine-backed acceptance (slow lane)
# ---------------------------------------------------------------------------

def _start_worker(port=0):
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


def _serving_cfg(fleet_cfg, num_slots=2):
    from deepspeed_tpu.serving import PagingConfig, ServingConfig
    return ServingConfig(num_slots=num_slots, max_len=128,
                         paging=PagingConfig(page_len=16),
                         fleet=fleet_cfg)


def _prompts(seed, n, vocab):
    r = np.random.RandomState(seed)
    return [r.randint(1, vocab, size=int(r.randint(5, 30)))
            for _ in range(n)]


def _ref_tokens(m, params, prompt, max_new):
    from deepspeed_tpu.inference.generation import generate
    return np.asarray(generate(
        m, params, np.asarray(prompt)[None], max_new_tokens=max_new,
        temperature=0.0, max_len=128))[0, len(prompt):]


def _assert_version_parity(handles, prompts, refs_by_version, max_new=6):
    """Every finished handle must match the reference for the weights
    version that served it — the per-version parity gate."""
    for pr, h in zip(prompts, handles):
        assert h.status == "finished", (h.request_id, h.status)
        m, params = refs_by_version[h.weights_version]
        np.testing.assert_array_equal(
            np.asarray(h.tokens), _ref_tokens(m, params, pr, max_new),
            err_msg=f"request {h.request_id} "
                    f"(weights_version={h.weights_version})")


def _run(fleet, max_iterations=800, until=None):
    for _ in range(max_iterations):
        if not fleet.busy and (until is None or until()):
            return
        fleet.advance()
    raise AssertionError("fleet did not converge within the step budget")


@pytest.mark.slow
class TestFederatedFleetEndToEnd:
    def test_two_host_disaggregated_token_exact_with_rolling_update(self):
        """The PR's acceptance scenario: a socket-only 2-'host' fleet
        (two federation worker subprocesses, disaggregated prefill/
        decode, KV handoffs as raw v3 blob frames) serves token-exact,
        then a mid-trace rolling update swaps both peers to new weights
        with zero dropped requests and per-version parity."""
        from deepspeed_tpu.models.gpt import build_demo_model
        from deepspeed_tpu.serving.fleet.manager import ServingFleet
        import dataclasses
        model_spec = {"vocab_size": 1601, "max_seq_len": 128,
                      "d_model": 32, "n_layers": 2, "n_heads": 2,
                      "seed": 0}
        p0, addr0 = _start_worker()
        p1, addr1 = _start_worker()
        fleet = None
        try:
            fcfg = FleetConfig(
                replicas=2, disaggregate=True, prefill_replicas=1,
                federation={"peers": [addr0, addr1]})
            cfg = _serving_cfg(fcfg)
            spec = {"serving": dataclasses.asdict(
                        dataclasses.replace(cfg, fleet=None)),
                    "model": model_spec}
            fleet = ServingFleet(None, None, cfg, spec=spec)
            assert all(r.backend == "remote"
                       for r in fleet._replicas.values())
            refs = {0: build_demo_model(**model_spec),
                    1: build_demo_model(**{**model_spec, "seed": 1})}

            batch_a = _prompts(7, 4, 1601)
            handles_a = [fleet.submit(pr, max_new_tokens=6,
                                      request_id=f"a{i}")
                         for i, pr in enumerate(batch_a)]
            _run(fleet)
            assert fleet.handoffs_completed >= 1   # pages crossed the wire
            assert all(h.weights_version == 0 for h in handles_a)

            # mid-trace rolling update: new weights = same arch, seed 1
            roll = fleet.start_rolling_update(
                spec_update={"model": {**model_spec, "seed": 1}})
            from deepspeed_tpu.serving.fleet.federation.rolling import (
                RollingUpdateError)
            with pytest.raises(RollingUpdateError, match="in progress"):
                fleet.start_rolling_update(spec_update={"x": 1})
            batch_b = _prompts(11, 2, 1601)
            handles_b = [fleet.submit(pr, max_new_tokens=6,
                                      request_id=f"b{i}")
                         for i, pr in enumerate(batch_b)]
            _run(fleet, until=lambda: roll.done)
            assert roll.done and roll.swapped == [0, 1]
            assert fleet.weights_version == 1
            assert fleet.rolling_updates == 1 and fleet.rolling_swaps == 2
            assert not fleet._draining     # everyone rejoined dispatch

            batch_c = _prompts(13, 2, 1601)
            handles_c = [fleet.submit(pr, max_new_tokens=6,
                                      request_id=f"c{i}")
                         for i, pr in enumerate(batch_c)]
            _run(fleet)
            assert all(h.weights_version == 1 for h in handles_c)

            # N/N: every request of the whole trace finished, each
            # parity-checked against its own version's reference
            _assert_version_parity(handles_a + handles_b + handles_c,
                                   batch_a + batch_b + batch_c, refs)
            assert fleet.requests_finished == 8
        finally:
            if fleet is not None:
                fleet.close()              # stop op tears the peers down
            for proc in (p0, p1):
                if proc.poll() is None:
                    proc.kill()
                proc.wait()

    def test_rolling_update_inprocess_drains_and_swaps(self):
        """In-process fleet: the update walks replicas one at a time
        (never more than one out of dispatch), in-flight requests
        finish on their old weights, and both request populations are
        parity-exact for their stamped version."""
        import jax
        import jax.numpy as jnp
        from deepspeed_tpu.models.gpt import GPT, GPTConfig
        from deepspeed_tpu.serving.fleet.manager import ServingFleet
        mc = GPTConfig(vocab_size=1607, max_seq_len=128, d_model=32,
                       n_layers=2, n_heads=2, dtype=jnp.float32)
        m = GPT(mc)
        params0 = m.init(jax.random.PRNGKey(0),
                         jnp.ones((1, 8), jnp.int32))["params"]
        params1 = m.init(jax.random.PRNGKey(1),
                         jnp.ones((1, 8), jnp.int32))["params"]
        fleet = ServingFleet(m, params0,
                             _serving_cfg(FleetConfig(replicas=2)))
        try:
            refs = {0: (m, params0), 1: (m, params1)}
            batch_a = _prompts(17, 3, 1607)
            handles_a = [fleet.submit(pr, max_new_tokens=6,
                                      request_id=f"a{i}")
                         for i, pr in enumerate(batch_a)]
            fleet.advance()                 # batch A is mid-flight...
            roll = fleet.start_rolling_update(params=params1)
            max_out = 0
            for _ in range(400):
                if roll.done:
                    break
                fleet.advance()
                max_out = max(max_out, len(fleet._draining))
            assert max_out <= 1             # zero-downtime invariant
            assert fleet.weights_version == 1 and fleet.rolling_swaps == 2
            batch_b = _prompts(19, 2, 1607)
            handles_b = [fleet.submit(pr, max_new_tokens=6,
                                      request_id=f"b{i}")
                         for i, pr in enumerate(batch_b)]
            _run(fleet)
            assert all(h.weights_version == 0 for h in handles_a)
            assert all(h.weights_version == 1 for h in handles_b)
            _assert_version_parity(handles_a + handles_b,
                                   batch_a + batch_b, refs)
        finally:
            fleet.close()

    def test_http_frontend_round_trip(self):
        """POST /v1/submit -> dispatch-thread drain -> GET /v1/result
        and /v1/stream: the ndjson stream replays every token plus the
        final done line, token-exact vs the direct engine."""
        import jax
        import jax.numpy as jnp
        from deepspeed_tpu.models.gpt import GPT, GPTConfig
        from deepspeed_tpu.serving.fleet.federation import FleetFrontend
        from deepspeed_tpu.serving.fleet.manager import ServingFleet
        mc = GPTConfig(vocab_size=1613, max_seq_len=128, d_model=32,
                       n_layers=2, n_heads=2, dtype=jnp.float32)
        m = GPT(mc)
        params = m.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 8), jnp.int32))["params"]
        fleet = ServingFleet(m, params,
                             _serving_cfg(FleetConfig(replicas=2)))
        frontend = FleetFrontend().start()
        fleet.attach_frontend(frontend)
        base = f"http://127.0.0.1:{frontend.port}"
        try:
            prompt = _prompts(23, 1, 1613)[0]
            body = json.dumps({"prompt": prompt.tolist(),
                               "max_new_tokens": 6}).encode()
            with urllib.request.urlopen(urllib.request.Request(
                    f"{base}/v1/submit", data=body,
                    headers={"Content-Type": "application/json"})) as r:
                assert r.status == 202
                accepted = json.loads(r.read())
                rid = accepted["request_id"]
                trace_id = accepted["trace_id"]
            assert trace_id          # minted at accept, before dispatch
            _run(fleet, until=lambda: not frontend.busy)
            # stream BEFORE the result read: /v1/result consumes a
            # finished record (read-once retention)
            with urllib.request.urlopen(f"{base}/v1/stream?id={rid}") as r:
                lines = [json.loads(ln) for ln in r.read().splitlines()]
            assert lines[-1] == {"done": True, "status": "finished",
                                 "trace_id": trace_id}
            # every stream event carries the stitched-trace join key
            assert all(ln["trace_id"] == trace_id for ln in lines)
            with urllib.request.urlopen(f"{base}/v1/result?id={rid}") as r:
                result = json.loads(r.read())
            assert result["done"] and result["status"] == "finished"
            assert result["trace_id"] == trace_id
            # the fleet-side request carries the SAME id end to end
            assert any(ev.get("trace_id") == trace_id
                       for ev in fleet.recorder.events)
            ref = _ref_tokens(m, params, prompt, 6)
            np.testing.assert_array_equal(np.asarray(result["tokens"]),
                                          ref)
            assert [ln["token"] for ln in lines[:-1]] == result["tokens"]
            assert frontend.submitted == 1 and frontend.finished == 1
            # the read consumed the finished record: a re-read is 404
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(f"{base}/v1/result?id={rid}")
            assert e.value.code == 404
            # malformed submission and unknown id stay client errors
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(urllib.request.Request(
                    f"{base}/v1/submit", data=b'{"nope": 1}',
                    headers={"Content-Type": "application/json"}))
            assert e.value.code == 400
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(f"{base}/v1/result?id=ghost")
            assert e.value.code == 404
        finally:
            fleet.close()                  # stops the attached frontend
