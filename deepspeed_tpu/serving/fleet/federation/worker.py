"""Federation worker: one ServingEngine served over a TCP frame socket.

``python -m deepspeed_tpu.serving.fleet.federation.worker --listen
HOST:PORT`` (also reachable as ``ds_tpu_serve --listen``) binds the
address, prints the bound endpoint (PORT may be 0 for ephemeral — the
caller parses the printed line), and serves one router connection at a
time. The op surface is exactly ``serving/fleet/worker.py``'s —
``_SocketWorker`` subclasses ``_Worker`` and swaps the transport:
replies travel as JSON frames, KV handoffs as raw v3 blob frames.

Reconnect semantics: the ENGINE outlives the connection. A dropped
router connection (crash, partition) parks the worker back in accept;
the next dial finds the same engine with its KV state intact — the
router side treats re-dialing as the supervision restart. A fresh
``init`` on a new connection rebuilds the engine (a rejoining router
must start from a known state); ``stop`` tears the engine down and
exits the process.

Byzantine-wire hardening (PR 19): the init/ready exchange negotiates
the wire revision (``wire_rev`` — new↔new pairs speak crc32-checked
DSF2, a DSF1 router keeps its length-only frames); every request's
``_epoch``/``_seq`` stamps are echoed into its reply so the router can
fence zombies and duplicates; ``ping`` answers ``pong`` even before
init (the router's heartbeat probe must work on a freshly-dialed
connection). Chaos hooks ride the init spec: ``chaos.netfaults``
attaches a deterministic wire-fault injector to this worker's replies
(kept across reconnects so the frame-ordinal clock never rewinds), and
``chaos.zombie_replay`` re-sends the last recorded reply on the next
rebound connection — the delayed-duplicate-crossing-a-restart case the
epoch fence exists for.
"""

import argparse
import socket
import sys

from deepspeed_tpu.serving.fleet.federation.frames import (
    DEFAULT_MAX_FRAME_BYTES,
    FrameError,
    WIRE_REV,
)
from deepspeed_tpu.serving.fleet.federation.transport import (
    FrameConnection,
    PeerGone,
    parse_address,
)
from deepspeed_tpu.serving.fleet.handoff import deserialize_handoff
from deepspeed_tpu.serving.fleet.worker import _Worker

READY_BANNER = "@fleet-federation listening "
_STAMP_KEYS = ("_epoch", "_seq")


def _stamp_of(msg: dict) -> dict:
    return {k: msg[k] for k in _STAMP_KEYS if k in msg}


class _SocketWorker(_Worker):
    """The pipe worker's op surface answered over a FrameConnection."""

    def __init__(self, spec: dict, conn: FrameConnection, server=None):
        self._conn = conn            # before super().__init__: the ready
        self._server = server        # reply already goes over the socket
        self._stamp = _stamp_of(spec)
        super().__init__(spec)

    def stamp(self, stamp: dict):
        """Adopt the in-flight request's fence stamp: every reply the
        dispatched handler produces echoes it."""
        self._stamp = stamp

    def _send_stamped(self, msg: dict, blob=None):
        out = {**self._stamp, **msg}
        if out.get("op") == "ready":
            # the negotiation half the router is waiting on
            out["wire_rev"] = WIRE_REV
        if self._server is not None:
            self._server.record_reply(out)
        self._conn.send_msg(out, blob=blob)

    def _reply(self, msg: dict):
        self._send_stamped(msg)

    def rebind(self, conn: FrameConnection):
        """A new router connection adopts the live engine."""
        self._conn = conn

    def op_export(self, msg):
        self._send_stamped({"op": "payload", "id": msg["id"]},
                           blob=self._export_blob(msg))

    def op_inject(self, msg, blob=None):
        if blob is None:
            return super().op_inject(msg)
        # deserialize_handoff verifies the v3 integrity digest: a blob
        # the wire (or anything else) flipped a bit in raises the named
        # HandoffError here and becomes a typed error reply — corrupt
        # pages never reach this engine's KV pool
        self._inject_payload(deserialize_handoff(blob))


class FederationWorkerServer:
    def __init__(self, host: str, port: int, *,
                 max_frame_bytes: int = DEFAULT_MAX_FRAME_BYTES):
        self.max_frame_bytes = max_frame_bytes
        self._listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._listener.bind((host, int(port)))
        self._listener.listen(4)
        self.host = host
        self.port = self._listener.getsockname()[1]
        self._worker = None
        self._stopping = False
        self._injector = None        # chaos.netfaults — one injector for
                                     # the server's lifetime: the ordinal
                                     # clock survives reconnects
        self._zombie_replay = False  # chaos.zombie_replay
        self._last_reply = None

    def record_reply(self, msg: dict):
        """Zombie-replay chaos memory: the last reply this worker
        produced, re-sent verbatim (OLD epoch stamp and all) on the
        next rebound connection."""
        if self._zombie_replay and msg.get("op") not in ("ready", "bye"):
            self._last_reply = dict(msg)

    def _adopt_chaos(self, spec: dict):
        chaos = dict(spec.get("chaos") or {})
        self._zombie_replay = bool(chaos.get("zombie_replay"))
        if chaos.get("netfaults") and self._injector is None:
            from deepspeed_tpu.serving.fleet.federation.netfaults import (
                WireFaultInjector, WireFaultPlan)
            self._injector = WireFaultInjector(
                WireFaultPlan.from_spec(chaos["netfaults"]))

    def serve_forever(self):
        try:
            while not self._stopping:
                try:
                    sock, peer = self._listener.accept()
                except OSError:
                    break
                conn = FrameConnection(
                    sock, max_frame_bytes=self.max_frame_bytes)
                # wire accountant: worker-side frames tally under the
                # router's address
                conn.peer = f"{peer[0]}:{peer[1]}"
                print(f"[federation-worker] router connected from "
                      f"{peer[0]}:{peer[1]}", flush=True)
                try:
                    self._serve_connection(conn)
                finally:
                    conn.close()
        finally:
            self._listener.close()
            if self._worker is not None:
                self._worker.engine.close()

    def _send_safe(self, conn: FrameConnection, msg: dict) -> bool:
        """A server-loop reply that must never crash the accept loop:
        a broken connection just parks the worker for the re-dial."""
        try:
            conn.send_msg(msg)
            return True
        except (OSError, FrameError):
            return False

    def _serve_connection(self, conn: FrameConnection):
        if self._injector is not None:
            conn.fault_injector = self._injector
        worker = self._worker
        if worker is not None:
            worker.rebind(conn)
            if self._last_reply is not None:
                # chaos: the pre-restart incarnation's delayed reply
                # arrives on the NEW connection — the router's epoch
                # fence must drop it (sent once, then forgotten)
                zombie, self._last_reply = self._last_reply, None
                self._send_safe(conn, zombie)
        while True:
            try:
                msg, blob = conn.recv_msg(timeout_s=None)
            except (PeerGone, FrameError, OSError) as e:
                # router gone (clean close, torn frame, reset): the
                # engine survives; park in accept for the re-dial
                print(f"[federation-worker] router connection lost "
                      f"({e}); awaiting reconnect", flush=True)
                return
            op = msg.get("op")
            stamp = _stamp_of(msg)
            if op == "ping":
                # liveness must work before init: a heartbeat is about
                # the CONNECTION, not the engine
                if not self._send_safe(conn, {**stamp, "op": "pong"}):
                    return
                continue
            if op == "init":
                conn.negotiate(msg.get("wire_rev"))
                self._adopt_chaos(msg)
                if self._injector is not None:
                    conn.fault_injector = self._injector
                if worker is not None:
                    # a rejoining router starts from a known state
                    worker.engine.close()
                worker = _SocketWorker(msg, conn, server=self)
                self._worker = worker
                continue
            if op == "stop":
                self._send_safe(conn, {**stamp, "op": "bye"})
                self._stopping = True
                return
            if worker is None:
                if not self._send_safe(conn, {**stamp, "op": "error",
                                              "detail":
                                              "no init received yet"}):
                    return
                continue
            worker.stamp(stamp)
            handler = getattr(worker, f"op_{op}", None)
            if handler is None:
                if not self._send_safe(conn, {**stamp, "op": "error",
                                              "detail":
                                              f"unknown op {op!r}"}):
                    return
                continue
            try:
                if op == "inject":
                    handler(msg, blob=blob)
                else:
                    handler(msg)
            except (OSError, FrameError) as e:
                # the REPLY path broke (router vanished mid-op, or a
                # chaos truncate severed the socket): park for re-dial
                # instead of crashing the accept loop
                print(f"[federation-worker] reply send failed ({e}); "
                      f"awaiting reconnect", flush=True)
                return
            except Exception as e:   # ds-tpu: lint-ok[PY001] — the
                # protocol boundary: op failures become typed error
                # replies, never a dead socket with no diagnosis
                if not self._send_safe(conn, {**stamp, "op": "error",
                                              "detail": f"{op}: {e}"}):
                    return


def serve_listen(address: str,
                 max_frame_bytes: int = DEFAULT_MAX_FRAME_BYTES) -> int:
    from deepspeed_tpu.utils.host_env import configure_compile_cache
    configure_compile_cache()
    host, port = parse_address(address)
    server = FederationWorkerServer(host, port,
                                    max_frame_bytes=max_frame_bytes)
    # the banner is the contract: callers with port 0 parse the bound
    # endpoint from this line
    print(f"{READY_BANNER}{server.host}:{server.port}", flush=True)
    server.serve_forever()
    return 0


def main(argv=None):
    parser = argparse.ArgumentParser(
        description="federated fleet worker (socket transport)")
    parser.add_argument("--listen", required=True, metavar="HOST:PORT",
                        help="bind address; port 0 picks an ephemeral "
                             "port, printed on the ready banner")
    parser.add_argument("--max-frame-bytes", type=int,
                        default=DEFAULT_MAX_FRAME_BYTES)
    args = parser.parse_args(argv)
    return serve_listen(args.listen, max_frame_bytes=args.max_frame_bytes)


if __name__ == "__main__":
    sys.exit(main())
