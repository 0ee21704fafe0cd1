"""One serving replica: a QueryService wrapped in a socket server.

The worker-pool child protocol (parallel/workers.py `child_main`)
promoted from an inherited pipe to an accepted TCP connection: the same
hello handshake before work is dispatched, the same pickled control
frames (now CRC32C-framed over a stream, hardened for short reads and
torn frames), the same crash taxonomy — a replica that dies mid-query
surfaces to the router exactly as a crashed worker surfaces to the
pool, and the query retries on a sibling replica instead of a sibling
process.

Run standalone (`python -m blaze_tpu.fleet.replica --replica-id r1
--port 0 --conf k=v ...`) the process prints one JSON "listening" line
on stdout and serves until SIGTERM, which triggers a graceful drain:
stop accepting, let in-flight queries finish up to
`auron.tpu.fleet.drainMs`, exit 0.  SIGKILL skips the drain — that is
the crash the router's retry path exists for.

Fault sites: `replica-crash` (the process really SIGKILLs itself while
holding a query — connection reset at the router), `replica-hang` (the
replica wedges: its socket stays open but pings go unanswered, so only
the router's liveness deadline can classify it down).
"""

from __future__ import annotations

import json
import logging
import os
import signal
import socket
import sys
import threading
import time
from typing import Any, Dict, Optional

from blaze_tpu import faults
from blaze_tpu.fleet import wire
from blaze_tpu.shuffle.ipc import FrameTransportClosed

log = logging.getLogger("blaze_tpu.fleet")


class ReplicaServer:
    """Socket front-end for one QueryService (one fleet crash domain).

    `process_mode=True` (the `__main__` path) makes the `replica-crash`
    fault site a REAL SIGKILL of this process; in-process servers (unit
    tests) simulate the same observable — connection reset + listener
    closed — without taking the test runner down with them.
    """

    def __init__(self, replica_id: str, host: str = "127.0.0.1",
                 port: int = 0, service: Optional[Any] = None,
                 process_mode: bool = False):
        self.replica_id = replica_id
        self.process_mode = process_mode
        self._listener = socket.socket(socket.AF_INET,
                                       socket.SOCK_STREAM)
        self._listener.setsockopt(socket.SOL_SOCKET,
                                  socket.SO_REUSEADDR, 1)
        self._listener.bind((host, port))
        self._listener.listen(128)
        self.host, self.port = self._listener.getsockname()[:2]
        self._service = service
        self._state = "up"           # up | draining | dead
        self._lock = threading.Lock()
        self._inflight = 0
        self._idle = threading.Condition(self._lock)
        self._queries_done = 0
        self._queries_failed = 0
        self._started_at = time.monotonic()
        self._hung = False
        self._accept_thread: Optional[threading.Thread] = None

    @property
    def addr(self):
        return (self.host, self.port)

    def service(self):
        """The wrapped QueryService, constructed lazily from the
        serving knobs so importing this module stays light."""
        with self._lock:
            if self._service is None:
                from blaze_tpu.serving import QueryService
                self._service = QueryService()
            return self._service

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> "ReplicaServer":
        self._accept_thread = threading.Thread(
            target=self._accept_loop,
            name=f"blaze-fleet-replica-{self.replica_id}", daemon=True)
        self._accept_thread.start()
        return self

    def _accept_loop(self) -> None:
        while self._state != "dead":
            try:
                conn, _peer = self._listener.accept()
            except OSError:
                return  # listener closed (drain end or kill)
            threading.Thread(
                target=self._serve_conn, args=(conn,),
                name=f"blaze-fleet-conn-{self.replica_id}",
                daemon=True).start()

    def drain(self, timeout_s: Optional[float] = None) -> None:
        """Graceful SIGTERM path: stop accepting, wait for in-flight
        queries up to `timeout_s` (default auron.tpu.fleet.drainMs),
        then shut the service down."""
        if timeout_s is None:
            from blaze_tpu import config
            timeout_s = config.FLEET_DRAIN_MS.get() / 1000.0
        with self._lock:
            if self._state != "up":
                return
            self._state = "draining"
        try:
            self._listener.close()
        except OSError:
            pass
        deadline = time.monotonic() + timeout_s
        with self._idle:
            while self._inflight > 0:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                self._idle.wait(timeout=remaining)
        svc, self._service = self._service, None
        if svc is not None:
            svc.shutdown(wait=True, cancel_running=True)
        with self._lock:
            self._state = "dead"

    def kill(self) -> None:
        """Abrupt death (the in-process stand-in for SIGKILL): listener
        and service vanish, in-flight connections reset."""
        with self._lock:
            self._state = "dead"
        try:
            self._listener.close()
        except OSError:
            pass
        svc, self._service = self._service, None
        if svc is not None:
            svc.shutdown(wait=False, cancel_running=True)

    # -- request handling --------------------------------------------------

    def _serve_conn(self, conn: socket.socket) -> None:
        try:
            while True:
                try:
                    msg = wire.recv_msg(conn)
                except (FrameTransportClosed, ConnectionError, OSError):
                    return
                if msg is None or self._state == "dead":
                    return
                reply = self._dispatch(msg, conn)
                if reply is None:
                    return  # handler consumed the connection (crash)
                try:
                    wire.send_msg(conn, reply)
                except (FrameTransportClosed, ConnectionError, OSError):
                    return
        finally:
            try:
                conn.close()
            except OSError:
                pass

    def _dispatch(self, msg: Dict[str, Any],
                  conn: socket.socket) -> Optional[Dict[str, Any]]:
        kind = msg.get("kind")
        if kind == "hello":
            return {"kind": "hello", "replica_id": self.replica_id,
                    "pid": os.getpid(), "proto": wire.PROTO_VERSION,
                    "state": self._state}
        if kind == "ping":
            if self._hung or faults.fires("replica-hang"):
                # the wedge: socket stays open, answer never comes —
                # only the router's liveness deadline can see this
                self._hung = True
                time.sleep(3600.0)
                return None
            return {"kind": "pong", "replica_id": self.replica_id,
                    "state": self._state, "health": self.health_row()}
        if kind == "stats":
            svc = self._service
            return {"kind": "stats", "replica_id": self.replica_id,
                    "health": self.health_row(),
                    "serving": svc.stats() if svc is not None else None}
        if kind == "drain":
            threading.Thread(target=self.drain, daemon=True,
                             name="blaze-fleet-drain").start()
            return {"kind": "draining", "replica_id": self.replica_id}
        if kind == "query":
            return self._handle_query(msg, conn)
        return {"kind": "error",
                "error": f"unknown message kind {kind!r}"}

    def _handle_query(self, msg: Dict[str, Any],
                      conn: socket.socket) -> Optional[Dict[str, Any]]:
        if self._state != "up":
            return {"kind": "result", "ok": False, "status": "draining",
                    "error": f"replica {self.replica_id} is draining",
                    "classify": "retryable",
                    "replica_id": self.replica_id}
        if faults.fires("replica-crash"):
            # host death mid-query: the router sees a connection reset,
            # never a reply — and must re-route the query end-to-end
            if self.process_mode:
                os.kill(os.getpid(), signal.SIGKILL)
            try:
                conn.close()
            finally:
                self.kill()
            return None
        from blaze_tpu.serving import QueryRejected
        with self._idle:
            self._inflight += 1
        try:
            handle = self.service().submit(
                msg["plan"], tenant=msg.get("tenant", "default"),
                deadline_ms=float(msg.get("deadline_ms", 0.0) or 0.0),
                query_id=msg.get("query_id"))
            err = handle.exception(
                timeout=float(msg.get("timeout_s", 600.0)))
            if handle.status == "done":
                with self._lock:
                    self._queries_done += 1
                return {"kind": "result", "ok": True,
                        "table": handle.result(),
                        "status": "done", "wall_s": handle.wall_s,
                        "replica_id": self.replica_id}
            with self._lock:
                self._queries_failed += 1
            return {"kind": "result", "ok": False,
                    "status": handle.status,
                    "error": repr(err) if err else handle.status,
                    "classify": (faults.classify_exception(err)
                                 if err else "fatal"),
                    "wall_s": handle.wall_s,
                    "replica_id": self.replica_id}
        except QueryRejected as e:
            with self._lock:
                self._queries_failed += 1
            # admission shed: retryable at FLEET scope — a sibling
            # replica may have queue headroom right now
            return {"kind": "result", "ok": False, "status": "rejected",
                    "error": repr(e), "classify": "retryable",
                    "replica_id": self.replica_id}
        except Exception as e:
            with self._lock:
                self._queries_failed += 1
            return {"kind": "result", "ok": False, "status": "failed",
                    "error": repr(e),
                    "classify": faults.classify_exception(e),
                    "replica_id": self.replica_id}
        finally:
            with self._idle:
                self._inflight -= 1
                if self._inflight == 0:
                    self._idle.notify_all()

    # -- health ------------------------------------------------------------

    def health_row(self) -> Dict[str, Any]:
        """One pool_health()-shaped row for this replica (the /fleet
        endpoint aggregates these next to the router's view)."""
        with self._lock:
            return {
                "replica": self.replica_id,
                "pid": os.getpid(),
                "addr": f"{self.host}:{self.port}",
                "state": self._state,
                "inflight": self._inflight,
                "queries_done": self._queries_done,
                "queries_failed": self._queries_failed,
                "uptime_s": round(
                    time.monotonic() - self._started_at, 3),
            }


def spawn_replica(replica_id: str, conf: Optional[Dict[str, Any]] = None,
                  env: Optional[Dict[str, str]] = None,
                  startup_timeout_s: float = 60.0,
                  platform: str = "cpu"):
    """Spawn one replica as a real process; returns (Popen, (host,
    port)).  The child prints a single `listening` JSON line once its
    socket is bound — the hello-before-dispatch contract at process
    granularity.  `platform` is the replica's JAX_PLATFORMS, stated by
    the spawner (host replicas by default); the child reports it back
    in its `listening` line and it is logged here.  A replica that
    would open the chip this process holds is refused."""
    import subprocess

    from blaze_tpu.bridge.placement import refuse_chip_contention
    cmd = [sys.executable, "-m", "blaze_tpu.fleet.replica",
           "--replica-id", replica_id, "--port", "0"]
    for k, v in (conf or {}).items():
        cmd += ["--conf", f"{k}={v}"]
    child_env = dict(os.environ)
    child_env["JAX_PLATFORMS"] = platform
    child_env.update(env or {})
    refuse_chip_contention(child_env, f"replica {replica_id}")
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                            stderr=subprocess.DEVNULL, text=True,
                            env=child_env)
    deadline = time.monotonic() + startup_timeout_s
    line = ""
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if not line:
            raise RuntimeError(
                f"replica {replica_id} died during startup "
                f"(exit={proc.poll()})")
        line = line.strip()
        if line.startswith("{"):
            break
    info = json.loads(line)
    if info.get("kind") != "listening":
        raise RuntimeError(
            f"replica {replica_id}: unexpected startup line {line!r}")
    log.info("replica %s (pid %s) listening on %s:%s, platform %s",
             replica_id, info.get("pid"), info["host"], info["port"],
             info.get("platform"))
    return proc, (info["host"], int(info["port"]))


def replica_main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        prog="python -m blaze_tpu.fleet.replica",
        description="serve one fleet replica until SIGTERM (drain) or "
                    "SIGKILL (crash)")
    ap.add_argument("--replica-id", required=True)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--conf", action="append", default=[],
                    metavar="KEY=VALUE",
                    help="config override, repeatable")
    ap.add_argument("--mem-bytes", type=int, default=4 << 30)
    args = ap.parse_args(argv)

    from blaze_tpu import config
    from blaze_tpu.memory import MemManager
    for item in args.conf:
        key, _, value = item.partition("=")
        config.conf.set(key, value)
    config.conf.set(config.FLEET_REPLICA_ID.key, args.replica_id)
    MemManager.init(args.mem_bytes)

    server = ReplicaServer(args.replica_id, host=args.host,
                           port=args.port, process_mode=True).start()
    done = threading.Event()

    def _sigterm(_signum, _frame):
        threading.Thread(target=lambda: (server.drain(), done.set()),
                         name="blaze-fleet-drain", daemon=True).start()

    signal.signal(signal.SIGTERM, _sigterm)
    import jax
    print(json.dumps({"kind": "listening", "host": server.host,
                      "port": server.port, "pid": os.getpid(),
                      "replica_id": args.replica_id,
                      "platform": jax.default_backend()}))
    sys.stdout.flush()
    done.wait()
    return 0


if __name__ == "__main__":
    sys.exit(replica_main())
