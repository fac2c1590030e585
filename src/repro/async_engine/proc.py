"""Multi-process socket Transport: real worker processes behind the
delivery protocol PR 6 built for exactly this backend.

Everything concurrent before this module was threads in one process.
Here the parent spawns worker processes (``multiprocessing`` "spawn"
context — fresh interpreters, no forked JAX state), a socket rendezvous
assigns worker ids, and the existing ``Envelope``/``Ack`` CRC frames
travel over length-prefixed sockets. The at-least-once machinery is
reused VERBATIM: children run the same ``ReliableSender`` retry loop
(``repro.async_engine.transport``) and the same ``execute_round`` inner
round (``repro.async_engine.engine``) the threaded runtime uses, the
parent keeps its ``DeliveryTracker`` dedup/quarantine bookkeeping, and
``FaultyTransport`` wraps the child's wire channels without touching the
protocol — ``make chaos`` runs unchanged over sockets
(``TRANSPORT=socket``).

Wire format
-----------

One frame = ``!II`` header (payload length, CRC32 of the payload bytes)
followed by a pickled tuple ``(tag, ...)``:

  parent <- child   ("join", {nonce, pid})        rendezvous hello
                    ("msg", Envelope)             credited data frame
                    ("hb", Envelope)              uncredited heartbeat
                    ("ctrl", "stats", {...})      per-channel fault tally
                    ("ctrl", "obs", {...})        low-rate span batch +
                                                  wire/compute counters
  parent -> child   ("assign", {wid, credit, cfg, faults, mode,
                               t_parent, obs, ...})
                    ("reject", reason)            no rendezvous slot
                    ("task", RoundTask, clock)    dispatched round
                    ("ack", Ack)                  delivery receipt
                    ("credit", n)                 flow-control window top-up
                    ("stop",)                     graceful shutdown

A corrupted frame on the wire (header CRC mismatch) raises ``WireError``
and tears the connection down — distinct from *injected* payload
corruption, which flips ``Envelope.crc`` before pickling and is rejected
by the parent's ``DeliveryTracker`` exactly as on the in-process path.

Rendezvous
----------

``WorkerProcessPool.ensure(wid)`` registers a one-time nonce, spawns the
child with ``(address, nonce)``, and blocks until the child connects and
presents the nonce; the parent then ASSIGNS the worker id (and ships the
``RunConfig`` + ``FaultSpec``) in the reply — ids are assigned over the
socket, never baked into argv. A join with an unknown/used nonce is
rejected (duplicate-join defense); a child that dies first fails
``ensure`` with a rendezvous error; ``close()`` stops, joins, and
terminates any straggler so no orphan process survives the parent.

Flow control
------------

Bounded backpressure matches ``InProcTransport`` semantics: each
connection holds ``capacity`` credits, a data frame costs one, and the
parent returns a credit when ``recv`` pops the message — a producer that
outruns the server parks in ``send`` (and honours timeout deadlines
exactly), no message is ever dropped by the channel itself.

Crash recovery
--------------

A dying worker process surfaces as a ``WorkerExit`` sentinel in the
parent's receive stream. The runtime respawns the process and resubmits
the pending ``RoundTask`` snapshot — a deterministic recompute of the
same round (same task_id), so deterministic mode replays the sim goldens
trace-identically straight through a mid-run process kill.
"""
from __future__ import annotations

import dataclasses
import multiprocessing as mp
import os
import pickle
import queue as _queue
import socket
import struct
import sys
import tempfile
import threading
import time
import zlib
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import numpy as np

from repro.async_engine.engine import (
    RoundResult, RoundTask, execute_round,
)
from repro.async_engine.faults import FaultyTransport
from repro.async_engine.transport import (
    AckWaiter, Envelope, KIND_ERROR, KIND_HEARTBEAT, KIND_RESULT,
    ReliableSender, Transport, TransportClosed, TransportTimeout,
    payload_crc,
)

_HDR = struct.Struct("!II")          # (payload length, CRC32 of payload)
_MAX_FRAME = 1 << 30


class WireError(Exception):
    """Malformed / checksum-failed frame on the wire (connection-fatal)."""


class RendezvousRejected(Exception):
    """The parent refused this join (unknown or already-used nonce)."""


@dataclass(frozen=True)
class WorkerExit:
    """Sentinel surfaced in the parent's receive stream when a worker
    process' connection drops outside a graceful shutdown."""
    wid: int
    incarnation: int


# ---------------------------------------------------------------------------
# Frame I/O
# ---------------------------------------------------------------------------

def _send_frame(sock: socket.socket, lock: threading.Lock, obj: Any,
                stats: Optional[Dict[str, Any]] = None) -> None:
    if stats is None:
        data = pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)
    else:
        t0 = time.perf_counter()
        data = pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)
        stats["ser_s"] += time.perf_counter() - t0
    hdr = _HDR.pack(len(data), zlib.crc32(data) & 0xFFFFFFFF)
    with lock:
        sock.sendall(hdr + data)
        if stats is not None:
            stats["frames_sent"] += 1
            stats["bytes_sent"] += len(hdr) + len(data)


def _read_exact(sock: socket.socket, n: int) -> bytes:
    chunks = []
    got = 0
    while got < n:
        chunk = sock.recv(n - got)
        if not chunk:
            raise EOFError("connection closed mid-frame")
        chunks.append(chunk)
        got += len(chunk)
    return b"".join(chunks)


def _recv_frame(sock: socket.socket,
                stats: Optional[Dict[str, Any]] = None) -> Any:
    length, crc = _HDR.unpack(_read_exact(sock, _HDR.size))
    if length > _MAX_FRAME:
        raise WireError(f"frame length {length} exceeds cap")
    data = _read_exact(sock, length)
    if zlib.crc32(data) & 0xFFFFFFFF != crc:
        if stats is not None:
            stats["crc_rejects"] += 1
        raise WireError("frame CRC mismatch on the wire")
    if stats is None:
        return pickle.loads(data)
    t0 = time.perf_counter()
    obj = pickle.loads(data)
    stats["deser_s"] += time.perf_counter() - t0
    stats["frames_recv"] += 1
    stats["bytes_recv"] += _HDR.size + length
    return obj


def _new_wire_stats() -> Dict[str, Any]:
    """Per-connection wire counters (the transport-metrics vocabulary of
    ``repro.telemetry.schema.TransportMetrics``, minus the compute
    fields). Updated under the send lock / by the single reader thread,
    so plain dict math is race-free."""
    return {"frames_sent": 0, "frames_recv": 0, "bytes_sent": 0,
            "bytes_recv": 0, "ser_s": 0.0, "deser_s": 0.0,
            "crc_rejects": 0, "credit_wait_s": 0.0}


# ---------------------------------------------------------------------------
# Host-side serialization of pytree payloads
# ---------------------------------------------------------------------------

def _np_tree(tree: Any) -> Any:
    """Device -> host: every leaf to ``np.asarray`` (fp32 bytes round-trip
    exactly, so ``payload_crc`` is identical on either side of the wire)."""
    return jax.tree.map(np.asarray, tree)


def host_task(task: RoundTask) -> RoundTask:
    """Wire form of a dispatched round: pytrees host-ified, the
    unpicklable device pin stripped (children own their devices)."""
    return dataclasses.replace(
        task, params=_np_tree(task.params), opt=_np_tree(task.opt),
        ef=_np_tree(task.ef), device=None)


def _host_envelope(env: Envelope) -> Envelope:
    if isinstance(env.payload, RoundResult):
        p = env.payload
        return dataclasses.replace(
            env, payload=dataclasses.replace(
                p, delta=_np_tree(p.delta), opt=_np_tree(p.opt),
                ef=_np_tree(p.ef)))
    return env


# ---------------------------------------------------------------------------
# Parent side: SocketTransport
# ---------------------------------------------------------------------------

class _Conn:
    """One accepted connection (registry entry + best-effort sender)."""

    def __init__(self, sock: socket.socket):
        self.sock = sock
        self.lock = threading.Lock()
        self.wid: Optional[int] = None
        self.incarnation: int = 0
        self.alive = True

    def send(self, obj: Any) -> bool:
        try:
            _send_frame(self.sock, self.lock, obj)
            return True
        except (OSError, ValueError):
            self.alive = False
            return False

    def kill(self):
        self.alive = False
        try:
            self.sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self.sock.close()
        except OSError:
            pass


def _default_family() -> str:
    fam = os.environ.get("REPRO_SOCKET_FAMILY", "")
    if fam in ("unix", "tcp"):
        return fam
    return "unix" if hasattr(socket, "AF_UNIX") else "tcp"


class SocketTransport(Transport):
    """The parent/receiver end of the socket backend — a genuine
    ``Transport``: ``send`` goes through a lazily-created loopback client
    over the real wire (so the backend is a drop-in for every transport-
    semantics test and can be wrapped by ``FaultyTransport``), ``recv``
    drains frames pushed by the per-connection reader threads. Bounded,
    FIFO per connection, close-wakes-everyone, exact timeout deadlines —
    the ``InProcTransport`` contract over sockets."""

    def __init__(self, capacity: int = 8, family: Optional[str] = None,
                 hb_sink: Optional[Transport] = None):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self.family = family or _default_family()
        self.hb_sink = hb_sink
        # pool hooks (None on a standalone transport):
        self.on_join: Optional[Callable[["_Conn", Dict], Optional[Dict]]] \
            = None
        self.on_ready: Optional[Callable[["_Conn"], None]] = None
        self.on_exit: Optional[Callable[["_Conn"], None]] = None
        self.on_control: Optional[Callable[["_Conn", str, Any], None]] = None
        self._dq: "list" = []                    # [(msg, conn-or-None)]
        lock = threading.Lock()
        self._not_empty = threading.Condition(lock)
        self._reg_lock = threading.Lock()
        self._conns: list = []
        self._closed = False
        self._tmpdir: Optional[str] = None
        self._loop_client: Optional["SocketClient"] = None
        self._loop_lock = threading.Lock()
        if self.family == "unix":
            self._tmpdir = tempfile.mkdtemp(prefix="heloco-sock-")
            path = os.path.join(self._tmpdir, "s")
            self._listener = socket.socket(socket.AF_UNIX,
                                           socket.SOCK_STREAM)
            self._listener.bind(path)
            self.address: Tuple[str, Any] = ("unix", path)
        else:
            self._listener = socket.socket(socket.AF_INET,
                                           socket.SOCK_STREAM)
            self._listener.setsockopt(socket.SOL_SOCKET,
                                      socket.SO_REUSEADDR, 1)
            self._listener.bind(("127.0.0.1", 0))
            self.address = ("tcp", self._listener.getsockname())
        self._listener.listen(64)
        self._acceptor = threading.Thread(target=self._accept_loop,
                                          name="heloco-sock-accept",
                                          daemon=True)
        self._acceptor.start()

    # -------------------------------------------------------------- accept
    def _accept_loop(self):
        while True:
            try:
                sock, _ = self._listener.accept()
            except OSError:
                return                           # listener closed
            conn = _Conn(sock)
            threading.Thread(target=self._conn_loop, args=(conn,),
                             name="heloco-sock-conn", daemon=True).start()

    def _conn_loop(self, conn: _Conn):
        try:
            frame = _recv_frame(conn.sock)
        except (EOFError, OSError, WireError, pickle.UnpicklingError):
            conn.kill()
            return
        if not (isinstance(frame, tuple) and frame
                and frame[0] == "join"):
            conn.send(("reject", "expected a join frame"))
            conn.kill()
            return
        info = frame[1] if len(frame) > 1 else {}
        if self.on_join is not None:
            payload = self.on_join(conn, info)
        else:                                    # standalone / loopback
            payload = {"wid": None, "credit": self.capacity}
        if payload is None:
            conn.send(("reject", "no pending rendezvous slot for this "
                                 "join (duplicate or unknown nonce)"))
            conn.kill()
            return
        with self._reg_lock:
            if self._closed:
                conn.send(("reject", "transport closed"))
                conn.kill()
                return
            self._conns.append(conn)
        if not conn.send(("assign", payload)):
            return
        if self.on_ready is not None:
            self.on_ready(conn)
        try:
            while True:
                frame = _recv_frame(conn.sock)
                tag = frame[0]
                if tag == "msg":
                    with self._not_empty:
                        self._dq.append((frame[1], conn))
                        self._not_empty.notify()
                elif tag == "hb":
                    if self.hb_sink is not None:
                        try:
                            self.hb_sink.send(frame[1], timeout=0.01)
                        except (TransportTimeout, TransportClosed):
                            pass                 # side channel full: drop
                    else:
                        with self._not_empty:
                            self._dq.append((frame[1], None))
                            self._not_empty.notify()
                elif tag == "ctrl":
                    if self.on_control is not None:
                        self.on_control(conn, frame[1], frame[2])
        except (EOFError, OSError, WireError, pickle.UnpicklingError):
            pass
        finally:
            conn.kill()
            with self._reg_lock:
                if conn in self._conns:
                    self._conns.remove(conn)
            if not self._closed and self.on_exit is not None:
                self.on_exit(conn)

    # ------------------------------------------------------- local inject
    def push_local(self, msg: Any):
        """Parent-side sentinel injection (``WorkerExit``): bypasses the
        wire and the credit window."""
        with self._not_empty:
            self._dq.append((msg, None))
            self._not_empty.notify()

    # ---------------------------------------------------------- Transport
    def _loopback(self) -> "SocketClient":
        with self._loop_lock:
            if self._loop_client is None or self._loop_client.closed:
                if self._closed:
                    raise TransportClosed("send on closed transport")
                self._loop_client = SocketClient.connect(
                    self.address, {"kind": "loopback"}, timeout=10.0)
                self._loop_client.start()
            return self._loop_client

    def send(self, msg: Any, timeout: Optional[float] = None) -> None:
        if self._closed:
            raise TransportClosed("send on closed transport")
        self._loopback().send_data(msg, timeout=timeout)

    def recv(self, timeout: Optional[float] = None) -> Any:
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._not_empty:
            while True:
                if self._dq:
                    msg, conn = self._dq.pop(0)
                    break
                if self._closed:
                    raise TransportClosed("recv on closed, drained "
                                          "transport")
                if deadline is None:
                    self._not_empty.wait()
                else:
                    rest = deadline - time.monotonic()
                    if rest <= 0:
                        raise TransportTimeout(f"recv idle > {timeout}s")
                    self._not_empty.wait(rest)
        if conn is not None and conn.alive:
            conn.send(("credit", 1))             # return the flow credit
        return msg

    def close(self) -> None:
        with self._not_empty:
            if self._closed:
                return
            self._closed = True
            self._not_empty.notify_all()
        try:
            self._listener.close()
        except OSError:
            pass
        with self._loop_lock:
            if self._loop_client is not None:
                self._loop_client.close()
        with self._reg_lock:
            conns, self._conns = list(self._conns), []
        for conn in conns:
            conn.kill()
        if self._tmpdir is not None:
            try:
                os.unlink(self.address[1])
            except OSError:
                pass
            try:
                os.rmdir(self._tmpdir)
            except OSError:
                pass
            self._tmpdir = None

    def depth(self) -> int:
        return len(self._dq)


# ---------------------------------------------------------------------------
# Client side (children + loopback)
# ---------------------------------------------------------------------------

class SocketClient:
    """The worker end of a connection: credited data sends, uncredited
    heartbeats, and a reader thread routing acks / tasks / credits /
    stop back to callbacks."""

    def __init__(self, sock: socket.socket):
        self._sock = sock
        self._send_lock = threading.Lock()
        self._cond = threading.Condition()
        self._credits = 0
        self.closed = False
        self.assign: Dict[str, Any] = {}
        #: cumulative wire counters (frames/bytes/ser/deser/crc/credit)
        self.wire: Dict[str, Any] = _new_wire_stats()
        #: child->parent perf_counter offset estimated at rendezvous
        #: (parent_time ~= child_time + clock_offset); 0.0 when the
        #: assign reply carried no parent timestamp (standalone mode)
        self.clock_offset = 0.0
        self.on_ack: Optional[Callable[[Any], None]] = None
        self.on_task: Optional[Callable[[Any, Any], None]] = None
        self.on_stop: Optional[Callable[[], None]] = None
        self.on_disconnect: Optional[Callable[[], None]] = None
        self._reader: Optional[threading.Thread] = None

    @classmethod
    def connect(cls, address: Tuple[str, Any], join_info: Dict,
                timeout: float = 30.0) -> "SocketClient":
        family, target = address
        if family == "unix":
            sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            sock.settimeout(timeout)
            sock.connect(target)
        else:
            sock = socket.create_connection(tuple(target), timeout=timeout)
        client = cls(sock)
        try:
            # the join->assign round trip doubles as the clock-offset
            # probe: the parent stamps its perf_counter into the assign
            # payload, and the midpoint of [t0, t1] estimates when that
            # stamp was taken on the child's clock (docs/observability.md,
            # "Cross-process collection")
            t0 = time.perf_counter()
            _send_frame(sock, client._send_lock, ("join", dict(join_info)),
                        client.wire)
            frame = _recv_frame(sock, client.wire)
            t1 = time.perf_counter()
        except (EOFError, OSError, WireError) as e:
            sock.close()
            raise RendezvousRejected(f"rendezvous failed: {e!r}") from e
        if frame[0] == "reject":
            sock.close()
            raise RendezvousRejected(frame[1])
        if frame[0] != "assign":
            sock.close()
            raise RendezvousRejected(f"unexpected frame {frame[0]!r}")
        sock.settimeout(None)
        client.assign = frame[1]
        client._credits = int(client.assign.get("credit", 8))
        t_parent = client.assign.get("t_parent")
        if t_parent is not None:
            client.clock_offset = float(t_parent) - (t0 + t1) / 2.0
        return client

    def start(self):
        self._reader = threading.Thread(target=self._read_loop,
                                        name="heloco-sock-client",
                                        daemon=True)
        self._reader.start()

    def _read_loop(self):
        try:
            while True:
                frame = _recv_frame(self._sock, self.wire)
                tag = frame[0]
                if tag == "credit":
                    with self._cond:
                        self._credits += frame[1]
                        self._cond.notify_all()
                elif tag == "ack":
                    if self.on_ack is not None:
                        self.on_ack(frame[1])
                elif tag == "task":
                    if self.on_task is not None:
                        self.on_task(frame[1], frame[2])
                elif tag == "stop":
                    if self.on_stop is not None:
                        self.on_stop()
        except (EOFError, OSError, WireError, pickle.UnpicklingError):
            pass
        finally:
            with self._cond:
                self.closed = True
                self._cond.notify_all()
            if self.on_disconnect is not None:
                self.on_disconnect()

    # --------------------------------------------------------------- sends
    def send_data(self, msg: Any, timeout: Optional[float] = None) -> None:
        """Credited send with ``InProcTransport`` blocking semantics."""
        if isinstance(msg, Envelope):
            msg = _host_envelope(msg)
        deadline = None if timeout is None else time.monotonic() + timeout
        t_wait = time.perf_counter()
        with self._cond:
            while True:
                if self.closed:
                    raise TransportClosed("send on closed transport")
                if self._credits > 0:
                    self._credits -= 1
                    # stall time spent parked on the credit window (the
                    # flow-control backpressure the panels surface)
                    self.wire["credit_wait_s"] += (time.perf_counter()
                                                   - t_wait)
                    break
                if deadline is None:
                    self._cond.wait()
                else:
                    rest = deadline - time.monotonic()
                    if rest <= 0:
                        raise TransportTimeout(
                            f"send blocked > {timeout}s (window "
                            f"exhausted)")
                    self._cond.wait(rest)
        try:
            _send_frame(self._sock, self._send_lock, ("msg", msg),
                        self.wire)
        except (OSError, ValueError) as e:
            raise TransportClosed(f"send failed: {e!r}") from e

    def send_hb(self, env: Envelope) -> None:
        """Uncredited heartbeat beacon (side channel semantics)."""
        if self.closed:
            raise TransportClosed("heartbeat on closed transport")
        try:
            _send_frame(self._sock, self._send_lock, ("hb", env), self.wire)
        except (OSError, ValueError) as e:
            raise TransportClosed(f"heartbeat failed: {e!r}") from e

    def send_ctrl(self, tag: str, obj: Any) -> None:
        _send_frame(self._sock, self._send_lock, ("ctrl", tag, obj),
                    self.wire)

    def close(self):
        with self._cond:
            self.closed = True
            self._cond.notify_all()
        try:
            self._sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self._sock.close()
        except OSError:
            pass


class _ChildChannel(Transport):
    """Child-side ``Transport`` facade over the shared ``SocketClient``
    — one per logical channel so ``FaultyTransport`` wraps data and
    heartbeats independently, exactly as the threaded runtime does."""

    def __init__(self, client: SocketClient, kind: str):
        assert kind in ("data", "hb")
        self.client = client
        self.kind = kind

    def send(self, msg: Any, timeout: Optional[float] = None) -> None:
        if self.kind == "data":
            self.client.send_data(msg, timeout=timeout)
        else:
            self.client.send_hb(msg)

    def recv(self, timeout: Optional[float] = None) -> Any:
        raise RuntimeError("child channels are send-only")

    def close(self) -> None:
        self.client.close()

    def depth(self) -> int:
        return 0


# ---------------------------------------------------------------------------
# Parent side: the worker-process pool
# ---------------------------------------------------------------------------

class WorkerProcessPool:
    """Spawns and tracks one process per worker id, owns the rendezvous,
    and bridges the runtime's submit/ack API onto per-connection frames."""

    RENDEZVOUS_TIMEOUT = 120.0

    def __init__(self, run_cfg, *, capacity: int = 8, faults=None,
                 mode: str = "deterministic", pace_scale: float = 0.0,
                 hb_sink: Optional[Transport] = None,
                 family: Optional[str] = None,
                 obs: bool = False, obs_every: int = 4):
        if jax.default_backend() != "cpu":
            # every worker process would open the accelerator the parent
            # already holds; one process drives all local chips instead
            raise RuntimeError(
                f"transport='socket' runs one JAX process per worker and "
                f"needs the CPU backend; this process holds "
                f"{jax.default_backend()!r}, whose chips belong to one "
                f"process at a time. Use transport='inproc', which runs "
                f"every worker in this process (one per chip).")
        self.run_cfg = run_cfg
        self.faults = faults
        self.mode = mode
        self.pace_scale = pace_scale
        #: cross-process observability: when set, children run their own
        #: SpanTracer + wire counters and ship ("ctrl","obs",...) frames
        #: every ``obs_every`` rounds and at graceful stop
        self.obs = bool(obs)
        self.obs_every = max(1, int(obs_every))
        #: parent hook receiving each child obs payload (runtime-owned)
        self.on_obs: Optional[Callable[[Dict], None]] = None
        #: wid -> number of obs reports received (any incarnation)
        self.obs_reports: Dict[int, int] = {}
        #: wids whose graceful final obs report arrived
        self.obs_final: set = set()
        self.transport = SocketTransport(capacity=capacity, family=family,
                                         hb_sink=hb_sink)
        self.transport.on_join = self._on_join
        self.transport.on_ready = self._on_ready
        self.transport.on_exit = self._on_exit
        self.transport.on_control = self._on_control
        self._ctx = mp.get_context("spawn")
        self._lock = threading.Lock()
        self._pending: Dict[str, Tuple[int, int]] = {}   # nonce->(wid,inc)
        self._conns: Dict[int, _Conn] = {}
        self._procs: Dict[int, Any] = {}
        self._inc: Dict[int, int] = {}
        self._ready: Dict[Tuple[int, int], threading.Event] = {}
        self._closing = False
        #: per-channel fault/protocol counters reported by children at
        #: graceful shutdown: {"data": {...}, "heartbeat": {...},
        #: "protocol": {"retries": n}}
        self.child_counters: Dict[str, Dict[str, int]] = {}
        self.proc_exits = 0
        self.clock: Tuple[Optional[float], float] = (None, pace_scale)

    # ----------------------------------------------------------- rendezvous
    def _on_join(self, conn: _Conn, info: Dict) -> Optional[Dict]:
        nonce = info.get("nonce")
        with self._lock:
            ent = self._pending.pop(nonce, None) if nonce else None
            if ent is None or self._closing:
                return None                      # reject (duplicate join)
            wid, inc = ent
            conn.wid, conn.incarnation = wid, inc
            self._conns[wid] = conn
        # t_parent lets the child estimate its clock offset against the
        # parent's perf_counter (midpoint of the join->assign round trip)
        return {"wid": wid, "credit": self.transport.capacity,
                "cfg": self.run_cfg, "faults": self.faults,
                "mode": self.mode, "pace_scale": self.pace_scale,
                "t_parent": time.perf_counter(),
                "obs": self.obs, "obs_every": self.obs_every}

    def _on_ready(self, conn: _Conn):
        ev = self._ready.get((conn.wid, conn.incarnation))
        if ev is not None:
            ev.set()

    def _on_exit(self, conn: _Conn):
        with self._lock:
            if self._closing or conn.wid is None:
                return
            if self._conns.get(conn.wid) is not conn:
                return                           # stale incarnation
            del self._conns[conn.wid]
            self.proc_exits += 1
        self.transport.push_local(WorkerExit(conn.wid, conn.incarnation))

    def _on_control(self, conn: _Conn, tag: str, obj: Any):
        if tag == "obs" and isinstance(obj, dict):
            wid = obj.get("wid", conn.wid)
            with self._lock:
                if wid is not None:
                    self.obs_reports[wid] = self.obs_reports.get(wid, 0) + 1
                    if obj.get("final"):
                        self.obs_final.add(wid)
            hook = self.on_obs
            if hook is not None:
                hook(obj)
            return
        if tag != "stats" or not isinstance(obj, dict):
            return
        with self._lock:
            for channel, counters in obj.items():
                acc = self.child_counters.setdefault(channel, {})
                for k, v in counters.items():
                    acc[k] = acc.get(k, 0) + int(v)

    # ------------------------------------------------------------ lifecycle
    def incarnation(self, wid: int) -> int:
        return self._inc.get(wid, 0)

    def alive(self, wid: int) -> bool:
        conn = self._conns.get(wid)
        return conn is not None and conn.alive

    def ensure(self, wid: int) -> Optional[int]:
        """Spawn (or respawn) the worker process for ``wid`` and complete
        the rendezvous. Returns the new incarnation when a process was
        started, None when a live one already serves the wid."""
        with self._lock:
            if self._closing:
                raise TransportClosed("worker pool closed")
            conn = self._conns.get(wid)
            if conn is not None and conn.alive:
                return None
            inc = self._inc.get(wid, 0) + 1
            self._inc[wid] = inc
            nonce = f"w{wid}-i{inc}-p{os.getpid()}"
            self._pending[nonce] = (wid, inc)
            ready = threading.Event()
            self._ready[(wid, inc)] = ready
        # children run on the CPU backend, as the parent does (see
        # __init__); spawn inherits the env
        prev = os.environ.get("JAX_PLATFORMS")
        os.environ["JAX_PLATFORMS"] = "cpu"
        try:
            proc = self._ctx.Process(target=_worker_main,
                                     args=(self.transport.address, nonce),
                                     name=f"heloco-proc-{wid}",
                                     daemon=True)
            proc.start()
        finally:
            if prev is None:
                os.environ.pop("JAX_PLATFORMS", None)
            else:
                os.environ["JAX_PLATFORMS"] = prev
        with self._lock:
            self._procs[wid] = proc
        deadline = time.monotonic() + self.RENDEZVOUS_TIMEOUT
        while not ready.wait(0.05):
            if not proc.is_alive():
                with self._lock:
                    self._pending.pop(nonce, None)
                    self._ready.pop((wid, inc), None)
                raise RuntimeError(
                    f"worker {wid} died before the rendezvous completed "
                    f"(exit code {proc.exitcode})")
            if time.monotonic() > deadline:
                proc.terminate()
                with self._lock:
                    self._pending.pop(nonce, None)
                    self._ready.pop((wid, inc), None)
                raise RuntimeError(f"worker {wid} rendezvous timed out "
                                   f"after {self.RENDEZVOUS_TIMEOUT}s")
        self._ready.pop((wid, inc), None)
        return inc

    # ------------------------------------------------------------- data path
    def submit(self, wid: int, task: RoundTask) -> None:
        """Frame a dispatched round to the worker's process. A send to a
        connection that just died is NOT an error: the reader thread
        surfaces a ``WorkerExit`` and the runtime resubmits."""
        conn = self._conns.get(wid)
        if conn is None:
            raise TransportClosed(f"worker {wid} has no live process")
        conn.send(("task", host_task(task), self.clock))

    def send_ack(self, wid: int, ack) -> None:
        conn = self._conns.get(wid)
        if conn is not None:
            conn.send(("ack", ack))

    def kill(self, wid: int) -> None:
        """Hard-remove a worker process (elastic leave / test kill).
        Deregisters first so no ``WorkerExit`` sentinel is emitted."""
        with self._lock:
            conn = self._conns.pop(wid, None)
            proc = self._procs.pop(wid, None)
        if conn is not None:
            conn.kill()
        if proc is not None and proc.is_alive():
            proc.terminate()
            proc.join(timeout=5.0)

    def close(self) -> None:
        """Graceful stop -> stats harvest -> join -> terminate stragglers
        -> close the listener. No orphan process survives this."""
        with self._lock:
            if self._closing:
                return
            self._closing = True
            conns = list(self._conns.values())
            self._conns.clear()
            procs = list(self._procs.values())
            self._procs.clear()
        for conn in conns:
            conn.send(("stop",))
        for proc in procs:
            proc.join(timeout=10.0)
        for proc in procs:
            if proc.is_alive():
                proc.terminate()
        for proc in procs:
            if proc.is_alive():
                proc.join(timeout=5.0)
        self.transport.close()


# ---------------------------------------------------------------------------
# Child side: the worker process entry point
# ---------------------------------------------------------------------------

_STOP = object()
_EOF = object()


def _worker_main(address: Tuple[str, Any], nonce: str) -> None:
    """Worker process entry (top-level: spawn re-imports this module).

    Rendezvous -> reconstruct the immutable run state from the ASSIGNED
    ``RunConfig`` (model, language specs, int8 layout — all deterministic
    in the config, so results are bit-identical to an in-process round)
    -> loop: execute ``RoundTask`` frames with the shared
    ``execute_round`` and deliver results through the shared
    ``ReliableSender``, optionally behind child-side ``FaultyTransport``
    wrappers (stream 0 = data, stream 1 = heartbeats — the same dice keys
    as the threaded runtime, so chaos runs replay identically)."""
    try:
        client = SocketClient.connect(address,
                                      {"nonce": nonce, "pid": os.getpid()})
    except RendezvousRejected:
        sys.exit(3)
    assign = client.assign
    wid = assign["wid"]
    cfg = assign["cfg"]
    faults = assign["faults"]
    mode = assign.get("mode", "deterministic")

    from repro.async_engine.runtime import RoundError
    from repro.core import packing
    from repro.data.synthetic import make_language_specs
    from repro.models import build_model

    model = build_model(cfg.model)
    specs = make_language_specs(cfg.model.vocab_size,
                                n_langs=max(cfg.n_workers, 2),
                                seed=cfg.seed)
    layout = None
    if cfg.outer.compression == "int8":
        init_params = model.init(jax.random.PRNGKey(cfg.seed))
        layout = packing.build_layout(init_params, None)
        del init_params

    clock = {"t0": None, "scale": assign.get("pace_scale", 0.0)}

    def vnow() -> float:
        t0 = clock["t0"]
        if t0 is None:
            return 0.0
        scale = clock["scale"] if clock["scale"] > 0 else 1.0
        return (time.monotonic() - t0) / scale

    tasks: "_queue.Queue" = _queue.Queue()
    waiter = AckWaiter()
    client.on_ack = waiter.put

    def on_task(task, clk):
        clock["t0"], clock["scale"] = clk
        tasks.put(task)

    def on_stop():
        tasks.put(_STOP)
        waiter.close()                   # abandon an in-flight retry loop

    def on_disconnect():
        waiter.close()
        tasks.put(_EOF)

    client.on_task = on_task
    client.on_stop = on_stop
    client.on_disconnect = on_disconnect
    client.start()

    # cross-process observability (docs/observability.md): when the
    # assign payload enables it, this child runs its own SpanTracer and
    # ships incremental span batches + cumulative wire counters to the
    # parent as low-rate ("ctrl", "obs", ...) frames every obs_every
    # rounds and once more (final=True) at graceful stop. Times stay in
    # this process's clock; the parent re-bases them via epoch_offset =
    # child_epoch + clock_offset (estimated at rendezvous).
    obs_on = bool(assign.get("obs"))
    obs_every = max(1, int(assign.get("obs_every", 4)))
    tracer = None
    compute = {"rounds": 0, "compute_s": 0.0}
    if obs_on:
        from repro.obs.spans import SpanTracer
        tracer = SpanTracer()

    def _ship_obs(final: bool = False) -> None:
        if not obs_on:
            return
        payload = {
            "wid": wid, "pid": os.getpid(), "final": bool(final),
            "offset": client.clock_offset,
            "metrics": {**client.wire, "retries": retries["n"],
                        **compute},
            "epoch_offset": tracer._epoch + client.clock_offset,
            "spans": tracer.export_new(),
        }
        try:
            client.send_ctrl("obs", payload)
        except (OSError, TransportClosed):
            pass

    data_tx: Transport = _ChildChannel(client, "data")
    hb_tx: Transport = _ChildChannel(client, "hb")
    if faults is not None:
        data_tx = FaultyTransport(data_tx, faults, stream=0, clock=vnow)
        hb_tx = FaultyTransport(hb_tx, faults, stream=1, clock=vnow)
    retries = {"n": 0}
    sender = ReliableSender(
        data_tx, spec=faults, tracer=tracer,
        on_retry=lambda env, att: retries.__setitem__("n",
                                                      retries["n"] + 1))

    last_gen = {"g": 0}
    hb_stop = threading.Event()
    if faults is not None and faults.liveness_enabled and mode == "free":
        def hb_loop():
            seq = 0
            while not hb_stop.wait(faults.heartbeat_interval):
                seq += 1
                try:
                    hb_tx.send(Envelope(wid=wid, generation=last_gen["g"],
                                        seq=seq, kind=KIND_HEARTBEAT,
                                        payload=None,
                                        sent_time=time.monotonic()),
                               timeout=0.01)
                except TransportTimeout:
                    continue
                except TransportClosed:
                    return
        threading.Thread(target=hb_loop, daemon=True).start()

    seq = 0
    while True:
        task = tasks.get()
        if task is _STOP or task is _EOF:
            break
        last_gen["g"] = task.generation
        t0 = time.monotonic()
        try:
            out: Any = execute_round(task, model=model, cfg=cfg,
                                     specs=specs, layout=layout,
                                     tracer=tracer)
        except Exception as e:                           # noqa: BLE001
            out = RoundError(task.wid, task.generation, task.round_seq,
                             repr(e))
        compute["rounds"] += 1
        compute["compute_s"] += time.monotonic() - t0
        if task.sleep_per_step > 0 and not isinstance(out, RoundError):
            rest = (task.h_steps * task.sleep_per_step
                    - (time.monotonic() - t0))
            if rest > 0:
                time.sleep(rest)
        seq += 1
        if isinstance(out, RoundError):
            env = Envelope(wid=wid, generation=task.generation, seq=seq,
                           kind=KIND_ERROR, payload=out)
        else:
            env = Envelope(wid=wid, generation=task.generation, seq=seq,
                           kind=KIND_RESULT, payload=out,
                           crc=payload_crc(out))
        if not sender.send(env, waiter):
            break                                # channel torn down
        if compute["rounds"] % obs_every == 0:
            _ship_obs()
    hb_stop.set()
    _ship_obs(final=True)
    stats: Dict[str, Dict[str, int]] = {
        "protocol": {"retries": retries["n"]}}
    if isinstance(data_tx, FaultyTransport):
        stats["data"] = dict(data_tx.counters)
        stats["heartbeat"] = dict(hb_tx.counters)
    try:
        client.send_ctrl("stats", stats)
    except (OSError, TransportClosed):
        pass
    client.close()
