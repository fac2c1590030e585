"""Wall-clock concurrent runtime: real asynchronous workers behind the
shared ``Engine`` API.

Each worker runs in its own thread (optionally pinned to its own
``jax.devices()`` entry when more than one is visible), executes the same
functional inner round as the simulator (``execute_round``), and
pushes its compressed pseudo-gradient through a ``Transport``. Two
backends: the bounded in-process queue (default) and
``transport="socket"`` — real worker *processes* behind the socket
rendezvous in ``repro.async_engine.proc``, same protocol, same commit
orders (docs/runtime.md, "Process transport").
The server loop drains arrivals and applies the packed fused update from
``Synchronizer.on_arrival`` while the other workers keep computing — the
compute/update overlap the paper's wall-clock claims rest on.

Two commit orders:

  mode="deterministic" (default)
      The virtual-clock event loop from ``EngineBase`` runs unchanged on
      the server thread; compute is merely *eager* (dispatched to the
      worker thread at capture time) instead of lazy. Arrivals are
      committed in virtual-deadline order no matter which thread finishes
      first, so with a fixed seed this runtime reproduces the simulator's
      arrival sequence ``(wid, s_i, staleness, lang)`` exactly and its
      final parameters to fp32 tolerance — the determinism contract
      (docs/runtime.md) and the acceptance anchor for every wall-clock
      experiment.

  mode="free"
      True arrival order: first pseudo-gradient through the transport is
      applied first. ``pace_scale`` maps the configured virtual paces
      onto wall-clock sleeps (a worker with pace p takes at least
      ``h * p * pace_scale`` wall seconds per round), reproducing the
      paper's (1, 2, 6, 15)-style device heterogeneity on homogeneous
      hardware. Failure / elastic event times are interpreted on the same
      scaled clock.

Unreliable delivery (docs/faults.md)
------------------------------------

The channel is never trusted. Every worker->server message is a framed
``Envelope`` (monotonic per-worker seq, generation, CRC32 of the packed
payload); the worker retries unacknowledged frames with exponential
backoff + deterministic jitter, and the server side is idempotent —
``DeliveryTracker`` dedups redeliveries by ``(wid, generation, seq)``,
rejects checksum-failed frames (never acked, so the sender retries), and
quarantines a worker after K consecutive corrupt frames. Pass
``faults=FaultSpec(...)`` to wrap the channel in a deterministic fault
injector (drop / duplicate / reorder / delay / corrupt / partition); the
committed history of a deterministic-mode run is unchanged by any
eventually-delivering fault pattern — only latency and the delivery
counters move. In free mode, workers additionally beat on a heartbeat
side channel and a liveness monitor routes silent workers through the
existing crash/rejoin generation machinery.

Fault tolerance rides the generation counters the simulator already
uses: a crash bumps the worker's generation, so the in-flight round that
eventually lands through the transport is discarded at the server —
exactly a lost round in a real deployment. The thread itself is only
torn down on elastic leave / shutdown (poison pill + transport close).
"""
from __future__ import annotations

import queue as _queue
import threading
import time
from dataclasses import dataclass, replace
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax

from repro.async_engine.engine import (
    ElasticEvent, EngineBase, FailureEvent, History, RoundResult, RoundTask,
    Worker,
)
from repro.async_engine.faults import (
    DELIVERY_COUNTERS, DeliveryTracker, FaultSpec, FaultyTransport,
)
from repro.async_engine.proc import WorkerExit, WorkerProcessPool
from repro.async_engine.transport import (
    Ack, AckWaiter, Envelope, InProcTransport, KIND_ERROR, KIND_HEARTBEAT,
    KIND_RESULT, ReliableSender, Transport, TransportClosed,
    TransportTimeout, payload_crc,
)
from repro.configs.base import RunConfig

#: transport backends selectable by name (``transport="socket"``)
TRANSPORTS = ("inproc", "socket")

PyTree = Any


@dataclass
class RoundError:
    """A worker thread raised; carried to the server and re-raised there."""
    wid: int
    generation: int
    round_seq: int
    error: str


class ConcurrentRuntime(EngineBase):
    ENGINE_NAME = "wallclock"

    #: ack wait on a fault-free channel before a (harmless) resend
    _RELIABLE_ACK_TIMEOUT = 5.0

    def __init__(self, run_cfg: RunConfig, *,
                 failures: Optional[List[FailureEvent]] = None,
                 elastic: Optional[List[ElasticEvent]] = None,
                 transport: Optional[Any] = None,
                 mode: str = "deterministic",
                 pace_scale: float = 0.0,
                 pin_devices: bool = True,
                 queue_capacity: Optional[int] = None,
                 result_timeout: float = 600.0,
                 faults: Optional[FaultSpec] = None,
                 telemetry=None, tracer=None,
                 runtime_record_every: int = 0):
        if mode not in ("deterministic", "free"):
            raise ValueError(f"mode must be 'deterministic' or 'free': {mode}")
        if faults is not None and faults.partitions and mode != "free":
            raise ValueError(
                "partition windows are defined on the free-running virtual "
                "clock; deterministic mode has no wall-to-virtual coupling "
                "to evaluate them against (use mode='free')")
        super().__init__(run_cfg, failures=failures, elastic=elastic,
                         telemetry=telemetry, tracer=tracer,
                         runtime_record_every=runtime_record_every)
        self.mode = mode
        self._run_t0: Optional[float] = None
        self.pace_scale = pace_scale
        self.result_timeout = result_timeout
        self.faults = faults
        self._capacity = queue_capacity or max(2 * len(self.workers), 4)
        self.transport_kind = "inproc"
        if isinstance(transport, str):
            if transport not in TRANSPORTS:
                raise ValueError(f"transport must be one of {TRANSPORTS} "
                                 f"or a Transport instance: {transport!r}")
            self.transport_kind = transport
            transport = None
        self._pool: Optional[WorkerProcessPool] = None
        self._last_task: Dict[int, Tuple[int, RoundTask]] = {}
        self._proc_counters: Dict[str, int] = {"proc_exits": 0,
                                               "proc_restarts": 0}
        self._channel_counters: Dict[str, Dict[str, int]] = {}
        self._own_transport = transport is None
        self._free_t0: Optional[float] = None
        # cross-process observability: child obs frames arrive on pool
        # reader threads, so merging into the tracer/telemetry is
        # lock-guarded; _child_wire keeps the latest CUMULATIVE counter
        # snapshot per (wid, pid) incarnation
        self._obs_lock = threading.Lock()
        self._child_wire: Dict[Tuple[int, int], Dict[str, Any]] = {}
        if self.transport_kind == "socket":
            # heartbeat sink first: the pool routes child beacons into it
            self._hb_channel: Transport = self._heartbeat_channel()
            self.transport = self._data_channel()
        else:
            if transport is not None and faults is not None:
                transport = self._wrap(transport, stream=0)
            self.transport = transport or self._data_channel()
            self._hb_channel = self._heartbeat_channel()
        self._sender = self._make_sender()
        self._hb_enabled = (faults is not None and faults.liveness_enabled
                            and mode == "free")
        self._delivery = DeliveryTracker(
            quarantine_after=(faults.quarantine_after if faults else 8))
        self._dlock = threading.Lock()
        self._fault_accum: Dict[str, int] = {}
        self._inboxes: Dict[int, "_queue.Queue"] = {}
        self._ack_waiters: Dict[int, AckWaiter] = {}
        self._threads: Dict[int, threading.Thread] = {}
        self._hb_threads: Dict[int, threading.Thread] = {}
        self._hb_stops: Dict[int, threading.Event] = {}
        self._last_beat: Dict[int, float] = {}
        self._miss_counted: Dict[int, int] = {}
        self._liveness_dead: set = set()
        self._quarantine_acted: set = set()
        self._results: Dict[int, RoundResult] = {}      # task_id -> result
        self._computing = 0
        self._comp_lock = threading.Lock()
        self._shut = False
        self.stats: Dict[str, Any] = {
            "mode": mode, "arrivals": 0, "server_busy_seconds": 0.0,
            "wall_seconds": 0.0, "queue_depth_samples": [],
            "overlap_samples": [], "compute_seconds_total": 0.0,
        }
        devices = jax.devices()
        # the server state lives on the default device, devices[0]
        self._server_device = devices[0]
        if pin_devices and len(devices) > 1 and self.transport_kind != "socket":
            for w in self.workers.values():
                w.device = devices[w.wid % len(devices)]

    # ------------------------------------------------------------- channels
    def _virtual_now(self) -> float:
        """Free-running virtual clock (partition windows live on it)."""
        if self._free_t0 is None:
            return 0.0
        scale = self.pace_scale if self.pace_scale > 0 else 1.0
        return (time.monotonic() - self._free_t0) / scale

    def _wrap(self, inner: Transport, stream: int) -> Transport:
        return FaultyTransport(inner, self.faults, stream=stream,
                               clock=self._virtual_now)

    def _data_channel(self) -> Transport:
        if self.transport_kind == "socket":
            # the pool's SocketTransport is deliberately UNWRAPPED here:
            # the worker processes inject faults on their side of the
            # wire (same streams, same dice), so wrapping again would
            # double-inject
            self._pool = WorkerProcessPool(
                self.cfg, capacity=self._capacity, faults=self.faults,
                mode=self.mode, pace_scale=self.pace_scale,
                hb_sink=self._hb_channel,
                obs=(self.tracer.enabled or self.telemetry is not None))
            self._pool.on_obs = self._on_obs
            return self._pool.transport
        inner = InProcTransport(self._capacity)
        return self._wrap(inner, stream=0) if self.faults else inner

    def _heartbeat_channel(self) -> Transport:
        # side channel: beacons never queue behind pseudo-gradient
        # backpressure, and partitions silence them like any other frame
        inner = InProcTransport(max(64 * max(len(self.workers), 1), 256))
        if self.transport_kind == "socket":
            return inner                 # children wrap their own hb stream
        return self._wrap(inner, stream=1) if self.faults else inner

    def _make_sender(self) -> ReliableSender:
        return ReliableSender(
            self.transport, spec=self.faults, tracer=self.tracer,
            default_timeout=self._RELIABLE_ACK_TIMEOUT,
            on_retry=lambda env, attempt: self._bump("retries"))

    # ------------------------------------------------------- worker threads
    def _start_worker_thread(self, wid: int):
        inbox: "_queue.Queue[Optional[RoundTask]]" = _queue.Queue()
        waiter = AckWaiter()
        self._inboxes[wid] = inbox
        self._ack_waiters[wid] = waiter
        # a (re)started thread begins a fresh delivery stream
        self._delivery.reset_stream(wid)
        self._last_beat[wid] = time.monotonic()
        self._miss_counted[wid] = 0
        t = threading.Thread(target=self._worker_loop,
                             args=(wid, inbox, waiter),
                             name=f"heloco-worker-{wid}", daemon=True)
        self._threads[wid] = t
        t.start()
        if self._hb_enabled:
            stop = threading.Event()
            self._hb_stops[wid] = stop
            ht = threading.Thread(target=self._heartbeat_loop,
                                  args=(wid, stop),
                                  name=f"heloco-hb-{wid}", daemon=True)
            self._hb_threads[wid] = ht
            ht.start()

    def _worker_loop(self, wid: int, inbox, waiter: AckWaiter):
        seq = 0                          # per-stream monotonic frame counter
        while True:
            task = inbox.get()
            if task is None:
                return
            t0 = time.monotonic()
            with self._comp_lock:
                self._computing += 1
            try:
                out: Any = (self._execute_pinned(task)
                            if task.device is not None
                            else self._execute(task))
            except Exception as e:                      # noqa: BLE001
                out = RoundError(task.wid, task.generation,
                                 task.round_seq, repr(e))
            finally:
                # the throttle sleep below is emulated device time, not
                # real compute: keep it out of the overlap evidence
                with self._comp_lock:
                    self._computing -= 1
            # pace throttle: a device at `pace` sec/step takes at least
            # h * pace * pace_scale wall seconds per round
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
            if not self._send_reliably(env, waiter):
                return                              # channel torn down

    def _execute_pinned(self, task: RoundTask) -> RoundResult:
        """Run a round on the worker's own device. Committed arrays
        override ``jax.default_device``, so the round's params, optimiser
        state and error feedback are first placed on that device; the
        delta then goes back to the server's device, where it is
        committed."""
        dev = task.device
        task = replace(task, params=jax.device_put(task.params, dev),
                       opt=jax.device_put(task.opt, dev),
                       ef=jax.device_put(task.ef, dev))
        with jax.default_device(dev):
            res = self._execute(task)
        res.delta = jax.device_put(res.delta, self._server_device)
        return res

    def _send_reliably(self, env: Envelope, waiter: AckWaiter) -> bool:
        """At-least-once send via the shared ``ReliableSender`` (the same
        class the socket worker processes run). Returns False when the
        channel is gone."""
        return self._sender.send(env, waiter)

    def _heartbeat_loop(self, wid: int, stop: threading.Event):
        """Liveness side channel: one beacon per interval until the
        worker is torn down. Beacons ride the same fault injector as data
        frames, so a partition silences them — which is exactly how the
        server detects it."""
        interval = self.faults.heartbeat_interval
        seq = 0
        while not stop.wait(interval):
            seq += 1
            w = self.workers.get(wid)
            gen = w.generation if w is not None else 0
            try:
                self._hb_channel.send(
                    Envelope(wid=wid, generation=gen, seq=seq,
                             kind=KIND_HEARTBEAT, payload=None,
                             sent_time=time.monotonic()),
                    timeout=0.01)
            except TransportTimeout:
                continue                         # channel full: drop beacon
            except TransportClosed:
                return

    # --------------------------------------------------------- engine hooks
    def _use_virtual_clock(self) -> bool:
        return self.mode == "deterministic"

    def _sleep_per_step(self, w: Worker) -> float:
        return w.pace * self.pace_scale if self.mode == "free" else 0.0

    def _submit(self, task: RoundTask):
        self._ensure_open()
        if self._pool is not None:
            inc = self._pool.ensure(task.wid)
            if inc is not None:          # fresh process: fresh stream
                self._delivery.reset_stream(task.wid)
                self._last_beat[task.wid] = time.monotonic()
                self._miss_counted[task.wid] = 0
            self._pool.clock = (self._free_t0, self.pace_scale)
            self._last_task[task.wid] = (self._pool.incarnation(task.wid),
                                         task)
            self._pool.submit(task.wid, task)
            return
        th = self._threads.get(task.wid)
        if th is None or not th.is_alive():
            self._start_worker_thread(task.wid)
        self._inboxes[task.wid].put(task)

    def _recv_result(self, timeout: Optional[float] = None) -> RoundResult:
        """One *accepted* transport message, with stats + error
        unwrapping. Duplicate, corrupt, and quarantined frames are
        consumed (and acked/rejected per the delivery protocol) without
        being returned. With an explicit ``timeout`` the
        ``TransportTimeout`` propagates (polling callers keep their event
        clock ticking); without one it is a hard liveness failure."""
        budget = self.result_timeout if timeout is None else timeout
        deadline = time.monotonic() + budget
        while True:
            rest = deadline - time.monotonic()
            try:
                if rest <= 0:
                    raise TransportTimeout(f"recv idle > {budget}s")
                msg = self.transport.recv(timeout=rest)
            except TransportTimeout:
                if timeout is not None:
                    raise
                raise RuntimeError(
                    f"no arrival within {self.result_timeout}s — worker "
                    f"thread/process dead, wedged, or quarantined (threads "
                    f"alive: "
                    f"{[w for w, t in self._threads.items() if t.is_alive()]},"
                    f" procs alive: "
                    f"{[w for w in self.workers if self._pool is not None and self._pool.alive(w)]},"
                    f" quarantined: {sorted(self._delivery.quarantined)})")
            if isinstance(msg, WorkerExit):
                self._handle_worker_exit(msg)
                continue
            if isinstance(msg, Envelope):
                payload = self._process_envelope(msg)
                if payload is None:
                    continue                     # dup / reject / heartbeat
                msg = payload
            self.stats["queue_depth_samples"].append(self.transport.depth())
            if isinstance(msg, RoundError):
                raise RuntimeError(
                    f"worker {msg.wid} round {msg.round_seq} failed: "
                    f"{msg.error}")
            self.stats["compute_seconds_total"] += msg.compute_seconds
            return msg

    # ------------------------------------------------- process supervision
    def _handle_worker_exit(self, ev: WorkerExit):
        """A worker process died outside a graceful shutdown. If the
        round the engine is waiting on was submitted to exactly that
        incarnation, respawn the process and resubmit the SAME task
        snapshot — a deterministic recompute of the same round (same
        task_id), so deterministic replay sails straight through a
        mid-run process kill. Anything else (stale incarnation, worker
        already crashed/departed) needs no action: the generation
        machinery has it covered."""
        self._proc_counters["proc_exits"] += 1
        if self._pool is None or self._shut:
            return
        entry = self._last_task.get(ev.wid)
        w = self.workers.get(ev.wid)
        if (entry is not None and w is not None and w.alive
                and entry[0] == ev.incarnation
                and w.pending_task_id is not None
                and entry[1].task_id == w.pending_task_id):
            self._proc_counters["proc_restarts"] += 1
            self._telemetry_fault("proc_restart", wid=ev.wid)
            self._submit(entry[1])

    # --------------------------------------------------- delivery protocol
    def _process_envelope(self, env: Envelope) -> Optional[Any]:
        """Idempotent-commit gate: CRC verification, (wid, generation,
        seq) dedup, quarantine policy, ack routing. Returns the payload
        only for first-time, checksum-clean deliveries."""
        if env.kind == KIND_HEARTBEAT:
            self._note_heartbeat(env)            # stray beacon: harmless
            return None
        verdict = self._delivery.process(env)
        if verdict.ack:
            self._send_ack(env, quarantined=env.wid
                           in self._delivery.quarantined)
        if verdict.quarantine:
            self._on_quarantine(env)
        elif verdict.status == "reject":
            self._telemetry_fault("checksum_reject", env)
        elif verdict.status == "dup":
            self._telemetry_fault("dedup", env)
        if verdict.status != "accept":
            return None
        return env.payload

    def _send_ack(self, env: Envelope, quarantined: bool = False):
        spec = self.faults
        if (spec is not None and not quarantined
                and spec.drops_ack(env.wid, env.seq, env.attempt)):
            self._bump("acks_dropped")           # lost receipt -> redelivery
            return
        if self._pool is not None:
            self._pool.send_ack(env.wid,
                                Ack(wid=env.wid, generation=env.generation,
                                    seq=env.seq, quarantined=quarantined))
            return
        waiter = self._ack_waiters.get(env.wid)
        if waiter is not None:
            waiter.put(Ack(wid=env.wid, generation=env.generation,
                           seq=env.seq, quarantined=quarantined))

    def _on_quarantine(self, env: Envelope):
        """K consecutive corrupt frames: stop accepting this worker.
        Free mode degrades gracefully (the worker leaves the rotation via
        the crash machinery, no restart); deterministic mode records the
        quarantine and the event loop surfaces a hard liveness error if
        it ends up starved of that worker's round."""
        if env.wid in self._quarantine_acted:
            return                      # already handled the transition
        self._quarantine_acted.add(env.wid)
        self._telemetry_fault("quarantine", env)
        w = self.workers.get(env.wid)
        if w is not None and w.alive and self.mode == "free":
            self._crash_worker(w)

    def _bump(self, key: str, n: int = 1):
        with self._dlock:
            self._delivery.counters[key] += n

    def _telemetry_fault(self, event: str, env: Optional[Envelope] = None,
                         wid: Optional[int] = None, detail=None):
        if self.telemetry is None:
            return
        self.telemetry.record_fault(
            event=event,
            wid=env.wid if env is not None else (-1 if wid is None else wid),
            seq=env.seq if env is not None else -1,
            generation=env.generation if env is not None else -1,
            detail=detail)

    # ------------------------------------------------------------- liveness
    def _note_heartbeat(self, env: Envelope):
        wid = env.wid
        # measure silence between *send* instants, not drain instants:
        # beacons queue on the side channel and the server may drain late
        beat_t = env.sent_time or time.monotonic()
        w = self.workers.get(wid)
        if (self._hb_enabled and w is not None and w.alive
                and wid not in self._liveness_dead):
            last = self._last_beat.get(wid)
            interval = self.faults.heartbeat_interval
            if last is not None and beat_t > last:
                missed = int((beat_t - last) / interval)
                if missed >= self.faults.liveness_misses:
                    # the worker WAS silent past the death threshold and
                    # only now resurfaced — the sweep may not have caught
                    # it in the act, but the semantics are the same:
                    # declare the death retroactively (generation bump
                    # drops whatever it computed while partitioned), then
                    # let this very beacon revive it below
                    counted = self._miss_counted.get(wid, 0)
                    if missed > counted:
                        self._bump("heartbeat_misses", missed - counted)
                    self._liveness_dead.add(wid)
                    self._bump("liveness_deaths")
                    self._telemetry_fault("liveness_dead", wid=wid)
                    self._crash_worker(w)
        self._last_beat[wid] = max(beat_t, self._last_beat.get(wid, 0.0))
        self._miss_counted[wid] = 0
        if (wid in self._liveness_dead and w is not None and not w.alive
                and wid not in self._delivery.quarantined):
            # the silent worker is back: rejoin through the generation
            # machinery (its lost round can never commit)
            self._liveness_dead.discard(wid)
            w.alive = True
            self._bump("liveness_revivals")
            self._telemetry_fault("liveness_revive", wid=wid)
            self._dispatch(w)

    def _drain_heartbeats(self):
        if not self._hb_enabled:
            return
        while True:
            try:
                env = self._hb_channel.recv(timeout=0.0)
            except (TransportTimeout, TransportClosed):
                return
            if isinstance(env, Envelope) and env.kind == KIND_HEARTBEAT:
                self._note_heartbeat(env)

    def _check_liveness(self):
        """Declare workers whose beacons stopped dead after
        ``liveness_misses`` whole intervals — the crash/rejoin machinery
        handles the rest (generation bump drops the in-flight round; a
        returning beacon revives the worker)."""
        if not self._hb_enabled:
            return
        interval = self.faults.heartbeat_interval
        now = time.monotonic()
        for wid, w in list(self.workers.items()):
            if not w.alive or wid in self._liveness_dead:
                continue
            last = self._last_beat.get(wid)
            if last is None:
                continue
            missed = int((now - last) / interval)
            counted = self._miss_counted.get(wid, 0)
            if missed > counted:
                self._bump("heartbeat_misses", missed - counted)
                self._miss_counted[wid] = missed
            if missed >= self.faults.liveness_misses:
                self._liveness_dead.add(wid)
                self._bump("liveness_deaths")
                self._telemetry_fault("liveness_dead", wid=wid)
                self._crash_worker(w)

    # ----------------------------------------------------------- commit path
    def _is_current(self, res: RoundResult) -> bool:
        """A result counts only if it is the round its worker is waiting
        on. Task ids are engine-unique, so a departed incarnation of a
        reused wid (or a crashed generation) can never be mistaken for
        the live worker's round."""
        w = self.workers.get(res.wid)
        return w is not None and res.task_id == w.pending_task_id

    def _obtain(self, w: Worker) -> RoundResult:
        """Block until THIS worker's outstanding round has landed; results
        from other workers are parked, stale rounds dropped (lost
        in-flight rounds of crashed / departed workers)."""
        want = w.pending_task_id
        while want not in self._results:
            res = self._recv_result()
            if self._is_current(res):
                self._results[res.task_id] = res
        return self._results.pop(want)

    def _commit(self, w: Worker, res: RoundResult):
        with self._comp_lock:
            overlap = self._computing
        t0 = time.monotonic()
        rec = super()._commit(w, res)
        # materialize the outer step so busy time is real, not dispatch time
        jax.block_until_ready(self.server._pbuf if self.server.packed
                              else jax.tree.leaves(self.server.state.params))
        self.stats["server_busy_seconds"] += time.monotonic() - t0
        self.stats["overlap_samples"].append(overlap)
        self.stats["arrivals"] += 1
        return rec

    def _commit_batch(self, pairs, reason: str = "batch-full"):
        with self._comp_lock:
            overlap = self._computing
        t0 = time.monotonic()
        recs = super()._commit_batch(pairs, reason=reason)
        jax.block_until_ready(self.server._pbuf if self.server.packed
                              else jax.tree.leaves(self.server.state.params))
        self.stats["server_busy_seconds"] += time.monotonic() - t0
        self.stats["overlap_samples"].append(overlap)
        self.stats["arrivals"] += len(pairs)
        return recs

    def _crash_worker(self, w: Worker):
        if w.pending_task_id is not None:               # drop a parked result
            self._results.pop(w.pending_task_id, None)
        super()._crash_worker(w)

    def _on_worker_removed(self, w: Worker):
        if self._pool is not None:
            self._pool.kill(w.wid)
        self._last_task.pop(w.wid, None)
        inbox = self._inboxes.pop(w.wid, None)
        if inbox is not None:
            inbox.put(None)                             # poison pill
        waiter = self._ack_waiters.pop(w.wid, None)
        if waiter is not None:
            waiter.close()                              # unblock a retry loop
        stop = self._hb_stops.pop(w.wid, None)
        if stop is not None:
            stop.set()
        self._hb_threads.pop(w.wid, None)
        self._threads.pop(w.wid, None)
        if w.pending_task_id is not None:
            self._results.pop(w.pending_task_id, None)

    # ------------------------------------------------------------ lifecycle
    def _ensure_open(self):
        if self._shut:
            if not self._own_transport:
                raise RuntimeError("transport closed; inject a fresh one")
            self._fold_fault_counters()
            if self.transport_kind == "socket":
                self._hb_channel = self._heartbeat_channel()
                self.transport = self._data_channel()   # fresh pool
            else:
                self.transport = self._data_channel()
                self._hb_channel = self._heartbeat_channel()
            self._sender = self._make_sender()
            self._shut = False

    def _fold_fault_counters(self):
        """Carry injected-fault counts across channel rebuilds."""
        for name, tr in (("data", self.transport),
                         ("heartbeat", self._hb_channel)):
            if isinstance(tr, FaultyTransport):
                acc = self._channel_counters.setdefault(name, {})
                for k, v in tr.counters.items():
                    self._fault_accum[k] = self._fault_accum.get(k, 0) + v
                    acc[k] = acc.get(k, 0) + v

    def _harvest_child_counters(self):
        """Fold the per-channel counters the worker processes reported at
        graceful shutdown into the run totals: injected faults join
        ``_fault_accum`` (so ``delivery_stats`` matches the in-process
        backend), protocol retries join the delivery counters."""
        if self._pool is None:
            return
        for channel, counters in self._pool.child_counters.items():
            acc = self._channel_counters.setdefault(channel, {})
            for k, v in counters.items():
                acc[k] = acc.get(k, 0) + v
                if channel == "protocol":
                    if k in DELIVERY_COUNTERS:
                        self._bump(k, v)
                else:
                    self._fault_accum[k] = self._fault_accum.get(k, 0) + v
        self._pool.child_counters.clear()

    # ------------------------------------------- cross-process observability
    def _on_obs(self, payload: Dict) -> None:
        """One child ("ctrl", "obs", ...) frame: merge the span batch into
        the parent tracer as a per-pid process row and emit a cumulative
        "transport" telemetry record. Runs on a pool reader thread —
        everything shared is taken under ``_obs_lock``. Observation only:
        never touches the engine/jax state."""
        try:
            wid = int(payload["wid"])
            pid = int(payload["pid"])
        except (KeyError, TypeError, ValueError):
            return                       # malformed frame: drop, never raise
        metrics = payload.get("metrics") or {}
        final = bool(payload.get("final"))
        offset = float(payload.get("offset", 0.0))
        with self._obs_lock:
            self._child_wire[(wid, pid)] = dict(metrics, final=final,
                                                clock_offset_s=offset)
            if self.tracer.enabled and payload.get("spans") is not None:
                spans = payload["spans"]
                self.tracer.ingest_remote(
                    pid=pid,
                    epoch_offset=float(payload.get("epoch_offset", 0.0)),
                    events=spans.get("events", []),
                    names=spans.get("names", {}),
                    process_name=f"heloco-worker-{wid} (pid {pid})")
            if self.telemetry is not None:
                self.telemetry.record_transport(
                    wid=wid, pid=pid,
                    frames_sent=int(metrics.get("frames_sent", 0)),
                    frames_recv=int(metrics.get("frames_recv", 0)),
                    bytes_sent=int(metrics.get("bytes_sent", 0)),
                    bytes_recv=int(metrics.get("bytes_recv", 0)),
                    ser_s=float(metrics.get("ser_s", 0.0)),
                    deser_s=float(metrics.get("deser_s", 0.0)),
                    crc_rejects=int(metrics.get("crc_rejects", 0)),
                    retries=int(metrics.get("retries", 0)),
                    credit_wait_s=float(metrics.get("credit_wait_s", 0.0)),
                    rounds=int(metrics.get("rounds", 0)),
                    compute_s=float(metrics.get("compute_s", 0.0)),
                    clock_offset_s=offset, final=final)

    def child_obs_report(self) -> Dict[str, Any]:
        """What the worker processes reported in: per-wid obs frame
        counts, which wids closed with a final report, and the summed
        latest-cumulative wire counters across all (wid, pid)
        incarnations. Empty when not on the socket transport."""
        if self._pool is None:
            return {"reports": {}, "final": [], "wire": {}}
        with self._obs_lock:
            wire: Dict[str, float] = {}
            for snap in self._child_wire.values():
                for k, v in snap.items():
                    if isinstance(v, (int, float)) and not isinstance(v, bool):
                        wire[k] = wire.get(k, 0) + v
        return {"reports": dict(self._pool.obs_reports),
                "final": sorted(self._pool.obs_final),
                "wire": wire}

    def assert_child_reports(self) -> None:
        """Loud check that every worker process the run dispatched to
        actually shipped observability frames back (satellite of the
        --trace/--stats-json/--telemetry launcher contract): a silent
        child means the collection path is broken, not that the run was
        quiet. Only meaningful on the socket transport with obs on."""
        if self._pool is None or not self._pool.obs:
            return
        dispatched = set(self._pool.obs_reports)
        silent = sorted(w for w in self._last_task
                        if w not in dispatched)
        if silent:
            raise RuntimeError(
                f"cross-process observability enabled but worker(s) "
                f"{silent} never reported in over the obs control "
                f"channel (reports: {dict(self._pool.obs_reports)}) — "
                f"child-side collection is broken or the processes died "
                f"before their first report")

    def shutdown(self):
        """Tear down worker threads/processes. Idempotent; ``run``/
        ``restore`` after shutdown transparently rebuild the channel +
        workers."""
        self._shut = True
        if self._pool is not None:
            self._pool.close()          # stop -> stats harvest -> join
            self._harvest_child_counters()
        else:
            self.transport.close()
        self._hb_channel.close()
        for stop in self._hb_stops.values():
            stop.set()
        for inbox in self._inboxes.values():
            inbox.put(None)
        for waiter in self._ack_waiters.values():
            waiter.close()
        for t in list(self._threads.values()) + list(self._hb_threads.values()):
            t.join(timeout=5.0)
        self._inboxes.clear()
        self._ack_waiters.clear()
        self._threads.clear()
        self._hb_threads.clear()
        self._hb_stops.clear()
        self._results.clear()

    # ------------------------------------------- runtime health snapshots
    def _runtime_snapshot(self) -> Dict:
        """Live counters for a telemetry "runtime" record: everything
        ``stats_summary()`` reports at exit, snapshotted mid-run, plus
        liveness states and the delivery/fault counters. Observation
        only — reads counters the run maintains anyway."""
        snap = super()._runtime_snapshot()
        wall = (time.monotonic() - self._run_t0
                if self._run_t0 is not None else 0.0)
        arrivals = self.stats["arrivals"]
        snap.update(
            arrivals=arrivals,
            arrivals_per_sec=arrivals / wall if wall > 0 else 0.0,
            server_occupancy=(self.stats["server_busy_seconds"] / wall
                              if wall > 0 else 0.0),
            compute_parallelism=(self.stats["compute_seconds_total"] / wall
                                 if wall > 0 else 0.0),
            queue_depth=self.transport.depth(),
            liveness={
                "dead": len(self._liveness_dead),
                "quarantined": len(self._delivery.quarantined),
                "threads_alive": sum(1 for t in self._threads.values()
                                     if t.is_alive()),
            },
            delivery={k: float(v)
                      for k, v in self.delivery_stats().items() if v})
        return snap

    # -------------------------------------------------------------- run
    def run(self, eval_every: int = 0,
            eval_fn: Optional[Callable[[PyTree, int, float], Dict]] = None,
            ckpt_every: int = 0, ckpt_dir: str = "",
            budget=None) -> History:
        t0 = time.monotonic()
        self._run_t0 = t0
        try:
            hist = super().run(eval_every, eval_fn, ckpt_every, ckpt_dir,
                               budget)
        finally:
            self.stats["wall_seconds"] += time.monotonic() - t0
            self.shutdown()
        return hist

    def _loop(self, eval_every, eval_fn, ckpt_every, ckpt_dir,
              budget=None) -> History:
        if self.mode == "free" and not self.server.method.sync:
            return self._run_free(eval_every, eval_fn, ckpt_every, ckpt_dir,
                                  budget)
        return super()._loop(eval_every, eval_fn, ckpt_every, ckpt_dir,
                             budget)

    def _finalize(self, eval_fn) -> History:
        hist = super()._finalize(eval_fn)
        if self.telemetry is not None:
            d = self.delivery_stats()
            if any(d.values()):
                self._telemetry_fault(
                    "summary", detail={k: float(v) for k, v in d.items()})
        return hist

    # ------------------------------------------------------- free-run loop
    def _run_free(self, eval_every, eval_fn, ckpt_every, ckpt_dir,
                  budget=None) -> History:
        """True arrival order on the wall clock. ``self.time`` is reported
        in virtual seconds (wall / pace_scale) so histories stay
        comparable with the simulator; with pace_scale == 0 it is raw wall
        seconds. Failure / elastic / restart times live on that clock.
        A ``Budget`` is accounted on the same clock (fixed_wallclock) or
        on committed tokens (fixed_tokens). Heartbeat liveness runs here:
        every loop iteration drains the side channel and sweeps for
        silent workers."""
        target = self.cfg.outer_steps
        self._free_t0 = t0 = time.monotonic()
        scale = self.pace_scale if self.pace_scale > 0 else 1.0
        fail_idx = el_idx = 0
        restarts: List[Tuple[float, int]] = []
        for w in self.workers.values():
            if w.alive and not w.in_flight:
                self._dispatch(w)

        def vnow() -> float:
            return (time.monotonic() - t0) / scale

        def process_events(vt: float):
            nonlocal fail_idx, el_idx
            while (fail_idx < len(self.failures)
                   and self.failures[fail_idx].time <= vt):
                ev = self.failures[fail_idx]
                fail_idx += 1
                w = self.workers.get(ev.wid)
                if w is None:
                    continue
                self._crash_worker(w)
                restarts.append((ev.time + ev.restart_delay, ev.wid))
                restarts.sort()
            while (el_idx < len(self.elastic)
                   and self.elastic[el_idx].time <= vt):
                self._handle_elastic(self.elastic[el_idx])
                el_idx += 1
            while restarts and restarts[0][0] <= vt:
                _, wid = restarts.pop(0)
                w = self.workers.get(wid)
                if w is not None and not w.alive:
                    w.alive = True
                    self._dispatch(w)

        def progress_possible() -> bool:
            """Someone will eventually produce an arrival: a live worker,
            a pending restart, an unfired failure/elastic event, or a
            liveness-dead worker whose beacon may yet return."""
            return (any(w.alive for w in self.workers.values())
                    or bool(restarts)
                    or bool(self._liveness_dead)
                    or fail_idx < len(self.failures)
                    or el_idx < len(self.elastic))

        while self.server.t < target and not self._stop:
            process_events(vnow())
            self._drain_heartbeats()
            self._check_liveness()
            if not progress_possible():
                break                   # every worker gone: starved run
            if budget is not None and budget.over_time(vnow()):
                break                   # clock horizon: stop committing
            try:
                msg = self._recv_result(timeout=0.05)
            except TransportTimeout:
                continue                # keep event clock ticking
            if not self._is_current(msg) or not self.workers[msg.wid].alive:
                continue                # stale: crashed / departed worker
            w = self.workers[msg.wid]
            self.time = vnow()
            if budget is not None and budget.over_time(self.time):
                break                   # arrived past the horizon: drop it
            # with commit_batch > 1, drain whatever else already landed
            # (non-blocking) and coalesce into one fused flush — same
            # labelled cap discipline as the deterministic loop, so a
            # batch never overshoots an eval/ckpt/close boundary. With
            # commit_batch == 1 the cap is 1 and this is exactly the old
            # single-commit path.
            limits = [(self.server.commit_batch, "batch-full"),
                      (target - self.server.t, "close")]
            if eval_every:
                limits.append(
                    (eval_every - self.server.t % eval_every, "eval"))
            if ckpt_every:
                limits.append(
                    (ckpt_every - self.server.t % ckpt_every, "ckpt"))
            cap, flush_reason = min(limits, key=lambda kv: kv[0])
            batch: List[Tuple[Worker, RoundResult]] = [(w, msg)]
            while len(batch) < cap:
                try:
                    extra = self._recv_result(timeout=0.001)
                except TransportTimeout:
                    break               # queue drained: commit what we have
                if (not self._is_current(extra)
                        or not self.workers[extra.wid].alive):
                    continue
                batch.append((self.workers[extra.wid], extra))
            if len(batch) == 1:
                self._commit(w, msg)
            else:
                self._commit_batch(batch, reason=flush_reason)
            self._post_commit(eval_every, eval_fn, ckpt_every, ckpt_dir)
            if budget is not None and budget.over_tokens(self.history.tokens):
                break
            if self.server.t < target:
                process_events(vnow())
                for bw, _ in batch:
                    if bw.alive:
                        self._dispatch(bw)
        self.time = vnow()
        return self._finalize(eval_fn)

    # -------------------------------------------------------- sync barrier
    def _execute_sync(self, tasks: List[RoundTask]) -> List[RoundResult]:
        """Sync DiLoCo round with genuinely parallel workers: all inner
        rounds run concurrently, the barrier is the transport collect."""
        for task in tasks:
            self._submit(task)
        want = {t.task_id: i for i, t in enumerate(tasks)}
        got: Dict[int, RoundResult] = {}
        while len(got) < len(tasks):
            res = self._recv_result()
            idx = want.get(res.task_id)
            if idx is not None:
                got[idx] = res
        return [got[i] for i in range(len(tasks))]

    # ----------------------------------------------------------- reporting
    def delivery_stats(self) -> Dict[str, int]:
        """Delivery-health counters: protocol events (retries, dedups,
        checksum rejects, quarantines, heartbeat misses, liveness
        transitions) plus the injected-fault tallies of the faulty
        channel(s)."""
        with self._dlock:
            out = {k: self._delivery.counters[k] for k in DELIVERY_COUNTERS}
        for k, v in self._fault_accum.items():
            out[k] = out.get(k, 0) + v
        for tr in (self.transport, self._hb_channel):
            if isinstance(tr, FaultyTransport):
                for k, v in tr.counters.items():
                    out[k] = out.get(k, 0) + v
        for k, v in self._proc_counters.items():
            if v:
                out[k] = out.get(k, 0) + v
        return out

    def delivery_channels(self) -> Dict[str, Dict[str, int]]:
        """Per-channel view of the injected-fault / protocol counters.
        In-process mode reads the live ``FaultyTransport`` wrappers;
        socket mode reports what the worker processes tallied on their
        side of the wire (harvested at graceful shutdown), keyed
        "data" / "heartbeat" / "protocol"."""
        out = {k: dict(v) for k, v in self._channel_counters.items()}
        for name, tr in (("data", self.transport),
                         ("heartbeat", self._hb_channel)):
            if isinstance(tr, FaultyTransport):
                acc = out.setdefault(name, {})
                for k, v in tr.counters.items():
                    acc[k] = acc.get(k, 0) + v
        return out

    def stats_summary(self) -> Dict[str, Any]:
        q = self.stats["queue_depth_samples"]
        ov = self.stats["overlap_samples"]
        wall = max(self.stats["wall_seconds"], 1e-9)
        return {
            "mode": self.mode,
            "arrivals": self.stats["arrivals"],
            "wall_seconds": self.stats["wall_seconds"],
            "arrivals_per_sec": self.stats["arrivals"] / wall,
            "server_busy_seconds": self.stats["server_busy_seconds"],
            "server_occupancy": self.stats["server_busy_seconds"] / wall,
            "compute_seconds_total": self.stats["compute_seconds_total"],
            # >1.0 means workers computed more seconds than wall passed:
            # genuine concurrency
            "compute_parallelism": self.stats["compute_seconds_total"] / wall,
            "queue_depth_mean": (sum(q) / len(q)) if q else 0.0,
            "queue_depth_max": max(q) if q else 0,
            # workers mid-round at the moment the server applied an update
            "overlap_mean": (sum(ov) / len(ov)) if ov else 0.0,
            "overlap_max": max(ov) if ov else 0,
            "overlap_commits": sum(1 for x in ov if x >= 1),
            "delivery": self.delivery_stats(),
            "delivery_channels": self.delivery_channels(),
            "transport": self.transport_kind,
            "proc_exits": self._proc_counters["proc_exits"],
            "proc_restarts": self._proc_counters["proc_restarts"],
            # cross-process collection (socket + obs only; else empty)
            "child_obs": self.child_obs_report(),
            "flush": dict(getattr(self.server, "flush_totals", {})),
        }
