"""Shared Engine contract for the asynchronous training runtimes.

Two engines implement it:

  - ``AsyncSimulator`` (``repro.async_engine.simulator``): event-driven
    virtual clock — the paper's reference runtime. Inner rounds execute
    serially at event-pop time; only *time* is simulated.
  - ``ConcurrentRuntime`` (``repro.async_engine.runtime``): wall-clock
    concurrency — one thread per worker (optionally pinned to its own
    ``jax.devices()`` entry), pseudo-gradients travel through a
    ``Transport``, and the server applies the packed fused update while
    other workers keep computing.

The contract is enforced structurally: everything that must behave
identically across engines lives here —

  - worker bookkeeping (``Worker``), dispatch capture (``_make_task``),
    the functional inner round (``_execute``: reads only its ``RoundTask``
    snapshot, so it is safe on any thread and a lost round leaves no
    trace), and the server-side commit (``_commit``: optimizer state,
    token/byte accounting, ``Synchronizer.on_arrival``);
  - the virtual-clock event loop (``_run_async``) with failure injection,
    elastic membership, and checkpoint cadence. The deterministic
    wall-clock mode reuses this loop verbatim — arrivals are committed in
    virtual-deadline order regardless of which thread finished first,
    which is the determinism contract (see docs/runtime.md): a
    FIFO-forced ``ConcurrentRuntime`` reproduces the simulator's arrival
    sequence ``(wid, s_i, staleness, lang)`` exactly.

Subclasses provide two hooks: ``_submit`` (where a captured round goes —
nowhere for the simulator, a worker inbox for the runtime) and
``_obtain`` (how the result comes back — computed in-line vs. received
through the transport).
"""
from __future__ import annotations

import os
import time as _time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Protocol, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import ckpt
from repro.configs.base import RunConfig
from repro.core.compression import roundtrip_with_error_feedback
from repro.obs.spans import NULL_TRACER, program_build_listener
from repro.async_engine.server import Synchronizer
from repro.data.synthetic import (
    ShardSampler, eval_batches, make_language_specs, mixture_weights,
)
from repro.models import build_model
from repro.optim.adamw import init_adam
from repro.train.inner import pseudo_gradient, run_inner

PyTree = Any


# ---------------------------------------------------------------------------
# Shared datatypes
# ---------------------------------------------------------------------------

class WorkerArena:
    """NumPy struct-of-arrays store for per-worker engine state.

    At O(10k) workers the per-worker bookkeeping dominated the event
    loop: every ``Worker`` was a Python dataclass, so aggregate queries
    (alive count, in-flight count, min pace) were full dict walks. Here
    every scalar field lives in one flat array indexed by slot; the
    ``Worker`` objects the engines pass around are thin views
    (``__slots__`` + properties) over a slot, so the per-worker
    attribute API is unchanged while aggregates become single vectorized
    reductions (docs/scale.md).

    Slots are recycled: elastic leave releases a slot (clearing its
    object cells so params/optimizer trees don't outlive the worker),
    a later join reuses it. A released view must not be read after the
    slot is re-allocated.
    """

    SCALAR_FIELDS = (
        ("wid", np.int64, -1),
        ("pace", np.float64, 1.0),       # seconds per inner step (virtual)
        ("s_i", np.int64, 0),            # outer step at dispatch
        ("h_steps", np.int64, 0),        # local steps this round
        ("inner_step_count", np.int64, 0),  # lifetime steps (LR schedule)
        ("dispatch_time", np.float64, 0.0),
        ("generation", np.int64, 0),     # bumped on crash: stale rounds drop
        ("round_seq", np.int64, 0),      # monotonic dispatch counter
        ("pending_task", np.int64, -1),  # engine-unique round id (-1 = none)
    )
    BOOL_FIELDS = (("used", True), ("alive", True), ("in_flight", False))
    OBJECT_FIELDS = ("lang", "mixture", "params", "opt", "ef", "cur_lang",
                     "device")

    def __init__(self, capacity: int = 64):
        cap = max(1, int(capacity))
        self.cols: Dict[str, np.ndarray] = {}
        for name, dt, _default in self.SCALAR_FIELDS:
            self.cols[name] = np.zeros(cap, dt)
        for name, _default in self.BOOL_FIELDS:
            self.cols[name] = np.zeros(cap, bool)
        for name in self.OBJECT_FIELDS:
            self.cols[name] = np.empty(cap, object)
        self._free = list(range(cap - 1, -1, -1))

    def _grow(self):
        old = len(self.cols["wid"])
        for name, arr in self.cols.items():
            ext = (np.empty(old, object) if arr.dtype == object
                   else np.zeros(old, arr.dtype))
            self.cols[name] = np.concatenate([arr, ext])
        self._free.extend(range(2 * old - 1, old - 1, -1))

    def alloc(self, wid: int) -> int:
        if not self._free:
            self._grow()
        slot = self._free.pop()
        for name, _dt, default in self.SCALAR_FIELDS:
            self.cols[name][slot] = default
        for name, default in self.BOOL_FIELDS:
            self.cols[name][slot] = default
        for name in self.OBJECT_FIELDS:
            self.cols[name][slot] = None
        self.cols["wid"][slot] = wid
        return slot

    def release(self, slot: int):
        self.cols["used"][slot] = False
        self.cols["alive"][slot] = False
        for name in self.OBJECT_FIELDS:
            self.cols[name][slot] = None     # drop param/opt references
        self._free.append(slot)

    # -- vectorized aggregates (O(capacity) array ops, no dict walks) -----
    def n_alive(self) -> int:
        return int(np.count_nonzero(self.cols["used"] & self.cols["alive"]))

    def n_in_flight(self) -> int:
        return int(np.count_nonzero(self.cols["used"]
                                    & self.cols["in_flight"]))

    def min_alive_pace(self, default: float = 1.0) -> float:
        mask = self.cols["used"] & self.cols["alive"]
        if not mask.any():
            return default
        return float(self.cols["pace"][mask].min())


def _scalar_prop(name, cast):
    def get(self):
        return cast(self.arena.cols[name][self.slot])

    def set(self, value):
        self.arena.cols[name][self.slot] = value

    return property(get, set)


def _object_prop(name):
    def get(self):
        return self.arena.cols[name][self.slot]

    def set(self, value):
        self.arena.cols[name][self.slot] = value

    return property(get, set)


class Worker:
    """Thin view over one ``WorkerArena`` slot — the attribute surface of
    the old per-worker dataclass, with every scalar living in the arena's
    flat arrays. Constructing one without an arena (standalone use) gives
    it a private single-slot arena."""

    __slots__ = ("arena", "slot")

    def __init__(self, wid: int, pace: float = 1.0,
                 lang: Optional[int] = None,
                 mixture: Optional[Tuple[float, ...]] = None,
                 params: PyTree = None, opt: Any = None, ef: PyTree = None,
                 device: Any = None, *,
                 arena: Optional[WorkerArena] = None):
        self.arena = arena if arena is not None else WorkerArena(1)
        self.slot = self.arena.alloc(wid)
        self.pace = pace
        self.lang = lang
        self.mixture = mixture
        self.params = params
        self.opt = opt
        self.ef = ef
        self.device = device

    wid = property(lambda self: int(self.arena.cols["wid"][self.slot]))
    pace = _scalar_prop("pace", float)
    s_i = _scalar_prop("s_i", int)
    h_steps = _scalar_prop("h_steps", int)
    inner_step_count = _scalar_prop("inner_step_count", int)
    dispatch_time = _scalar_prop("dispatch_time", float)
    generation = _scalar_prop("generation", int)
    round_seq = _scalar_prop("round_seq", int)
    alive = _scalar_prop("alive", bool)
    in_flight = _scalar_prop("in_flight", bool)
    lang = _object_prop("lang")
    mixture = _object_prop("mixture")
    params = _object_prop("params")
    opt = _object_prop("opt")
    ef = _object_prop("ef")
    cur_lang = _object_prop("cur_lang")
    device = _object_prop("device")

    @property
    def pending_task_id(self) -> Optional[int]:
        v = int(self.arena.cols["pending_task"][self.slot])
        return None if v < 0 else v

    @pending_task_id.setter
    def pending_task_id(self, value: Optional[int]):
        self.arena.cols["pending_task"][self.slot] = \
            -1 if value is None else int(value)

    def __repr__(self):
        return (f"Worker(wid={self.wid}, pace={self.pace}, "
                f"alive={self.alive}, in_flight={self.in_flight})")


class EventQueue:
    """Vectorized virtual-clock event queue.

    Events are (time, seq, kind, wid, gen) rows kept in NumPy column
    arrays sorted by (time, seq) — the exact order the old ``heapq``
    produced (seq is unique, so later tuple fields never tie-break).
    Pushes land in a staging list and merge lazily at the next pop, so a
    same-tick batch of K ready arrivals is ONE sorted-array slice
    (``pop_batch``) instead of K heap pops.

    Crash/rejoin storms orphan in-flight "return" events (their worker's
    generation has moved on); the engine reports each orphaning via
    ``note_stale`` and the queue compacts — one boolean-mask filter —
    as soon as stale entries outnumber live ones, so a storm can never
    make the loop quadratically re-pop dead events (``stale_skipped``
    counts the dead entries that survived to a pop; tests assert it
    stays bounded)."""

    KIND_RETURN = 0
    KIND_RESTART = 1
    _KINDS = {"return": KIND_RETURN, "restart": KIND_RESTART}
    _NAMES = ("return", "restart")
    _COMPACT_MIN = 64                # don't bother below this many entries

    def __init__(self):
        self._time = np.empty(0, np.float64)
        self._seq = np.empty(0, np.int64)
        self._kind = np.empty(0, np.int8)
        self._wid = np.empty(0, np.int64)
        self._gen = np.empty(0, np.int64)
        self._head = 0               # consumed prefix of the sorted arrays
        self._staging: List[Tuple] = []
        self._next_seq = 0
        self.stale = 0               # known-dead entries still queued
        self.stale_skipped = 0       # dead entries that reached a pop
        self.compactions = 0

    def __len__(self) -> int:
        return (len(self._time) - self._head) + len(self._staging)

    def push(self, time: float, kind: str, wid: int, gen: int):
        self._staging.append((float(time), self._next_seq,
                              self._KINDS[kind], int(wid), int(gen)))
        self._next_seq += 1

    def clear(self):
        self.__init__()

    def note_stale(self, n: int = 1):
        self.stale += n

    def note_skip(self):
        self.stale_skipped += 1
        self.stale = max(0, self.stale - 1)

    def _merge(self):
        if not self._staging:
            return
        t, s, k, w, g = (np.asarray(c) for c in zip(*self._staging))
        self._staging = []
        t = np.concatenate([self._time[self._head:], t.astype(np.float64)])
        s = np.concatenate([self._seq[self._head:], s.astype(np.int64)])
        k = np.concatenate([self._kind[self._head:], k.astype(np.int8)])
        w = np.concatenate([self._wid[self._head:], w.astype(np.int64)])
        g = np.concatenate([self._gen[self._head:], g.astype(np.int64)])
        order = np.lexsort((s, t))
        self._time, self._seq = t[order], s[order]
        self._kind, self._wid, self._gen = k[order], w[order], g[order]
        self._head = 0

    def pop_batch(self, max_n: int = 1) -> List[Tuple[float, str, int, int]]:
        """Pop the head event; when it is a "return", also pop up to
        ``max_n - 1`` further same-tick "return" events in seq order (a
        same-tick "restart" interleaved by seq ends the batch so global
        event order is preserved)."""
        self._merge()
        if self._head >= len(self._time):
            return []
        i = self._head
        t0 = self._time[i]
        if self._kind[i] != self.KIND_RETURN or max_n <= 1:
            end = i + 1
        else:
            tick_end = int(np.searchsorted(self._time, t0, side="right"))
            kinds = self._kind[i:tick_end]
            nonret = np.nonzero(kinds != self.KIND_RETURN)[0]
            end = i + int(nonret[0]) if len(nonret) else tick_end
            end = min(end, i + max_n)
        rows = [(float(self._time[j]), self._NAMES[self._kind[j]],
                 int(self._wid[j]), int(self._gen[j]))
                for j in range(i, end)]
        self._head = end
        return rows

    def maybe_compact(self, keep) -> bool:
        """Drop dead entries once they outnumber live ones. ``keep(kind,
        wid, gen) -> bool`` decides (restart events are always kept by
        the engine's predicate)."""
        n = len(self)
        if n < self._COMPACT_MIN or 2 * self.stale <= n:
            return False
        self._merge()
        mask = np.fromiter(
            (keep(self._NAMES[self._kind[j]], int(self._wid[j]),
                  int(self._gen[j]))
             for j in range(self._head, len(self._time))),
            bool, count=len(self._time) - self._head)
        for name in ("_time", "_seq", "_kind", "_wid", "_gen"):
            setattr(self, name, getattr(self, name)[self._head:][mask])
        self._head = 0
        self.stale = 0
        self.compactions += 1
        return True


@dataclass
class FailureEvent:
    time: float
    wid: int
    restart_delay: float = 60.0      # simulated seconds until rejoin


@dataclass
class ElasticEvent:
    time: float
    action: str                      # "join" | "leave"
    wid: int
    pace: float = 1.0
    lang: Optional[int] = None


#: most-recent arrivals kept in History.arrivals (same contract as
#: TelemetryRecorder's in-memory window; the unbounded per-commit stream
#: goes to the telemetry JSONL sink — docs/telemetry.md).
HISTORY_WINDOW = 4096


@dataclass
class History:
    """Run history. ``arrivals`` is a ring of the most recent ``window``
    arrival records — at O(10k) workers an unbounded list dominates
    memory — while ``total_arrivals`` counts every commit ever appended
    (summaries and checkpoint metadata use the total, never the ring
    length)."""
    arrivals: List[Dict] = field(default_factory=list)
    evals: List[Dict] = field(default_factory=list)
    tokens: int = 0
    comm_bytes: int = 0
    final_time: float = 0.0
    total_arrivals: int = 0
    window: int = HISTORY_WINDOW

    def append_arrival(self, rec: Dict):
        self.arrivals.append(rec)
        self.total_arrivals += 1
        if len(self.arrivals) > self.window:
            del self.arrivals[:len(self.arrivals) - self.window]

    def summary(self) -> Dict:
        return {
            "outer_steps": self.total_arrivals,
            "tokens": self.tokens,
            "comm_bytes": self.comm_bytes,
            "final_time": self.final_time,
            "final_eval": self.evals[-1] if self.evals else None,
        }


@dataclass(frozen=True)
class Budget:
    """Stopping rule for budgeted comparisons (paper Table 2): train to a
    fixed token count or a fixed (virtual/wall) clock horizon instead of a
    fixed number of outer steps. Both engines honour it within ONE outer
    round of the target:

      fixed_tokens     stop at the first commit whose cumulative token
                       count reaches ``amount``;
      fixed_wallclock  never commit an arrival past ``amount`` seconds of
                       engine time (sim: virtual; free-running: scaled
                       wall clock) — the run stops at the last arrival
                       inside the horizon.

    The configured ``outer_steps`` remains a hard cap on top.
    """
    kind: str                        # "fixed_tokens" | "fixed_wallclock"
    amount: float

    KINDS = ("fixed_tokens", "fixed_wallclock")

    def __post_init__(self):
        assert self.kind in self.KINDS, self.kind
        assert self.amount > 0, self.amount

    def over_time(self, t: float) -> bool:
        return self.kind == "fixed_wallclock" and t > self.amount + 1e-9

    def over_tokens(self, tokens: int) -> bool:
        return self.kind == "fixed_tokens" and tokens >= self.amount


@dataclass
class RoundTask:
    """Snapshot of one dispatched inner round. Captured on the server
    thread; ``_execute`` reads only this, never the live ``Worker``, so a
    concurrently-injected crash (generation bump) cannot race the compute
    — the stale result is simply discarded at commit."""
    task_id: int                     # engine-unique: never reused, even when
    wid: int                         # a wid rejoins as a fresh Worker
    generation: int
    round_seq: int
    params: PyTree
    opt: Any
    ef: PyTree
    s_i: int
    h_steps: int
    lang: Optional[int]
    inner_step_offset: int
    mixture: Optional[Tuple[float, ...]] = None
    dispatch_time: float = 0.0
    sleep_per_step: float = 0.0      # free-running pace throttle (wall sec)
    device: Any = None
    batch_size: int = 0              # per-round mini-batch (0 = cfg default;
    # nonzero under the hogwild ramp-up schedule, RunConfig.batch_rampup)


@dataclass
class RoundResult:
    task_id: int
    wid: int
    generation: int
    round_seq: int
    delta: PyTree
    opt: Any
    ef: PyTree
    nbytes: int
    s_i: int
    h_steps: int
    lang: Optional[int]
    compute_seconds: float = 0.0
    batch_size: int = 0              # per-round mini-batch actually trained
    # (0 = cfg default; token accounting uses this under ramp-up)


def execute_round(task: RoundTask, *, model, cfg: RunConfig, specs,
                  layout=None, tracer=None) -> RoundResult:
    """The functional inner round, shared VERBATIM between every engine
    thread and the socket worker processes: reads only the ``RoundTask``
    snapshot plus immutable run-wide state (model, config, language
    specs, optional packed int8 layout) — all deterministically
    reconstructible from the ``RunConfig`` in a fresh process, which is
    what makes the socket backend trace-identical to the in-process
    engines."""
    tracer = tracer if tracer is not None else NULL_TRACER
    t0 = _time.perf_counter()
    with tracer.span("worker_round", cat="compute", wid=task.wid,
                     s_i=task.s_i, h=task.h_steps):
        sampler = ShardSampler(specs, task.lang,
                               task.batch_size or cfg.batch_size,
                               cfg.seq_len,
                               seed=cfg.seed * 977 + task.wid,
                               mixture=task.mixture)
        result = run_inner(model, cfg.inner, task.params,
                           task.opt, sampler, task.h_steps,
                           step_offset=task.inner_step_offset,
                           tracer=tracer)
        with tracer.span("pseudo_gradient", cat="compute"):
            delta = pseudo_gradient(task.params, result.params)
    # int8 rides the server's packed layout: per-block scales, O(1)
    # kernel launches, and a packed error-feedback buffer per worker.
    with tracer.span("compress_roundtrip", cat="compute", wid=task.wid):
        decoded, ef, nbytes = roundtrip_with_error_feedback(
            delta, task.ef, cfg.outer.compression,
            cfg.outer.topk_ratio, layout=layout)
    if not cfg.outer.error_feedback:
        ef = None
    return RoundResult(
        task_id=task.task_id, wid=task.wid, generation=task.generation,
        round_seq=task.round_seq, delta=decoded, opt=result.opt, ef=ef,
        nbytes=nbytes, s_i=task.s_i, h_steps=task.h_steps,
        lang=task.lang, compute_seconds=_time.perf_counter() - t0,
        batch_size=task.batch_size)


class Engine(Protocol):
    """What callers (launchers, benchmarks, examples) may rely on."""
    cfg: RunConfig
    server: Synchronizer
    workers: Dict[int, Worker]
    history: History
    time: float

    def run(self, eval_every: int = 0,
            eval_fn: Optional[Callable[[PyTree, int, float], Dict]] = None,
            ckpt_every: int = 0, ckpt_dir: str = "",
            budget: Optional[Budget] = None) -> History: ...
    def checkpoint(self, ckpt_dir: str) -> str: ...
    def restore(self, path: str) -> None: ...


# ---------------------------------------------------------------------------
# Shared engine implementation
# ---------------------------------------------------------------------------

class EngineBase:
    ENGINE_NAME = "sim"              # telemetry RunMeta.engine vocabulary:
    # the make_engine dialect ("sim" | "wallclock"), one value per engine

    def __init__(self, run_cfg: RunConfig, *,
                 failures: Optional[List[FailureEvent]] = None,
                 elastic: Optional[List[ElasticEvent]] = None,
                 telemetry=None, tracer=None,
                 runtime_record_every: int = 0):
        self.cfg = run_cfg
        self.model = build_model(run_cfg.model)
        self.specs = make_language_specs(run_cfg.model.vocab_size,
                                         n_langs=max(run_cfg.n_workers, 2),
                                         seed=run_cfg.seed)
        key = jax.random.PRNGKey(run_cfg.seed)
        init_params = self.model.init(key)
        # telemetry: a repro.telemetry.TelemetryRecorder (or None). The
        # synchronizer then emits update-quality stats from the same fused
        # sweeps (zero extra launches); the engine streams arrival/eval
        # records into the recorder at commit time, plus a periodic
        # "runtime" health snapshot every `runtime_record_every` commits.
        # tracer: a repro.obs.spans.SpanTracer (or None -> shared no-op)
        # timing worker rounds / commits / evals as Chrome trace spans.
        self.telemetry = telemetry
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.runtime_record_every = int(runtime_record_every or 0)
        topology = getattr(run_cfg, "topology", "hub")
        if topology != "hub":
            # NoLoCo-style decentralized exchange: per-worker replicas,
            # pairwise peer averaging instead of a hub server. Duck-types
            # the Synchronizer surface the engines consume.
            from repro.async_engine.topology import PeerMixer
            self.server = PeerMixer(init_params, run_cfg.outer,
                                    run_cfg.n_workers, kind=topology,
                                    seed=run_cfg.seed)
        else:
            self.server = Synchronizer(
                init_params, run_cfg.outer, run_cfg.n_workers,
                telemetry=telemetry is not None,
                commit_batch=getattr(run_cfg, "commit_batch", 1))
        self.arena = WorkerArena(capacity=max(run_cfg.n_workers, 4))
        self.workers: Dict[int, Worker] = {}
        for wid in range(run_cfg.n_workers):
            pace = run_cfg.worker_paces[wid % len(run_cfg.worker_paces)]
            mixture = self._mixture_for(wid)
            if mixture is not None:
                lang = int(np.argmax(mixture))   # dominant shard (accounting)
            else:
                lang = (wid % len(self.specs)) if run_cfg.non_iid else None
            self.workers[wid] = Worker(
                wid=wid, pace=pace, lang=lang, mixture=mixture,
                opt=init_adam(init_params), arena=self.arena)
        self.failures = sorted(failures or [], key=lambda f: f.time)
        self.elastic = sorted(elastic or [], key=lambda e: e.time)
        self.lang_tokens = np.zeros(len(self.specs), np.int64)
        self.history = History()
        self.time = 0.0
        self._events = EventQueue()
        self._task_counter = 0
        self._min_pace = self.arena.min_alive_pace()
        self._stop = False               # cooperative kill switch (request_stop)
        self.restored_arrivals = 0       # commits accounted by a restored ckpt

    def request_stop(self) -> None:
        """Cooperative kill switch: the run loop exits at the next commit
        boundary (server state stays consistent — a checkpoint taken after
        ``run`` returns is a valid resume point). Models killing the
        server mid-run; combined with ``checkpoint``/``restore`` it is the
        recovery path docs/faults.md describes."""
        self._stop = True

    # -------------------------------------------------------- engine hooks
    def _submit(self, task: RoundTask) -> None:
        """Hand a captured round to whatever executes it."""
        raise NotImplementedError

    def _obtain(self, w: Worker) -> RoundResult:
        """Produce/collect the result of the worker's outstanding round."""
        raise NotImplementedError

    def _sleep_per_step(self, w: Worker) -> float:
        """Wall-clock pace throttle (free-running runtime only)."""
        return 0.0

    def _on_worker_removed(self, w: Worker) -> None:
        """Crash / elastic-leave notification (runtime stops the thread)."""

    # ------------------------------------------------------------------ utils
    def _push(self, time: float, kind: str, wid: int, gen: int):
        self._events.push(time, kind, wid, gen)

    def _event_is_live(self, kind: str, wid: int, gen: int) -> bool:
        """Compaction predicate: restart events always survive; a return
        event survives only while its (wid, generation) is still the live
        worker's outstanding round."""
        if kind == "restart":
            return True
        w = self.workers.get(wid)
        return w is not None and w.alive and w.generation == gen

    def _mixture_for(self, wid: int) -> Optional[Tuple[float, ...]]:
        """Per-worker Dirichlet language mixture (deterministic in
        (seed, wid), stable across crash/rejoin and elastic join)."""
        if not (self.cfg.non_iid and self.cfg.mixture_alpha):
            return None
        return tuple(mixture_weights(len(self.specs), self.cfg.mixture_alpha,
                                     wid, seed=self.cfg.seed))

    def _h_steps(self, w: Worker) -> int:
        if self.cfg.dylu:
            return max(1, int(round(self.cfg.inner_steps *
                                    self._min_pace / w.pace)))
        return self.cfg.inner_steps

    def _pick_lang(self, w: Worker) -> Optional[int]:
        if not self.cfg.non_iid:
            return None
        if w.mixture is not None:        # Dirichlet mixture: lang is the
            return w.lang                # dominant shard (accounting only)
        if self.cfg.shard_assignment == "flexible":
            return int(np.argmin(self.lang_tokens))
        return w.lang

    # --------------------------------------------------------------- dispatch
    def _make_task(self, w: Worker) -> RoundTask:
        """Capture the worker's initialization + round snapshot (server
        thread only — reads Synchronizer state and shard accounting)."""
        with self.tracer.span("round_dispatch", cat="server", wid=w.wid):
            w.params = jax.tree.map(jnp.copy, self.server.worker_init(w.wid))
            w.s_i = self.server.t
            w.h_steps = self._h_steps(w)
            w.cur_lang = self._pick_lang(w)
            w.dispatch_time = self.time
            w.round_seq += 1
            w.in_flight = True
            self._task_counter += 1
            w.pending_task_id = self._task_counter
            return RoundTask(
                task_id=self._task_counter,
                wid=w.wid, generation=w.generation, round_seq=w.round_seq,
                params=w.params, opt=w.opt, ef=w.ef, s_i=w.s_i,
                h_steps=w.h_steps, lang=w.cur_lang, mixture=w.mixture,
                inner_step_offset=w.inner_step_count,
                dispatch_time=self.time,
                sleep_per_step=self._sleep_per_step(w), device=w.device,
                batch_size=self._round_batch())

    def _round_batch(self) -> int:
        """Per-round mini-batch under the hogwild ramp-up schedule
        (RunConfig.batch_rampup): linear from batch_size at t=0 to the
        target at the final outer step. 0 (= cfg.batch_size) without."""
        target = getattr(self.cfg, "batch_rampup", None)
        if not target:
            return 0
        frac = min(1.0, self.server.t / max(self.cfg.outer_steps - 1, 1))
        return max(1, int(round(self.cfg.batch_size
                                + frac * (target - self.cfg.batch_size))))

    def _dispatch(self, w: Worker):
        """Capture the round, schedule its virtual return, submit it."""
        task = self._make_task(w)
        if self._use_virtual_clock():
            self._push(self.time + task.h_steps * w.pace, "return",
                       w.wid, w.generation)
        self._submit(task)

    def _use_virtual_clock(self) -> bool:
        return True

    # ------------------------------------------------------------ inner round
    def _execute(self, task: RoundTask) -> RoundResult:
        """Run one inner round from the task snapshot. Reads no mutable
        engine state — safe to call from any thread, results of a lost
        (crashed-generation) round can be discarded without side effects."""
        layout = (self.server.layout
                  if self.cfg.outer.compression == "int8" else None)
        return execute_round(task, model=self.model, cfg=self.cfg,
                             specs=self.specs, layout=layout,
                             tracer=self.tracer)

    # ----------------------------------------------------------------- commit
    def _commit_worker(self, w: Worker, res: RoundResult):
        """Fold a completed round back into worker + shared accounting
        (server thread only; order of commits defines the history)."""
        w.opt = res.opt
        w.ef = res.ef
        w.inner_step_count += res.h_steps
        w.in_flight = False
        w.pending_task_id = None
        toks = (res.h_steps * (res.batch_size or self.cfg.batch_size)
                * self.cfg.seq_len)
        self.history.tokens += toks
        if res.lang is not None:
            self.lang_tokens[res.lang] += toks
        self.history.comm_bytes += res.nbytes

    def _commit(self, w: Worker, res: RoundResult):
        self._commit_worker(w, res)
        with self.tracer.span("server_commit", cat="server", wid=res.wid,
                              s_i=res.s_i):
            rec = self.server.on_arrival(
                res.delta, res.s_i, res.wid, sim_time=self.time,
                lang=(self.specs[res.lang].lang
                      if res.lang is not None else "iid"))
        self.history.append_arrival(rec.__dict__)
        if self.telemetry is not None:
            self.telemetry.record_arrival(rec, mixture=w.mixture,
                                          tokens_total=self.history.tokens)
        return rec

    def _commit_batch(self, pairs: List[Tuple[Worker, RoundResult]],
                      reason: str = "batch-full"):
        """Commit a coalesced batch of same-tick arrivals through the
        server's commit buffer: one fused multi-apply instead of
        len(pairs) sequential outer steps (docs/scale.md). Only reached
        with ``commit_batch > 1``; a batch of one goes through _commit.
        ``reason`` labels the trailing flush (why the batch was capped:
        batch-full / eval / ckpt / close) for the flush telemetry."""
        recs = []
        with self.tracer.span("server_commit_batch", cat="server",
                              k=len(pairs)):
            for w, res in pairs:
                self._commit_worker(w, res)
                out = self.server.buffer_arrival(
                    res.delta, res.s_i, res.wid, sim_time=self.time,
                    lang=(self.specs[res.lang].lang
                          if res.lang is not None else "iid"))
                if out:
                    recs.extend(out)
            recs.extend(self.server.flush(reason))
        for (w, _res), rec in zip(pairs, recs):
            self.history.append_arrival(rec.__dict__)
            if self.telemetry is not None:
                self.telemetry.record_arrival(rec, mixture=w.mixture,
                                              tokens_total=self.history.tokens)
        self._drain_flush_log()
        return recs

    def _drain_flush_log(self):
        """Turn the server's pending flush events into "flush" telemetry
        records (observation only; the log is tiny — one dict per flush
        since the last drain)."""
        log = getattr(self.server, "flush_log", None)
        if not log:
            return
        if self.telemetry is not None:
            for ev in log:
                self.telemetry.record_flush(outer_step=self.server.t,
                                            sim_time=self.time, **ev)
        log.clear()

    def _post_commit(self, eval_every, eval_fn, ckpt_every, ckpt_dir):
        t = self.server.t
        if eval_every and eval_fn and t % eval_every == 0:
            with self.tracer.span("eval", cat="eval", step=t):
                ev = eval_fn(self.server.state.params, t, self.time)
            self.history.evals.append(ev)
            if self.telemetry is not None:
                self.telemetry.record_eval(ev)
        if ckpt_every and ckpt_dir and t % ckpt_every == 0:
            with self.tracer.span("checkpoint", cat="ckpt", step=t):
                self.checkpoint(ckpt_dir)
        if (self.telemetry is not None and self.runtime_record_every
                and self.history.total_arrivals
                % self.runtime_record_every == 0):
            self._record_runtime()

    # ----------------------------------------------- runtime health records
    def _runtime_snapshot(self) -> Dict:
        """Worker-membership health view; the concurrent runtime overrides
        this to add occupancy/parallelism/queue/liveness/delivery from its
        live counters. Pure observation: no jax ops, no RNG — telemetry-on
        runs stay byte-identical to the goldens."""
        return {
            "workers_alive": self.arena.n_alive(),
            "workers_total": len(self.workers),
            "in_flight": self.arena.n_in_flight(),
        }

    def _record_runtime(self):
        if self.telemetry is None:
            return
        self.telemetry.record_runtime(outer_step=self.server.t,
                                      sim_time=self.time,
                                      **self._runtime_snapshot())

    def _finalize(self, eval_fn) -> History:
        self.history.final_time = self.time
        if eval_fn and (not self.history.evals
                        or self.history.evals[-1]["step"] != self.server.t):
            with self.tracer.span("eval", cat="eval", step=self.server.t):
                ev = eval_fn(self.server.state.params, self.server.t,
                             self.time)
            self.history.evals.append(ev)
            if self.telemetry is not None:
                self.telemetry.record_eval(ev)
        if self.telemetry is not None and self.runtime_record_every:
            self._record_runtime()           # end-of-run snapshot
        return self.history

    # -------------------------------------------------------------- main loop
    def _ensure_telemetry_meta(self):
        if self.telemetry is not None:
            self.telemetry.ensure_meta(
                method=self.server.method.name,
                engine=self.ENGINE_NAME,
                n_workers=self.cfg.n_workers,
                outer_steps=self.cfg.outer_steps,
                seed=self.cfg.seed,
                non_iid=self.cfg.non_iid,
                mixture_alpha=self.cfg.mixture_alpha)

    def run(self, eval_every: int = 0,
            eval_fn: Optional[Callable[[PyTree, int, float], Dict]] = None,
            ckpt_every: int = 0, ckpt_dir: str = "",
            budget: Optional[Budget] = None) -> History:
        self._ensure_telemetry_meta()
        # with tracing on, every lowering of a program in the process
        # becomes a ``program_build`` span of this engine's tracer
        listener = None
        if self.tracer.enabled:
            listener = program_build_listener(self.tracer)
            jax.monitoring.register_event_duration_secs_listener(listener)
        try:
            return self._loop(eval_every, eval_fn, ckpt_every, ckpt_dir,
                              budget)
        finally:
            if listener is not None:
                jax.monitoring.unregister_event_duration_listener(listener)

    def _loop(self, eval_every, eval_fn, ckpt_every, ckpt_dir,
              budget: Optional[Budget] = None) -> History:
        """The run loop of the job's method; the concurrent runtime adds
        its free-running loop."""
        if self.server.method.sync:
            return self._run_sync(eval_every, eval_fn, ckpt_every, ckpt_dir,
                                  budget)
        return self._run_async(eval_every, eval_fn, ckpt_every, ckpt_dir,
                               budget)

    def _run_async(self, eval_every, eval_fn, ckpt_every, ckpt_dir,
                   budget: Optional[Budget] = None) -> History:
        """Virtual-clock event loop. Used by the simulator AND by the
        deterministic wall-clock runtime (which overlaps compute but
        commits in exactly this event order).

        With ``RunConfig.commit_batch > 1``, up to that many same-tick
        ready arrivals pop as ONE vectorized batch and commit through the
        server's fused multi-apply; the batch is capped so an
        eval/checkpoint boundary always lands exactly at a batch end
        (docs/scale.md). commit_batch=1 is the exact sequential path."""
        for w in self.workers.values():
            if w.alive and not w.in_flight:
                self._dispatch(w)
        fail_idx = el_idx = 0
        target = self.cfg.outer_steps
        commit_batch = max(1, int(getattr(self.cfg, "commit_batch", 1)))
        while self.server.t < target and len(self._events) and not self._stop:
            # labelled cap: the tightest boundary names the flush reason
            # (min picks the FIRST minimal entry, so a coinciding
            # eval/ckpt boundary still reads "batch-full")
            limits = [(commit_batch, "batch-full"),
                      (target - self.server.t, "close")]
            if eval_every:
                limits.append((eval_every - self.server.t % eval_every,
                               "eval"))
            if ckpt_every:
                limits.append((ckpt_every - self.server.t % ckpt_every,
                               "ckpt"))
            cap, flush_reason = min(limits, key=lambda kv: kv[0])
            events = self._events.pop_batch(cap)
            time = events[0][0]
            if budget is not None and budget.over_time(time):
                break   # fixed clock horizon: never commit past it
            # interleave failure / elastic events that occur first
            while (fail_idx < len(self.failures)
                   and self.failures[fail_idx].time <= time):
                self._handle_failure(self.failures[fail_idx])
                fail_idx += 1
            while (el_idx < len(self.elastic)
                   and self.elastic[el_idx].time <= time):
                self._handle_elastic(self.elastic[el_idx])
                el_idx += 1
            self.time = time
            ready: List[Worker] = []
            for _t, kind, wid, gen in events:
                if kind == "restart":
                    w = self.workers.get(wid)
                    if w is not None:
                        w.alive = True
                        self._dispatch(w)
                    continue
                w = self.workers.get(wid)
                if w is None or not w.alive or gen != w.generation:
                    self._events.note_skip()
                    continue  # stale event (crashed/removed worker)
                ready.append(w)
            if not ready:
                continue
            if len(ready) == 1:
                self._commit(ready[0], self._obtain(ready[0]))
            else:
                self._commit_batch([(w, self._obtain(w)) for w in ready],
                                   reason=flush_reason)
            self._post_commit(eval_every, eval_fn, ckpt_every, ckpt_dir)
            if budget is not None and budget.over_tokens(self.history.tokens):
                break   # token budget reached at this commit
            for w in ready:
                if self.server.t < target:
                    self._dispatch(w)
        return self._finalize(eval_fn)

    # ------------------------------------------------------------- sync mode
    def _execute_sync(self, tasks: List[RoundTask]) -> List[RoundResult]:
        """Barrier round execution; the concurrent runtime overrides this
        to compute all workers in parallel threads."""
        return [self._execute(t) for t in tasks]

    def _run_sync(self, eval_every, eval_fn, ckpt_every, ckpt_dir,
                  budget: Optional[Budget] = None) -> History:
        target = self.cfg.outer_steps
        while self.server.t < target and not self._stop:
            alive = [w for w in self.workers.values() if w.alive]
            round_time = max(self._h_steps(w) * w.pace for w in alive)
            if budget is not None and budget.over_time(self.time + round_time):
                break   # the next barrier round would cross the horizon
            tasks = [self._make_task(w) for w in alive]
            results = self._execute_sync(tasks)
            for w, res in zip(alive, results):
                self._commit_worker(w, res)
            self.time += round_time  # barrier: slowest worker gates the round
            rec = self.server.on_sync_round([r.delta for r in results],
                                            sim_time=self.time)
            self.history.append_arrival(rec.__dict__)
            if self.telemetry is not None:
                self.telemetry.record_arrival(
                    rec, tokens_total=self.history.tokens)
            self._post_commit(eval_every, eval_fn, ckpt_every, ckpt_dir)
            if budget is not None and budget.over_tokens(self.history.tokens):
                break
        return self._finalize(eval_fn)

    # ------------------------------------------------------- fault tolerance
    def _crash_worker(self, w: Worker):
        """Shared crash bookkeeping: the in-flight round is lost."""
        if w.in_flight and self._use_virtual_clock():
            self._events.note_stale()    # its return event is now dead
        w.alive = False
        w.generation += 1
        w.ef = None
        w.in_flight = False
        w.pending_task_id = None
        self._events.maybe_compact(self._event_is_live)

    def _handle_failure(self, ev: FailureEvent):
        w = self.workers.get(ev.wid)
        if w is None:
            return
        self._crash_worker(w)
        self._push(ev.time + ev.restart_delay, "restart", w.wid, w.generation)

    def _handle_elastic(self, ev: ElasticEvent):
        if ev.action == "join":
            mixture = self._mixture_for(ev.wid)
            lang = (int(np.argmax(mixture)) if mixture is not None
                    else ev.lang)
            w = Worker(wid=ev.wid, pace=ev.pace, lang=lang, mixture=mixture,
                       opt=init_adam(self.server.state.params),
                       arena=self.arena)
            self.workers[ev.wid] = w
            self.server.set_n_workers(self.arena.n_alive())
            self._dispatch(w)
        elif ev.action == "leave":
            w = self.workers.pop(ev.wid, None)
            if w is not None:
                if w.in_flight and self._use_virtual_clock():
                    self._events.note_stale()
                w.generation += 1
                self._on_worker_removed(w)
                self.arena.release(w.slot)
                self._events.maybe_compact(self._event_is_live)
            self.server.set_n_workers(self.arena.n_alive())
        self._min_pace = self.arena.min_alive_pace(default=1.0)

    # ---------------------------------------------------------- checkpointing
    def server_tree(self) -> Dict:
        state = self.server.state
        tree = {"params": state.params, "momentum": state.momentum,
                "step": state.step}
        if state.aux is not None:        # per-method auxiliary state
            tree["aux"] = state.aux      # (e.g. delayed-Nesterov buffer)
        return tree

    def checkpoint(self, ckpt_dir: str) -> str:
        path = os.path.join(ckpt_dir, f"step_{self.server.t}.npz")
        meta = {"time": self.time, "tokens": int(self.history.tokens),
                "arrivals": self.history.total_arrivals}
        ckpt.save(path, self.server_tree(), meta)
        return path

    def restore(self, path: str):
        tree, meta = ckpt.restore(path, self.server_tree())
        self.server.state = self.server.state._replace(
            params=tree["params"],
            momentum=tree["momentum"],
            step=jnp.asarray(tree["step"]),
            aux=tree.get("aux", self.server.state.aux))
        self.time = float(meta.get("time", 0.0))
        self.history.tokens = int(meta.get("tokens", 0))
        # committed-arrival count up to the checkpoint: a resumed run's
        # total accounting is restored_arrivals + len(history.arrivals)
        self.restored_arrivals = int(meta.get("arrivals", 0))
        self._stop = False
        # in-flight worker rounds are lost on restart (real-world semantics)
        self._events.clear()
        for w in self.workers.values():
            w.generation += 1
            w.in_flight = False
            w.pending_task_id = None
            if w.alive:
                self._dispatch(w)


# ---------------------------------------------------------------------------
# Factory + shared eval protocol
# ---------------------------------------------------------------------------

ENGINES = ("sim", "wallclock")


def make_engine(run_cfg: RunConfig, engine: Optional[str] = None, *,
                failures: Optional[List[FailureEvent]] = None,
                elastic: Optional[List[ElasticEvent]] = None,
                telemetry=None, tracer=None,
                runtime_record_every: Optional[int] = None,
                **runtime_kw) -> Engine:
    """Build a training engine. ``engine``: "sim" (default, virtual clock)
    or "wallclock" (threaded ``ConcurrentRuntime``; extra keywords —
    ``mode``, ``pace_scale``, ``transport``, ... — are forwarded to it).
    ``telemetry``: optional ``repro.telemetry.TelemetryRecorder`` the run
    streams arrival/eval diagnostics into (valid alongside a Scenario —
    observation, not configuration). ``tracer``: optional
    ``repro.obs.spans.SpanTracer`` recording worker-round / transport /
    commit / eval spans (same observation-only status).
    ``runtime_record_every``: emit a telemetry "runtime" health snapshot
    every N commits (None defers to the Scenario's ``telemetry_every``
    knob; 0 disables).

    Also accepts a ``repro.scenarios`` ``Scenario`` as the first argument:
    its ``materialize()`` then supplies the run config, engine choice,
    runtime options, and failure/elastic schedules — the declarative
    single-source-of-truth entry point."""
    if hasattr(run_cfg, "materialize"):          # Scenario (duck-typed to
        if engine is not None or failures or elastic or runtime_kw:
            raise TypeError("pass the engine choice, schedules, and "
                            "options inside the Scenario, not alongside it")
        if telemetry is not None:
            telemetry.ensure_meta(
                method=run_cfg.method, engine=run_cfg.engine,
                n_workers=run_cfg.n_workers,
                outer_steps=run_cfg.outer_steps, seed=run_cfg.seed,
                non_iid=run_cfg.non_iid,
                mixture_alpha=run_cfg.mixture_alpha,
                scenario=run_cfg.name)
        if runtime_record_every is None:
            runtime_record_every = getattr(run_cfg, "telemetry_every", 0)
        m = run_cfg.materialize()                # avoids a circular import
        return make_engine(m.run_cfg, m.engine, failures=m.failures,
                           elastic=m.elastic, telemetry=telemetry,
                           tracer=tracer,
                           runtime_record_every=runtime_record_every,
                           **m.engine_kw)
    obs_kw = dict(telemetry=telemetry, tracer=tracer,
                  runtime_record_every=runtime_record_every or 0)
    engine = engine or "sim"
    if engine in ("sim", "simulator", "virtual"):
        if runtime_kw:
            raise TypeError(f"simulator takes no runtime options: {runtime_kw}")
        from repro.async_engine.simulator import AsyncSimulator
        return AsyncSimulator(run_cfg, failures=failures, elastic=elastic,
                              **obs_kw)
    if engine in ("wallclock", "concurrent", "runtime"):
        from repro.async_engine.runtime import ConcurrentRuntime
        return ConcurrentRuntime(run_cfg, failures=failures, elastic=elastic,
                                 **obs_kw, **runtime_kw)
    raise ValueError(f"unknown engine {engine!r}; expected one of {ENGINES}")


def make_eval_fn(engine, batch: int = 16, seq: int = None):
    """Per-language + mean validation loss (Fig. 2/3 protocol)."""
    seq = seq or engine.cfg.seq_len
    batches = eval_batches(engine.specs, batch, seq,
                           seed=engine.cfg.seed + 4242)
    model = engine.model

    @jax.jit
    def loss_of(params, tokens, labels):
        return model.loss(params, {"tokens": tokens, "labels": labels})[0]

    def eval_fn(params, step, time):
        per = {}
        for b in batches:
            per[b["lang"]] = float(loss_of(params, jnp.asarray(b["tokens"]),
                                           jnp.asarray(b["labels"])))
        mean = float(np.mean(list(per.values())))
        return {"step": step, "time": time, "mean": mean, "per_lang": per}

    return eval_fn
