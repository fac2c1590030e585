"""One run of one cell: set-up, the measured window, the correctness
check against the plain reference, and the metrics.

The run goes through the program's normal path (``make_engine`` ->
``engine.run``) with a tracer of the benchmark's own, which sees the
program's ``worker_round``, ``server_commit*`` and ``eval`` spans. Set-up
trains the job from the seed until every worker has committed once and
one eval has run; the first three commits are kept for the check. At the
next commit boundary the window opens (after ``block_until_ready`` on
the server's state); at the first commit boundary past ``seconds`` it
closes the same way and asks the engine to stop.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import math
import os
import shutil
import time
from typing import Callable, Dict, List, Optional

import jax
import numpy as np
from jax import monitoring

from chipbench import trace
from chipbench.cells import HERE, ROOT, Cell
from chipbench.reference import Reference, leaf_names, leaf_norms

#: commits of the job the reference follows
CHECK_STEPS = 3
#: where a traced run writes its profile (inside the checkout; removed
#: once read)
TRACE_DIR = os.path.join(ROOT, ".chipbench", "trace")
GIB = 2 ** 30


class CompileLog:
    """Backend compiles and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        self.compiles = 0
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += duration


class BenchTracer:
    """Duck-types the program's span tracer. Keeps each span's name,
    host start and end and its ``k`` (arrivals in a commit), calls
    ``on_commit`` as each commit span closes and, when ``annotate``,
    puts every span into the profiler's trace as well."""

    enabled = True

    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.spans: List[tuple] = []
        self.on_commit: Callable[[], None] = lambda: None

    @contextlib.contextmanager
    def span(self, name: str, cat: str = "engine", **args):
        t0 = time.perf_counter()
        if self.annotate:
            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield
        self.spans.append((name, t0, time.perf_counter(),
                           int(args.get("k", 1))))
        if name.startswith("server_commit"):
            self.on_commit()

    def instant(self, name: str, cat: str = "engine", **args) -> None:
        return None


def _host(tree):
    return jax.tree.map(np.asarray, tree)


class Window:
    """The state machine driven from the commit spans."""

    def __init__(self, engine, eval_fn, tracer: BenchTracer, log: CompileLog,
                 seconds: float, traced: bool):
        self.engine, self.eval_fn, self.tracer, self.log = \
            engine, eval_fn, tracer, log
        self.seconds, self.traced = seconds, traced
        self.p0 = _host(engine.server.state.params)
        self.names = leaf_names(self.p0)
        self.prog: Dict[str, list] = {"loss": []}
        self.steps = 0
        self.phase = "warm"
        self.mark: Dict[str, dict] = {}
        tracer.on_commit = self.on_commit

    def _sync(self):
        jax.block_until_ready(self.engine.server.state)

    def _snapshot(self) -> dict:
        h = self.engine.history
        return dict(t=time.perf_counter(), tokens=int(h.tokens),
                    arrivals=int(h.total_arrivals),
                    dropped=sum(bool(a.get("dropped")) for a in h.arrivals),
                    compiles=self.log.compiles, hits=self.log.hits,
                    misses=self.log.misses, span=len(self.tracer.spans))

    def _warm(self) -> bool:
        eng = self.engine
        seen = {a["worker_id"] for a in eng.history.arrivals}
        evals = sum(1 for s in self.tracer.spans if s[0] == "eval")
        return (self.steps >= CHECK_STEPS and evals >= 1
                and seen >= set(eng.workers))

    def on_commit(self):
        self.steps += 1
        if self.phase == "warm":
            if self.steps <= CHECK_STEPS:
                state = self.engine.server.state
                ev = self.eval_fn(state.params, self.steps, 0.0)
                self.prog["loss"].append(float(ev["mean"]))
                if self.steps == 1:
                    self.prog["grad"] = leaf_norms(state.momentum)
                if self.steps == CHECK_STEPS:
                    self.prog["change"] = leaf_norms(jax.tree.map(
                        lambda a, b: np.asarray(a, np.float64) - b,
                        _host(state.params), self.p0))
                    self.p0 = None
            if self._warm():
                self._sync()
                if self.traced:
                    shutil.rmtree(TRACE_DIR, ignore_errors=True)
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0
                    jax.profiler.start_trace(TRACE_DIR,
                                             profiler_options=opts)
                    _marker("chipbench.window_open")
                self.mark["open"] = self._snapshot()
                self.phase = "timed"
        elif (self.phase == "timed" and time.perf_counter()
              - self.mark["open"]["t"] >= self.seconds):
            self._sync()
            self.mark["close"] = self._snapshot()
            if self.traced:
                _marker("chipbench.window_close")
                jax.profiler.stop_trace()
            self.engine.request_stop()
            self.phase = "closed"


def _marker(name: str):
    with jax.profiler.TraceAnnotation(name):
        pass


#: keys of a traffic file that belong to the harness; every other key is
#: a field of the program's ``RunConfig``
HARNESS_KEYS = ("about", "engine", "runtime", "eval_batch", "eval_every")


def _tuples(value):
    """JSON lists as tuples, inside dicts too, so that a frozen
    configuration hashes."""
    if isinstance(value, list):
        return tuple(_tuples(v) for v in value)
    if isinstance(value, dict):
        return {k: _tuples(v) for k, v in value.items()}
    return value


def model_config(model: dict):
    """The program's ``ModelConfig`` for a configuration's ``model``:
    lists become tuples, and the nested ``moe``, ``ssm``, ``xlstm`` and
    ``frontend`` groups their configuration classes."""
    from repro.configs.base import (FrontendConfig, ModelConfig, MoEConfig,
                                    SSMConfig, XLSTMConfig)
    groups = {"moe": MoEConfig, "ssm": SSMConfig, "xlstm": XLSTMConfig,
              "frontend": FrontendConfig}
    fields = _tuples(model)
    for key, cls in groups.items():
        if key in fields:
            fields[key] = cls(**fields[key])
    return ModelConfig(**fields)


def run_config(cell: Cell, seed: int):
    """The program's ``RunConfig`` for this cell's job: every key of the
    traffic file but the harness's own, with the model of the cell's
    configuration; the job runs until the window closes."""
    from repro.configs.base import (HeLoCoConfig, InnerOptConfig,
                                    OuterOptConfig, RunConfig)
    fields = {k: v for k, v in cell.job.items() if k not in HARNESS_KEYS}
    outer = dict(fields["outer"])
    outer["heloco"] = HeLoCoConfig(**outer["heloco"])
    fields.update(inner=InnerOptConfig(**fields["inner"]),
                  outer=OuterOptConfig(**outer),
                  worker_paces=tuple(float(p)
                                     for p in fields["worker_paces"]))
    return RunConfig(model=model_config(cell.model), seed=seed,
                     outer_steps=10 ** 9, **fields)


def build_engine(cell: Cell, seed: int, tracer):
    """The program's engine and eval function for this cell's job."""
    from repro.async_engine.engine import make_engine, make_eval_fn
    engine = make_engine(run_config(cell, seed), cell.job["engine"],
                         tracer=tracer, **cell.job.get("runtime", {}))
    return engine, make_eval_fn(engine, batch=cell.job["eval_batch"])


def load_peaks(kind: str) -> dict:
    """The chip's published peaks; an unknown device is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"no peaks for device_kind {kind!r} in peaks.json; "
                       f"known: {sorted(k for k in table if k != 'source')}")
    return table[kind]


def _leaf_gaps(prog: dict, ref: dict, key: str):
    """Per leaf, the gap between the program's and the reference's norm
    over the larger of that leaf's and the median leaf's reference norm,
    with the mask of the leaves kept. Leaves whose reference loss
    gradient is under a thousandth of the median leaf's move by round-off
    alone under Adam and are left out."""
    raw = np.asarray(ref["raw_grad"])
    keep = raw >= 1e-3 * np.median(raw)
    r, p = np.asarray(ref[key])[keep], np.asarray(prog[key])[keep]
    return keep, np.abs(p - r) / np.maximum(r, np.median(r))


def compare(prog: dict, ref: dict) -> Dict[str, float]:
    """The numbers a check can hold to its limits: the widest gap of the
    mean eval loss over the first steps; and the leaf gaps
    (``_leaf_gaps``) of the first outer gradient (the momentum after step
    1) and of the parameters' change after the last step, at the worst
    leaf (``*_gap``) and at the median leaf (``*_gap_median``)."""
    out = {"loss_gap": float(max(abs(a - b) for a, b in
                                 zip(prog["loss"], ref["loss"])))}
    for key in ("grad", "change"):
        _, gaps = _leaf_gaps(prog, ref, key)
        out[key + "_gap"] = float(np.max(gaps))
        out[key + "_gap_median"] = float(np.median(gaps))
    return out


def worst_leaves(prog: dict, ref: dict) -> Dict[str, str]:
    """Names of the leaves behind ``grad_gap`` and ``change_gap``."""
    out = {}
    for key in ("grad", "change"):
        keep, gaps = _leaf_gaps(prog, ref, key)
        names = [n for n, k in zip(ref["names"], keep) if k]
        out[key + "_leaf"] = names[int(np.argmax(gaps))]
    return out


def reference_readings(cell: Cell, seed: int, **kw) -> dict:
    return Reference(cell.arch, cell.model, cell.job, seed,
                     **kw).run(CHECK_STEPS)


def quantity(metric: str) -> str:
    """What a metric measures: ``tokens_per_s.int8`` is ``tokens_per_s``
    in the cells that report it under that name."""
    return metric.split(".", 1)[0]


def _metric_reader(name: str):
    """``metrics/<name>.py``'s ``read``, or its quantity's where the
    metric has no reader of its own."""
    path = os.path.join(HERE, "metrics", name + ".py")
    if not os.path.exists(path):
        path = os.path.join(HERE, "metrics", quantity(name) + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             t_start: float, peaks: dict,
             say: Callable[[str], None] = print,
             numbers_out: Optional[dict] = None) -> dict:
    """One run; returns the result object of the last output line.
    ``numbers_out``, when given, receives every number ``compare`` makes,
    held to a limit or not."""
    devices = jax.devices()[:cell.chips]
    log = CompileLog()
    tracer = BenchTracer(annotate=traced)
    engine, eval_fn = build_engine(cell, seed, tracer)
    win = Window(engine, eval_fn, tracer, log, seconds, traced)
    engine.run(eval_every=cell.job["eval_every"], eval_fn=eval_fn)
    if "close" not in win.mark:
        raise RuntimeError("the run ended before the window closed")
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    opened, closed = win.mark["open"], win.mark["close"]
    window_s = closed["t"] - opened["t"]
    spans = tracer.spans[opened["span"]:closed["span"]]
    say(f"chipbench: cell={cell.name} seed={seed} "
        f"setup_s={opened['t'] - t_start:.3f} window_s={window_s:.3f} "
        f"commits={closed['arrivals'] - opened['arrivals']} "
        f"first_run_in_checkout={int(opened['misses'] > 0)} "
        f"compiles_in_window={closed['compiles'] - opened['compiles']} "
        f"cache_hits_in_window={closed['hits'] - opened['hits']} "
        f"cache_misses_in_window={closed['misses'] - opened['misses']} "
        f"backend_compile_s={log.seconds:.1f} cache_hits={log.hits} "
        f"cache_misses={log.misses}")
    prog, names = win.prog, win.names
    del engine, eval_fn, win, tracer
    gc.collect()

    metrics, extra = {}, {}
    if traced:
        run = trace.TracedRun.load(
            TRACE_DIR, cell=cell, spans=spans, peaks=peaks,
            tokens=closed["tokens"] - opened["tokens"],
            n_chips=cell.chips)
        shutil.rmtree(os.path.dirname(TRACE_DIR), ignore_errors=True)
        for m in cell.per_layer:
            value = _metric_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        extra = {"busy_s": run.busy_s(), "window_s": run.window_s}
        breakdown = run.breakdown()
    else:
        values = {
            "tokens_per_s": (closed["tokens"] - opened["tokens"]) / window_s,
            "peak_hbm_gib": peak / GIB,
            "setup_s": opened["t"] - t_start,
        }
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[quantity(m["name"])],
                                  "unit": m["unit"]}

    ref = reference_readings(cell, seed)
    if ref["names"] != names:
        raise RuntimeError("the reference's parameter tree differs from "
                           "the program's")
    numbers = compare(prog, ref)
    leaves = worst_leaves(prog, ref)
    say("chipbench: worst leaves " + " ".join(
        f"{k}={v}" for k, v in leaves.items()))
    if numbers_out is not None:
        numbers_out.update(numbers, **leaves)
    checks = {k: {"value": numbers[k], "limit": limit}
              for k, limit in cell.limits.items()}
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values())
    out = {"correct": correct,
           "attempted": closed["arrivals"] - opened["arrivals"],
           "failed": closed["dropped"] - opened["dropped"],
           "metrics": metrics,
           "device": {"platform": devices[0].platform,
                      "kind": devices[0].device_kind,
                      "count": jax.device_count(),
                      "memory_peak_bytes": peak, **extra}}
    if traced:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out
