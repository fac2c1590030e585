"""Shared harness for the paper-reproduction benchmarks.

Each benchmark names a scenario (a ``repro.scenarios.Scenario`` — the
single source of truth the launcher and tests also build from), runs a
training engine (the event-driven simulator by default; pass
engine="wallclock" for the threaded concurrent runtime — same Engine API,
real overlap), and caches results as JSON under results/experiments/ so
EXPERIMENTS.md assembly and reruns are cheap.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax

from repro.configs.base import RunConfig
from repro.core import methods as outer_methods
from repro.async_engine.engine import make_engine, make_eval_fn
from repro.scenarios.spec import Scenario

RESULTS_DIR = os.environ.get("REPRO_RESULTS", "results/experiments")

# paper Table 3 (Appendix A.5): benchmark-dialect names ("async-heloco")
# -> raw method + defaults, straight from the ``repro.core.methods``
# registry (the aliases live ON the method definitions; the duplicated
# alias table this module used to keep is gone).
METHODS = {alias: dict(method=raw, **outer_methods.get(raw).defaults())
           for alias, raw in outer_methods.alias_table().items()}


def scenario_for(paces: Sequence[float], *, method: str, non_iid: bool,
                 outer_steps: int, inner_steps: int, dylu: bool = False,
                 seed: int = 0, compression: str = "none",
                 drop_stale_after: Optional[int] = None,
                 shard_assignment: str = "fixed",
                 mixture_alpha: Optional[float] = None,
                 batch_size: int = 4, seq_len: int = 64,
                 name: str = "bench", **scenario_kw) -> Scenario:
    """The benchmark dialect, compiled to a Scenario: `method` accepts the
    benchmark preset names ("async-heloco", ...) or raw method names
    (``Scenario`` canonicalizes through the method registry)."""
    return Scenario(
        name=name, method=method,
        n_workers=len(paces),
        worker_paces=tuple(float(p) for p in paces),
        outer_steps=outer_steps, inner_steps=inner_steps,
        batch_size=batch_size, seq_len=seq_len,
        non_iid=non_iid, dylu=dylu, seed=seed,
        compression=compression, drop_stale_after=drop_stale_after,
        shard_assignment=shard_assignment, mixture_alpha=mixture_alpha,
        **scenario_kw)


def base_run(paces: Sequence[float], *, method: str, non_iid: bool,
             outer_steps: int, inner_steps: int, dylu: bool = False,
             seed: int = 0, compression: str = "none",
             drop_stale_after: Optional[int] = None,
             shard_assignment: str = "fixed") -> RunConfig:
    return scenario_for(
        paces, method=method, non_iid=non_iid, outer_steps=outer_steps,
        inner_steps=inner_steps, dylu=dylu, seed=seed,
        compression=compression, drop_stale_after=drop_stale_after,
        shard_assignment=shard_assignment).run_config()


def _key(rc: RunConfig, eval_every: int, engine: str = "sim",
         engine_kw: Optional[Dict] = None, eval_batch: int = 8,
         budget=None, telemetry: bool = False) -> str:
    blob = json.dumps(dataclasses.asdict(rc), sort_keys=True, default=str)
    # keep pre-engine cache keys stable for the default simulator/eval
    tag = ("" if engine == "sim"
           else engine + json.dumps(engine_kw or {}, sort_keys=True,
                                    default=str))
    if eval_batch != 8:
        tag += f"eb{eval_batch}"
    if budget is not None:
        tag += f"|budget:{budget.kind}:{budget.amount}"
    if telemetry:
        tag += "|telem"
    # a result is only ever served back on the device kind it ran on
    dev = jax.devices()[0]
    tag += f"|{dev.platform}:{dev.device_kind}"
    return hashlib.sha1((blob + str(eval_every) + tag).encode()
                        ).hexdigest()[:16]


def run_cached(name: str, rc: RunConfig, eval_every: int = 0,
               force: bool = False, engine: str = "sim",
               eval_batch: int = 8, budget=None,
               telemetry_path: Optional[str] = None, **engine_kw) -> Dict:
    """Run (or reload) one cached training run.

    budget: optional ``repro.async_engine.engine.Budget`` stopping rule —
    part of the cache key, applied via ``eng.run(budget=...)``.
    telemetry_path: when set, stream per-arrival update-quality telemetry
    (``repro.telemetry``) to this JSONL path; the cache is only reused if
    the stream file still exists alongside the result JSON.
    """
    os.makedirs(RESULTS_DIR, exist_ok=True)
    key = _key(rc, eval_every, engine, engine_kw, eval_batch, budget,
               telemetry_path is not None)
    path = os.path.join(RESULTS_DIR, f"{name}__{key}.json")
    if os.path.exists(path) and not force and (
            telemetry_path is None or os.path.exists(telemetry_path)):
        return json.load(open(path))
    rec = None
    if telemetry_path is not None:
        from repro.telemetry import RunMeta, TelemetryRecorder
        rec = TelemetryRecorder(meta=RunMeta(
            method=rc.outer.method, engine=engine,
            n_workers=rc.n_workers, outer_steps=rc.outer_steps,
            seed=rc.seed, non_iid=rc.non_iid,
            mixture_alpha=rc.mixture_alpha, scenario=name))
    eng = make_engine(rc, engine, telemetry=rec, **engine_kw)
    eval_fn = make_eval_fn(eng, batch=eval_batch, seq=rc.seq_len)
    t0 = time.time()
    hist = eng.run(eval_every=eval_every or max(rc.outer_steps // 8, 1),
                   eval_fn=eval_fn, budget=budget)
    out = {
        "name": name,
        "engine": engine,
        "config": {"paces": rc.worker_paces, "method": rc.outer.method,
                   "non_iid": rc.non_iid, "dylu": rc.dylu,
                   "outer_steps": rc.outer_steps,
                   "inner_steps": rc.inner_steps,
                   "compression": rc.outer.compression,
                   "drop_stale_after": rc.outer.drop_stale_after},
        "evals": hist.evals,
        "final_loss": hist.evals[-1]["mean"] if hist.evals else None,
        "per_lang": hist.evals[-1]["per_lang"] if hist.evals else None,
        "tokens": hist.tokens,
        "comm_bytes": hist.comm_bytes,
        "final_time": hist.final_time,
        "staleness": [a["staleness"] for a in hist.arrivals],
        "arrival_workers": [a["worker_id"] for a in hist.arrivals],
        "n_dropped": sum(1 for a in hist.arrivals if a.get("dropped")),
        "wall_seconds": time.time() - t0,
    }
    if budget is not None:
        out["budget"] = {"kind": budget.kind, "amount": budget.amount}
    if rec is not None:
        out["telemetry"] = rec.write_jsonl(telemetry_path)
        out["telemetry_summary"] = rec.summary()
    if hasattr(eng, "stats_summary"):
        out["runtime_stats"] = eng.stats_summary()
    json.dump(out, open(path, "w"), indent=1)
    return out


def run_cached_scenario(name: str, scn: Scenario, eval_every: int = 0,
                        force: bool = False, budget=None,
                        telemetry_path: Optional[str] = None) -> Dict:
    """run_cached driven entirely by a Scenario: engine choice, runtime
    options, and the eval cadence/batch all come from the spec, so the
    curve is comparable with the scenario's golden trace. ``budget`` and
    ``telemetry_path`` forward to :func:`run_cached` (the sweep harness
    entry point)."""
    m = scn.materialize()
    if m.failures or m.elastic:
        raise ValueError("run_cached_scenario does not cache runs with "
                         "failure/elastic schedules; use scn.build()")
    return run_cached(name, m.run_cfg,
                      eval_every=eval_every or scn.eval_cadence,
                      force=force, engine=m.engine,
                      eval_batch=scn.eval_batch, budget=budget,
                      telemetry_path=telemetry_path, **m.engine_kw)


def loss_at_time(result: Dict, t: float) -> Optional[float]:
    """Loss of the last eval snapshot at sim-time <= t."""
    best = None
    for e in result["evals"]:
        if e["time"] <= t + 1e-9:
            best = e["mean"]
    return best
