"""On-chip smoke run of the HeLoCo trainer at tinygpt-15m's published widths.

Drives the main path (Scenario -> make_engine -> execute_round ->
Synchronizer -> packed Pallas kernels) on a TPU with random weights made
from a seed, and checks what comes out. The timings it prints describe
this one smoke run, not a benchmark.

  python chip_smoke.py            one chip: phases A, B and C
  python chip_smoke.py --chips 4  four chips: phase D only

  A  the paper's job: 4 non-IID workers on paces (1,2,6,15), HeLoCo,
     simulator engine, commit_batch 1
  B  the same job with commit_batch 4 and int8 compression (K-stacked
     multi kernels, packed int8 kernels)
  C  one full-width arrival through the compiled packed commit against
     the plain jnp per-leaf reference; then a K=4 fused flush against
     four sequential reference arrivals
  D  the wallclock engine (deterministic mode) with one worker per chip,
     against the same run with every worker on the first chip

Exits non-zero, printing no result, when JAX finds no TPU or a check
fails. The last line of standard output is one JSON object naming the
device.
"""
from __future__ import annotations

import argparse
import collections
import heapq
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax                                                   # noqa: E402
import jax.numpy as jnp                                      # noqa: E402
import numpy as np                                           # noqa: E402
from jax import monitoring                                   # noqa: E402

from repro.async_engine.engine import make_engine, make_eval_fn  # noqa: E402
from repro.core import packing                               # noqa: E402
from repro.core.heloco import (                              # noqa: E402
    apply_arrival, apply_arrival_packed, apply_arrivals,
    apply_arrivals_packed, init_outer_state,
)
from repro.kernels import ops                                # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.scenarios.spec import Scenario                    # noqa: E402

#: the paper's job at tinygpt-15m's published widths (4 layers, d_model
#: 256, 8 heads, d_ff 1024, vocab 50257); depth of the run cut to a few
#: outer steps
JOB = Scenario(
    name="chip_smoke",
    description="paper job, tinygpt-15m full width, smoke length",
    arch="tinygpt-15m", smoke=False, engine="sim",
    n_workers=4, worker_paces=(1.0, 2.0, 6.0, 15.0),
    inner_steps=4, outer_steps=8, batch_size=8, seq_len=512,
    method="heloco", eval_batch=8, seed=0)

#: phase D paces: every worker commits within the run's outer steps
PINNED_PACES = (1.0, 2.0, 3.0, 4.0)

#: packed commit vs the per-leaf reference, fp32 on both sides
ARRIVAL_RTOL, ARRIVAL_ATOL = 1e-4, 1e-5
#: pinned vs unpinned final eval loss (same programs, same chip kind)
PINNED_LOSS_ATOL = 1e-3


class CompileLog:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
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
            self.seconds += duration


class Checks:
    def __init__(self):
        self.failed = []

    def __call__(self, ok: bool, what: str):
        print(f"smoke: check {'ok  ' if ok else 'FAIL'} {what}")
        if not ok:
            self.failed.append(what)


def scheduled_arrivals(paces, h, n_commits, batch):
    """(wid, s_i, staleness) in commit order, from the virtual-clock
    schedule alone: worker w returns every h * pace_w virtual seconds,
    returns at one tick commit together (up to ``batch``) in dispatch
    order, and a worker is re-dispatched at the outer step after its
    commit."""
    heap = [(h * p, w, w) for w, p in enumerate(paces)]
    heapq.heapify(heap)
    s_i = {w: 0 for w in range(len(paces))}
    seq, t, out = len(paces), 0, []
    while t < n_commits:
        tick = heap[0][0]
        group = []
        while (heap and heap[0][0] == tick
               and len(group) < min(batch, n_commits - t)):
            group.append(heapq.heappop(heap))
        for j, (_, _, w) in enumerate(group):
            out.append((w, s_i[w], t + j - s_i[w]))
        t += len(group)
        for tick_w, _, w in group:
            s_i[w] = t
            heapq.heappush(heap, (tick_w + h * paces[w], seq, w))
            seq += 1
    return out


def arrivals_of(hist):
    return [(a["worker_id"], a["outer_step"] - 1 - a["staleness"],
             a["staleness"]) for a in hist.arrivals]


def run_job(name, scn, check, engine=None):
    """Build the run through the normal path and train it; eval at the
    start and at the end. Returns (first eval, final eval, history)."""
    t0 = time.perf_counter()
    eng = engine if engine is not None else make_engine(scn)
    eval_fn = make_eval_fn(eng, batch=scn.eval_batch)
    first = eval_fn(eng.server.state.params, 0, 0.0)
    hist = eng.run(eval_fn=eval_fn)
    last = hist.evals[-1]
    wall = time.perf_counter() - t0
    print(f"smoke: phase {name} wall_s={wall:.1f} "
          f"loss_first={first['mean']:.4f} loss_final={last['mean']:.4f} "
          f"arrivals={len(hist.arrivals)} tokens={hist.tokens}")
    print(f"smoke: phase {name} per-language loss "
          + " ".join(f"{k}={first['per_lang'][k]:.4f}->{v:.4f}"
                     for k, v in last["per_lang"].items()))
    losses = ([first["mean"], last["mean"]] + list(first["per_lang"].values())
              + list(last["per_lang"].values()))
    check(all(np.isfinite(losses)), f"{name}: every eval loss is finite")
    want = scheduled_arrivals(scn.paces, scn.inner_steps, scn.outer_steps,
                              scn.commit_batch)
    check(arrivals_of(hist) == want,
          f"{name}: arrivals as scheduled {want}")
    return first, last, hist


def phase_a(check):
    first, last, hist = run_job("A", JOB, check)
    # Each language draws its private tokens near-uniformly from ~11k ids,
    # so a few commits learn only which language comes next: the language
    # committed most gains at the others' expense, and the mean over
    # languages rises until every worker has committed many times. The
    # training signal this run can show is that language's own loss.
    lang = collections.Counter(
        a["lang"] for a in hist.arrivals).most_common(1)[0][0]
    a, b = first["per_lang"][lang], last["per_lang"][lang]
    check(b < a, f"A: final loss of the most-committed language {lang!r} "
          f"{b:.4f} < first {a:.4f}")


def phase_b(check):
    run_job("B", JOB.overridden(commit_batch=4, compression="int8"), check)


def _mixed_arrival(params, seed):
    """Momentum and a pseudo-gradient whose per-block cosines fall in every
    branch of the correction: aligned (keep), anti-aligned (damp) and
    weakly aligned (rotate)."""
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), 2 * len(leaves))
    mom, delta = [], []
    for i, x in enumerate(leaves):
        m = 1e-2 * jax.random.normal(keys[2 * i], x.shape, jnp.float32)
        n = 1e-2 * jax.random.normal(keys[2 * i + 1], x.shape, jnp.float32)
        mom.append(m)
        delta.append((1.0, -1.0, 0.1)[i % 3] * m + 0.5 * n)
    return treedef.unflatten(mom), treedef.unflatten(delta)


def _max_err(got, want):
    """Largest |got - want| and whether every leaf is allclose."""
    err, ok = 0.0, True
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        g, w = np.asarray(g), np.asarray(w)
        err = max(err, float(np.max(np.abs(g - w))))
        ok &= bool(np.allclose(g, w, rtol=ARRIVAL_RTOL, atol=ARRIVAL_ATOL))
    return err, ok


def phase_c(check):
    t0 = time.perf_counter()
    check(ops._auto_interpret(None) is False,
          "C: Pallas kernels resolve to compiled Mosaic (interpret=False)")
    scn = JOB
    cfg = scn.outer_config()
    kw = dict(method=cfg.method, outer_lr=cfg.outer_lr, mu=cfg.momentum,
              h=cfg.heloco)
    from repro.models import build_model
    model = build_model(scn.model_config())
    params = model.init(jax.random.PRNGKey(scn.seed))
    mom, delta = _mixed_arrival(params, scn.seed + 1)
    layout = packing.build_layout(params)
    pbuf = packing.pack(layout, params)
    mbuf = packing.pack(layout, mom)
    state = init_outer_state(params)._replace(momentum=mom)

    # one arrival (tau 3): compiled packed commit vs per-leaf jnp
    packed = jax.jit(lambda p, m, d: apply_arrival_packed(
        p, m, d, layout, rho=1.0, tau=3.0, **kw))
    tc = time.perf_counter()
    compiled = packed.lower(pbuf, mbuf, delta).compile()
    compile_s = time.perf_counter() - tc
    check("tpu_custom_call" in compiled.as_text(),
          "C: the commit's HLO holds tpu_custom_call")
    p1, m1 = compiled(pbuf, mbuf, delta)
    ref = jax.jit(lambda s, d: apply_arrival(
        s, d, rho=1.0, tau=3.0, use_kernel=False, **kw))(state, delta)
    err_p, ok_p = _max_err(packing.unpack(layout, p1), ref.params)
    err_m, ok_m = _max_err(packing.unpack(layout, m1), ref.momentum)
    print(f"smoke: phase C arrival d={layout.total_elems} "
          f"blocks={layout.n_blocks} rows={layout.n_rows} "
          f"commit_compile_s={compile_s:.2f} max_abs_err params={err_p:.3g} "
          f"momentum={err_m:.3g}")
    check(ok_p and ok_m, f"C: packed arrival allclose to the reference "
          f"(rtol {ARRIVAL_RTOL}, atol {ARRIVAL_ATOL})")

    # K=4 fused flush (one multi-Gram + one multi sweep) vs 4 sequential
    k = 4
    deltas = [_mixed_arrival(params, scn.seed + 2 + j)[1] for j in range(k)]
    taus = [float(j) for j in range(k)]
    pk4, mk4 = jax.jit(lambda p, m, ds: apply_arrivals_packed(
        p, m, ds, layout, rhos=[1.0] * k, taus=taus, **kw))(
            pbuf, mbuf, deltas)
    ref4 = jax.jit(lambda s, ds: apply_arrivals(
        s, ds, rhos=[1.0] * k, taus=taus, use_kernel=False, **kw))(
            state, deltas)
    err_p, ok_p = _max_err(packing.unpack(layout, pk4), ref4.params)
    err_m, ok_m = _max_err(packing.unpack(layout, mk4), ref4.momentum)
    print(f"smoke: phase C flush K={k} max_abs_err params={err_p:.3g} "
          f"momentum={err_m:.3g} wall_s={time.perf_counter() - t0:.1f}")
    check(ok_p and ok_m, f"C: fused K={k} flush allclose to {k} sequential "
          f"reference arrivals (rtol {ARRIVAL_RTOL}, atol {ARRIVAL_ATOL})")


def phase_d(check):
    devices = jax.devices()
    check(len(devices) == 4, f"D: four chips visible ({len(devices)})")
    scn = JOB.overridden(engine="wallclock", worker_paces=PINNED_PACES)
    m = scn.materialize()
    runs = {}
    for pin in (True, False):
        eng = make_engine(m.run_cfg, m.engine, pin_devices=pin, **m.engine_kw)
        produced = {}
        execute = eng._execute

        def record(task, execute=execute, produced=produced):
            res = execute(task)
            produced.setdefault(task.wid, set()).update(
                d for leaf in jax.tree.leaves(res.delta)
                for d in leaf.devices())
            return res
        eng._execute = record
        name = "D pinned" if pin else "D unpinned"
        _, last, hist = run_job(name, scn, check, engine=eng)
        runs[pin] = (last["mean"], arrivals_of(hist), produced)
        print(f"smoke: phase {name} delta devices "
              + " ".join(f"w{w}={sorted(str(d) for d in ds)}"
                         for w, ds in sorted(produced.items())))
    (last_p, arr_p, prod_p), (last_u, arr_u, _) = runs[True], runs[False]
    check(arr_p == arr_u, "D: pinned and unpinned arrival sequences match")
    check(abs(last_p - last_u) <= PINNED_LOSS_ATOL,
          f"D: final eval loss pinned {last_p:.6f} vs unpinned {last_u:.6f} "
          f"(atol {PINNED_LOSS_ATOL})")
    check(sorted(prod_p) == list(range(4)) and all(
        prod_p[w] == {devices[w]} for w in range(4)),
        "D: each worker's delta was produced on that worker's chip")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: phases A-C on one chip; 4: phase D only")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    log = CompileLog()
    print(f"smoke: device_kind={dev.device_kind} count={len(jax.devices())} "
          f"jax={jax.__version__} compile_cache={cache_dir}")
    check = Checks()
    t0 = time.perf_counter()
    phases = [phase_d] if args.chips == 4 else [phase_a, phase_b, phase_c]
    for phase in phases:
        phase(check)
    print(f"smoke: total_wall_s={time.perf_counter() - t0:.1f} "
          f"backend_compile_s={log.seconds:.1f} cache_hits={log.hits} "
          f"cache_misses={log.misses}")
    if check.failed:
        print(f"chip_smoke: {len(check.failed)} check(s) failed: "
              f"{check.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
