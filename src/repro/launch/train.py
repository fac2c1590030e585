"""Training launcher.

Two ways to describe a run:

  - ``--scenario NAME``: a registered ``repro.scenarios`` spec — the same
    single source of truth the benchmarks, examples, and golden-trace CI
    gate build from (``--list-scenarios`` enumerates them).
  - ad-hoc flags: compiled into an anonymous ``Scenario`` first, so both
    paths construct the run identically.

Engines (--engine):
  - sim (default): the asynchronous HeLoCo training engine with
    heterogeneous virtual-clock workers — the paper's experiment runtime.
    Any --arch is accepted; pass --smoke to use its reduced config on CPU.
  - wallclock: the threaded concurrent runtime — one thread per worker,
    pseudo-gradients through a bounded transport, genuine compute/update
    overlap. Deterministic (simulator-equivalent) by default; add --free
    for true arrival order with --pace-scale wall-clock throttling.

For the production-mesh lower/compile pass defer to repro.launch.dryrun
(see that module's CLI).

    PYTHONPATH=src python -m repro.launch.train --arch tinygpt-15m --smoke \
        --method heloco --paces 1,1,6,6,6 --outer 50 --inner 10 \
        --engine wallclock --ckpt-dir /tmp/ck --resume
    PYTHONPATH=src python -m repro.launch.train --scenario paper_hetero_severe
"""
from __future__ import annotations

import argparse
import contextlib

from repro.checkpoint import ckpt as ckpt_lib
from repro.core import methods as outer_methods
from repro.async_engine.engine import make_engine, make_eval_fn
from repro.async_engine.faults import FaultSpec
from repro.launch.compile_cache import enable_compile_cache
from repro.scenarios import registry
from repro.scenarios.spec import Scenario

# --chaos preset: the docs/faults.md lossy channel (chaos_lossy's fault
# mix) keyed off the run seed — a quick way to smoke any wallclock run
# against an unreliable delivery layer.
def _chaos_faults(seed: int) -> FaultSpec:
    return FaultSpec(drop_p=0.2, dup_p=0.1, reorder_p=0.2,
                     delay_p=0.1, delay_s=0.01, ack_drop_p=0.05,
                     seed=seed + 97)


def scenario_from_args(args) -> Scenario:
    """Compile the launcher's flag dialect into a Scenario."""
    paces = tuple(float(p) for p in args.paces.split(","))
    outer_lr = args.outer_lr
    cap = outer_methods.get(args.method).outer_lr_cap
    if outer_lr is not None and cap is not None:
        outer_lr = min(outer_lr, cap)
    return Scenario(
        name="cli",
        arch=args.arch, smoke=args.smoke,
        engine=args.engine,
        mode="free" if args.free else "deterministic",
        pace_scale=args.pace_scale,
        transport=getattr(args, "transport", "inproc"),
        topology=getattr(args, "topology", "hub"),
        n_workers=args.workers, worker_paces=paces,
        inner_steps=args.inner, outer_steps=args.outer,
        batch_size=args.batch, seq_len=args.seq,
        non_iid=not args.iid, mixture_alpha=args.mixture_alpha,
        shard_assignment=args.shard_assignment, dylu=args.dylu,
        method=args.method, outer_lr=outer_lr, momentum=args.momentum,
        compression=args.compression,
        drop_stale_after=args.drop_stale_after,
        inner_lr=args.inner_lr, seed=args.seed,
        commit_batch=getattr(args, "commit_batch", 1),
        faults=(_chaos_faults(args.seed)
                if getattr(args, "chaos", False) else None))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenario", default="",
                    help="run a registered scenario by name (overrides the "
                         "ad-hoc config flags)")
    ap.add_argument("--list-scenarios", action="store_true")
    ap.add_argument("--arch", default="tinygpt-15m")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-friendly)")
    ap.add_argument("--method", default="heloco",
                    choices=outer_methods.cli_names(),
                    help="any registered repro.core.methods name or "
                         "benchmark-dialect alias")
    ap.add_argument("--workers", type=int, default=5)
    ap.add_argument("--paces", default="1,1,1,1,1")
    ap.add_argument("--outer", type=int, default=50)
    ap.add_argument("--inner", type=int, default=10)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--iid", action="store_true")
    ap.add_argument("--mixture-alpha", type=float, default=None,
                    help="per-worker Dirichlet(alpha) language mixtures "
                         "instead of one shard per worker")
    ap.add_argument("--dylu", action="store_true")
    ap.add_argument("--outer-lr", type=float, default=None,
                    help="default: the method's paper value (Table 3)")
    ap.add_argument("--momentum", type=float, default=0.9)
    ap.add_argument("--inner-lr", type=float, default=3e-3)
    ap.add_argument("--compression", default="none",
                    choices=["none", "int8", "topk"])
    ap.add_argument("--drop-stale-after", type=int, default=None)
    ap.add_argument("--shard-assignment", default="fixed",
                    choices=["fixed", "flexible"])
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--eval-every", type=int, default=None,
                    help="default: 10, or the scenario's golden-trace "
                         "cadence when --scenario is given")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--telemetry", default="", metavar="PATH",
                    help="stream per-arrival update-quality telemetry "
                         "(repro.telemetry JSONL) to this path, written "
                         "live (per-record flush) so `python -m repro.obs "
                         "console PATH` can tail the run")
    ap.add_argument("--telemetry-every", type=int, default=None,
                    metavar="N",
                    help="emit a runtime-health telemetry record every N "
                         "commits (default 1 when --telemetry is set, "
                         "else the scenario's telemetry_every)")
    ap.add_argument("--trace", default="", metavar="PATH",
                    help="profile the run with trace spans and export "
                         "Chrome trace-event JSON (Perfetto-loadable) "
                         "to this path")
    ap.add_argument("--profile", default="", metavar="DIR",
                    help="capture the run with jax.profiler into DIR "
                         "(TensorBoard / Perfetto); implies trace spans, "
                         "which land on the capture's host plane above "
                         "the device ops")
    ap.add_argument("--stats-json", default="", metavar="PATH",
                    help="dump the runtime stats_summary() as JSON at "
                         "exit (machine-readable CI artifact)")
    ap.add_argument("--engine", default="sim", choices=["sim", "wallclock"])
    ap.add_argument("--transport", default="inproc",
                    choices=["inproc", "socket"],
                    help="wallclock engine backend: threaded workers over "
                         "the in-process queue, or real worker processes "
                         "over the socket transport")
    ap.add_argument("--topology", default="hub",
                    choices=["hub", "ring", "gossip"],
                    help="exchange topology: hub-and-spoke server, or "
                         "decentralized NoLoCo-style ring/gossip peer "
                         "averaging (async methods only)")
    ap.add_argument("--commit-batch", type=int, default=1,
                    help="server commit-buffer size (docs/scale.md): >1 "
                         "coalesces up to K arrivals into one fused "
                         "flush; flush depth/reason telemetry lands in "
                         "the stream's 'flush' records")
    ap.add_argument("--free", action="store_true",
                    help="wallclock engine: free-running arrival order "
                         "instead of the deterministic simulator schedule")
    ap.add_argument("--pace-scale", type=float, default=0.0,
                    help="wallclock+free: wall seconds per virtual second "
                         "of worker pace (0 = no throttling)")
    ap.add_argument("--chaos", action="store_true",
                    help="wallclock engine: inject the docs/faults.md "
                         "lossy-channel preset (20%% drop, 10%% dup, 20%% "
                         "reorder, delays, lost acks); the at-least-once "
                         "delivery layer must absorb it")
    args = ap.parse_args()
    if args.chaos and args.engine != "wallclock":
        ap.error("--chaos needs --engine wallclock (the simulator has no "
                 "transport to inject faults into)")
    if args.transport == "socket" and args.engine != "wallclock":
        ap.error("--transport socket needs --engine wallclock (the "
                 "simulator has no worker processes)")

    if args.list_scenarios:
        for s in registry.all_scenarios():
            print(f"{s.name:24s} engine={s.engine}/{s.mode}  "
                  f"{s.description}")
        return
    enable_compile_cache()

    if args.scenario:
        scn = registry.get_scenario(args.scenario)
        if args.transport != "inproc" and scn.engine == "wallclock":
            scn = scn.overridden(transport=args.transport)
        if args.commit_batch > 1:
            scn = scn.overridden(commit_batch=args.commit_batch)
        print(f"scenario {scn.name}: {scn.description}")
    else:
        scn = scenario_from_args(args)
    # match the golden-trace eval cadence so a --scenario run is
    # comparable with its committed results/golden/<name>.json artifact
    eval_every = (args.eval_every if args.eval_every is not None
                  else (scn.eval_cadence if args.scenario else 10))
    recorder = None
    if args.telemetry:
        from repro.telemetry import TelemetryRecorder
        recorder = TelemetryRecorder(sink=args.telemetry)
    tracer = None
    if args.trace or args.profile:
        from repro.obs.spans import SpanTracer
        tracer = SpanTracer()
    # runtime-health cadence: explicit flag > "on" whenever telemetry is
    # streamed > the scenario's own telemetry_every knob
    runtime_every = (args.telemetry_every
                     if args.telemetry_every is not None
                     else (1 if args.telemetry else None))
    eng = make_engine(scn, telemetry=recorder, tracer=tracer,
                      runtime_record_every=runtime_every)
    if args.resume and args.ckpt_dir:
        latest = ckpt_lib.latest(args.ckpt_dir)
        if latest:
            eng.restore(latest)
            print(f"resumed from {latest} (outer step {eng.server.t})")

    eval_fn = make_eval_fn(eng, batch=scn.eval_batch)
    capture = contextlib.nullcontext()
    if args.profile:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # program spans, not every call
        capture = jax.profiler.trace(args.profile, profiler_options=opts)
    with capture:
        hist = eng.run(eval_every=eval_every, eval_fn=eval_fn,
                       ckpt_every=args.ckpt_every if args.ckpt_dir else 0,
                       ckpt_dir=args.ckpt_dir)
    if args.profile:
        print(f"profile -> {args.profile} (load in TensorBoard or "
              f"https://ui.perfetto.dev)")
    for e in hist.evals:
        print(f"step {e['step']:5d}  t={e['time']:8.0f}s  "
              f"loss={e['mean']:.4f}")
    taus = [a["staleness"] for a in hist.arrivals] or [0]
    print(f"done: arrivals={len(hist.arrivals)} tokens={hist.tokens} "
          f"mean_staleness={sum(taus) / len(taus):.2f} "
          f"comm={hist.comm_bytes / 1e6:.1f}MB")
    # cross-process collection contract: on the socket transport with any
    # observability output requested, a worker process that never shipped
    # an obs frame means the collection path is broken — fail loudly
    # instead of writing a parent-only trace/stats/stream (satellite of
    # docs/observability.md, "Cross-process collection")
    if ((args.trace or args.stats_json or args.telemetry)
            and hasattr(eng, "assert_child_reports")):
        eng.assert_child_reports()
    if hasattr(eng, "stats_summary"):
        s = eng.stats_summary()
        print(f"runtime[{s['mode']}]: {s['arrivals_per_sec']:.2f} arrivals/s "
              f"occupancy={s['server_occupancy']:.2f} "
              f"parallelism={s['compute_parallelism']:.2f} "
              f"overlap_max={s['overlap_max']}")
        d = s.get("delivery", {})
        if any(d.values()):
            hot = {k: v for k, v in d.items() if v}
            print(f"delivery: {hot}")
    if args.stats_json:
        import json
        import os
        summary = (eng.stats_summary() if hasattr(eng, "stats_summary")
                   else {"arrivals": len(hist.arrivals),
                         "tokens": hist.tokens,
                         "comm_bytes": hist.comm_bytes,
                         "mean_staleness": sum(taus) / len(taus)})
        os.makedirs(os.path.dirname(args.stats_json) or ".",
                    exist_ok=True)
        with open(args.stats_json, "w") as f:
            json.dump(summary, f, indent=2, sort_keys=True, default=str)
        print(f"stats -> {args.stats_json}")
    if recorder is not None:
        recorder.close()       # stream already on disk, live-flushed
        t = recorder.summary()
        print(f"telemetry -> {args.telemetry}: {t['arrivals']} arrivals "
              f"mean_cos={t['mean_cos_align']:.3f} "
              f"mean_corrected_frac={t['mean_corrected_frac']:.3f}")
    if args.trace:
        path = tracer.write(args.trace)
        print(f"trace -> {path}: {len(tracer)} events (load in "
              f"https://ui.perfetto.dev or chrome://tracing)")


if __name__ == "__main__":
    main()
