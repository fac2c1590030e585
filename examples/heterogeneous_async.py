"""End-to-end driver: train a ~100M-class config (or the tiny default) for
a few hundred steps, comparing HeLoCo to the paper's baselines under a
chosen pace configuration. Demonstrates DyLU, compression, stale-drop,
and Dirichlet language mixtures. Runs are described as
``repro.scenarios`` specs — the same source of truth as the launcher and
the golden-trace CI gate; ``--scenario NAME`` replays a registered one.

    PYTHONPATH=src:. python examples/heterogeneous_async.py \
        --paces 1,1,6,6,6 --methods async-heloco,async-mla --outer 30 \
        --engine wallclock
    PYTHONPATH=src:. python examples/heterogeneous_async.py \
        --scenario paper_hetero_severe
"""
import argparse

from benchmarks.common import METHODS, run_cached_scenario, scenario_for
from repro.scenarios import registry


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenario", default="",
                    help="replay a registered scenario instead of the "
                         "ad-hoc flags below")
    ap.add_argument("--paces", default="0.74,1.5,3,6,7.5")
    ap.add_argument("--methods", default="async-heloco,async-mla,"
                                         "async-nesterov,sync-nesterov")
    ap.add_argument("--outer", type=int, default=30)
    ap.add_argument("--inner", type=int, default=8)
    ap.add_argument("--iid", action="store_true")
    ap.add_argument("--mixture-alpha", type=float, default=None)
    ap.add_argument("--dylu", action="store_true")
    ap.add_argument("--compression", default="none",
                    choices=["none", "int8", "topk"])
    ap.add_argument("--drop-stale-after", type=int, default=None)
    ap.add_argument("--engine", default="sim", choices=["sim", "wallclock"],
                    help="wallclock = threaded concurrent runtime "
                         "(deterministic mode: same results, real overlap)")
    args = ap.parse_args()

    if args.scenario:
        scn = registry.get_scenario(args.scenario)
        print(f"scenario {scn.name}: {scn.description}")
        eng = scn.build()
        hist = eng.run()
        taus = [a["staleness"] for a in hist.arrivals] or [0]
        print(f"arrivals={len(hist.arrivals)} tokens={hist.tokens} "
              f"mean_staleness={sum(taus) / len(taus):.2f} "
              f"sim_time={hist.final_time:.0f}s")
        return

    paces = tuple(float(p) for p in args.paces.split(","))
    print(f"paces={paces} non_iid={not args.iid} dylu={args.dylu} "
          f"compression={args.compression} engine={args.engine}")
    print("method,final_loss,mean_staleness,sim_time_s,comm_MB")
    for method in args.methods.split(","):
        assert method in METHODS, method
        scn = scenario_for(paces, method=method, non_iid=not args.iid,
                           outer_steps=args.outer, inner_steps=args.inner,
                           dylu=args.dylu, compression=args.compression,
                           drop_stale_after=args.drop_stale_after,
                           mixture_alpha=args.mixture_alpha,
                           engine=args.engine)
        r = run_cached_scenario(f"example_{method}", scn)
        tau = sum(r["staleness"]) / max(len(r["staleness"]), 1)
        print(f"{method},{r['final_loss']:.4f},{tau:.2f},"
              f"{r['final_time']:.0f},{r['comm_bytes'] / 1e6:.1f}")


if __name__ == "__main__":
    main()
