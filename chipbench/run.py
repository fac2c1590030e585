"""Run one benchmark cell once on the chips of this machine.

  python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in BENCHMARK.json. With ``--trace 0`` the
last line of standard output holds the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics from a profiler trace of the window.
Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell needs. Compiled programs are cached in
``$JAX_COMPILATION_CACHE_DIR`` where it is set, else in ``.jax_cache/`` of
the checkout; every program is kept, however quickly it compiled, so a
second run in a checkout compiles nothing.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the TPU runtime would otherwise keep its logs in a fixed /tmp directory
os.environ.setdefault("TPU_LOG_DIR", "disabled")
for path in (ROOT, os.path.join(ROOT, "src")):
    if path not in sys.path:
        sys.path.insert(0, path)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def find_chips(need: int):
    """The cell's chips, or a reason why there are none."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return None, (f"needs a TPU, JAX found {devices[0].platform!r} "
                      f"({devices[0].device_kind})")
    if len(devices) < need:
        return None, f"needs {need} chips, JAX found {len(devices)}"
    return devices, ""


def use_compile_cache() -> str:
    """The persistent compilation cache, keeping every program (JAX keeps
    only those that took a second or more to compile by default)."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache


def main(argv=None) -> int:
    args = parse(argv)
    from chipbench.cells import load_cell
    cell = load_cell(args.workload)
    devices, why = find_chips(cell.chips)
    if devices is None:
        print(f"chipbench: {why}", file=sys.stderr)
        return 1
    cache = use_compile_cache()
    print(f"chipbench: device_kind={devices[0].device_kind} "
          f"count={len(devices)} compile_cache={cache}", file=sys.stderr)
    from chipbench.harness import load_peaks, run_cell
    peaks = load_peaks(devices[0].device_kind)
    say = lambda line: print(line, file=sys.stderr, flush=True)  # noqa: E731
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), T_START,
                   peaks, say=say)
    for name, c in out["checks"].items():
        say(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
