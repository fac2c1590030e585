"""Readings that set the limits of a cell's correctness check.

  python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,... \\
      --control-seeds 5,6,7 [--seconds 2] [--out calib.jsonl]

In one process: for each of ``--seeds`` a whole run of the cell through
the harness (set-up, a short window, the check), printing the numbers the
check compares (the lower readings); then for each of ``--control-seeds``
the same numbers for the reference computed with fp8 matrix products in
the program's place (the control) and for the reference with each of
the faults of ``reference.Reference.FAULTS`` planted in it. Not part of
a benchmark run.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    from chipbench.cells import load_cell
    from chipbench.run import find_chips, use_compile_cache
    cell = load_cell(args.workload)
    devices, why = find_chips(cell.chips)
    if devices is None:
        print(f"calibrate: {why}", file=sys.stderr)
        return 1
    use_compile_cache()
    from chipbench.harness import (compare, load_peaks, reference_readings,
                                   run_cell)
    peaks = load_peaks(devices[0].device_kind)
    out = open(args.out, "a") if args.out else None

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    t0 = T_START
    for seed in [int(s) for s in args.seeds.split(",") if s]:
        numbers = {}
        res = run_cell(cell, seed, args.seconds, False, t0, peaks,
                       say=lambda s: print(s, file=sys.stderr, flush=True),
                       numbers_out=numbers)
        emit({"cell": cell.name, "kind": "program", "seed": seed,
              "correct": res["correct"], "numbers": numbers,
              "metrics": {k: v["value"] for k, v in res["metrics"].items()}})
        t0 = time.perf_counter()
    for seed in [int(s) for s in args.control_seeds.split(",") if s]:
        ref = reference_readings(cell, seed)
        for kind, kw in (("control_fp8", {"matmul": "fp8"}),
                         ("fault_half_batch", {"fault": "half_batch"}),
                         ("fault_state_unchanged",
                          {"fault": "state_unchanged"}),
                         ("fault_delta_altered", {"fault": "delta_altered"})):
            emit({"cell": cell.name, "kind": kind, "seed": seed,
                  "numbers": compare(reference_readings(cell, seed, **kw),
                                     ref)})
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
