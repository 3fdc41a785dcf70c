#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one run of one cell of ``BENCHMARK.json``; the last line of
standard output is the result.  Needs the TPU chips the cell asks for:
without them it says what JAX reports, prints no result and exits 2.
"""

import time

T_START = time.perf_counter()

import argparse          # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import sys               # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    if not os.path.isdir(os.path.join(ROOT, "parsec_tpu")):
        print("benchmark: the program (parsec_tpu/) is not in this "
              "directory tree: nothing to measure", file=sys.stderr)
        return 2
    from benchmark import harness
    harness.place_compile_cache()
    spec, cell, config, traffic = harness.load_cell(args.workload)
    harness.require_chips(int(cell["chips"]))
    result = harness.run_cell(spec, cell, config, traffic, args.seed,
                              args.seconds, bool(args.trace), T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
