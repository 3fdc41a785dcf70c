#!/usr/bin/env python3
"""The control of a cell's comparison, on the chip at the cell's own size:

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 [--store fp8|config]

Puts the plain reference (``apps/<app>.py: control``) in the program's
place, computed in the nearest precision below the one the configuration
states, and prints one line a seed with every number the comparison
reads beside its limit.  Every such line has to read over a limit: that
is what the limits in the configuration's file were set against.  Not
part of a benchmark run; ``--override`` merges a JSON object into the
configuration for a reading at another setting.
"""

import argparse
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--store", choices=("fp8", "config"), default="fp8")
    ap.add_argument("--override", default="{}")
    args = ap.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark import harness
    harness.place_compile_cache()
    _spec, cell, config, traffic = harness.load_cell(args.workload)
    config = {**config, **json.loads(args.override)}
    dev = harness.require_chips(int(cell["chips"]))
    app = importlib.import_module(f"benchmark.apps.{config['app']}")
    for seed in (int(s) for s in args.seeds.split(",")):
        got = app.control(config, traffic, seed, args.store)
        over = {k: got[k] > lim for k, lim in config["limits"].items()}
        print(json.dumps({"workload": cell["name"], "seed": seed,
                          "store": args.store, "control": got,
                          "limits": config["limits"], "over_limit": over,
                          "fails": any(over.values()), "device": dev}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
