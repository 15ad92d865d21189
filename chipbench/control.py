#!/usr/bin/env python3
"""Readings of the control of a cell: the plain reference computed from
bfloat16 inputs, put in the engine's place, and compared as a run
compares the engine.  Run on the chip, by hand, to set the limits of
``chipbench/check.py``; the benchmark's own runs never run it.

    python3 chipbench/control.py --workload fraud-xgb500.serve \\
        --seeds 1,2,3 [--seconds 10]

It compares at the positions a run of ``--seconds`` compares: the
sampled rows of the table (scan), or the row of every request the mix
sends in that time (serve).  One JSON line per seed.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

if __name__ == "__main__":
    _root = Path(__file__).resolve().parent.parent
    sys.path[:1] = [str(_root), str(_root / "src")]

from chipbench import check, loads, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    bench = spec.load_benchmark()
    cell = spec.workload(bench, args.workload)
    cfg = spec.config(bench, cell["config"])
    mix = spec.traffic(cell["traffic"])
    # a window whose every output is due and answered: what the
    # comparison reads of it is where it compares
    window = loads.loop(mix).positions(cfg, mix, args.seconds)
    for seed in (int(s) for s in args.seeds.split(",")):
        values = check.readings(cfg, mix, seed, window, narrow=True)
        correct, _ = check.verdict(values)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": values, "control_passes": correct}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
