#!/usr/bin/env python3
"""Find the knee of a serve cell: the highest offered rate that the serve
plane sustains with no growing backlog.  Run once on the chip, by hand;
the benchmark's own runs never run it.

    python3 chipbench/knee.py --workload fraud-xgb500.serve \\
        --rates 2000,4000,8000 [--seconds 5] [--seed 1]

One process builds the cell's deployment once and offers each rate in
turn, on the cell's own mix with only the rate changed.  For each rate
it prints one JSON line: requests sent, latency percentiles (from the
due time), the generator's lateness, the backlog when the last request
was sent, and the median latency of the first and the last fifth of the
requests.  A backlog that grows shows as a last fifth much slower than
the first.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

if __name__ == "__main__":
    _root = Path(__file__).resolve().parent.parent
    sys.path[:1] = [str(_root), str(_root / "src")]

import numpy as np  # noqa: E402

from chipbench import loads, spec  # noqa: E402


def offer(system, rate: float, seconds: float) -> dict:
    system.mix["rate_per_s"] = rate
    r = system.run(seconds, False)
    lat = r.latency_s * 1e3
    fifth = max(1, len(lat) // 5)
    backlog = int(np.sum(r.finished_s > r.sent_s[-1])) if r.ok.all() \
        else -1
    return {"rate_per_s": rate, "sent": len(lat),
            "failed": int((~r.ok).sum()),
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "p99_ms": float(np.percentile(lat, 99)),
            "max_ms": float(lat.max()),
            "send_late_p99_ms": float(np.percentile(r.sent_s - r.due_s,
                                                    99) * 1e3),
            "backlog_at_last_send": backlog,
            "first_fifth_p50_ms": float(np.median(lat[:fifth])),
            "last_fifth_p50_ms": float(np.median(lat[-fifth:]))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    bench = spec.load_benchmark()
    cell = spec.workload(bench, args.workload)
    mix = spec.traffic(cell["traffic"])
    if mix["loop"] != "open":
        raise SystemExit(f"{args.workload} serves no requests")
    system = loads.build(spec.config(bench, cell["config"]), mix, args.seed)
    try:
        system.warm()
        for rate in (float(x) for x in args.rates.split(",")):
            print(json.dumps(offer(system, rate, args.seconds)), flush=True)
    finally:
        system.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
