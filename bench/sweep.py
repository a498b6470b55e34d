"""Find an open-loop cell's knee: the highest offered rate without a
growing backlog, in one process on the chip.

    python3 bench/sweep.py --workload ingp-paper.session-64 --seconds 40 \
        --rates 0.7,0.9,1.1,1.3 --seed 5

For each rate the cell runs once with its traffic's ``rate_per_s``
replaced.  Each row reports the frames served per second of the window,
the latency percentiles, and the backlog's growth: the slope of frame
latency against due time (s of latency per s of window; near 0 below
the knee) and how long the queue took to drain after the last arrival.
Not part of a benchmark run; the rate it finds is written into the
traffic file by hand.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args(argv)
    import numpy as np

    from bench import harness, traffic
    load = traffic.load
    for rate in (float(r) for r in args.rates.split(",")):
        def with_rate(name, bdir=traffic.HERE, rate=rate):
            spec = load(name, bdir)
            spec["loop"]["rate_per_s"] = rate
            return spec
        traffic.load = with_rate
        seen = {}
        r = harness.run(args.workload, args.seed, args.seconds, False, time.perf_counter(),
                        observe=seen)
        due = np.asarray([q.arrival_s for q in seen["served"]])
        lat = np.asarray(seen["latency_s"])
        slope = float(np.polyfit(due, lat, 1)[0]) if len(due) > 2 else None
        drain = max(q.done_at for q in seen["served"]) - seen["t0"] - max(due)
        row = {"rate_per_s": rate, "frames": r["attempted"],
               "served_per_s": len(due) / (max(q.done_at for q in seen["served"]) - seen["t0"]),
               "latency_slope": slope, "drain_s": drain,
               **{k: v["value"] for k, v in r["metrics"].items()}}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
