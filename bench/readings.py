"""Readings for a cell's limits, in one process on the chip.

    python3 bench/readings.py --workload <name> --seconds <s> \
        --seeds 11,12,13 --control-seeds 21,22,23 [--out FILE]

For each of ``--seeds`` a run of the cell (its timed path, a window of
``--seconds``) prints its check numbers: the lower readings.  For each
of ``--control-seeds`` the control (the reference computed in bfloat16)
is compared with the float32 reference on the pixels a run of that seed
compares, at the cell's own size: the upper readings.  Each reading is
one JSON line on stdout (and in ``--out``).  Not part of a benchmark run.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def control(workload: str, seed: int, seconds: float) -> dict:
    """bfloat16 reference against float32 on the pixels a run compares."""
    import importlib

    import jax
    import jax.numpy as jnp

    from bench import check, harness, traffic as traffic_lib
    cell = harness.cell_spec(workload)
    cfg = json.loads((ROOT / cell["config_spec"]["file"]).read_text())
    spec = traffic_lib.load(cell["traffic"])
    reference = importlib.import_module(f"bench.reference.{cfg['architecture']}")
    params = reference.make_params(cfg, seed)
    frames = None
    if spec["loop"]["kind"] == "closed":
        system = importlib.import_module(f"bench.system.{cfg['architecture']}").build(cfg)
        frames = harness.closed_frames(system, params, traffic_lib.warm_up(spec)[0], spec,
                                       seconds)
    offered = traffic_lib.requests(spec, seed, seconds, frames)
    k = spec["check_pixels_per_frame"]
    fake = [(rid, cam, _zeros(cam)) for rid, cam, _ in offered]
    o, d, _ = check.rays(reference, fake, seed, k)
    t0 = time.perf_counter()
    ref = reference.render(cfg, params, o, d)
    low = reference.render(cfg, params, o, d, dtype=jnp.bfloat16)
    jax.block_until_ready(params)
    out = check.readings(low, ref)
    out.update(kind="control", workload=workload, seed=seed, frames=len(offered),
               rays=int(o.shape[0]), seconds=time.perf_counter() - t0)
    return out


def _zeros(cam):
    import numpy as np
    return np.zeros((cam[0], cam[1], 3), np.float32)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    from bench import harness
    seeds = [int(s) for s in args.seeds.split(",") if s]
    cseeds = [int(s) for s in args.control_seeds.split(",") if s]
    rows = []
    for s in cseeds:
        rows.append(control(args.workload, s, args.seconds))
        print(json.dumps(rows[-1]), flush=True)
    for s in seeds:
        r = harness.run(args.workload, s, args.seconds, False, time.perf_counter())
        if r is None:
            return 2
        rows.append({"kind": "program", "workload": args.workload, "seed": s,
                     "frames": r["attempted"], "failed": r["failed"],
                     **{n: c["value"] for n, c in r["checks"].items() if n != "failed_frames"},
                     "metrics": {n: m["value"] for n, m in r["metrics"].items()}})
        print(json.dumps(rows[-1]), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        with open(args.out, "a") as f:
            f.writelines(json.dumps(r) + "\n" for r in rows)
    return 0


if __name__ == "__main__":
    sys.exit(main())
