"""What TensoRF's VM encode costs on the chip, by gather layout.

    python3 benchmarks/tensorf_gathers.py           # on a TPU, CONFIG
    python3 benchmarks/tensorf_gathers.py --small   # SMOKE, any backend

Times, on ``configs/tensorf_vm192.CONFIG`` with seeded tables:

  * one-index row gathers of 131,072 and 65,536 random indices into a
    table of ``3 * grid^2`` rows of 16, 48, 64, 96 and 192 float32: the
    one-corner rows of the density and appearance sides (16, 48) and
    the appearance rows packed two corners wide (96), and both sides' rows
    packed four corners wide (64, 192);
  * one chunk step's encode of each side (``block_size * chunk`` points
    for density, half as many color anchors for appearance) in three
    layouts: one gather a plane corner and a line end (18 indices a
    point), two plane corners a row (9), and the program's four plane
    corners a row (6, ``tensorf.vm_features``), each checked against the
    first.  The packed tables (``tensorf.pack_tables``) are derived
    outside the timed calls, and their derivation is timed on its own.

Each timing prints the first call (compile included) and the median and
minimum of the following calls, on the host clock around
``block_until_ready``, and a gather's nanoseconds an index.  The last
line is the device's peak HBM.
"""
import argparse
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.configs.tensorf_vm192 import CONFIG, SMOKE  # noqa: E402
from repro.core import tensorf  # noqa: E402

ROW_WIDTHS = (16, 48, 64, 96, 192)


def timeit(name, f, *args, n=7, per=None):
    t0 = time.perf_counter()
    out = jax.block_until_ready(f(*args))
    first = time.perf_counter() - t0
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(f(*args))
        ts.append(time.perf_counter() - t0)
    med = float(np.median(ts))
    extra = f" ns_per_index={1e9 * med / per:.3f}" if per else ""
    print(f"{name}: first_s={first:.3f} median_ms={1e3 * med:.4f} "
          f"min_ms={1e3 * min(ts):.4f}{extra}", flush=True)
    return out


def _voxel(points, n):
    g = jnp.clip(points.astype(jnp.float32), 0.0, 1.0) * (n - 1)
    base = jnp.minimum(jnp.floor(g), n - 2).astype(jnp.int32)
    return base, g - base


def features_18(points, tables, n):
    """One gather a plane corner and a line end: 18 indices a point."""
    R = tables["planes"].shape[-1]
    base, frac = _voxel(points, n)
    planes = tables["planes"].reshape(3 * n * n, R)
    lines = tables["lines"].reshape(3 * n, R)
    out = []
    for m, ((a, b), c) in enumerate(zip(tensorf.PLANE_AXES, tensorf.LINE_AXES)):
        fa, fb, fc = frac[:, a:a + 1], frac[:, b:b + 1], frac[:, c:c + 1]
        row = m * n * n + base[:, a] * n + base[:, b]
        p = (planes[row] * ((1 - fa) * (1 - fb)) + planes[row + 1] * ((1 - fa) * fb)
             + planes[row + n] * (fa * (1 - fb)) + planes[row + n + 1] * (fa * fb))
        line = m * n + base[:, c]
        v = lines[line] * (1 - fc) + lines[line + 1] * fc
        out.append(p * v)
    return jnp.stack(out, axis=1)


def two_corners(packed):
    """Plane rows two corners wide (``row, row+1``: the first half of a
    packed four-corner row) beside the packed line rows."""
    R = packed["ends"].shape[-1] // 2
    return {"corners": packed["corners"][..., :2 * R], "ends": packed["ends"]}


def features_two(points, packed, n):
    """The same products from ``two_corners``' rows: 6 + 3 indices a point."""
    R = packed["ends"].shape[-1] // 2
    base, frac = _voxel(points, n)
    planes = packed["corners"].reshape(3 * n * n, 2 * R)
    ends = packed["ends"].reshape(3 * n, 2 * R)
    out = []
    for m, ((a, b), c) in enumerate(zip(tensorf.PLANE_AXES, tensorf.LINE_AXES)):
        fa, fb, fc = frac[:, a:a + 1], frac[:, b:b + 1], frac[:, c:c + 1]
        row = m * n * n + base[:, a] * n + base[:, b]
        lo, hi = planes[row], planes[row + n]
        p = (lo[:, :R] * ((1 - fa) * (1 - fb)) + lo[:, R:] * ((1 - fa) * fb)
             + hi[:, :R] * (fa * (1 - fb)) + hi[:, R:] * (fa * fb))
        e = ends[m * n + base[:, c]]
        v = e[:, :R] * (1 - fc) + e[:, R:] * fc
        out.append(p * v)
    return jnp.stack(out, axis=1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--small", action="store_true", help="SMOKE config (a rehearsal)")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if not args.small and dev.platform != "tpu":
        print("tensorf_gathers: CONFIG needs a TPU; use --small elsewhere", file=sys.stderr)
        return 2
    bundle = SMOKE if args.small else CONFIG
    cfg, acfg = bundle.model, bundle.asdr
    n = cfg.grid
    print(f"device_kind: {dev.device_kind} config={bundle.name}", flush=True)
    P = 512 if args.small else acfg.block_size * acfg.chunk
    rows = 3 * n * n

    gather = jax.jit(lambda t, i: t[i])
    for W in ROW_WIDTHS:
        table = jax.random.normal(jax.random.PRNGKey(W), (rows, W))
        for N in (P, P // 2):
            idx = jax.random.randint(jax.random.PRNGKey(N), (N,), 0, rows)
            timeit(f"gather rows={rows} width={W} indices={N}", gather, table, idx, per=N)
        del table

    params = tensorf.init_params(jax.random.PRNGKey(0), cfg)
    packed = timeit("pack_tables (both sides)", tensorf.pack_tables, params, n=3)
    pts = jax.random.uniform(jax.random.PRNGKey(1), (P, 3))
    layouts = (("9 indices (2 corners a row)", features_two, two_corners),
               ("6 indices (4 corners a row, the program's)", tensorf.vm_features,
                lambda t: t))
    for side, N in (("density", P), ("appearance", P // 2)):
        x = pts[:N]
        want = timeit(f"{side} encode 18 indices N={N}",
                      jax.jit(lambda p, t: features_18(p, t, n)), x, params[side])
        for name, f, prepare in layouts:
            got = timeit(f"{side} encode {name} N={N}",
                         jax.jit(lambda p, t, f=f: f(p, t, n)), x, prepare(packed[side]))
            print(f"  max_abs_vs_18={float(jnp.max(jnp.abs(got - want))):.3e}", flush=True)
    print("peak_bytes_in_use", (dev.memory_stats() or {}).get("peak_bytes_in_use"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
