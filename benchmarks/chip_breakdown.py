"""Where a CONFIG frame's time goes on the chip.

    python3 benchmarks/chip_breakdown.py           # on a TPU, CONFIG
    python3 benchmarks/chip_breakdown.py --small   # SMOKE, any backend
    python3 benchmarks/chip_breakdown.py --levels  # the per-level part alone

Times, on ``configs/ingp_asdr.CONFIG`` with seeded weights (tables
scaled x1e4, as in ``chip_smoke.py``):

  * the hash encode of one march step (block_size x chunk = 131,072
    points) in nine formulations of the same gather, each checked
    against ``hashgrid.encode``: seven gather 8 table rows a level, two
    gather one corner row a point at the levels the device's memory
    holds (``hashgrid.corner_tables``, derived outside the timed call);
  * the corner-row and x-neighbour-row build the served field runs
    (``ops.corner_tables``), then each level past ``res^3 <= T`` that it
    covers, one level at a time: its 8-row gather (``encode_level``)
    against its corner row (``encode_corner_level``) or its 4 x-neighbour
    rows (``encode_xrow_level``), checked equal;
  * the fused-MLP Pallas kernels against XLA's own matmuls (``core.mlp``);
  * the field's density (encode + MLP) in a jit that closes over the
    params, i.e. holds them as constants;
  * one pooled march batch (``serve.pool.batched_march``) at three
    budgets, the 800x800 probe, and the probe's sample fraction against
    the table scale.

Each timing prints the first call (compile included) and the median and
minimum of the following calls, on the host clock around
``block_until_ready``.  The last line is the device's peak HBM.
"""
import argparse
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.configs.ingp_asdr import CONFIG, SMOKE  # noqa: E402
from repro.core import hashgrid, mlp, pipeline, scene  # noqa: E402
from repro.core import model as model_lib  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.serve import pool as pool_lib  # noqa: E402

GRID_SCALE = 1e4


def timeit(name, f, *args, n=5):
    t0 = time.perf_counter()
    out = jax.block_until_ready(f(*args))
    first = time.perf_counter() - t0
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(f(*args))
        ts.append(time.perf_counter() - t0)
    print(f"{name}: first_s={first:.3f} median_ms={1e3 * np.median(ts):.3f} "
          f"min_ms={1e3 * min(ts):.3f}", flush=True)
    return out


def pack_corners(c, lanes=128):
    """Corner rows (V, W) packed ``lanes // W`` to a row: (ceil(V*W/lanes), lanes)."""
    c = c.reshape(-1)
    c = jnp.pad(c, (0, (-c.shape[0]) % lanes))
    return c.reshape(-1, lanes)


def encode_corners_packed(points, tables, packed, g):
    """``hashgrid.encode`` with the corner levels read from packed corner
    rows: one 128-lane row a point, its voxel's slice picked by a tree of
    ``where`` on the low bits of the voxel id (exact: no arithmetic)."""
    by_level = dict(zip(hashgrid.corner_levels(g), packed))
    outs = []
    for l in range(g.n_levels):
        res = g.level_resolution(l)
        if l not in by_level:
            outs.append(hashgrid.encode_level(points, tables[l], res,
                                              g.level_is_dense(l)))
            continue
        width = 8 * g.feature_dim
        per_row = by_level[l].shape[1] // width
        base, frac = hashgrid._voxel(points, res)
        vox = base[:, 0] + res * (base[:, 1] + res * base[:, 2])
        row = by_level[l][vox // per_row]                  # (N, 128)
        slot = vox % per_row
        while row.shape[1] > width:
            half = row.shape[1] // 2
            bit = (slot & (half // width)) != 0
            row = jnp.where(bit[:, None], row[:, half:], row[:, :half])
        feats = row.reshape(points.shape[0], 8, g.feature_dim)
        outs.append(hashgrid._blend(feats, frac, points.dtype))
    return jnp.concatenate(outs, -1)


def encoders(g, T, F):
    """The same multiresolution hash encode, gathered nine ways: each
    entry is (name, encode(points, arg), prepare(tables) -> arg)."""
    L = g.n_levels
    res = [(g.level_resolution(l), g.level_is_dense(l)) for l in range(L)]

    def corners(points, r, dense):
        scaled = points * r
        base = jnp.clip(jnp.floor(scaled).astype(jnp.int32), 0, r - 1)
        frac = scaled - base
        offs = hashgrid._corner_offsets()
        idx = hashgrid.level_indices(base[:, None, :] + offs[None], r, dense,
                                     T)
        offs = offs.astype(points.dtype)
        w = jnp.prod(jnp.where(offs[None] == 1.0, frac[:, None, :],
                               1.0 - frac[:, None, :]), -1)
        return idx, w

    def per_level(points, gather):
        outs = []
        for l, (r, dense) in enumerate(res):
            idx, w = corners(points, r, dense)
            outs.append(jnp.sum(gather(l, idx) * w[..., None], 1))
        return jnp.concatenate(outs, -1)

    def all_levels(points, gather):
        idxs, ws = [], []
        for l, (r, dense) in enumerate(res):
            idx, w = corners(points, r, dense)
            idxs.append(idx + l * T)
            ws.append(w)
        feats = gather(jnp.stack(idxs, 1))            # (N, L, 8, F)
        w = jnp.stack(ws, 1)
        return jnp.sum(feats * w[..., None], 2).reshape(points.shape[0], L * F)

    def rows(p, t):                        # the repo's encode
        return hashgrid.encode(p, t, g)

    def feat_1d(p, t):                     # per-feature 1-D gathers
        tt = jnp.transpose(t, (0, 2, 1))
        return per_level(p, lambda l, idx: jnp.stack(
            [tt[l, f][idx] for f in range(F)], -1))

    def flat_1d(p, t):                     # one flat 1-D gather per level
        return per_level(p, lambda l, idx: t[l].reshape(-1)[
            idx[..., None] * F + jnp.arange(F)])

    def all_rows(p, t):                    # one row gather over all levels
        return all_levels(p, lambda idx: t.reshape(L * T, F)[idx])

    def all_feat_1d(p, t):                 # all levels, per-feature 1-D
        tt = jnp.transpose(t, (2, 0, 1)).reshape(F, L * T)
        return all_levels(p, lambda idx: jnp.stack(
            [tt[f][idx] for f in range(F)], -1))

    def rows_promise(p, t):                # row gather, promise_in_bounds
        return per_level(p, lambda l, idx: t[l].at[idx].get(
            mode="promise_in_bounds"))

    def take_axis1(p, t):                  # feature-major (L, F, T) take
        tt = jnp.transpose(t, (0, 2, 1))
        return per_level(p, lambda l, idx: jnp.take(
            tt[l], idx.reshape(-1), axis=1, mode="clip").reshape(
                F, -1, 8).transpose(1, 2, 0))

    def corner_rows(p, tc):                # one (V, 8F) corner row a point
        return hashgrid.encode(p, tc[0], g, corners=tc[1])

    def corner_packed(p, tc):              # corner rows, 128 lanes a row
        return encode_corners_packed(p, tc[0], tc[1], g)

    def with_corners(t):
        return t, hashgrid.corner_tables(t, g, ops.device_memory_bytes(t))

    def with_packed(t):
        return t, tuple(pack_corners(c) for c in hashgrid.corner_tables(t, g))

    same = [("A_rows", rows), ("B_feat1d", feat_1d), ("C_flat1d", flat_1d),
            ("E_alllev_rows", all_rows), ("F_alllev_feat1d", all_feat_1d),
            ("G_rows_promise", rows_promise), ("H_take_axis1", take_axis1)]
    return [(n, f, lambda t: t) for n, f in same] + [
        ("I_corner_rows", corner_rows, with_corners),
        ("J_corner_packed", corner_packed, with_packed)]


def per_level(tables, g, pts):
    """Build the served corner and x-neighbour rows, then time each level
    past ``res^3 <= T`` they cover by its 8 table rows and by its corner
    row or its 4 x-neighbour rows."""
    memory = ops.device_memory_bytes(tables)
    levels = hashgrid.corner_levels(g, memory)
    (xlevels,) = hashgrid.field_xrow_levels((g,), memory)
    print(f"corner levels {levels} of memory_bytes={memory}: "
          f"{sum(hashgrid.corner_bytes(g, l) for l in levels)} B; x-neighbour "
          f"levels {xlevels}: {sum(hashgrid.xrow_bytes(g, l) for l in xlevels)} B",
          flush=True)
    (corners,), (xrows,) = timeit("corner_tables build", ops.corner_tables,
                                  (tables,), (g,), memory, n=3)
    served = [(l, "corner row", hashgrid.encode_corner_level, c)
              for l, c in zip(levels, corners)]
    served += [(l, "4 x-neighbour rows", hashgrid.encode_xrow_level, x)
               for l, x in zip(xlevels, xrows)]
    for l, name, encode, arg in served:
        res = g.level_resolution(l)
        if res ** 3 <= g.table_size:
            continue
        rows = timeit(f"level {l} res={res} 8 rows", jax.jit(
            lambda p, t: hashgrid.encode_level(p, t, res, g.level_is_dense(l))),
            pts, tables[l])
        got = timeit(f"level {l} res={res} {name}", jax.jit(
            lambda p, a: encode(p, a, res)), pts, arg)
        print(f"  max_abs={float(jnp.max(jnp.abs(rows - got))):.3e}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--small", action="store_true",
                    help="SMOKE config, 48x48 frame (a rehearsal)")
    ap.add_argument("--levels", action="store_true",
                    help="only the row build and the per-level gathers")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if not args.small and dev.platform != "tpu":
        print("chip_breakdown: CONFIG needs a TPU; use --small elsewhere",
              file=sys.stderr)
        return 2
    bundle = SMOKE if args.small else CONFIG
    cfg, acfg = bundle.model, bundle.asdr
    print(f"device_kind: {dev.device_kind} config={bundle.name}", flush=True)
    params = model_lib.init_ngp(jax.random.PRNGKey(0), cfg)
    params = dict(params, grid=params["grid"] * GRID_SCALE)
    tables = params["grid"]
    _, T, F = tables.shape
    P = 512 if args.small else acfg.block_size * acfg.chunk
    pts = jax.random.uniform(jax.random.PRNGKey(1), (P, 3))

    if args.levels:
        per_level(tables, cfg.grid, pts)
        print("peak_bytes_in_use",
              (dev.memory_stats() or {}).get("peak_bytes_in_use"))
        return 0

    # A formulation that does not compile for this backend is a result,
    # not a fault: print the refusal and time the next one.
    ref = None
    for name, f, prepare in encoders(cfg.grid, T, F):
        try:
            arg = jax.block_until_ready(prepare(tables))
            out = timeit(f"encode {name} P={P}", jax.jit(f), pts, arg)
        except Exception as e:  # noqa: BLE001
            print(f"encode {name}: FAILED {type(e).__name__}: "
                  f"{str(e)[:300]}", flush=True)
            continue
        ref = out if ref is None else ref
        print(f"  max_abs_vs_A={float(jnp.max(jnp.abs(out - ref))):.3e}",
              flush=True)

    enc = hashgrid.encode(pts, tables, cfg.grid)
    dirs = jax.random.normal(jax.random.PRNGKey(2), (P, 3))
    dirs = dirs / jnp.linalg.norm(dirs, axis=-1, keepdims=True)
    m = params["mlps"]
    ops.packed_weights(m, cfg.net)
    timeit("density_mlp pallas",
           jax.jit(lambda e: ops.density_mlp(e, m, cfg.net)), enc)
    timeit("density jnp", jax.jit(lambda e: mlp.density_apply(m, e)), enc)
    _, geo = jax.jit(lambda e: ops.density_mlp(e, m, cfg.net))(enc)
    half = P // 2      # the color chain runs on every group-th sample
    timeit("color_mlp pallas P/2",
           jax.jit(lambda g, d: ops.color_mlp(g, d, m, cfg.net)),
           geo[:half], dirs[:half])
    timeit("color jnp P/2",
           jax.jit(lambda g, d: mlp.color_apply(m, g, d, cfg.net.sh_degree)),
           geo[:half], dirs[:half])

    fns = ops.field_fns(params, cfg)
    # closes over the params: they become constants of the executable
    timeit("field density P (encode+mlp)",
           jax.jit(lambda p: fns.density(p)[0]), pts)

    hw = 48 if args.small else bundle.image_hw[0]
    cam = scene.look_at_camera(hw, hw, 0.6, 0.5)
    o, d = scene.camera_rays(cam)
    N, B = (4 if args.small else 16), acfg.block_size
    o_b, d_b = o[:N * B].reshape(N, B, 3), d[:N * B].reshape(N, B, 3)
    march = pool_lib.batched_march(fns, acfg)
    for budget in (acfg.ns_full, acfg.candidates[-2], acfg.candidates[0]):
        out = timeit(f"pooled march N={N} budget={budget}", march, o_b, d_b,
                     jnp.full((N,), budget, jnp.int32), n=2)
        print(f"  chunks={np.asarray(out[3]).tolist()}", flush=True)

    timeit(f"probe_phase {hw}x{hw}",
           lambda: pipeline.probe_phase(fns, acfg, cam)[0], n=2)
    for scale in (1.0, 1e2, 1e3, 3e3, 1e4):
        f2 = ops.field_fns(dict(params, grid=tables * (scale / GRID_SCALE)),
                           cfg)
        c = np.asarray(pipeline.probe_phase(f2, acfg, cam)[0])
        vals, cnt = np.unique(c, return_counts=True)
        print(f"grid_scale={scale}: count_fraction="
              f"{c.mean() / acfg.ns_full:.4f} "
              f"hist={dict(zip(vals.tolist(), cnt.tolist()))}", flush=True)
        out = pool_lib.batched_march(f2, acfg)(
            o_b, d_b, jnp.full((N,), acfg.ns_full, jnp.int32))
        print(f"  chunks@{acfg.ns_full}={np.asarray(out[3]).tolist()} "
              f"acc_mean={float(out[1].mean()):.3f}", flush=True)
    print("peak_bytes_in_use",
          (dev.memory_stats() or {}).get("peak_bytes_in_use"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
