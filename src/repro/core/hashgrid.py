"""Multi-resolution hash-grid encoding (Instant-NGP) with ASDR's level-split layout.

The paper (§5.2.1) observes that *low-resolution* levels waste hash-table
space (a 16³ grid uses 1/128 of a 2^19 table) and that hashing them causes
access conflicts; it therefore stores low-res levels *de-hashed* (direct
(x,y,z)-derived addresses) and keeps hashing only for levels whose dense
size exceeds the table.  That is exactly the split we implement: a level is
"dense" when ``(res+1)^3 <= table_size`` — dense levels index directly
(perfect locality, the TPU analogue of conflict-free crossbar rows) and
high-res levels use Instant-NGP's spatial hash (Eq. 2).

Serving reads its coarser levels from *corner rows* (``corner_tables``):
such a level stores, for each voxel, its 8 corners' features in one row
of ``8*F`` floats, so a point gathers one row there instead of 8.  The
rows are fetched through ``level_indices`` (dense and hashed alike,
collisions included) and blended with the same weights, so the encoding
is the same at any level; only memory bounds which levels get them
(``corner_levels``).  A level's rows take ``res^3 * 8 * F * 4`` bytes:
levels are taken in order of resolution while their rows fit in a
quarter of the serving device's memory.  On a 16 GB chip at the paper's
config (T = 2^19, F = 2) that is levels 0-9 (res 16 ... 294; 6-9 are
hashed past ``res^3 <= T``): 40.7M voxels of 64 B, 2.6 GB beside the
64 MB of stacked tables; level 10 alone would take 4.28 GB.  Where the
device reports no memory size, the levels with ``res^3 <= T`` (0-5,
52.7 MB) qualify.  A field of several grids (nerfacto's main grid and
two proposal grids) shares one such budget (``field_corner_levels``):
levels of all its grids in order of their rows' size.  The rows are
built z-slab by z-slab from each slab's vertex features (one index a
vertex), so a level's build holds a small fraction of its rows in
temporaries.

The hashed levels corner rows do not reach are read from *x-neighbour
rows* (``xrow_table``) where they fit beside them.  With ``PRIMES[0] ==
1`` and ``T`` a power of two, the hash of a vertex's x-neighbour is an
XOR: ``h(x+1, y, z) = h(x, y, z) ^ ((2^(t+1) - 1) & (T-1))``, ``t`` the
number of trailing one bits of ``x``.  So row ``h`` holds ``T[h]``, then
``T[h ^ (2^(t+1) - 1)]`` for ``t = 0 .. floor(log2(res))``, and one
gathered row serves both corners on a voxel's x-edge: a point gathers 4
rows a level there (one a (dy, dz)), not 8, through ``level_indices``
itself, so collisions are kept and the encoding is the same to the bit.
The rows stay indexed by the hash slot: ``T * (k+2) * F * 4`` bytes a
level, 42-55 MB each for levels 10-15 at the paper's config (289 MB), far
below their corner rows (4.28 GB at level 10).  After the corner levels
are chosen, a field's next hashed levels take them in order of their
size while both kinds of rows fit in the same quarter of the device's
memory (``field_xrow_levels``); where the device reports no memory size
none do.  On a 16 GB chip at the paper's config a point then gathers
10 + 6 x 4 = 34 indices, not 58.  Training keeps the stacked tables
alone: it updates them every step and takes gradients into them.

All functions are pure; parameters are plain pytrees.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# Instant-NGP's hash primes (Eq. 2 of the ASDR paper / Müller et al. 2022).
PRIMES = (1, 2654435761, 805459861)

# The 8 corners of a unit voxel as (dx, dy, dz) offsets, in the order a
# corner row stores them.
CORNERS = np.stack(np.meshgrid([0, 1], [0, 1], [0, 1], indexing="ij"),
                   axis=-1).reshape(8, 3)

# Voxels a corner-row slab holds: 64 MB of rows at F = 2.
SLAB_VOXELS = 1 << 20


@dataclasses.dataclass(frozen=True)
class HashGridConfig:
    n_levels: int = 16
    log2_table_size: int = 19
    feature_dim: int = 2
    base_resolution: int = 16
    max_resolution: int = 2048

    @property
    def table_size(self) -> int:
        return 1 << self.log2_table_size

    @property
    def growth_factor(self) -> float:
        if self.n_levels == 1:
            return 1.0
        return float(
            np.exp(
                (np.log(self.max_resolution) - np.log(self.base_resolution))
                / (self.n_levels - 1)
            )
        )

    def level_resolution(self, level: int) -> int:
        return int(np.floor(self.base_resolution * self.growth_factor**level))

    def level_resolutions(self) -> Tuple[int, ...]:
        return tuple(self.level_resolution(l) for l in range(self.n_levels))

    def level_is_dense(self, level: int) -> bool:
        res = self.level_resolution(level)
        return (res + 1) ** 3 <= self.table_size

    @property
    def output_dim(self) -> int:
        return self.n_levels * self.feature_dim


def init_hashgrid(key: jax.Array, cfg: HashGridConfig, dtype=jnp.float32):
    """Uniform(-1e-4, 1e-4) init, as in Instant-NGP.

    Returns a single stacked table ``(n_levels, table_size, feature_dim)``.
    Dense levels only use their first ``(res+1)^3`` rows; the remainder is
    the "storage headroom" the paper talks about (we report utilization in
    benchmarks/reuse_cache.py).
    """
    shape = (cfg.n_levels, cfg.table_size, cfg.feature_dim)
    return jax.random.uniform(key, shape, dtype, minval=-1e-4, maxval=1e-4)


def _corner_offsets() -> jnp.ndarray:
    """The 8 corners of a unit voxel, shape (8, 3), int32."""
    return jnp.asarray(CORNERS, dtype=jnp.int32)


def level_indices(coords: jnp.ndarray, res: int, dense: bool, table_size: int) -> jnp.ndarray:
    """Map integer vertex coords (..., 3) -> table row indices (...,).

    Dense levels: direct row-major address (paper's de-hashed addressing).
    Hashed levels: Instant-NGP spatial hash (Eq. 2).
    """
    coords = coords.astype(jnp.uint32)
    if dense:
        stride = res + 1
        idx = coords[..., 0] + stride * (coords[..., 1] + stride * coords[..., 2])
        return idx.astype(jnp.int32)
    h = coords[..., 0] * np.uint32(PRIMES[0])
    h = h ^ (coords[..., 1] * np.uint32(PRIMES[1]))
    h = h ^ (coords[..., 2] * np.uint32(PRIMES[2]))
    return (h % np.uint32(table_size)).astype(jnp.int32)


def _voxel(points: jnp.ndarray, res: int):
    """The voxel holding each point (N, 3) at resolution ``res``: its
    lower corner (clipped into the grid) and the point's offset in it."""
    scaled = points * res  # (N, 3)
    base = jnp.floor(scaled).astype(jnp.int32)
    base = jnp.clip(base, 0, res - 1)
    return base, scaled - base  # frac (N, 3) in [0, 1)


def _blend(feats: jnp.ndarray, frac: jnp.ndarray, dtype) -> jnp.ndarray:
    """Trilinear blend of the 8 corners' features (N, 8, F) -> (N, F)."""
    # weights: prod over axes of (1-frac) or frac per corner bit
    offs = _corner_offsets().astype(dtype)  # (8, 3)
    w = jnp.where(offs[None, :, :] == 1.0, frac[:, None, :], 1.0 - frac[:, None, :])
    w = jnp.prod(w, axis=-1)  # (N, 8)
    return jnp.sum(feats * w[..., None], axis=1)  # (N, F)


def encode_level(
    points: jnp.ndarray, table: jnp.ndarray, res: int, dense: bool
) -> jnp.ndarray:
    """Encode points (N, 3) in [0,1]^3 against one level's table (T, F)."""
    base, frac = _voxel(points, res)
    corners = base[:, None, :] + _corner_offsets()[None, :, :]  # (N, 8, 3)
    idx = level_indices(corners, res, dense, table.shape[0])  # (N, 8)
    feats = table[idx]  # (N, 8, F)  -- XLA gather
    return _blend(feats, frac, points.dtype)


def corner_bytes(cfg: HashGridConfig, level: int) -> int:
    """Bytes of one level's corner rows (res^3, 8*F) in float32."""
    return cfg.level_resolution(level) ** 3 * 8 * cfg.feature_dim * 4


def corner_levels(cfg: HashGridConfig, memory_bytes: int | None = None) -> Tuple[int, ...]:
    """The levels served from corner rows, always the first few.

    With ``memory_bytes`` (the serving device's memory), the levels in
    order of resolution while their corner rows together fit in a
    quarter of it; without, those with ``res^3 <= T``."""
    return field_corner_levels((cfg,), memory_bytes)[0]


def field_corner_levels(grids: Tuple[HashGridConfig, ...],
                        memory_bytes: int | None = None) -> Tuple[Tuple[int, ...], ...]:
    """``corner_levels`` for each of a field's grids under one budget.

    With ``memory_bytes``, the levels of all the grids in order of their
    rows' size while together they fit in a quarter of it, so each grid
    gets its first few; a field of one grid gets what ``corner_levels``
    describes.  Without, each grid's levels with ``res^3 <= T``."""
    if memory_bytes is None:
        return tuple(tuple(l for l in range(g.n_levels)
                           if g.level_resolution(l) ** 3 <= g.table_size)
                     for g in grids)
    taken, used = [[] for _ in grids], 0
    for size, i, l in sorted((corner_bytes(g, l), i, l)
                             for i, g in enumerate(grids) for l in range(g.n_levels)):
        used += size
        if used > memory_bytes // 4:
            break
        taken[i].append(l)
    return tuple(tuple(t) for t in taken)


def corner_table(table: jnp.ndarray, res: int, dense: bool) -> jnp.ndarray:
    """One level's corner rows (res^3, 8*F) from its table (T, F), by
    definition: ``corner_table_slabs`` builds the same rows in bounded
    memory.

    Row ``v = x + res*(y + res*z)`` holds the features of voxel (x, y, z)'s
    8 corners in ``_corner_offsets()`` order, each fetched through
    ``level_indices``: a hashed level's row holds what its hash reads,
    collisions included."""
    r = jnp.arange(res, dtype=jnp.int32)
    z, y, x = jnp.meshgrid(r, r, r, indexing="ij")
    base = jnp.stack([x, y, z], axis=-1).reshape(-1, 3)  # row-major voxels
    corners = base[:, None, :] + _corner_offsets()[None, :, :]
    idx = level_indices(corners, res, dense, table.shape[0])  # (res^3, 8)
    return table[idx].reshape(res**3, -1)


def _slab_rows(table, res: int, dense: bool, z0, planes: int) -> jnp.ndarray:
    """Corner rows (planes*res^2, 8*F) of the voxel planes z0 ..
    z0+planes-1: their vertices' features gathered once each (one index
    a vertex), then each row assembled from 8 shifted slices.  Feature
    major, so the slices keep x on the minor axis: the TPU compiler takes
    ~10 s for level 9's build this way, ~35 s with F on the minor axis
    (or with the transpose done on the 2-D (8*F, V) view)."""
    r = jnp.arange(res + 1, dtype=jnp.int32)
    zs = z0 + jnp.arange(planes + 1, dtype=jnp.int32)
    z, y, x = jnp.meshgrid(zs, r, r, indexing="ij")
    idx = level_indices(jnp.stack([x, y, z], axis=-1), res, dense, table.shape[0])
    F = table.shape[1]
    verts = jnp.stack([table[:, f][idx] for f in range(F)])
    feats = jnp.stack([verts[:, dz:dz + planes, dy:dy + res, dx:dx + res]
                       for dx, dy, dz in CORNERS])  # (8, F, planes, res, res)
    return feats.reshape(8, F, -1).transpose(2, 0, 1).reshape(-1, 8 * F)


def corner_table_slabs(table: jnp.ndarray, res: int, dense: bool,
                       planes: int | None = None) -> jnp.ndarray:
    """``corner_table``, built ``planes`` z-planes of voxels at a time
    (default: ``SLAB_VOXELS`` voxels a slab) into the rows in place.
    Where ``planes`` does not divide ``res`` the last slab starts at
    ``res - planes`` and writes some rows again, with the same values."""
    if planes is None:
        planes = SLAB_VOXELS // res**2
    planes = max(1, min(planes, res))

    def slab(i, rows):
        z0 = jnp.minimum(i * planes, res - planes)
        return lax.dynamic_update_slice(
            rows, _slab_rows(table, res, dense, z0, planes), (z0 * res * res, 0))

    rows = jnp.zeros((res**3, 8 * table.shape[1]), table.dtype)
    return lax.fori_loop(0, -(-res // planes), slab, rows)


def corner_tables(tables: jnp.ndarray, cfg: HashGridConfig,
                  memory_bytes: int | None = None) -> Tuple[jnp.ndarray, ...]:
    """The corner rows of every level in ``corner_levels(cfg,
    memory_bytes)``, from the stacked tables (L, T, F)."""
    return field_corner_tables((tables,), (cfg,), memory_bytes)[0]


def field_corner_tables(tables, grids: Tuple[HashGridConfig, ...],
                        memory_bytes: int | None = None):
    """``corner_tables`` of each of a field's grids (stacked tables
    ``tables[i]`` of ``grids[i]``) under ``field_corner_levels``' one
    budget."""
    return tuple(
        tuple(corner_table_slabs(t[l], g.level_resolution(l), g.level_is_dense(l))
              for l in levels)
        for t, g, levels in zip(tables, grids, field_corner_levels(grids, memory_bytes)))


def encode_corner_level(points: jnp.ndarray, corners: jnp.ndarray, res: int) -> jnp.ndarray:
    """``encode_level`` from the level's corner rows (res^3, 8*F): one
    gathered row a point, the same features and the same blend."""
    base, frac = _voxel(points, res)
    vox = base[:, 0] + res * (base[:, 1] + res * base[:, 2])
    feats = corners[vox].reshape(points.shape[0], 8, -1)  # (N, 8, F)
    return _blend(feats, frac, points.dtype)


def xrow_width(res: int) -> int:
    """Entries of an x-neighbour row at resolution ``res``: the vertex's
    own, then one for each trailing-ones count ``t = 0 .. k`` an ``x`` in
    ``[0, res-1]`` can have, ``k = floor(log2(res))``."""
    return res.bit_length() + 1


def xrow_bytes(cfg: HashGridConfig, level: int) -> int:
    """Bytes of one level's x-neighbour rows (T, xrow_width*F) in float32."""
    return cfg.table_size * xrow_width(cfg.level_resolution(level)) * cfg.feature_dim * 4


def field_xrow_levels(grids: Tuple[HashGridConfig, ...],
                      memory_bytes: int | None = None) -> Tuple[Tuple[int, ...], ...]:
    """The levels each of a field's grids serves from x-neighbour rows:
    with ``memory_bytes``, the hashed levels right after its corner levels
    (``field_corner_levels``), taken over all the grids in order of their
    rows' size while corner rows and x-neighbour rows together fit in a
    quarter of it.  Without, none.  A grid whose first level past its
    corner levels is dense takes none: the levels a grid serves from rows
    are always its first few, corner rows then x-neighbour rows."""
    corners = field_corner_levels(grids, memory_bytes)
    taken = [[] for _ in grids]
    if memory_bytes is None:
        return tuple(tuple(t) for t in taken)
    used = sum(corner_bytes(g, l) for g, c in zip(grids, corners) for l in c)
    for size, i, l in sorted((xrow_bytes(g, l), i, l)
                             for i, (g, c) in enumerate(zip(grids, corners))
                             if len(c) < g.n_levels and not g.level_is_dense(len(c))
                             for l in range(len(c), g.n_levels)):
        used += size
        if used > memory_bytes // 4:
            break
        taken[i].append(l)
    return tuple(tuple(t) for t in taken)


def xrow_table(table: jnp.ndarray, res: int) -> jnp.ndarray:
    """One hashed level's x-neighbour rows (T, xrow_width(res)*F) from its
    table (T, F): row ``h`` holds ``T[h]``, then ``T[h ^ m_t]`` for
    ``t = 0 .. k``, ``m_t = (2^(t+1) - 1) & (T-1)``.  One one-index
    gather of T rows an entry: the TPU compiler takes ~1 s for these at
    level 15, ~280 s for the same entries as block reversals, and one
    gather of all the entries' indices holds ~70x the rows in
    temporaries."""
    T = table.shape[0]
    h = jnp.arange(T, dtype=jnp.int32)
    return jnp.concatenate([table] + [table[h ^ (((2 << t) - 1) & (T - 1))]
                                      for t in range(xrow_width(res) - 1)], axis=1)


def field_xrow_tables(tables, grids: Tuple[HashGridConfig, ...],
                      memory_bytes: int | None = None):
    """The x-neighbour rows of each of a field's grids (stacked tables
    ``tables[i]`` of ``grids[i]``) at its ``field_xrow_levels``."""
    return tuple(tuple(xrow_table(t[l], g.level_resolution(l)) for l in levels)
                 for t, g, levels in zip(tables, grids,
                                         field_xrow_levels(grids, memory_bytes)))


def encode_xrow_level(points: jnp.ndarray, xrows: jnp.ndarray, res: int) -> jnp.ndarray:
    """``encode_level`` of a hashed level from its x-neighbour rows
    (``xrow_table``): one gathered row for each (dy, dz) edge, at the
    hash of its x-lower vertex; the x-upper vertex is entry ``1 + t``,
    ``t`` the trailing ones of the voxel's x.  The same features and the
    same blend."""
    N = points.shape[0]
    W = xrow_width(res)
    base, frac = _voxel(points, res)
    x = base[:, 0].astype(jnp.uint32)
    t = lax.population_count(x ^ (x + 1)).astype(jnp.int32) - 1
    lo, hi = [], []
    for dy, dz in ((0, 0), (0, 1), (1, 0), (1, 1)):
        idx = level_indices(base + jnp.asarray([0, dy, dz], jnp.int32), res, False,
                            xrows.shape[0])
        row = xrows[idx].reshape(N, W, -1)
        up = row[:, 1]
        for j in range(1, W - 1):
            up = jnp.where((t == j)[:, None], row[:, 1 + j], up)
        lo.append(row[:, 0])
        hi.append(up)
    return _blend(jnp.stack(lo + hi, axis=1), frac, points.dtype)  # CORNERS order


def rows_per_point(cfg: HashGridConfig, corners=(), xrows=()) -> int:
    """Gather indices a point takes in ``encode`` with these rows: one a
    corner-row level, 4 an x-neighbour-row level, 8 any other."""
    n, m = len(corners), len(xrows)
    return n + 4 * m + 8 * (cfg.n_levels - n - m)


def encode(points: jnp.ndarray, tables: jnp.ndarray, cfg: HashGridConfig,
           corners: Tuple[jnp.ndarray, ...] = (),
           xrows: Tuple[jnp.ndarray, ...] = ()) -> jnp.ndarray:
    """Full multi-resolution encoding: (N, 3) -> (N, n_levels * feature_dim).

    With ``corners`` (``corner_tables``: rows of the first
    ``len(corners)`` levels) those levels gather one corner row a point
    instead of 8 table rows; with ``xrows`` (``field_xrow_tables``: rows
    of the next ``len(xrows)`` levels) those gather 4 x-neighbour rows.
    The result is the same.  Its ops carry the ``hash_encode`` scope in
    the profiler's metadata."""
    with jax.named_scope("hash_encode"):
        outs = []
        for l in range(cfg.n_levels):
            res = cfg.level_resolution(l)
            if l < len(corners):
                outs.append(encode_corner_level(points, corners[l], res))
            elif l < len(corners) + len(xrows):
                outs.append(encode_xrow_level(points, xrows[l - len(corners)], res))
            else:
                outs.append(encode_level(points, tables[l], res, cfg.level_is_dense(l)))
        return jnp.concatenate(outs, axis=-1)


def level_voxel_ids(points: jnp.ndarray, cfg: HashGridConfig) -> jnp.ndarray:
    """Voxel id per (point, level) — used by reuse/locality profiling.

    Returns (N, n_levels) int32: the row-major id of the voxel containing
    each point at each level (NOT the hash — two points share a voxel id iff
    they actually fall in the same cube, matching the paper's Fig. 15).
    """
    ids = []
    for l in range(cfg.n_levels):
        res = cfg.level_resolution(l)
        base = jnp.clip(jnp.floor(points * res).astype(jnp.int64), 0, res - 1)
        ids.append(base[:, 0] + res * (base[:, 1] + res * base[:, 2]))
    return jnp.stack(ids, axis=-1).astype(jnp.int64)


def storage_utilization(cfg: HashGridConfig) -> dict:
    """Reproduces the paper's Fig. 13 numbers structurally.

    'naive' = every level hash-mapped into a full table (dense levels waste
    the tail). 'hybrid' = dense levels sized exactly + replicated copies to
    fill the same physical budget (paper: 85.95% -> we report the analytic
    utilization of both layouts for our config).
    """
    T = cfg.table_size
    naive_used, hybrid_used, total = 0, 0, 0
    copies = {}
    for l in range(cfg.n_levels):
        res = cfg.level_resolution(l)
        dense_size = (res + 1) ** 3
        total += T
        if dense_size <= T:
            naive_used += dense_size  # hashing a small level still only touches dense_size rows
            n_copies = max(1, T // dense_size)
            copies[l] = n_copies
            hybrid_used += n_copies * dense_size
        else:
            naive_used += T
            hybrid_used += T
            copies[l] = 1
    return {
        "naive_utilization": naive_used / total,
        "hybrid_utilization": hybrid_used / total,
        "copies_per_level": copies,
    }
