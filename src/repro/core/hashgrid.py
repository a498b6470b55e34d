"""Multi-resolution hash-grid encoding (Instant-NGP) with ASDR's level-split layout.

The paper (§5.2.1) observes that *low-resolution* levels waste hash-table
space (a 16³ grid uses 1/128 of a 2^19 table) and that hashing them causes
access conflicts; it therefore stores low-res levels *de-hashed* (direct
(x,y,z)-derived addresses) and keeps hashing only for levels whose dense
size exceeds the table.  That is exactly the split we implement: a level is
"dense" when ``(res+1)^3 <= table_size`` — dense levels index directly
(perfect locality, the TPU analogue of conflict-free crossbar rows) and
high-res levels use Instant-NGP's spatial hash (Eq. 2).

Serving reads the coarse levels from *corner rows* (``corner_tables``): a
level whose voxel count ``res^3`` is at most the table's row count ``T``
stores, for each voxel, its 8 corners' features in one row of ``8*F``
floats, so a point gathers one row there instead of 8.  The rows are
fetched through ``level_indices`` (dense and hashed alike, collisions
included) and blended with the same weights, so the encoding is the same.
Only those levels qualify because ``res^3 <= T`` bounds the copy at 8x
that level's table; finer levels would need more rows than the table has.
At the paper's config (T = 2^19, F = 2) levels 0-5 (res 16, 22, 30, 42,
58, 80; level 5 is hashed) qualify: 822,944 voxels of 64 B, i.e. 0.26,
0.68, 1.73, 4.74, 12.49 and 32.77 MB, 52.7 MB in all, beside the 64 MB
of stacked tables.  Training keeps the stacked tables alone: it updates
them every step and takes gradients into them.

All functions are pure; parameters are plain pytrees.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Instant-NGP's hash primes (Eq. 2 of the ASDR paper / Müller et al. 2022).
PRIMES = (1, 2654435761, 805459861)


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
    offs = np.stack(np.meshgrid([0, 1], [0, 1], [0, 1], indexing="ij"), axis=-1)
    return jnp.asarray(offs.reshape(8, 3), dtype=jnp.int32)


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


def corner_levels(cfg: HashGridConfig) -> Tuple[int, ...]:
    """The levels served from corner rows: those with ``res^3 <= T``."""
    return tuple(l for l in range(cfg.n_levels)
                 if cfg.level_resolution(l) ** 3 <= cfg.table_size)


def corner_table(table: jnp.ndarray, res: int, dense: bool) -> jnp.ndarray:
    """One level's corner rows (res^3, 8*F) from its table (T, F).

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


def corner_tables(tables: jnp.ndarray, cfg: HashGridConfig) -> Tuple[jnp.ndarray, ...]:
    """``corner_table`` of every level in ``corner_levels(cfg)``, from the
    stacked tables (L, T, F)."""
    return tuple(
        corner_table(tables[l], cfg.level_resolution(l), cfg.level_is_dense(l))
        for l in corner_levels(cfg))


def encode_corner_level(points: jnp.ndarray, corners: jnp.ndarray, res: int) -> jnp.ndarray:
    """``encode_level`` from the level's corner rows (res^3, 8*F): one
    gathered row a point, the same features and the same blend."""
    base, frac = _voxel(points, res)
    vox = base[:, 0] + res * (base[:, 1] + res * base[:, 2])
    feats = corners[vox].reshape(points.shape[0], 8, -1)  # (N, 8, F)
    return _blend(feats, frac, points.dtype)


def encode(points: jnp.ndarray, tables: jnp.ndarray, cfg: HashGridConfig,
           corners: Tuple[jnp.ndarray, ...] = ()) -> jnp.ndarray:
    """Full multi-resolution encoding: (N, 3) -> (N, n_levels * feature_dim).

    With ``corners`` (``corner_tables(tables, cfg)``) the levels of
    ``corner_levels(cfg)`` gather one corner row a point instead of 8
    table rows; the result is the same.  Its ops carry the
    ``hash_encode`` scope in the profiler's metadata."""
    by_level = dict(zip(corner_levels(cfg), corners))
    with jax.named_scope("hash_encode"):
        outs = []
        for l in range(cfg.n_levels):
            res = cfg.level_resolution(l)
            if l in by_level:
                outs.append(encode_corner_level(points, by_level[l], res))
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
