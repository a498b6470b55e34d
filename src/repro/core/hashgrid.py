"""Multi-resolution hash-grid encoding (Instant-NGP) with ASDR's level-split layout.

The paper (§5.2.1) observes that *low-resolution* levels waste hash-table
space (a 16³ grid uses 1/128 of a 2^19 table) and that hashing them causes
access conflicts; it therefore stores low-res levels *de-hashed* (direct
(x,y,z)-derived addresses) and keeps hashing only for levels whose dense
size exceeds the table.  That is exactly the split we implement: a level is
"dense" when ``(res+1)^3 <= table_size`` — dense levels index directly
(perfect locality, the TPU analogue of conflict-free crossbar rows) and
high-res levels use Instant-NGP's spatial hash (Eq. 2).

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


def encode_level(
    points: jnp.ndarray, table: jnp.ndarray, res: int, dense: bool
) -> jnp.ndarray:
    """Encode points (N, 3) in [0,1]^3 against one level's table (T, F)."""
    scaled = points * res  # (N, 3)
    base = jnp.floor(scaled).astype(jnp.int32)
    base = jnp.clip(base, 0, res - 1)
    frac = scaled - base  # (N, 3) in [0, 1)

    corners = base[:, None, :] + _corner_offsets()[None, :, :]  # (N, 8, 3)
    idx = level_indices(corners, res, dense, table.shape[0])  # (N, 8)
    feats = table[idx]  # (N, 8, F)  -- XLA gather

    # Trilinear weights: prod over axes of (1-frac) or frac per corner bit.
    offs = _corner_offsets().astype(points.dtype)  # (8, 3)
    w = jnp.where(offs[None, :, :] == 1.0, frac[:, None, :], 1.0 - frac[:, None, :])
    w = jnp.prod(w, axis=-1)  # (N, 8)
    return jnp.sum(feats * w[..., None], axis=1)  # (N, F)


def encode(points: jnp.ndarray, tables: jnp.ndarray, cfg: HashGridConfig) -> jnp.ndarray:
    """Full multi-resolution encoding: (N, 3) -> (N, n_levels * feature_dim).
    Its ops carry the ``hash_encode`` scope in the profiler's metadata."""
    with jax.named_scope("hash_encode"):
        outs = []
        for l in range(cfg.n_levels):
            res = cfg.level_resolution(l)
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
