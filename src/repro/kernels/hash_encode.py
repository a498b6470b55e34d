"""Multi-resolution hash-grid encode Pallas kernel — the TPU analogue of the
paper's Encoding Engine (§5.2: hybrid address generator + Mem Xbars +
fusion unit).

CIM insights ported:
  * Hybrid addressing (§5.2.1): the per-level metadata carries an
    ``is_dense`` flag; dense (low-res) levels compute direct row-major
    addresses (conflict-free, perfectly coalesced — the de-hashed copies
    trick) while high-res levels hash (Eq. 2).  The select happens on
    traced scalars so one kernel serves both.
  * Data reuse (§5.2.2): one grid step holds a whole level's table block in
    VMEM while a spatially-sorted tile of points gathers against it —
    consecutive samples hit the same voxel rows (the measured 70-98%
    repetition, Fig. 15), so the gathers coalesce in VMEM instead of
    re-reading HBM.  The register-cache becomes "table-block residency".
  * Fusion unit: trilinear interpolation happens in-register before the
    features ever leave the kernel.

Grid = (n_levels, n_point_tiles); each step re-binds the level's table
(BlockSpec picks row ``l``), so tables stream through VMEM once per level
while point tiles iterate — table traffic is L*T*F bytes total regardless
of N (vs N*8*L*F naive).

The per-level metadata table sits in SMEM and each grid step reads its
level's scalars there.

TPU status: this kernel runs in interpret mode only.  Mosaic refuses the
VMEM vector gather ``table[idx]`` in ``encode_level`` ("Shape mismatch in
input, indices and output"), and the (T, F=2) table block is laid out in
128-lane tiles, so a level's VMEM footprint is 64x its bytes.  The serving
path therefore encodes with the XLA gather (``core.hashgrid.encode``);
``tests/test_tpu_compile.py`` pins the refusal.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.hashgrid import PRIMES
from . import resolve_interpret

TILE = 256   # points per block program
PPAD = 8     # padded point row: [x, y, z, 0...]


def encode_level(pts, res, is_dense, rows, table):
    """One level's trilinear hash encode: (M, 3) points x (T, F) table ->
    (M, F) features.

    The in-kernel building block of this module's per-level grid steps.
    ``res``/``is_dense``/``rows`` are traced scalars (one metadata row).
    """
    scaled = pts * res.astype(jnp.float32)
    base = jnp.clip(jnp.floor(scaled).astype(jnp.int32), 0, res - 1)
    frac = scaled - base.astype(jnp.float32)             # (M, 3)

    acc = jnp.zeros((pts.shape[0], table.shape[-1]), jnp.float32)
    # unrolled 8-corner loop with python-scalar offsets (no array constants)
    for c in range(8):
        ox, oy, oz = (c >> 2) & 1, (c >> 1) & 1, c & 1
        cx = (base[:, 0] + ox).astype(jnp.uint32)
        cy = (base[:, 1] + oy).astype(jnp.uint32)
        cz = (base[:, 2] + oz).astype(jnp.uint32)
        stride = (res + 1).astype(jnp.uint32)
        dense_idx = cx + stride * (cy + stride * cz)
        h = cx * np.uint32(PRIMES[0])
        h = h ^ (cy * np.uint32(PRIMES[1]))
        h = h ^ (cz * np.uint32(PRIMES[2]))
        hash_idx = h % rows.astype(jnp.uint32)
        idx = jnp.where(is_dense > 0, dense_idx, hash_idx).astype(jnp.int32)

        feats = table[idx]                               # (M, F) gather
        wx = frac[:, 0] if ox else 1.0 - frac[:, 0]
        wy = frac[:, 1] if oy else 1.0 - frac[:, 1]
        wz = frac[:, 2] if oz else 1.0 - frac[:, 2]
        w = wx * wy * wz                                 # (M,)
        acc = acc + feats.astype(jnp.float32) * w[:, None]
    return acc


def _encode_kernel(pts_ref, meta_ref, table_ref, out_ref):
    level = pl.program_id(0)
    pts = pts_ref[...][:, :3]                            # (TILE, 3)
    out_ref[...] = encode_level(pts, meta_ref[level, 0], meta_ref[level, 1],
                                meta_ref[level, 2], table_ref[...])


def hash_encode_call(points_padded, meta, tables):
    """points_padded (N, PPAD); meta (L, 8) int32 [res, is_dense, rows, ...]
    (whole table in SMEM); tables (L, T, F) -> features (L, N, F) f32."""
    n = points_padded.shape[0]
    L, T, F = tables.shape
    assert n % TILE == 0, "ops.py pads N to a TILE multiple"
    return pl.pallas_call(
        _encode_kernel,
        grid=(L, n // TILE),
        in_specs=[
            pl.BlockSpec((TILE, PPAD), lambda l, i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((None, T, F), lambda l, i: (l, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, TILE, F), lambda l, i: (l, i, 0)),
        out_shape=jax.ShapeDtypeStruct((L, n, F), jnp.float32),
        interpret=resolve_interpret(),
    )(points_padded, meta, tables)
