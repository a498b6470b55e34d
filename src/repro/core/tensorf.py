"""TensoRF's VM-decomposed radiance field (Chen et al., "TensoRF: Tensorial
Radiance Fields", ECCV 2022, arXiv:2203.09517) as a ``FieldFns``.

The program's unit cube is TensoRF's box [-1.5, 1.5]^3 (Blender's
``scene_bbox``): ``x_w = 3x - 1.5``, so the normalized coordinate is
``x^ = 2x - 1`` and the grid coordinate of an axis of ``n`` voxels is
``(x^ + 1) / 2 * (n - 1) = x * (n - 1)`` (``align_corners=True``).

Three plane/line pairs m, with plane axes (0,1), (0,2), (1,2) and line
axes 2, 1, 0 (``matMode``, ``vecMode``).  A side with R components a pair
reads, at a point, the bilinear plane value ``P_{m,r}(x^_a, x^_b)`` and
the linear line value ``V_{m,r}(x^_c)``:

* density: ``sigma_feat = sum_{m,r} P^s_{m,r} V^s_{m,r}``;
  TensoRF's ``sigma = softplus(sigma_feat + density_shift)`` and
  ``alpha = 1 - exp(-sigma * dist_w * distance_scale)``.  A program
  distance is ``BOX_SIZE`` world units, so the field returns
  ``BOX_SIZE * DISTANCE_SCALE * sigma`` and the march's
  ``1 - exp(-sigma * dt)`` is TensoRF's alpha.  Zero outside the box.
* appearance: ``f = B concat_{m,r}[P^c_{m,r} V^c_{m,r}]`` (no bias);
  color ``sigmoid(MLP([f, d, PE(f), PE(d)]))`` with
  ``PE(p, L) = [sin(p_i 2^k), cos(p_i 2^k)]`` ordered i*L + k, every
  sine before every cosine (``MLPRender_Fea``).

``density`` returns the sample point itself as ``geo``, so the
appearance tables are read only where ``color`` runs: at the color
anchors of color-density decoupling.  ``color`` clamps a point to the
box (TensoRF never evaluates color outside it; an anchor just past the
box can still be interpolated into a sample inside).

``init_params`` stores the tables a row a grid point: planes
``(3, n*n, R)`` (row ``i_a * n + i_b``), lines ``(3, n, R)``.
``field_fns`` serves them packed (``pack_tables``): a plane row holds the
texel's four bilinear corners side by side, a line row its segment's two
ends, so a point gathers one plane row and one line row a pair: 6 rows
a side, each a one-index gather.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict

import jax
import jax.numpy as jnp

from .fields import Bound, FieldFns

PLANE_AXES = ((0, 1), (0, 2), (1, 2))   # matMode
LINE_AXES = (2, 1, 0)                   # vecMode
ROWS_PER_POINT = 3 + 3                  # a packed plane row + line row, 3 pairs
FEATURE_PE = VIEW_PE = 2                # fea_pe, view_pe
DENSITY_SHIFT = -10.0
DISTANCE_SCALE = 25.0
BOX_SIZE = 3.0                          # world side of the box on the unit cube
HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class TensoRFConfig:
    grid: int = 300                  # voxels an axis (N_voxel_final = 300**3)
    density_components: int = 16     # n_lamb_sigma, a pair
    appearance_components: int = 48  # n_lamb_sh, a pair
    feature_dim: int = 27            # data_dim_color
    hidden: int = 128                # featureC

    @property
    def mlp_sizes(self):
        """Color MLP layer sizes, input first: [f, d, PE(f), PE(d)]."""
        F = self.feature_dim
        n_in = F + 3 + 2 * FEATURE_PE * F + 2 * VIEW_PE * 3
        return (n_in, self.hidden, self.hidden, 3)

    def gather_stats(self, params) -> Dict[str, int]:
        """Table rows gathered a point: the density side at every marched
        sample, the color side at every point its color runs."""
        del params
        return {"encode_gather_rows_per_sample": ROWS_PER_POINT,
                "color_gather_rows_per_sample": ROWS_PER_POINT}


def init_params(key, cfg: TensoRFConfig, scale: float = 0.1) -> Dict:
    """Seeded weights: planes and lines N(0, scale^2) (TensoRF's init is
    0.1 * randn), basis and MLP weights Glorot-uniform, MLP biases as
    PyTorch's ``nn.Linear`` draws them with the last one zero (TensoRF
    zeroes it)."""
    n = cfg.grid
    ks = jax.random.split(key, 6)

    def side(k, R):
        kp, kl = jax.random.split(k)
        return {"planes": scale * jax.random.normal(kp, (3, n * n, R)),
                "lines": scale * jax.random.normal(kl, (3, n, R))}

    def glorot(k, a, b):
        lim = math.sqrt(6.0 / (a + b))
        return jax.random.uniform(k, (a, b), jnp.float32, -lim, lim)

    sizes = cfg.mlp_sizes
    kw = jax.random.split(ks[4], len(sizes) - 1)
    kb = jax.random.split(ks[5], len(sizes) - 1)
    weights = [glorot(k, a, b) for k, a, b in zip(kw, sizes[:-1], sizes[1:])]
    biases = [jax.random.uniform(k, (b,), jnp.float32, -1.0, 1.0) / math.sqrt(a)
              for k, a, b in zip(kb, sizes[:-1], sizes[1:])]
    biases[-1] = jnp.zeros_like(biases[-1])
    return {"density": side(ks[0], cfg.density_components),
            "appearance": side(ks[1], cfg.appearance_components),
            "basis": glorot(ks[2], 3 * cfg.appearance_components, cfg.feature_dim),
            "mlp": {"weights": weights, "biases": biases}}


def _pack_side(side):
    """A side's packed tables: ``corners`` ``(3, n*n, 4R)``, row ``i_a*n +
    i_b`` holding plane rows ``row, row+1, row+n, row+n+1``; ``ends``
    ``(3, n, 2R)``, row ``i`` holding line rows ``i, i+1``.  Rows with an
    index ``n-1`` on an axis are never read (``base <= n-2``) and hold
    zeros past the table's edge."""
    planes, lines = side["planes"], side["lines"]
    n, R = lines.shape[1], lines.shape[2]
    p = jnp.pad(planes.reshape(3, n, n, R), ((0, 0), (0, 1), (0, 1), (0, 0)))
    corners = jnp.concatenate([p[:, :n, :n], p[:, :n, 1:], p[:, 1:, :n], p[:, 1:, 1:]],
                              axis=-1)
    v = jnp.pad(lines, ((0, 0), (0, 1), (0, 0)))
    return {"corners": corners.reshape(3, n * n, 4 * R),
            "ends": jnp.concatenate([v[:, :n], v[:, 1:]], axis=-1)}


@jax.jit
def pack_tables(params: Dict) -> Dict:
    """Both sides of ``init_params``' tables packed (``_pack_side``), as
    one executable of its own name."""
    return {k: _pack_side(params[k]) for k in ("density", "appearance")}


def vm_features(points, tables, n: int):
    """(N, 3) points in the unit cube (clamped to it) -> (N, 3, R): the
    products ``P_{m,r} * V_{m,r}`` of the three pairs from a side's packed
    tables (``_pack_side``), 6 rows gathered a point.  Each row is a
    gather of its own, one index a point: the TPU compiler takes ~80 s
    for one gather of 12 indices a point over 131,072 points, and under a
    second for each one-index gather."""
    R = tables["ends"].shape[-1] // 2
    g = jnp.clip(points.astype(jnp.float32), 0.0, 1.0) * (n - 1)
    base = jnp.minimum(jnp.floor(g), n - 2).astype(jnp.int32)     # (N, 3)
    frac = g - base
    corners = tables["corners"].reshape(3 * n * n, 4 * R)
    ends = tables["ends"].reshape(3 * n, 2 * R)
    out = []
    for m, ((a, b), c) in enumerate(zip(PLANE_AXES, LINE_AXES)):
        fa, fb, fc = frac[:, a:a + 1], frac[:, b:b + 1], frac[:, c:c + 1]
        q = corners[m * n * n + base[:, a] * n + base[:, b]]
        p = (q[:, :R] * ((1 - fa) * (1 - fb)) + q[:, R:2 * R] * ((1 - fa) * fb)
             + q[:, 2 * R:3 * R] * (fa * (1 - fb)) + q[:, 3 * R:] * (fa * fb))
        e = ends[m * n + base[:, c]]
        v = e[:, :R] * (1 - fc) + e[:, R:] * fc
        out.append(p * v)
    return jnp.stack(out, axis=1)


def positional_encoding(x, freqs: int):
    """TensoRF's ``positional_encoding``: (N, D) -> (N, 2*D*freqs)."""
    scaled = (x[..., None] * (2.0 ** jnp.arange(freqs))).reshape(x.shape[0], -1)
    return jnp.concatenate([jnp.sin(scaled), jnp.cos(scaled)], axis=-1)


def field_fns(params: Dict, cfg: TensoRFConfig) -> FieldFns:
    """The VM field over ``params`` (``init_params``' layout).  Its tables
    are packed here, once per call (``pack_tables``), and the packed ones
    alone are carried in the ``Bound`` params, so jitted callers
    (``fields.unbind``) pass them as arguments and ``rebind`` inside a
    trace only reads them."""
    # waited for here, so a field is ready when built: the packing never
    # runs on the device inside the caller's first march
    packed = jax.block_until_ready(pack_tables(params))
    return _bound_field_fns(dict(params, **packed), cfg)


def _bound_field_fns(params: Dict, cfg: TensoRFConfig) -> FieldFns:
    """``field_fns`` over params whose tables are already packed."""
    n = cfg.grid

    def density(points):
        with jax.named_scope("vm_density"):
            inside = jnp.all((points >= 0.0) & (points <= 1.0), axis=-1)
            # sigma is zero outside the box, so a point there reads the
            # tables at its coordinates modulo the box: clamped, the points
            # past a face all gather the same few border rows, which one
            # v5e gathers up to 1.7x slower than spread rows, by a share
            # that depends on the view
            spread = jnp.where(inside[:, None], points, jnp.mod(points, 1.0))
            feat = jnp.sum(vm_features(spread, params["density"], n), axis=(1, 2))
            sigma = BOX_SIZE * DISTANCE_SCALE * jax.nn.softplus(feat + DENSITY_SHIFT)
        return jnp.where(inside, sigma, 0.0), points

    def color(geo, dirs):
        with jax.named_scope("vm_appearance"):
            prod = vm_features(geo, params["appearance"], n)
            f = jnp.dot(prod.reshape(prod.shape[0], -1), params["basis"],
                        precision=HIGHEST)
        with jax.named_scope("color_mlp"):
            x = jnp.concatenate([f, dirs, positional_encoding(f, FEATURE_PE),
                                 positional_encoding(dirs, VIEW_PE)], axis=-1)
            ws, bs = params["mlp"]["weights"], params["mlp"]["biases"]
            for k, (w, b) in enumerate(zip(ws, bs)):
                x = jnp.dot(x, w, precision=HIGHEST) + b
                if k < len(ws) - 1:
                    x = jax.nn.relu(x)
            return jax.nn.sigmoid(x)

    return FieldFns(density=density, color=color,
                    bound=Bound(_bound_field_fns, cfg, params))
