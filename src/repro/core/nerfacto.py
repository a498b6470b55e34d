"""nerfacto, nerfstudio's default model (Tancik et al., "Nerfstudio: A
Modular Framework for Neural Radiance Field Development", SIGGRAPH 2023,
arXiv:2302.04264), as a ``FieldFns`` that brings its own sampler.

Scene.  A program point maps to nerfstudio's world by
``x_w = (x - CENTER) / WORLD_SCALE``, so a program distance is
``1 / WORLD_SCALE`` world units; rays run from ``NEAR`` to ``FAR`` world
units.  Every grid reads ``(contract(x_w) + 2) / 4`` with the L-inf
``SceneContraction``: ``contract(x) = x`` where ``|x|_inf <= 1``, else
``(2 - 1/|x|_inf) x / |x|_inf``; a point outside (0, 1)^3 there has zero
density.

Sampler (``ProposalNetworkSampler`` at eval).  Spacing
``g(t) = t/2`` (t < 1), ``1 - 1/(2t)`` (t >= 1); an edge ``s`` in [0, 1]
is at world distance ``g^-1(s g(FAR) + (1 - s) g(NEAR))``.  The initial
sampler takes ``samples[0] + 1`` edges uniform in s.  Proposal field i
(``HashMLPDensityField``: a hash grid, then ``F*L -> hidden`` ReLU
``-> 1``, ``sigma = exp(h)``) weighs the current intervals'
midpoints, ``w_i = T_i alpha_i`` with ``alpha_i = 1 - exp(-sigma_i
Delta_i)`` in world units, and ``PDFSampler`` resamples
``samples[i + 1]`` intervals from them (``pdf_edges``).  The march takes
the last ``samples[-1]`` intervals' midpoints.

Main field (``NerfactoField``).  The hash grid and ``ops.density_mlp``
as Instant-NGP serves them (``kernels.ops``: corner rows, the fused-MLP
kernels): ``sigma = exp(h_0)``, ``geo = h_1..15``.  The color head reads
``[SH_4(d) (16), geo (15), e (32)]`` with ``e`` the appearance embeddings'
mean (nerfstudio's eval path), 63 -> 64 -> 64 -> 3, ReLU, sigmoid, no
biases (tcnn).  ``e`` is a constant at render time, and SH_4's first
component is the constant ``SH_C0``, so ``fold_appearance`` adds
``W1[31:63]^T e / SH_C0`` to that component's weight row: the head then
runs on ``ops.color_mlp`` at Instant-NGP's 31 inputs.  The field returns
sigma per program unit (``sigma_w / WORLD_SCALE``), so the march's
``1 - exp(-sigma * dt)`` over program widths is nerfstudio's alpha.

The three grids' corner and x-neighbour rows share one budget
(``ops.field_corner_tables``).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..kernels import ops
from . import hashgrid, mlp
from .fields import Bound, FieldFns, Sampler

NEAR, FAR = 0.05, 1000.0          # world units: near_plane, far_plane
WORLD_SCALE = 1.2                 # program units a world unit
CENTER = (0.5, 0.5, 0.42)         # the program point at the world origin
HISTOGRAM_PADDING = 0.01          # PDFSampler's histogram_padding
PDF_EPS = 1e-5                    # PDFSampler's eps
SH_C0 = 0.28209479177387814       # mlp.sh_encode's constant first component
G_NEAR, G_FAR = NEAR / 2, 1 - 1 / (2 * FAR)   # g(NEAR), g(FAR): NEAR < 1 <= FAR
HIGHEST = jax.lax.Precision.HIGHEST


def _proposal_grid(max_resolution: int) -> hashgrid.HashGridConfig:
    return hashgrid.HashGridConfig(n_levels=5, log2_table_size=17, feature_dim=2,
                                   base_resolution=16, max_resolution=max_resolution)


@dataclasses.dataclass(frozen=True)
class NerfactoConfig:
    grid: hashgrid.HashGridConfig = hashgrid.HashGridConfig()
    net: mlp.MLPConfig = mlp.MLPConfig()        # mlp_base 32-64-16, head 64 x 2
    proposal_grids: Tuple[hashgrid.HashGridConfig, ...] = (
        _proposal_grid(128), _proposal_grid(256))
    samples: Tuple[int, ...] = (256, 96, 48)   # proposal samples, then the march's

    @property
    def samples_per_ray(self) -> int:
        """Proposal-field evaluations a ray."""
        return sum(self.samples[:-1])

    def gather_stats(self, params) -> Dict[str, int]:
        """Hash-table rows gathered: a marched point's (one a corner-row
        level, 4 an x-neighbour-row level, 8 at every other level of the
        main grid), and a ray's over both proposal fields."""
        return {"encode_gather_rows_per_sample": hashgrid.rows_per_point(
                    self.grid, params["corners"], params["xrows"]),
                "corner_levels": len(params["corners"]),
                "xrow_levels": len(params["xrows"]),
                "color_gather_rows_per_sample": 0,
                "proposal_samples_per_ray": self.samples_per_ray,
                "proposal_gather_rows_per_ray": sum(
                    s * hashgrid.rows_per_point(g, p["corners"], p["xrows"])
                    for s, g, p in
                    zip(self.samples, self.proposal_grids, params["proposals"]))}


def contract(x):
    """nerfstudio's ``SceneContraction(order=inf)``."""
    n = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    return jnp.where(n <= 1.0, x, (2.0 - 1.0 / n) * (x / n))


def grid_points(x_w):
    """World points (N, 3) -> (grid coordinates in (0, 1)^3, inside)."""
    pos = (contract(x_w) + 2.0) / 4.0
    inside = jnp.all((pos > 0.0) & (pos < 1.0), axis=-1)
    return pos * inside[:, None], inside


def world_distance(s):
    """Spacing-space edges -> world distances along the ray."""
    u = s * G_FAR + (1.0 - s) * G_NEAR
    return jnp.where(u < 0.5, 2.0 * u, 1.0 / (2.0 - 2.0 * u))


def program_distance(s):
    """Spacing-space edges -> program distances (the march's)."""
    return WORLD_SCALE * world_distance(s)


def interval_weights(sigma, t):
    """``w_i = T_i alpha_i`` of intervals with world edges t (R, S+1)."""
    dd = sigma * (t[:, 1:] - t[:, :-1])
    trans = jnp.exp(-jnp.concatenate(
        [jnp.zeros_like(dd[:, :1]), jnp.cumsum(dd, axis=-1)[:, :-1]], axis=-1))
    return jnp.nan_to_num((1.0 - jnp.exp(-dd)) * trans)


def pdf_edges(s, w, n: int):
    """``PDFSampler`` at eval: ``n + 1`` edges (R, n+1) resampled from the
    intervals with edges s (R, S+1) and weights w (R, S)."""
    w = w + HISTOGRAM_PADDING
    w_sum = jnp.sum(w, axis=-1, keepdims=True)
    pad = jax.nn.relu(PDF_EPS - w_sum)
    pdf = (w + pad / w.shape[-1]) / (w_sum + pad)
    cdf = jnp.concatenate([jnp.zeros_like(pdf[:, :1]),
                           jnp.minimum(1.0, jnp.cumsum(pdf, axis=-1)[:, :-1]),
                           jnp.ones_like(pdf[:, :1])], axis=-1)
    u = jnp.linspace(0.0, 1.0 - 1.0 / (n + 1), n + 1) + 1.0 / (2 * (n + 1))
    # searchsorted(cdf, u, side="right") of each ray
    inds = jnp.sum(cdf[:, None, :] <= u[None, :, None], axis=-1)
    below = jnp.clip(inds - 1, 0, s.shape[1] - 1)
    above = jnp.clip(inds, 0, s.shape[1] - 1)
    c0, c1 = (jnp.take_along_axis(cdf, i, axis=-1) for i in (below, above))
    b0, b1 = (jnp.take_along_axis(s, i, axis=-1) for i in (below, above))
    t = jnp.clip(jnp.nan_to_num((u - c0) / (c1 - c0)), 0.0, 1.0)
    return b0 + t * (b1 - b0)


def _proposal_sigma(p, grid: hashgrid.HashGridConfig, x_w):
    """``HashMLPDensityField``'s density at world points (N, 3)."""
    pos, inside = grid_points(x_w)
    enc = hashgrid.encode(pos, p["grid"], grid, p["corners"], p["xrows"])
    h = jax.nn.relu(jnp.dot(enc, p["mlp"][0], precision=HIGHEST))
    h = jnp.dot(h, p["mlp"][1], precision=HIGHEST)[:, 0]
    return jnp.where(inside, jnp.exp(h), 0.0)


def fold_appearance(mlps: Dict, appearance, net: mlp.MLPConfig) -> Dict:
    """The color head with the mean appearance embedding folded in: its
    first layer's rows ``[SH (16), geo (15), e (32)]`` become the kernel's
    ``[geo, SH]``, with ``W[31:63]^T e / SH_C0`` added to SH_0's row."""
    w = mlps["color"][0]
    S, G = net.sh_dim, net.geo_feature_dim
    e = jnp.mean(appearance, axis=0)
    w_sh = w[:S].at[0].add(jnp.dot(e, w[S + G:], precision=HIGHEST) / SH_C0)
    return dict(mlps, color=[jnp.concatenate([w[S:S + G], w_sh])] + list(mlps["color"][1:]))


def field_fns(params: Dict, cfg: NerfactoConfig) -> FieldFns:
    """The field over ``params`` (``{"grid", "mlps": {"density", "color"},
    "appearance" (images, 32), "proposals": [{"grid", "mlp"}, ...]}``).
    The corner and x-neighbour rows of all three grids are derived here
    under one budget and carried, with the folded head, in the ``Bound``
    params, so jitted callers pass them as arguments."""
    props = params["proposals"]
    corners, xrows = ops.field_corner_tables(
        (params["grid"],) + tuple(p["grid"] for p in props),
        (cfg.grid,) + cfg.proposal_grids)
    bound = {"grid": params["grid"], "corners": corners[0], "xrows": xrows[0],
             "mlps": fold_appearance(params["mlps"], params["appearance"], cfg.net),
             "proposals": [dict(p, corners=c, xrows=x)
                           for p, c, x in zip(props, corners[1:], xrows[1:])]}
    return _bound_field_fns(bound, cfg)


def _bound_field_fns(params: Dict, cfg: NerfactoConfig) -> FieldFns:
    """``field_fns`` over params that carry their corner and x-neighbour
    rows and the folded head."""
    center = jnp.asarray(CENTER, jnp.float32)

    def density(points):
        with jax.named_scope("nerfacto_density"):
            pos, inside = grid_points((points - center) / WORLD_SCALE)
            enc = hashgrid.encode(pos, params["grid"], cfg.grid, params["corners"],
                                  params["xrows"])
            sigma, geo = ops.density_mlp(enc, params["mlps"], cfg.net)
            return jnp.where(inside, sigma / WORLD_SCALE, 0.0), geo

    def color(geo, dirs):
        with jax.named_scope("nerfacto_color"):
            return ops.color_mlp(geo, dirs, params["mlps"], cfg.net)

    def edges(origins, dirs):
        o_w = (origins - center) / WORLD_SCALE
        R = origins.shape[0]
        s = jnp.broadcast_to(jnp.linspace(0.0, 1.0, cfg.samples[0] + 1),
                             (R, cfg.samples[0] + 1))
        for i, (p, grid) in enumerate(zip(params["proposals"], cfg.proposal_grids)):
            with jax.named_scope(f"proposal_{i}"):
                t = world_distance(s)
                mid = 0.5 * (t[:, 1:] + t[:, :-1])
                x = o_w[:, None, :] + mid[..., None] * dirs[:, None, :]
                sigma = _proposal_sigma(p, grid, x.reshape(-1, 3)).reshape(R, -1)
                w = interval_weights(sigma, t)
            with jax.named_scope("pdf_sample"):
                s = pdf_edges(s, w, cfg.samples[i + 1])
        return s

    return FieldFns(density=density, color=color,
                    bound=Bound(_bound_field_fns, cfg, params),
                    sampler=Sampler(edges, program_distance, cfg.samples_per_ray))
