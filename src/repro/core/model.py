"""Instant-NGP neural field assembly + the baseline (paper's "original") renderer.

`NGPConfig` bundles the hash-grid and MLP configs.  `paper_mlp=True` uses a
color head sized so the density:color FLOP split matches the paper's
reported 8%:92% (§3 Challenge 2); the default matches the open-source
Instant-NGP sizes (64-wide, 1+2 hidden layers, ~33%:67%).
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp

from . import hashgrid, mlp, scene


@dataclasses.dataclass(frozen=True)
class NGPConfig:
    grid: hashgrid.HashGridConfig = hashgrid.HashGridConfig()
    net: mlp.MLPConfig = mlp.MLPConfig()

    @staticmethod
    def make(
        n_levels=16, log2_table_size=19, feature_dim=2,
        base_resolution=16, max_resolution=2048, paper_mlp=False,
    ) -> "NGPConfig":
        grid = hashgrid.HashGridConfig(
            n_levels=n_levels, log2_table_size=log2_table_size,
            feature_dim=feature_dim, base_resolution=base_resolution,
            max_resolution=max_resolution,
        )
        if paper_mlp:
            net = mlp.MLPConfig(
                encoding_dim=grid.output_dim, color_hidden=128, color_layers=3
            )
        else:
            net = mlp.MLPConfig(encoding_dim=grid.output_dim)
        return NGPConfig(grid=grid, net=net)

    @staticmethod
    def small(paper_mlp=False) -> "NGPConfig":
        """CPU-trainable config used by examples/tests (full config is used
        by the dry-run via ShapeDtypeStructs only)."""
        return NGPConfig.make(
            n_levels=8, log2_table_size=14, max_resolution=256,
            paper_mlp=paper_mlp,
        )

    def gather_stats(self, params) -> Dict[str, int]:
        """Hash-table rows gathered a point: one at each of the
        ``corner_levels`` levels whose corner rows ``params`` carries
        (``params["corners"]``, the kernel-backed field), 4 at each of the
        ``xrow_levels`` levels whose x-neighbour rows it carries
        (``params["xrows"]``), 8 at every other level; the color side
        reads no table."""
        c, x = params.get("corners", ()), params.get("xrows", ())
        return {"encode_gather_rows_per_sample": hashgrid.rows_per_point(self.grid, c, x),
                "corner_levels": len(c), "xrow_levels": len(x),
                "color_gather_rows_per_sample": 0}


def init_ngp(key: jax.Array, cfg: NGPConfig) -> Dict:
    k1, k2 = jax.random.split(key)
    return {
        "grid": hashgrid.init_hashgrid(k1, cfg.grid),
        "mlps": mlp.init_mlps(k2, cfg.net),
    }


def query_density(params: Dict, cfg: NGPConfig, points: jnp.ndarray):
    """points (N,3) -> (sigma (N,), geo_feat (N, geo))  — zero outside cube."""
    enc = hashgrid.encode(points, params["grid"], cfg.grid)
    sigma, geo = mlp.density_apply(params["mlps"], enc)
    inside = jnp.all((points >= 0.0) & (points <= 1.0), axis=-1)
    return jnp.where(inside, sigma, 0.0), geo


def query_color(params: Dict, cfg: NGPConfig, geo_feat, dirs):
    return mlp.color_apply(params["mlps"], geo_feat, dirs, cfg.net.sh_degree)


def query_field(params: Dict, cfg: NGPConfig, points, dirs):
    sigma, geo = query_density(params, cfg, points)
    color = query_color(params, cfg, geo, dirs)
    return sigma, color


def render_fixed(
    params: Dict, cfg: NGPConfig, origins, dirs, n_samples: int, key=None,
    white_background: bool = True,
):
    """The paper's baseline: fixed `n_samples` per ray, full MLP per sample.

    Returns (rgb (R,3), aux dict with per-sample sigmas/colors/deltas for
    the adaptive-sampling probe pass to reuse).
    """
    from . import pipeline

    return pipeline.render_fixed_fns(
        field_fns(params, cfg), origins, dirs, n_samples, key,
        white_background=white_background,
    )


def field_fns(params: Dict, cfg: NGPConfig):
    """Bind (params, cfg) into the pipeline's FieldFns protocol."""
    from . import fields

    return fields.FieldFns(
        density=lambda pts: query_density(params, cfg, pts),
        color=lambda geo, dirs: query_color(params, cfg, geo, dirs),
        bound=fields.Bound(field_fns, cfg, params),
    )


def render_image(params, cfg, cam, n_samples=128, chunk=4096, renderer=None):
    """Render a full image in ray chunks (host loop; memory-bounded)."""
    o, d = scene.camera_rays(cam)
    render = renderer or (
        lambda oo, dd: render_fixed(params, cfg, oo, dd, n_samples)[0]
    )
    outs = []
    for s in range(0, o.shape[0], chunk):
        outs.append(render(o[s : s + chunk], d[s : s + chunk]))
    img = jnp.concatenate(outs, axis=0)
    return img.reshape(cam.height, cam.width, 3)
