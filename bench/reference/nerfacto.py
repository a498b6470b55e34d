"""Plain reference of nerfacto rendered as nerfstudio renders it at eval.

Imports nothing of the program (the hash encode, MLP and spherical
harmonics are ``bench/reference/ingp.py``'s).  It follows the published
description (Tancik et al., "Nerfstudio", SIGGRAPH 2023,
arXiv:2302.04264) and nerfstudio's ``models/nerfacto.py``,
``fields/nerfacto_field.py``, ``fields/density_fields.py``,
``model_components/ray_samplers.py`` and
``field_components/spatial_distortions.py``:

* World: a program point x is at ``x_w = (x - c) / k`` in the world,
  ``c`` = ``scene.world_center``, ``k`` = ``scene.world_scale``; rays are
  cast in the world from ``near_plane`` to ``far_plane``.
* Contraction (``SceneContraction``, L-inf): ``contract(x) = x`` if
  ``|x|_inf <= 1``, else ``(2 - 1/|x|_inf) x / |x|_inf``.  Every grid
  reads ``p = (contract(x_w) + 2) / 4``; a point with p outside (0, 1)^3
  has zero density.
* Hash grids (Instant-NGP's, ``reference/ingp.py``): L levels of
  resolution ``floor(N_min b^l)``, dense where ``(res+1)^3 <= T``, else
  the spatial hash; trilinear interpolation.
* Initial sampler (``UniformLinDispPiecewiseSampler``, no jitter):
  ``g(t) = t/2`` (t < 1), ``1 - 1/(2t)`` (t >= 1); ``n + 1`` edges
  ``s = linspace(0, 1, n + 1)``, at world distance
  ``t = g^-1(s g(far) + (1 - s) g(near))``.
* Proposal fields (``HashMLPDensityField``): a grid, then ``L F -> 16``
  ReLU ``-> 1``, ``sigma = exp(h)`` (``trunc_exp``'s forward pass, times
  ``average_init_density`` = 1), at the intervals' midpoints
  ``o_w + (t_i + t_{i+1}) / 2 d``.
* Weights: ``alpha_i = 1 - exp(-sigma_i D_i)``, ``D_i = t_{i+1} - t_i``,
  ``T_i = exp(-sum_{j<i} sigma_j D_j)``, ``w_i = T_i alpha_i``; the
  anneal exponent is 1 at eval.
* PDF resampling (``PDFSampler``, ``include_original=False``, eval):
  ``w <- w + histogram_padding``; with ``W = sum w``, ``w <- w +
  relu(eps - W) / S``; ``pdf = w / (W + relu(eps - W))``; ``cdf = [0,
  min(1, cumsum(pdf)[:-1]), 1]``; ``u = linspace(0, 1 - 1/(n+1), n+1) +
  1/(2(n+1))``; ``i = searchsorted(cdf, u, right)``, ``below = clip(i-1,
  0, S)``, ``above = clip(i, 0, S)``; each new edge is the spacing-space
  edge interpolated linearly between ``below`` and ``above`` by ``(u -
  cdf[below]) / (cdf[above] - cdf[below])`` (0 where undefined, clipped to
  [0, 1]).  256 intervals -> 96 from the first proposal field, 96 -> 48
  from the second.
* Main field (``NerfactoField``): a grid, ``mlp_base`` ``L F -> 64`` ReLU
  ``-> 16``, ``sigma = exp(h_0)``, ``geo = h_1..15``; the head reads
  ``[SH_4(d) (16), geo (15), e (32)]``, ``e`` the mean of the appearance
  embeddings (``use_average_appearance_embedding``), 63 -> 64 -> 64 -> 3
  with ReLUs and a sigmoid.  No layer has a bias (tcnn's fused MLPs).
* Rendering: ``rgb = sum_i w_i c_i + (1 - sum_i w_i)`` over the 48
  intervals, white background.

Departures, as the configuration states them: the white background (the
model's default is ``last_sample``), and the repository's hash-grid
vertex convention, not tcnn's half-voxel offset.  The program's density
kernel clips h at +-15 before exp; the reference, like nerfstudio, does
not, so the comparison sees that departure too.

``dtype`` float32 is the reference, at ``highest`` matmul precision;
bfloat16 is the control: tables, weights, features, MLPs and the final
compositing in bfloat16, while the sampler's edges, its weights and the
inverse CDF stay in float32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.ingp import _encode, _mlp, _sh4, camera_rays, levels  # noqa: F401


def _spacing(t: float) -> float:
    return t / 2 if t < 1 else 1 - 1 / (2 * t)


def mlp_sizes(cfg: dict):
    """(density, color, [proposal ...]) layer sizes, input first."""
    g, m = cfg["grid"], cfg["mlp"]
    dens = ([g["n_levels"] * g["feature_dim"]] + [m["density_hidden"]] * m["density_layers"]
            + [1 + m["geo_feature_dim"]])
    col = ([m["sh_degree"] ** 2 + m["geo_feature_dim"] + m["appearance_embed_dim"]]
           + [m["color_hidden"]] * m["color_layers"] + [3])
    props = [[p["n_levels"] * p["feature_dim"]] + [p["hidden_dim"]] * (p["num_layers"] - 1)
             + [1] for p in cfg["proposal_nets"]]
    return dens, col, props


@functools.partial(jax.jit, static_argnums=0)
def _make_params(shapes, seed_lo, seed_hi):
    main, (prop_shapes, prop_range), dens, col, props, appearance = shapes
    key = jax.random.fold_in(jax.random.PRNGKey(seed_lo), seed_hi)
    k = jax.random.split(key, 4 + 2 * len(props))

    def glorot(kk, sizes):
        ks = jax.random.split(kk, len(sizes) - 1)
        return [jax.random.uniform(q, (a, b), jnp.float32, -math.sqrt(6.0 / (a + b)),
                                   math.sqrt(6.0 / (a + b)))
                for q, a, b in zip(ks, sizes[:-1], sizes[1:])]

    shape, table_range = main
    return {
        "grid": jax.random.uniform(k[0], shape, jnp.float32, -table_range, table_range),
        "mlps": {"density": glorot(k[1], dens), "color": glorot(k[2], col)},
        "appearance": jax.random.normal(k[3], appearance, jnp.float32),
        "proposals": [
            {"grid": jax.random.uniform(k[4 + 2 * i], s, jnp.float32, -prop_range, prop_range),
             "mlp": glorot(k[5 + 2 * i], sizes)}
            for i, (s, sizes) in enumerate(zip(prop_shapes, props))],
    }


def make_params(cfg: dict, seed: int):
    """Seeded weights, made on the device in one call:
    ``{"grid": (L, T, F), "mlps": {"density": [...], "color": [...]},
    "appearance": (images, 32), "proposals": [{"grid", "mlp"}, ...]}``.
    Tables are uniform in +-``table_init * table_scale`` (the proposal
    fields' in +-``table_init * proposal_table_scale``), MLP weights
    Glorot-uniform, the appearance embeddings N(0, 1) (``nn.Embedding``)."""
    g, w = cfg["grid"], cfg["weights"]
    dens, col, props = mlp_sizes(cfg)
    shapes = (
        ((g["n_levels"], 1 << g["log2_table_size"], g["feature_dim"]),
         float(w["table_init"] * w["table_scale"])),
        (tuple((p["n_levels"], 1 << p["log2_table_size"], p["feature_dim"])
               for p in cfg["proposal_nets"]),
         float(w["table_init"] * w["proposal_table_scale"])),
        tuple(dens), tuple(col), tuple(tuple(p) for p in props),
        (int(w["appearance_images"]), cfg["mlp"]["appearance_embed_dim"]))
    return _make_params(shapes, np.uint32(seed & 0xFFFFFFFF),
                        np.uint32((seed >> 32) & 0xFFFFFFFF))


def contract(x):
    """``SceneContraction`` with the L-inf norm."""
    n = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    return jnp.where(n <= 1.0, x, (2.0 - 1.0 / n) * (x / n))


def spacing_to_world(cfg: dict, s):
    """Spacing-space edges s in [0, 1] -> world distances."""
    sc = cfg["scene"]
    g_near, g_far = _spacing(sc["near_plane"]), _spacing(sc["far_plane"])
    u = s * g_far + (1.0 - s) * g_near
    return jnp.where(u < 0.5, 2.0 * u, 1.0 / (2.0 - 2.0 * u))


def weights(sigma, t):
    """Interval weights ``T_i alpha_i``; t (R, S+1) world edges."""
    dd = sigma * (t[:, 1:] - t[:, :-1])
    acc = jnp.concatenate([jnp.zeros_like(dd[:, :1]), jnp.cumsum(dd, axis=-1)[:, :-1]], -1)
    return jnp.nan_to_num((1.0 - jnp.exp(-dd)) * jnp.exp(-acc))


def pdf_edges(cfg: dict, s, w, n: int):
    """``PDFSampler``: n + 1 spacing-space edges from edges s (R, S+1)
    and weights w (R, S), in float32."""
    sp = cfg["sampler"]
    w = w.astype(jnp.float32) + sp["histogram_padding"]
    W = jnp.sum(w, axis=-1, keepdims=True)
    pad = jnp.maximum(sp["pdf_eps"] - W, 0.0)
    pdf = (w + pad / w.shape[-1]) / (W + pad)
    cdf = jnp.concatenate([jnp.zeros_like(W), jnp.minimum(1.0, jnp.cumsum(pdf, -1)[:, :-1]),
                           jnp.ones_like(W)], axis=-1)
    u = jnp.linspace(0.0, 1.0 - 1.0 / (n + 1), n + 1) + 1.0 / (2 * (n + 1))
    i = jax.vmap(lambda c: jnp.searchsorted(c, u, side="right"))(cdf)
    last = s.shape[-1] - 1
    below, above = jnp.clip(i - 1, 0, last), jnp.clip(i, 0, last)
    rows = jnp.arange(s.shape[0])[:, None]
    c0, c1 = cdf[rows, below], cdf[rows, above]
    frac = jnp.clip(jnp.nan_to_num((u[None, :] - c0) / (c1 - c0)), 0.0, 1.0)
    return s[rows, below] + frac * (s[rows, above] - s[rows, below])


def _grid_field(tables, ws, lv, x_w, dtype):
    """A density field at world points (N, 3): (sigma, the other outputs)."""
    p = (contract(x_w) + 2.0) / 4.0
    inside = jnp.all((p > 0.0) & (p < 1.0), axis=-1)
    h = _mlp([w.astype(dtype) for w in ws],
             _encode(p * inside[:, None], tables.astype(dtype), lv, dtype), dtype)
    return jnp.where(inside, jnp.exp(h[:, 0]), 0).astype(dtype), h[:, 1:]


def _static(cfg: dict, dtype):
    return (tuple(levels(cfg)),
            tuple(tuple(levels({"grid": p})) for p in cfg["proposal_nets"]),
            tuple(cfg["sampler"]["num_proposal_samples_per_ray"]),
            cfg["sampler"]["num_nerf_samples_per_ray"], jnp.dtype(dtype))


def sample(cfg: dict, params, o_w, d, static=None):
    """The proposal sampler: each ray's spacing-space edges (R, n+1) of
    the intervals the main field is evaluated on."""
    _lv, prop_lv, n_prop, n_nerf, dtype = static or _static(cfg, jnp.float32)
    R = o_w.shape[0]
    s = jnp.broadcast_to(jnp.linspace(0.0, 1.0, n_prop[0] + 1), (R, n_prop[0] + 1))
    for i, (p, lv) in enumerate(zip(params["proposals"], prop_lv)):
        t = spacing_to_world(cfg, s)
        x = o_w[:, None, :] + (0.5 * (t[:, 1:] + t[:, :-1]))[..., None] * d[:, None, :]
        sigma, _ = _grid_field(p["grid"], p["mlp"], lv, x.reshape(-1, 3), dtype)
        w = weights(sigma.reshape(R, -1).astype(jnp.float32), t)
        s = pdf_edges(cfg, s, w, (n_prop[1:] + (n_nerf,))[i])
    return s


def _render_rays(cfg_json, static, params, o, d):
    """One block of rays -> (rgb (R, 3), acc (R,)) in float32."""
    import json
    cfg = json.loads(cfg_json)
    lv, _plv, _np, _nn, dtype = static
    sc = cfg["scene"]
    o_w = (o - jnp.asarray(sc["world_center"], jnp.float32)) / sc["world_scale"]
    R = o.shape[0]
    t = spacing_to_world(cfg, sample(cfg, params, o_w, d, static))
    S = t.shape[1] - 1
    x = o_w[:, None, :] + (0.5 * (t[:, 1:] + t[:, :-1]))[..., None] * d[:, None, :]
    sigma, geo = _grid_field(params["grid"], params["mlps"]["density"], lv,
                             x.reshape(-1, 3), dtype)
    e = jnp.mean(params["appearance"], axis=0).astype(dtype)
    h = jnp.concatenate([_sh4(jnp.repeat(d, S, axis=0), dtype), geo,
                         jnp.broadcast_to(e, (R * S, e.shape[0]))], axis=-1)
    color = jax.nn.sigmoid(_mlp([w.astype(dtype) for w in params["mlps"]["color"]], h, dtype))
    w = weights(sigma.reshape(R, S), t.astype(dtype))
    acc = jnp.sum(w, axis=-1)
    rgb = jnp.sum(w[..., None] * color.reshape(R, S, 3), axis=1) + (1.0 - acc)[:, None]
    return rgb.astype(jnp.float32), acc.astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _render_blocks(cfg_json, static, block, params, o, d):
    n = o.shape[0] // block
    rgb, acc = jax.lax.map(lambda od: _render_rays(cfg_json, static, params, od[0], od[1]),
                           (o.reshape(n, block, 3), d.reshape(n, block, 3)))
    return rgb.reshape(-1, 3), acc.reshape(-1)


def render(cfg: dict, params, origins, dirs, dtype=jnp.float32, block: int = 256,
           with_acc: bool = False):
    """Reference rgb (N, 3) of the rays (and their opacity, ``with_acc``),
    rendered in blocks of ``block`` rays; float32 at ``highest``
    precision, or the bfloat16 control."""
    import json
    if cfg["mlp"]["sh_degree"] != 4:
        raise ValueError("the reference takes degree-4 SH")
    keys = ("grid", "mlp", "proposal_nets", "sampler", "scene")
    cfg_json = json.dumps({k: cfg[k] for k in keys}, sort_keys=True)
    N = origins.shape[0]
    pad = (-N) % block
    o = np.concatenate([origins, np.zeros((pad, 3), np.float32)])
    d = np.concatenate([dirs, np.tile(np.float32([[0, 0, 1]]), (pad, 1))])
    prec = "highest" if jnp.dtype(dtype) == jnp.float32 else "default"
    with jax.default_matmul_precision(prec):
        rgb, acc = _render_blocks(cfg_json, _static(cfg, dtype), block, params,
                                  jnp.asarray(o), jnp.asarray(d))
    rgb, acc = np.asarray(rgb)[:N], np.asarray(acc)[:N]
    return (rgb, acc) if with_acc else rgb
