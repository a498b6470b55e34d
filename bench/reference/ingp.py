"""Plain reference of an Instant-NGP field rendered as ASDR renders it.

Imports nothing of the program.  It follows the published description:

* Instant-NGP (Mueller et al., SIGGRAPH 2022, section 3): L levels of
  resolution floor(N_min * b**l), b = exp((ln N_max - ln N_min) / (L - 1));
  a level whose (res+1)**3 vertices fit the table is indexed densely,
  the others through the spatial hash XOR(x_i * pi_i) mod T with
  pi = (1, 2654435761, 805459861); trilinear interpolation of the 8
  corner features; a density MLP (ReLU hidden layers, sigma =
  exp(clip(out[0], -15, 15)), the other outputs the geometry feature);
  a color MLP on [geometry feature, degree-4 real spherical harmonics of
  the view direction] with a sigmoid output.
* ASDR (arXiv:2508.02304, sections 4.2 and 4.3): the original renderer
  takes ``ns_full`` midpoint samples per ray between the near and far
  planes and composites them over a white background; color-density
  decoupling evaluates the color MLP at every ``group``-th sample and
  interpolates linearly between anchors.  The anchors follow the
  configuration's march grid of ``chunk`` samples: a sample past the
  last anchor of its chunk takes that anchor's color.  (The paper has no
  chunks; the configuration states them, and the served frames depend
  on them.)

Every ray of the reference takes ``ns_full`` samples and composites them
all: ASDR's adaptive counts and early termination are approximations
of this, and the comparison measures them.

``dtype`` float32 is the reference, computed at ``highest`` matmul
precision; bfloat16 is the control (tables, weights, features, MLP and
compositing in bfloat16; sample positions and hash indices stay exact).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

PRIMES = (1, 2654435761, 805459861)
SH_C = (0.28209479177387814, 0.48860251190291987, 1.0925484305920792,
        0.31539156525251999, 0.94617469575755997, 0.54627421529603959,
        0.59004358992664352, 2.8906114426405538, 0.45704579946446572,
        0.3731763325901154, 1.4453057213202769)


def levels(cfg: dict):
    """[(resolution, dense)] per level of the hash grid."""
    g = cfg["grid"]
    L, T = g["n_levels"], 1 << g["log2_table_size"]
    b = math.exp((math.log(g["max_resolution"]) - math.log(g["base_resolution"]))
                 / (L - 1)) if L > 1 else 1.0
    out = []
    for lv in range(L):
        res = int(math.floor(g["base_resolution"] * b ** lv))
        out.append((res, (res + 1) ** 3 <= T))
    return out


def mlp_sizes(cfg: dict):
    """(density layer sizes, color layer sizes), input first."""
    g, m = cfg["grid"], cfg["mlp"]
    d = ([g["n_levels"] * g["feature_dim"]] + [m["density_hidden"]] * m["density_layers"]
         + [1 + m["geo_feature_dim"]])
    c = ([m["geo_feature_dim"] + m["sh_degree"] ** 2] + [m["color_hidden"]] * m["color_layers"]
         + [3])
    return d, c


@functools.partial(jax.jit, static_argnums=0)
def _make_params(shapes, seed_lo, seed_hi):
    (L, T, F), table_range, dens, col = shapes
    key = jax.random.fold_in(jax.random.PRNGKey(seed_lo), seed_hi)
    kg, kd, kc = jax.random.split(key, 3)
    grid = jax.random.uniform(kg, (L, T, F), jnp.float32, -table_range, table_range)

    def glorot(k, sizes):
        ks = jax.random.split(k, len(sizes) - 1)
        return [jax.random.uniform(kk, (a, b), jnp.float32, -math.sqrt(6.0 / (a + b)),
                                   math.sqrt(6.0 / (a + b)))
                for kk, a, b in zip(ks, sizes[:-1], sizes[1:])]

    return {"grid": grid, "mlps": {"density": glorot(kd, dens), "color": glorot(kc, col)}}


def make_params(cfg: dict, seed: int):
    """Seeded weights, made on the device in one call, in the layout
    ``{"grid": (L, T, F), "mlps": {"density": [...], "color": [...]}}``.
    Tables are uniform in +-``table_init * table_scale``; MLP weights are
    Glorot-uniform."""
    g, w = cfg["grid"], cfg["weights"]
    d, c = mlp_sizes(cfg)
    shapes = ((g["n_levels"], 1 << g["log2_table_size"], g["feature_dim"]),
              float(w["table_init"] * w["table_scale"]), tuple(d), tuple(c))
    return _make_params(shapes, np.uint32(seed & 0xFFFFFFFF),
                        np.uint32((seed >> 32) & 0xFFFFFFFF))


def camera_rays(cam, pix):
    """Rays through pixel indices ``pix`` (flat, row-major) of a pinhole
    camera ``cam = (H, W, focal, rot (3,3) columns right/up/forward,
    origin (3,))``: (origins (N,3), unit directions (N,3)), float32."""
    H, W, focal, rot, origin = cam
    pix = np.asarray(pix, np.int64)
    j, i = (pix // W).astype(np.float64), (pix % W).astype(np.float64)
    d = np.stack([(i - W * 0.5 + 0.5) / focal, -(j - H * 0.5 + 0.5) / focal,
                  np.ones_like(i)], axis=-1) @ np.asarray(rot, np.float64).T
    d /= np.linalg.norm(d, axis=-1, keepdims=True)
    o = np.broadcast_to(np.asarray(origin, np.float64), d.shape)
    return o.astype(np.float32), d.astype(np.float32)


def _encode(x, tables, lv, dtype):
    """(N, 3) points -> (N, L*F) features, every level in one gather."""
    L, T, F = tables.shape
    res = jnp.asarray([r for r, _ in lv], jnp.float32)
    scaled = x[:, None, :] * res[None, :, None]                 # (N, L, 3)
    base = jnp.floor(scaled).astype(jnp.int32)
    base = jnp.clip(base, 0, jnp.asarray([r - 1 for r, _ in lv], jnp.int32)[None, :, None])
    frac = scaled - base
    offs = np.stack(np.meshgrid([0, 1], [0, 1], [0, 1], indexing="ij"), -1).reshape(8, 3)
    c = (base[:, :, None, :] + jnp.asarray(offs, jnp.int32)).astype(jnp.uint32)
    stride = jnp.asarray([r + 1 for r, _ in lv], jnp.uint32)[None, :, None]
    dense = c[..., 0] + stride * (c[..., 1] + stride * c[..., 2])
    hashed = ((c[..., 0] * np.uint32(PRIMES[0])) ^ (c[..., 1] * np.uint32(PRIMES[1]))
              ^ (c[..., 2] * np.uint32(PRIMES[2]))) % np.uint32(T)
    is_dense = jnp.asarray([dn for _, dn in lv])[None, :, None]
    row = jnp.where(is_dense, dense, hashed).astype(jnp.int32)
    row = row + (jnp.arange(L, dtype=jnp.int32) * T)[None, :, None]
    feats = tables.reshape(L * T, F)[row]                         # (N, L, 8, F)
    w = jnp.prod(jnp.where(jnp.asarray(offs == 1)[None, None], frac[:, :, None, :],
                           1.0 - frac[:, :, None, :]), axis=-1).astype(dtype)
    return jnp.sum(feats * w[..., None], axis=2).reshape(x.shape[0], L * F)


def _mlp(ws, x, dtype):
    for k, w in enumerate(ws):
        x = jnp.dot(x, w, preferred_element_type=dtype)
        if k < len(ws) - 1:
            x = jnp.maximum(x, 0)
    return x


def _sh4(d, dtype):
    x, y, z = d[:, 0], d[:, 1], d[:, 2]
    xx, yy, zz = x * x, y * y, z * z
    C = SH_C
    comps = [jnp.full_like(x, C[0]), -C[1] * y, C[1] * z, -C[1] * x,
             C[2] * x * y, -C[2] * y * z, C[4] * zz - C[3], -C[2] * x * z,
             C[5] * (xx - yy), C[6] * y * (-3.0 * xx + yy), C[7] * x * y * z,
             C[8] * y * (1.0 - 5.0 * zz), C[9] * z * (5.0 * zz - 3.0),
             C[8] * x * (1.0 - 5.0 * zz), C[10] * z * (xx - yy),
             C[6] * x * (-xx + 3.0 * yy)]
    return jnp.stack(comps, axis=-1).astype(dtype)


def _render_rays(static, params, o, d):
    """One block of rays -> rgb (R, 3) in float32."""
    lv, ns, chunk, group, near, far, sh_degree, dtype = static
    R = o.shape[0]
    tables = params["grid"].astype(dtype)
    dens = [w.astype(dtype) for w in params["mlps"]["density"]]
    cols = [w.astype(dtype) for w in params["mlps"]["color"]]
    dt = (far - near) / ns
    ts = near + (jnp.arange(ns, dtype=jnp.float32) + 0.5) * dt       # (S,)
    pts = (o[:, None, :] + ts[None, :, None] * d[:, None, :]).reshape(-1, 3)
    out = _mlp(dens, _encode(pts, tables, lv, dtype), dtype)
    inside = jnp.all((pts >= 0.0) & (pts <= 1.0), axis=-1)
    sigma = jnp.where(inside, jnp.exp(jnp.clip(out[:, 0], -15.0, 15.0)), 0).reshape(R, ns)
    geo = out[:, 1:].reshape(R, ns, -1)
    # anchors at every group-th sample of each chunk
    j = np.arange(ns)
    off = j % chunk
    n_anchor = -(-chunk // group)
    left = (j - off) + np.minimum(off // group, n_anchor - 1) * group
    right = (j - off) + np.minimum(off // group + 1, n_anchor - 1) * group
    t = ((off % group) / group).astype(np.float32)
    anchors = np.unique(left)
    idx = {a: k for k, a in enumerate(anchors)}
    geo_a = geo[:, anchors].reshape(R * anchors.size, -1)
    sh = _sh4(jnp.repeat(d, anchors.size, axis=0), dtype)
    col_a = jax.nn.sigmoid(_mlp(cols, jnp.concatenate([geo_a, sh], -1), dtype))
    col_a = col_a.reshape(R, anchors.size, 3)
    li = np.asarray([idx[a] for a in left])
    ri = np.asarray([idx[a] for a in right])
    color = col_a[:, li] + (col_a[:, ri] - col_a[:, li]) * jnp.asarray(t, dtype)[None, :, None]
    alpha = 1.0 - jnp.exp(-sigma * jnp.asarray(dt, dtype))
    trans = jnp.cumprod(jnp.concatenate([jnp.ones((R, 1), dtype), 1.0 - alpha[:, :-1]], 1), 1)
    w = trans * alpha
    rgb = jnp.sum(w[..., None] * color, axis=1) + (1.0 - jnp.sum(w, axis=1))[:, None]
    return rgb.astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _render_blocks(static, block, params, o, d):
    n = o.shape[0] // block
    rgb = jax.lax.map(lambda od: _render_rays(static, params, od[0], od[1]),
                      (o.reshape(n, block, 3), d.reshape(n, block, 3)))
    return rgb.reshape(-1, 3)


def render(cfg: dict, params, origins, dirs, dtype=jnp.float32, block: int = 512):
    """Reference rgb (N, 3) of the rays, rendered in blocks of ``block``
    rays; float32 at ``highest`` precision, or the bfloat16 control."""
    a = cfg["asdr"]
    if cfg["mlp"]["sh_degree"] != 4 or a["ns_full"] % a["chunk"]:
        raise ValueError("the reference takes degree-4 SH and whole chunks of samples")
    static = (tuple(levels(cfg)), a["ns_full"], a["chunk"], a["group"],
              cfg["scene"]["near"], cfg["scene"]["far"], cfg["mlp"]["sh_degree"],
              jnp.dtype(dtype))
    N = origins.shape[0]
    pad = (-N) % block
    o = np.concatenate([origins, np.zeros((pad, 3), np.float32)])
    d = np.concatenate([dirs, np.tile(np.float32([[0, 0, 1]]), (pad, 1))])
    prec = "highest" if jnp.dtype(dtype) == jnp.float32 else "default"
    with jax.default_matmul_precision(prec):
        rgb = _render_blocks(static, block, params, jnp.asarray(o), jnp.asarray(d))
    return np.asarray(rgb)[:N]
