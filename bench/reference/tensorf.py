"""Plain reference of a TensoRF VM field rendered as ASDR renders it.

Imports nothing of the program.  It follows the published description
(Chen, Xu, Geiger, Yu, Su, "TensoRF: Tensorial Radiance Fields", ECCV
2022, arXiv:2203.09517, section 4 and the authors' ``TensorVMSplit``):

* Box and grid: the program's unit cube is the box [-1.5, 1.5]^3, so a
  point x maps to the world as x_w = 3x - 1.5 and to TensoRF's normalized
  coordinate as x^ = x_w / 1.5 = 2x - 1.  An axis of n grid points reads
  at the grid coordinate g = (x^ + 1) / 2 * (n - 1) (``align_corners``).
  Samples outside the box have sigma = 0 (``ray_valid``).  The box is the
  configuration's ``aabb`` on every axis.
* Planes and lines: three pairs m with plane axes (a, b) = (0,1), (0,2),
  (1,2) and line axes c = 2, 1, 0.  P_{m,r}(x^_a, x^_b) is bilinear over
  ``planes[m, i_a * n + i_b, r]``, V_{m,r}(x^_c) linear over
  ``lines[m, i_c, r]``.
* Density: sigma_feat = sum_{m, r < R_s} P^s_{m,r} V^s_{m,r};
  sigma = softplus(sigma_feat + shift) (shift -10) and
  alpha = 1 - exp(-sigma * dist_w * 25) (``distance_scale``).  A program
  distance is 3 world units, so in program units sigma is
  75 * softplus(sigma_feat - 10) and alpha = 1 - exp(-sigma * dt).
* Appearance: f = B concat_{m, r < R_c}[P^c_{m,r} V^c_{m,r}], the index
  m * R_c + r, B of 3 R_c -> 27 with no bias.
* Color (``MLPRender_Fea``): the input is [f, d, PE(f, 2), PE(d, 2)],
  PE(p, L) = [sin(p_i 2^k) for i, k] + [cos(p_i 2^k) for i, k] in the
  order i * L + k; Linear-ReLU-Linear-ReLU-Linear with biases, then a
  sigmoid.  White background.
* ASDR (arXiv:2508.02304, sections 4.2 and 4.3): ``ns_full`` midpoint
  samples between near and far, color at every ``group``-th sample of
  each chunk of ``chunk`` samples and linear interpolation between
  anchors; a sample past the last anchor of its chunk takes that
  anchor's color.  Where ``ns_full`` is not a whole number of chunks, a
  sample of the last chunk may interpolate towards an anchor past the
  last sample: that anchor's color is taken at its own point.

Departures from the paper, as the configuration states them: samples sit
at ASDR's midpoints, not from TensoRF's box entry; the alpha mask, the
weight-threshold skip and box shrinking are left out (they depend on
trained weights); TensoRF never computes color outside the box, and
here, as in the program, a point's color reads the tables at the point
clamped to the box.

``dtype`` float32 is the reference, at ``highest`` matmul precision;
bfloat16 is the control (tables, weights, features, MLP and compositing
in bfloat16; sample positions, grid indices and interpolation fractions
stay exact).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.ingp import camera_rays  # noqa: F401 (the harness reads it here)

PLANES = ((0, 1), (0, 2), (1, 2))
LINES = (2, 1, 0)


def mlp_sizes(cfg: dict):
    t = cfg["tensorf"]
    F = t["data_dim_color"]
    n_in = F + 3 + 2 * t["fea_pe"] * F + 2 * t["view_pe"] * 3
    return (n_in, t["featureC"], t["featureC"], 3)


@functools.partial(jax.jit, static_argnums=0)
def _make_params(shapes, seed_lo, seed_hi):
    n, rs, rc, F, sizes, scale = shapes
    key = jax.random.fold_in(jax.random.PRNGKey(seed_lo), seed_hi)
    k = jax.random.split(key, 8)

    def side(kp, kl, R):
        return {"planes": scale * jax.random.normal(kp, (3, n * n, R), jnp.float32),
                "lines": scale * jax.random.normal(kl, (3, n, R), jnp.float32)}

    def glorot(kk, a, b):
        lim = math.sqrt(6.0 / (a + b))
        return jax.random.uniform(kk, (a, b), jnp.float32, -lim, lim)

    kw = jax.random.split(k[6], len(sizes) - 1)
    kb = jax.random.split(k[7], len(sizes) - 1)
    weights = [glorot(kk, a, b) for kk, a, b in zip(kw, sizes[:-1], sizes[1:])]
    biases = [jax.random.uniform(kk, (b,), jnp.float32, -1.0, 1.0) / math.sqrt(a)
              for kk, a, b in zip(kb, sizes[:-2], sizes[1:-1])]
    biases.append(jnp.zeros((sizes[-1],), jnp.float32))
    return {"density": side(k[0], k[1], rs), "appearance": side(k[2], k[3], rc),
            "basis": glorot(k[4], 3 * rc, F),
            "mlp": {"weights": weights, "biases": biases}}


def make_params(cfg: dict, seed: int):
    """Seeded weights, made on the device in one call:
    ``{"density"|"appearance": {"planes": (3, n*n, R), "lines": (3, n, R)},
    "basis": (3 R_c, F), "mlp": {"weights": [...], "biases": [...]}}``.
    Planes and lines are N(0, s^2) with s = ``weights.scale``; the basis and
    MLP weights Glorot-uniform; MLP biases uniform in +-1/sqrt(fan-in)
    (PyTorch's ``nn.Linear``), the last zero (TensoRF zeroes it)."""
    t = cfg["tensorf"]
    shapes = (t["grid"], t["n_lamb_sigma"], t["n_lamb_sh"], t["data_dim_color"],
              mlp_sizes(cfg), float(cfg["weights"]["scale"]))
    return _make_params(shapes, np.uint32(seed & 0xFFFFFFFF),
                        np.uint32((seed >> 32) & 0xFFFFFFFF))


def _interp(tables, x, n, dtype):
    """(M, 3) points -> (M, 3, R): P_{m,r}(x^_a, x^_b) * V_{m,r}(x^_c)."""
    xhat = 2.0 * jnp.clip(x, 0.0, 1.0) - 1.0
    g = (xhat + 1.0) / 2.0 * (n - 1)
    i0 = jnp.clip(jnp.floor(g), 0, n - 2).astype(jnp.int32)
    fr = g - i0
    planes, lines = tables["planes"].astype(dtype), tables["lines"].astype(dtype)
    out = []
    for m, ((a, b), c) in enumerate(zip(PLANES, LINES)):
        ia, ib, ic = i0[:, a], i0[:, b], i0[:, c]
        fa, fb, fc = fr[:, a:a + 1], fr[:, b:b + 1], fr[:, c:c + 1]
        P = planes[m]
        p = (P[ia * n + ib] * ((1 - fa) * (1 - fb)).astype(dtype)
             + P[ia * n + ib + 1] * ((1 - fa) * fb).astype(dtype)
             + P[(ia + 1) * n + ib] * (fa * (1 - fb)).astype(dtype)
             + P[(ia + 1) * n + ib + 1] * (fa * fb).astype(dtype))
        V = lines[m]
        v = V[ic] * (1 - fc).astype(dtype) + V[ic + 1] * fc.astype(dtype)
        out.append(p * v)
    return jnp.stack(out, axis=1)


def _pe(p, L):
    sc = jnp.stack([p * (2.0 ** k) for k in range(L)], axis=-1).reshape(p.shape[0], -1)
    return jnp.concatenate([jnp.sin(sc), jnp.cos(sc)], axis=-1)


def sigma(cfg: dict, params, x, dtype=jnp.float32):
    """sigma in program units at points (M, 3): 0 outside the box."""
    t = cfg["tensorf"]
    feat = jnp.sum(_interp(params["density"], x, t["grid"], dtype), axis=(1, 2))
    lo, hi = t["aabb"]
    s = (hi - lo) * t["distance_scale"] * jnp.logaddexp(
        jnp.zeros((), dtype), feat + jnp.asarray(t["density_shift"], dtype))
    inside = jnp.all((x >= 0.0) & (x <= 1.0), axis=-1)
    return jnp.where(inside, s, 0).astype(dtype)


def rgb(cfg: dict, params, x, d, dtype=jnp.float32):
    """Color at points (M, 3) seen along unit directions (M, 3)."""
    t = cfg["tensorf"]
    prod = _interp(params["appearance"], x, t["grid"], dtype)
    f = jnp.dot(prod.reshape(x.shape[0], -1), params["basis"].astype(dtype),
                preferred_element_type=dtype)
    d = d.astype(dtype)
    h = jnp.concatenate([f, d, _pe(f, t["fea_pe"]), _pe(d, t["view_pe"])], axis=-1)
    ws, bs = params["mlp"]["weights"], params["mlp"]["biases"]
    for k, (w, b) in enumerate(zip(ws, bs)):
        h = jnp.dot(h, w.astype(dtype), preferred_element_type=dtype) + b.astype(dtype)
        if k < len(ws) - 1:
            h = jnp.maximum(h, 0)
    return jax.nn.sigmoid(h)


def _render_rays(static, cfg_items, params, o, d):
    """One block of rays -> rgb (R, 3) in float32."""
    ns, chunk, group, near, far, dtype = static
    cfg = {"tensorf": dict(cfg_items)}
    R = o.shape[0]
    dt = (far - near) / ns
    j = np.arange(ns)
    off = j % chunk
    n_anchor = -(-chunk // group)
    left = (j - off) + np.minimum(off // group, n_anchor - 1) * group
    right = (j - off) + np.minimum(off // group + 1, n_anchor - 1) * group
    frac = ((off % group) / group).astype(np.float32)
    anchors = np.unique(np.concatenate([left, right]))
    idx = {a: k for k, a in enumerate(anchors)}

    def at(ks):
        ts = near + (jnp.asarray(ks, jnp.float32) + 0.5) * dt
        return (o[:, None, :] + ts[None, :, None] * d[:, None, :]).reshape(-1, 3)

    sig = sigma(cfg, params, at(j), dtype).reshape(R, ns)
    col_a = rgb(cfg, params, at(anchors), jnp.repeat(d, anchors.size, axis=0), dtype)
    col_a = col_a.reshape(R, anchors.size, 3)
    li = np.asarray([idx[a] for a in left])
    ri = np.asarray([idx[a] for a in right])
    color = col_a[:, li] + (col_a[:, ri] - col_a[:, li]) * jnp.asarray(frac, dtype)[None, :, None]
    alpha = 1.0 - jnp.exp(-sig * jnp.asarray(dt, dtype))
    trans = jnp.cumprod(jnp.concatenate([jnp.ones((R, 1), dtype), 1.0 - alpha[:, :-1]], 1), 1)
    w = trans * alpha
    out = jnp.sum(w[..., None] * color, axis=1) + (1.0 - jnp.sum(w, axis=1))[:, None]
    return out.astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _render_blocks(static, cfg_items, block, params, o, d):
    n = o.shape[0] // block
    out = jax.lax.map(lambda od: _render_rays(static, cfg_items, params, od[0], od[1]),
                      (o.reshape(n, block, 3), d.reshape(n, block, 3)))
    return out.reshape(-1, 3)


def render(cfg: dict, params, origins, dirs, dtype=jnp.float32, block: int = 32):
    """Reference rgb (N, 3) of the rays, rendered in blocks of ``block``
    rays; float32 at ``highest`` precision, or the bfloat16 control."""
    a = cfg["asdr"]
    static = (a["ns_full"], a["chunk"], a["group"], cfg["scene"]["near"],
              cfg["scene"]["far"], jnp.dtype(dtype))
    cfg_items = tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                             for k, v in cfg["tensorf"].items()))
    N = origins.shape[0]
    pad = (-N) % block
    o = np.concatenate([origins, np.zeros((pad, 3), np.float32)])
    d = np.concatenate([dirs, np.tile(np.float32([[0, 0, 1]]), (pad, 1))])
    prec = "highest" if jnp.dtype(dtype) == jnp.float32 else "default"
    with jax.default_matmul_precision(prec):
        out = _render_blocks(static, cfg_items, block, params, jnp.asarray(o),
                             jnp.asarray(d))
    return np.asarray(out)[:N]
