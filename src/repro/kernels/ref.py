"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Each ``ref_*`` mirrors the kernel's *public wrapper* semantics (ops.py), so
tests can sweep shapes/dtypes and ``assert_allclose(kernel, ref)``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core import hashgrid as hg
from ..core import decouple as dec
from ..core import mlp as mlp_lib
from ..core import rendering


# ---------------------------------------------------------------- hash encode
def ref_hash_encode(points, tables, resolutions, dense_flags):
    """points (N,3) in [0,1], tables (L,T,F) -> features (L, N, F).

    resolutions/dense_flags: python sequences of length L (static).
    """
    outs = []
    for l, (res, dense) in enumerate(zip(resolutions, dense_flags)):
        outs.append(hg.encode_level(points, tables[l], int(res), bool(dense)))
    return jnp.stack(outs, axis=0)


# ------------------------------------------------------------------ fused MLP
def relu(x):
    return jnp.maximum(x, 0.0)


def ref_density_mlp(enc, wd):
    """enc (N, D) x list of density weights -> (sigma (N,), geo (N, G))."""
    x = enc
    for i, w in enumerate(wd):
        x = x @ w
        if i < len(wd) - 1:
            x = relu(x)
    sigma = mlp_lib.trunc_exp(x[..., 0])
    return sigma, x[..., 1:]


def ref_color_mlp(geo, sh, wc):
    """(geo (N,G), sh (N,S)) x color weights -> rgb (N,3) in [0,1]."""
    x = jnp.concatenate([geo, sh], axis=-1)
    for i, w in enumerate(wc):
        x = x @ w
        if i < len(wc) - 1:
            x = relu(x)
    return jax.nn.sigmoid(x)


def ref_fused_field(enc, sh, wd, wc):
    """Full density->color chain. Returns (sigma (N,), rgb (N,3), geo)."""
    sigma, geo = ref_density_mlp(enc, wd)
    rgb = ref_color_mlp(geo, sh, wc)
    return sigma, rgb, geo


# --------------------------------------------------------------------- march
def ref_march(fns, acfg, o_b, d_b, budgets, density_only=False):
    """The chunked march (core/pipeline._march_block) under ``lax.map``,
    over whatever FieldFns the caller passes — with a pure-jnp field it is
    the oracle a kernel-backed field's march must match, chunk counters
    exactly."""
    from ..core import pipeline

    march = lambda a: pipeline._march_block(  # noqa: E731
        fns, acfg, *a, density_only=density_only)
    return jax.lax.map(march, (o_b, d_b, budgets))


# -------------------------------------------------------------- volume render
def ref_volume_render(sigmas, anchor_colors, deltas, group: int,
                      valid=None, white_background: bool = True):
    """Decoupled volume rendering oracle.

    sigmas (R, S); anchor_colors (R, A, 3) with A = ceil(S/group);
    deltas (R, S).  Expands anchors by lerp (paper §4.3) then composites
    Eq. (1).  Returns (rgb (R,3), acc (R,)).
    """
    S = sigmas.shape[-1]
    colors = dec.interpolate_group_colors(anchor_colors, group, S)
    rgb, acc, _ = rendering.composite(
        sigmas, colors, deltas, valid=valid, white_background=white_background
    )
    return rgb, acc
