"""Fused density→color MLP Pallas kernel — the TPU analogue of the paper's
CIM MLP engine (§5.3).

CIM insight ported: ReRAM crossbars hold the MLP weights *in place* so no
weight traffic occurs per sample.  On TPU we get the same effect by giving
every weight matrix a BlockSpec whose index_map is constant across the
sample grid: the compiler keeps the (padded) weights resident in VMEM for
the whole point stream while activation tiles flow through, and each
128x128 padded matmul maps 1:1 onto one MXU pass.

Data layout (all feature dims padded to P=128 by ops.py):
  * density input  : encoding tile (TILE, P)
  * density output : cols 0..G-1 = geo feature, col G = sigma logit
                     (ops.py permutes the last weight's columns so the
                     color input needs no lane shift)
  * sh input       : direction encoding pre-placed at cols G..G+S-1
  * color input    : geo_mask(dout) + sh  — a single masked add
  * kernel output  : (TILE, P) with col 0 = sigma, cols 1..3 = rgb,
                     cols 4..3+G = geo (packed result block)

Weight count is static (unrolled); VMEM footprint = (nd+nc) * 64KB of
weights + 3 activation tiles — far under the ~16MB VMEM budget, leaving
room for the encode kernel's table block to co-reside when fused further.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import resolve_interpret

P = 128          # padded feature width (MXU lane width)
TILE = 256       # points per block program


def _relu(x):
    return jnp.maximum(x, 0.0)


def _trunc_exp(x):
    return jnp.exp(jnp.clip(x, -15.0, 15.0))


def _density_chain(x, wd_ref, nd):
    for i in range(nd):
        x = jnp.dot(x, wd_ref[i], preferred_element_type=jnp.float32)
        if i < nd - 1:
            x = _relu(x)
    return x  # (TILE, P): cols 0..G-1 geo, col G sigma logit


def _color_chain(x, wc_ref, nc):
    for i in range(nc):
        x = jnp.dot(x, wc_ref[i], preferred_element_type=jnp.float32)
        if i < nc - 1:
            x = _relu(x)
    return jax.nn.sigmoid(x)


def _fused_kernel(enc_ref, sh_ref, wd_ref, wc_ref, out_ref, *, nd, nc, geo_dim):
    dout = _density_chain(enc_ref[...].astype(jnp.float32), wd_ref, nd)
    lane = jax.lax.broadcasted_iota(jnp.int32, dout.shape, 1)
    geo = jnp.where(lane < geo_dim, dout, 0.0)
    cin = geo + sh_ref[...].astype(jnp.float32)
    rgb = _color_chain(cin, wc_ref, nc)
    sigma = _trunc_exp(dout[:, geo_dim])
    packed = jnp.concatenate(
        [
            sigma[:, None],
            rgb[:, :3],
            geo[:, :geo_dim],
            jnp.zeros((dout.shape[0], P - 4 - geo_dim), jnp.float32),
        ],
        axis=1,
    )
    out_ref[...] = packed


def _density_kernel(enc_ref, wd_ref, out_ref, *, nd, geo_dim):
    dout = _density_chain(enc_ref[...].astype(jnp.float32), wd_ref, nd)
    lane = jax.lax.broadcasted_iota(jnp.int32, dout.shape, 1)
    geo = jnp.where(lane < geo_dim, dout, 0.0)
    sigma = _trunc_exp(dout[:, geo_dim])
    packed = jnp.concatenate(
        [
            sigma[:, None],
            geo[:, :geo_dim],
            jnp.zeros((dout.shape[0], P - 1 - geo_dim), jnp.float32),
        ],
        axis=1,
    )
    out_ref[...] = packed


def _color_kernel(cin_ref, wc_ref, out_ref, *, nc):
    rgb = _color_chain(cin_ref[...].astype(jnp.float32), wc_ref, nc)
    out_ref[...] = rgb


def _weights_spec(n):
    return pl.BlockSpec((n, P, P), lambda i: (0, 0, 0))


def _tile_spec():
    return pl.BlockSpec((TILE, P), lambda i: (i, 0))


def fused_field_call(enc, sh, wd, wc, geo_dim: int):
    """enc/sh (N, P) padded; wd (nd,P,P); wc (nc,P,P) -> packed (N, P)."""
    n = enc.shape[0]
    assert n % TILE == 0, "ops.py pads N to a TILE multiple"
    kern = functools.partial(
        _fused_kernel, nd=wd.shape[0], nc=wc.shape[0], geo_dim=geo_dim
    )
    return pl.pallas_call(
        kern,
        grid=(n // TILE,),
        in_specs=[_tile_spec(), _tile_spec(),
                  _weights_spec(wd.shape[0]), _weights_spec(wc.shape[0])],
        out_specs=_tile_spec(),
        out_shape=jax.ShapeDtypeStruct((n, P), jnp.float32),
        interpret=resolve_interpret(),
    )(enc, sh, wd, wc)


def density_call(enc, wd, geo_dim: int):
    n = enc.shape[0]
    assert n % TILE == 0
    kern = functools.partial(_density_kernel, nd=wd.shape[0], geo_dim=geo_dim)
    return pl.pallas_call(
        kern,
        grid=(n // TILE,),
        in_specs=[_tile_spec(), _weights_spec(wd.shape[0])],
        out_specs=_tile_spec(),
        out_shape=jax.ShapeDtypeStruct((n, P), jnp.float32),
        interpret=resolve_interpret(),
    )(enc, wd)


def color_call(cin, wc):
    n = cin.shape[0]
    assert n % TILE == 0
    kern = functools.partial(_color_kernel, nc=wc.shape[0])
    return pl.pallas_call(
        kern,
        grid=(n // TILE,),
        in_specs=[_tile_spec(), _weights_spec(wc.shape[0])],
        out_specs=_tile_spec(),
        out_shape=jax.ShapeDtypeStruct((n, P), jnp.float32),
        interpret=resolve_interpret(),
    )(cin, wc)
