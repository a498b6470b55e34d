"""Volume-rendering Pallas kernel with fused decoupled-color interpolation —
the TPU analogue of the paper's Volume Rendering Engine (§5.4: approximation
unit + RGB unit in one pass).

CIM insight ported: the paper's approximation unit interpolates non-anchor
colors with dedicated multiplier/adder trees.  On TPU we express the
group-anchor linear interpolation (§4.3) as a *matmul against a constant
expansion matrix* E (A_pad x S_pad): colors_full = anchors @ E.  That turns
the irregular per-sample lerp into one MXU pass and fuses it with Eq. (1)
compositing, so anchor colors never round-trip to HBM.

Numerics: 1 - alpha_i = exp(-sigma_i * delta_i) exactly, so transmittance
T_i = exp(-cumsum_excl(sigma*delta)) — no log/clip needed in-kernel.

Layouts (prepared by ops.py):
  sigmas  (R_pad, S_pad) f32   — padded samples carry sigma = 0 (w = 0)
  deltas  (R_pad, S_pad) f32
  anchors (R_pad, 3*A_pad) f32 — channels packed [r | g | b] along lanes
  E       (A_pad, S_pad) f32   — constant lerp-expansion matrix
  out     (R_pad, P) f32       — col 0 = acc, cols 1..3 = rgb
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from . import resolve_interpret

P = 128        # output lane width
RTILE = 128    # rays per block program


def expansion_matrix(S: int, S_pad: int, A: int, A_pad: int, group: int):
    """E[a, j] — lerp weights mapping anchor a to sample j (numpy, const).

    Sample j in group gi = j // group with t = (j % group) / group gets
    (1-t) * anchor[gi] + t * anchor[min(gi+1, A-1)]  (paper §4.3).
    """
    E = np.zeros((A_pad, S_pad), np.float32)
    for j in range(S):
        gi = j // group
        t = (j % group) / group
        E[min(gi, A - 1), j] += 1.0 - t
        E[min(gi + 1, A - 1), j] += t
    return jnp.asarray(E)


def _vr_kernel(sig_ref, del_ref, col_ref, e_ref, out_ref, *, a_pad):
    sd = sig_ref[...] * del_ref[...]                      # (T, S_pad)
    excl = jax.lax.cumsum(sd, axis=1) - sd                # exclusive prefix
    trans = jnp.exp(-excl)
    w = trans * (1.0 - jnp.exp(-sd))                      # weights (T, S_pad)
    acc = jnp.sum(w, axis=1, keepdims=True)               # (T, 1)
    e = e_ref[...]
    chans = []
    for c in range(3):
        anch = col_ref[:, c * a_pad : (c + 1) * a_pad]    # (T, A_pad)
        full = jnp.dot(anch, e, preferred_element_type=jnp.float32)
        chans.append(jnp.sum(w * full, axis=1, keepdims=True))
    packed = jnp.concatenate(
        [acc] + chans + [jnp.zeros((w.shape[0], P - 4), jnp.float32)], axis=1
    )
    out_ref[...] = packed


def volume_render_call(sigmas, deltas, anchors, E, a_pad: int):
    """sigmas/deltas (R, S_pad), anchors (R, 3*A_pad), E (A_pad, S_pad)
    -> packed (R, P) with col0 = acc, cols 1..3 = rgb."""
    R, S_pad = sigmas.shape
    assert R % RTILE == 0, "ops.py pads rays to an RTILE multiple"
    kern = functools.partial(_vr_kernel, a_pad=a_pad)
    return pl.pallas_call(
        kern,
        grid=(R // RTILE,),
        in_specs=[
            pl.BlockSpec((RTILE, S_pad), lambda i: (i, 0)),
            pl.BlockSpec((RTILE, S_pad), lambda i: (i, 0)),
            pl.BlockSpec((RTILE, 3 * a_pad), lambda i: (i, 0)),
            pl.BlockSpec((a_pad, S_pad), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((RTILE, P), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, P), jnp.float32),
        interpret=resolve_interpret(),
    )(sigmas, deltas, anchors, E)
