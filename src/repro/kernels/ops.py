"""Public jit'd wrappers around the Pallas kernels.

Each wrapper owns the padding/layout contract documented in the kernel
modules and exposes the *logical* shapes used by core/:

  density_mlp(enc, params, cfg)           -> (sigma (N,), geo (N, G))
  color_mlp(geo, dirs, params, cfg)       -> rgb (N, 3)
  fused_field(enc, dirs, params, cfg)     -> (sigma, rgb, geo)
  hash_encode(points, tables, cfg)        -> (N, L*F)
  volume_render(sigmas, anchors, deltas, group) -> (rgb, acc)

``field_fns(params, cfg)`` returns a kernels-backed FieldFns — the XLA
gather encode feeding ``density_mlp``/``color_mlp`` — so the ASDR
pipeline (core/pipeline.py) marches on the MLP kernels; tests assert it
matches the pure-jnp model path.  ``hash_encode`` and ``volume_render``
are on no served path: Mosaic refuses the encode kernel's gather, and
the march composites in XLA (kernels/README.md).

The kernels interpret exactly on the CPU and run compiled everywhere else
(``repro.kernels.resolve_interpret``); no wrapper overrides that.
"""
from __future__ import annotations

import threading
from collections import OrderedDict
from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp

from ..core import hashgrid
from ..core import mlp as mlp_lib
from ..core.fields import Bound, FieldFns
from . import fused_mlp as FM
from . import hash_encode as HE
from . import volume_render as VR


def _pad_rows(x, mult):
    pad = (-x.shape[0]) % mult
    if pad:
        x = jnp.concatenate(
            [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)], axis=0
        )
    return x, pad


def _pad_cols(x, width):
    if x.shape[-1] < width:
        x = jnp.concatenate(
            [x, jnp.zeros(x.shape[:-1] + (width - x.shape[-1],), x.dtype)],
            axis=-1,
        )
    return x


# ---------------------------------------------------------------- hash encode
def grid_meta(cfg) -> jnp.ndarray:
    """(L, 8) int32 metadata rows: [res, is_dense, table_rows, 0, ...]."""
    rows = []
    for l in range(cfg.n_levels):
        res = cfg.level_resolution(l)
        rows.append([res, int(cfg.level_is_dense(l)), cfg.table_size,
                     0, 0, 0, 0, 0])
    return jnp.asarray(rows, jnp.int32)


_hash_encode_padded = jax.jit(HE.hash_encode_call)


def hash_encode(points, tables, cfg):
    """points (N,3) in [0,1] -> encoding (N, L*F), matching hashgrid.encode."""
    n = points.shape[0]
    pts = _pad_cols(points.astype(jnp.float32), HE.PPAD)
    pts, _ = _pad_rows(pts, HE.TILE)
    feats = _hash_encode_padded(pts, grid_meta(cfg), tables)
    feats = feats[:, :n]                                  # strip row pad
    L, _, F = feats.shape
    return jnp.transpose(feats, (1, 0, 2)).reshape(n, L * F)


# ------------------------------------------------------------------ fused MLP
def pack_density_weights(params: Dict, cfg: mlp_lib.MLPConfig) -> jnp.ndarray:
    """Pad density weights to (nd, P, P); permute the last layer's output
    columns to [geo(0..G-1), sigma(G)] so no lane shift is needed in-kernel."""
    G = cfg.geo_feature_dim
    ws = []
    for i, w in enumerate(params["density"]):
        w = w.astype(jnp.float32)
        if i == len(params["density"]) - 1:
            # original cols: [sigma, geo...] -> new: [geo..., sigma]
            w = jnp.concatenate([w[:, 1 : 1 + G], w[:, :1]], axis=1)
        wp = jnp.zeros((FM.P, FM.P), jnp.float32)
        wp = wp.at[: w.shape[0], : w.shape[1]].set(w)
        ws.append(wp)
    return jnp.stack(ws)


def pack_color_weights(params: Dict) -> jnp.ndarray:
    """Pad color weights to (nc, P, P) — input layout [geo, sh] is already
    contiguous so only zero-padding is needed."""
    ws = []
    for w in params["color"]:
        w = w.astype(jnp.float32)
        wp = jnp.zeros((FM.P, FM.P), jnp.float32)
        wp = wp.at[: w.shape[0], : w.shape[1]].set(w)
        ws.append(wp)
    return jnp.stack(ws)


# Padded/permuted weight stacks are pure functions of the weight arrays,
# yet every wrapper used to rebuild them per call — repeated engine
# construction and multi-scene hot-swap re-laid-out identical weights on
# each frame.  Memoized here keyed on weight-array identity (an LRU like
# serve/pool.py's jitted-march cache); the cached entry keeps references
# to the source arrays so their ids cannot be recycled while it lives.
_PACK_CACHE: "OrderedDict[tuple, tuple]" = OrderedDict()
_PACK_LOCK = threading.Lock()
_PACK_MAX = 16
_PACK_STATS = {"hits": 0, "misses": 0}


def packed_weights(params: Dict, cfg: mlp_lib.MLPConfig):
    """Memoized ``(wd, wc)`` padded weight stacks for an mlps param dict.

    Traced weights (params passed into a jit as arguments) are packed
    inside the trace and never cached: a tracer's id names nothing after
    its trace ends."""
    if isinstance(params["density"][0], jax.core.Tracer):
        return pack_density_weights(params, cfg), pack_color_weights(params)
    key = (tuple(id(w) for w in params["density"]),
           tuple(id(w) for w in params["color"]), cfg.geo_feature_dim)
    with _PACK_LOCK:
        hit = _PACK_CACHE.get(key)
        if hit is not None:
            _PACK_CACHE.move_to_end(key)
            _PACK_STATS["hits"] += 1
            return hit[0], hit[1]
        _PACK_STATS["misses"] += 1
    # concrete weights pack eagerly even when the first call comes from
    # inside a trace: a staged pack would cache that trace's tracers
    with jax.ensure_compile_time_eval():
        wd = pack_density_weights(params, cfg)
        wc = pack_color_weights(params)
    with _PACK_LOCK:
        _PACK_CACHE[key] = (wd, wc,
                            list(params["density"]), list(params["color"]))
        _PACK_CACHE.move_to_end(key)
        while len(_PACK_CACHE) > _PACK_MAX:
            _PACK_CACHE.popitem(last=False)
    return wd, wc


def pack_cache_stats() -> Dict[str, int]:
    with _PACK_LOCK:
        return dict(_PACK_STATS, size=len(_PACK_CACHE))


def _sh_padded(dirs, cfg: mlp_lib.MLPConfig):
    """SH(dirs) placed at cols [G, G+sh_dim) of a (N, P) buffer."""
    sh = mlp_lib.sh_encode(dirs, cfg.sh_degree).astype(jnp.float32)
    n = sh.shape[0]
    buf = jnp.zeros((n, FM.P), jnp.float32)
    return buf.at[:, cfg.geo_feature_dim : cfg.geo_feature_dim + sh.shape[1]].set(sh)


_fused_field_padded = jax.jit(FM.fused_field_call,
                              static_argnames=("geo_dim",))


def fused_field(enc, dirs, params: Dict, cfg: mlp_lib.MLPConfig):
    """(enc (N,D), dirs (N,3)) -> (sigma (N,), rgb (N,3), geo (N,G))."""
    n = enc.shape[0]
    G = cfg.geo_feature_dim
    encp = _pad_cols(enc.astype(jnp.float32), FM.P)
    encp, _ = _pad_rows(encp, FM.TILE)
    shp, _ = _pad_rows(_sh_padded(dirs, cfg), FM.TILE)
    wd, wc = packed_weights(params, cfg)
    out = _fused_field_padded(encp, shp, wd, wc, geo_dim=G)[:n]
    return out[:, 0], out[:, 1:4], out[:, 4 : 4 + G]


_density_padded = jax.jit(FM.density_call, static_argnames=("geo_dim",))


@jax.named_scope("density_mlp")
def density_mlp(enc, params: Dict, cfg: mlp_lib.MLPConfig):
    """enc (N, D) -> (sigma (N,), geo (N, G))."""
    n = enc.shape[0]
    G = cfg.geo_feature_dim
    encp = _pad_cols(enc.astype(jnp.float32), FM.P)
    encp, _ = _pad_rows(encp, FM.TILE)
    wd, _wc = packed_weights(params, cfg)
    out = _density_padded(encp, wd, geo_dim=G)[:n]
    return out[:, 0], out[:, 1 : 1 + G]


_color_padded = jax.jit(FM.color_call)


@jax.named_scope("color_mlp")
def color_mlp(geo, dirs, params: Dict, cfg: mlp_lib.MLPConfig):
    """(geo (N,G), dirs (N,3)) -> rgb (N,3)."""
    n = geo.shape[0]
    G = cfg.geo_feature_dim
    cin = _sh_padded(dirs, cfg).at[:, :G].set(geo.astype(jnp.float32))
    cin, _ = _pad_rows(cin, FM.TILE)
    _wd, wc = packed_weights(params, cfg)
    out = _color_padded(cin, wc)[:n]
    return out[:, :3]


# -------------------------------------------------------------- volume render
def volume_render(sigmas, anchor_colors, deltas, group: int,
                  valid=None, white_background: bool = True):
    """Decoupled compositing. sigmas/deltas (R,S), anchors (R,A,3) with
    A = ceil(S/group) -> (rgb (R,3), acc (R,))."""
    R, S = sigmas.shape
    A = anchor_colors.shape[1]
    s_pad = -(-S // 128) * 128
    a_pad = -(-A // 128) * 128

    sig = sigmas.astype(jnp.float32)
    if valid is not None:
        sig = jnp.where(valid, sig, 0.0)
    sig = _pad_cols(sig, s_pad)
    dlt = _pad_cols(deltas.astype(jnp.float32), s_pad)
    anch = jnp.transpose(anchor_colors.astype(jnp.float32), (0, 2, 1))  # (R,3,A)
    anch = _pad_cols(anch, a_pad).reshape(R, 3 * a_pad)

    sig, _ = _pad_rows(sig, VR.RTILE)
    dlt, _ = _pad_rows(dlt, VR.RTILE)
    anch, _ = _pad_rows(anch, VR.RTILE)
    E = VR.expansion_matrix(S, s_pad, A, a_pad, group)

    out = VR.volume_render_call(sig, dlt, anch, E, a_pad)[:R]
    acc = out[:, 0]
    rgb = out[:, 1:4]
    if white_background:
        rgb = rgb + (1.0 - acc[:, None])
    return rgb, acc


# ------------------------------------------------------------------- FieldFns
@partial(jax.jit, static_argnums=(1, 2))
def corner_tables(tables, grids, memory_bytes):
    """``hashgrid.field_corner_tables`` and ``field_xrow_tables`` as one
    executable of its own name."""
    return (hashgrid.field_corner_tables(tables, grids, memory_bytes),
            hashgrid.field_xrow_tables(tables, grids, memory_bytes))


def field_corner_tables(tables, grids):
    """The corner rows and x-neighbour rows of a field's grids
    (``tables[i]`` the stacked tables of ``grids[i]``) under one budget, a
    quarter of the memory of the device holding ``tables[0]``
    (``hashgrid.field_corner_levels``, ``field_xrow_levels``): a pair of
    tuples, each with one entry a grid.  Waited for here, so a field is
    ready when built: the derivation never runs on the device inside the
    caller's first march."""
    return jax.block_until_ready(corner_tables(
        tuple(tables), tuple(grids), device_memory_bytes(tables[0])))


def device_memory_bytes(x) -> int | None:
    """The memory of the devices that hold ``x`` (the least
    ``bytes_limit``), or None where the backend reports none (the CPU)."""
    limits = [(d.memory_stats() or {}).get("bytes_limit") for d in x.devices()]
    return None if None in limits else min(limits)


def field_fns(params: Dict, cfg) -> FieldFns:
    """Kernel-backed FieldFns (cfg is core.model.NGPConfig).

    The hash encode is the XLA gather (``core.hashgrid.encode``) on every
    platform, feeding the fused-MLP kernels: the Pallas encode kernel does
    not compile for TPU (see hash_encode.py).  The coarsest levels gather
    one corner row a point: as many as ``hashgrid.corner_levels`` fits in
    a quarter of the memory of the device holding ``params["grid"]``
    (levels 0-9, 2.6 GB, on a 16 GB chip at the paper's config), the
    budget of the field's one grid (a field of several grids shares one
    budget over them: ``field_corner_tables``).  The hashed levels past
    them gather 4 x-neighbour rows a point where those fit in the same
    budget (levels 10-15, 289 MB, there).  Its corner and x-neighbour
    tables are derived here from ``params["grid"]``, once per call, and
    carried in the ``Bound`` params beside it, so jitted callers
    (``fields.unbind``) pass them as arguments and ``rebind`` inside a
    trace only reads them.
    """
    (corners,), (xrows,) = field_corner_tables((params["grid"],), (cfg.grid,))
    return _bound_field_fns(dict(params, corners=corners, xrows=xrows), cfg)


def _bound_field_fns(params: Dict, cfg) -> FieldFns:
    """``field_fns`` over params that already carry their corner and
    x-neighbour tables."""

    def density(points):
        enc = hashgrid.encode(points, params["grid"], cfg.grid,
                              params["corners"], params["xrows"])
        sigma, geo = density_mlp(enc, params["mlps"], cfg.net)
        inside = jnp.all((points >= 0.0) & (points <= 1.0), axis=-1)
        return jnp.where(inside, sigma, 0.0), geo

    def color(geo, dirs):
        return color_mlp(geo, dirs, params["mlps"], cfg.net)

    return FieldFns(density=density, color=color,
                    bound=Bound(_bound_field_fns, cfg, params))

