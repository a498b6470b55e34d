"""Public jit'd wrappers around the Pallas kernels.

Each wrapper owns the padding/layout contract documented in the kernel
modules and exposes the *logical* shapes used by core/:

  hash_encode(points, tables, cfg)        -> (N, L*F)
  density_mlp(enc, params, cfg)           -> (sigma (N,), geo (N, G))
  color_mlp(geo, dirs, params, cfg)       -> rgb (N, 3)
  fused_field(points|enc, ...)            -> (sigma, rgb)
  volume_render(sigmas, anchors, deltas, group) -> (rgb, acc)

``field_fns(params, cfg)`` returns a kernels-backed FieldFns so the whole
ASDR pipeline (core/pipeline.py) can run on the kernel path; tests assert
it matches the pure-jnp model path.

The kernels interpret exactly on the CPU and run compiled everywhere else
(``repro.kernels.resolve_interpret``); no wrapper overrides that.
"""
from __future__ import annotations

import math
import threading
from collections import OrderedDict
from typing import Dict

import jax
import jax.numpy as jnp

from ..core import hashgrid
from ..core import mlp as mlp_lib
from ..core import rendering, scene
from ..core.fields import Bound, FieldFns
from . import fused_march as FMA
from . import fused_mlp as FM
from . import hash_encode as HE
from . import resolve_interpret
from . import volume_render as VR

# Why the fused march cannot run compiled on a TPU (tests/test_tpu_compile.py
# pins each refusal); ``fused_march_blocks`` raises this instead of
# interpreting.
FUSED_MARCH_TPU_REFUSAL = (
    "march_backend='fused' does not compile for TPU: Mosaic refuses the "
    "in-kernel VMEM gather table[idx] of hash_encode.encode_level ('Shape "
    "mismatch in input, indices and output'), and the streamed supply's "
    "per-level DMA tables_ref.at[level] ('Slice shape along dimension 2 "
    "must be aligned to tiling (128), but is 2': the (T, F=2) tables pad "
    "to 128 lanes). Use march_backend='reference'.")


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


# ---------------------------------------------------------------- fused march
# Per-core VMEM budget the resident/streamed auto-select lowers against.
# Re-exported from the kernel module so tests can shrink it (monkeypatch
# THIS name) and force the streamed path on scaled-down shapes.
FUSED_MARCH_VMEM_LIMIT = FMA.VMEM_LIMIT_BYTES


class FusedMarchResources:
    """Device-resident inputs for the fused streaming march kernel.

    A plain class (identity hash/eq, like the FieldFns closures) so a
    FieldFns carrying one stays hashable for serve/pool.py's jitted-march
    LRU.  Holds the grid meta/tables and the memoized packed weight
    stacks — building one is cheap after the first ``packed_weights``
    call for the params.
    """

    def __init__(self, params: Dict, cfg):
        self.meta = grid_meta(cfg.grid)
        self.tables = params["grid"].astype(jnp.float32)
        self.wd, self.wc = packed_weights(params["mlps"], cfg.net)
        self.net = cfg.net


def fused_march_vmem_bytes(acfg, res: FusedMarchResources,
                           streamed: bool = False) -> int:
    """Estimated VMEM bytes one fused-march grid step holds live.

    The accounting behind the resident/streamed auto-select: the table
    term is the whole (L, T, F) stack when resident but only the
    (2, T, F) double-buffer pair when streamed — at the full config
    (16 x 2^19 x 2 x 4 B = 64 MB stack vs an 8 MB pair against a 16 MB
    VMEM) that difference is exactly why residency cannot ship.  The
    weight stacks, ray/SH tiles, meta and output tile are counted too
    so the select stays honest for fat blocks or deep MLPs.
    """
    L, T, F = res.tables.shape
    B = acfg.block_size
    f32 = 4
    tables = (2 if streamed else L) * T * F * f32
    weights = (res.wd.shape[0] + res.wc.shape[0]) * FMA.P * FMA.P * f32
    rays = 2 * B * FMA.PPAD * f32          # origins + dirs tiles
    sh = B * FMA.P * f32                   # per-ray SH color input
    out = B * FMA.OUT_W * f32
    meta = (L + 1) * 8 * 4                 # grid meta rows + budget row
    return tables + weights + rays + sh + out + meta


def _select_streaming(acfg, res: FusedMarchResources) -> bool:
    """Resolve ``ASDRConfig.march_table_streaming`` to a concrete path.

    "auto" streams exactly when the resident footprint would blow the
    VMEM budget; "resident" is an explicit pin that refuses (rather
    than silently overflows) configs that do not fit.
    """
    mode = getattr(acfg, "march_table_streaming", "auto")
    if mode == "streamed":
        return True
    resident_bytes = fused_march_vmem_bytes(acfg, res, streamed=False)
    if mode == "resident":
        if resident_bytes > FUSED_MARCH_VMEM_LIMIT:
            raise ValueError(
                f"resident fused march needs {resident_bytes} B of VMEM "
                f"(> {FUSED_MARCH_VMEM_LIMIT} B limit); this config only "
                "runs with march_table_streaming='streamed' (or 'auto')")
        return False
    if mode != "auto":
        raise ValueError(f"march_table_streaming={mode!r} not in "
                         "('auto', 'resident', 'streamed')")
    return resident_bytes > FUSED_MARCH_VMEM_LIMIT


def fused_march_blocks(res: FusedMarchResources, acfg, o_b, d_b, budgets,
                       density_only: bool = False):
    """Run the single-kernel streaming march over a batch of blocks.

    o_b/d_b (N, B, 3), budgets (N,) int32 -> (rgb (N,B,3), acc (N,B),
    depth (N,B), chunks (N,), ray_chunks (N,B)) with
    core.pipeline._march_block semantics (same chunk count, budget
    masking, early termination; ray_chunks counts the chunks each ray
    was still live for).  SH features are computed once per RAY here
    (the reference path recomputes them per anchor-sample every chunk)
    and placed at the color-input lanes.  Table supply (VMEM-resident
    stack vs double-buffered DMA streaming) resolves per config via
    ``_select_streaming`` — the two are bit-identical where both run.

    Runs only where Pallas interprets (the CPU): on a TPU it raises
    ``ValueError(FUSED_MARCH_TPU_REFUSAL)`` rather than interpret there.
    """
    if not resolve_interpret():
        raise ValueError(FUSED_MARCH_TPU_REFUSAL)
    N, B, _ = o_b.shape
    o8 = _pad_cols(o_b.astype(jnp.float32).reshape(N * B, 3), FMA.PPAD)
    d_flat = d_b.astype(jnp.float32).reshape(N * B, 3)
    d8 = _pad_cols(d_flat, FMA.PPAD)
    sh = (jnp.zeros((N * B, FMA.P), jnp.float32)
          if density_only else _sh_padded(d_flat, res.net))
    out = FMA.fused_march_call(
        o8, d8, sh, budgets.astype(jnp.int32), res.meta, res.tables,
        res.wd, res.wc,
        block_size=B, geo_dim=res.net.geo_feature_dim, group=acfg.group,
        chunk=acfg.chunk, near=scene.NEAR, far=scene.FAR,
        log_eps_t=math.log(rendering.EARLY_TERM_TRANSMITTANCE),
        early_term=acfg.early_termination,
        white_background=acfg.white_background,
        with_color=not density_only,
        stream_tables=_select_streaming(acfg, res),
        per_ray_exit=getattr(acfg, "per_ray_early_exit", False))
    out = out.reshape(N, B, FMA.OUT_W)
    acc = out[:, :, 0]
    rgb = out[:, :, 1:4]
    depth = out[:, :, 4]
    chunks = out[:, 0, 5].astype(jnp.int32)
    ray_chunks = out[:, :, 6].astype(jnp.int32)
    return rgb, acc, depth, chunks, ray_chunks


# ------------------------------------------------------------------- FieldFns
def field_fns(params: Dict, cfg) -> FieldFns:
    """Kernel-backed FieldFns (cfg is core.model.NGPConfig).

    The hash encode is the XLA gather (``core.hashgrid.encode``) on every
    platform, feeding the fused-MLP kernels: the Pallas encode kernel does
    not compile for TPU (see hash_encode.py).  Carries
    FusedMarchResources so ``ASDRConfig.march_backend="fused"`` routes
    Phase II through the single-kernel streaming march where Pallas
    interprets, and ``Bound`` so jitted callers pass params as arguments.
    """

    def density(points):
        enc = hashgrid.encode(points, params["grid"], cfg.grid)
        sigma, geo = density_mlp(enc, params["mlps"], cfg.net)
        inside = jnp.all((points >= 0.0) & (points <= 1.0), axis=-1)
        return jnp.where(inside, sigma, 0.0), geo

    def color(geo, dirs):
        return color_mlp(geo, dirs, params["mlps"], cfg.net)

    return FieldFns(density=density, color=color,
                    fused=FusedMarchResources(params, cfg),
                    bound=Bound(field_fns, cfg, params))
