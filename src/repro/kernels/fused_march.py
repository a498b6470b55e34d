"""Fused streaming-march Pallas kernel — Phase II as ONE kernel launch.

The paper's CIM array wins (§5.3) come from keeping weights and sample
streams in place: a sample is generated, encoded, pushed through the
MLPs and composited without ever leaving the array.  The chunked
reference march (core/pipeline._march_block) instead calls the encode /
density / color kernels as separate jitted ops per chunk, so every
per-sample encoding and geo feature round-trips through HBM between
launches.  This kernel is the TPU port of the paper's dataflow: per
block program it

  1. generates the chunk's sample positions from the block's rays
     (ray setup is in-register; only origins/dirs/budget are read),
  2. hash-encodes them against the table stack — either all L levels
     co-resident in VMEM for the whole march (small configs), or, at
     production table sizes, STREAMED level-by-level through a
     double-buffered ping/pong pair of level-sized VMEM buffers with
     async DMA: level l+1's copy is launched while level l encodes, so
     the table working set in VMEM is two levels, never the stack,
  3. runs the density chain on every sample and the color chain on
     every ``group``-th anchor only — §4.3's decoupling moves INSIDE
     the kernel; anchor selection and the non-anchor lerp are lowered
     to the lane-shuffle idiom (iota-built one-hot matmuls on the MXU)
     instead of a static C-way unroll,
  4. composites transmittance/rgb/acc/depth and carries the running
     log-transmittance across chunks in a ``while_loop`` with the exact
     early-termination contract of the reference march (same chunk
     count, same budget masking), emitting per-RAY chunks_done so the
     serve layer can account (and, with
     ``ASDRConfig.per_ray_early_exit``, actually stop) the sample work
     of rays that saturate before their block does.

Per-sample features (encodings, geo, anchor colors) never exist outside
the kernel.  The only HBM traffic per block is rays in (B x 8 x 2),
per-ray SH in (B x 128, computed ONCE per ray instead of once per
anchor-sample), the packed (B x 8) result out — plus, under streaming,
the level DMAs (2 x T x F in flight, overlapped with encode compute).

Data layout (prepared by ops.fused_march_blocks):
  o / d    (N*B, PPAD) f32  — rays padded to 8 lanes, one block per
                              grid step
  sh       (N*B, P)    f32  — SH(dir) pre-placed at cols [G, G+S)
  budgets  (N,)        i32  — per-block sample budget (SMEM, read by
                              program id)
  meta     (L, 8)      i32  — hash_encode.grid_meta rows (SMEM)
  tables   (L, T, F)   f32  — resident: VMEM for all grid steps;
                              streamed: stays in HBM (ANY memory
                              space), DMA'd per level into a
                              (2, T, F) VMEM scratch ping/pong pair
  wd / wc  (n, P, P)   f32  — fused_mlp packed weights (sigma col
                              permuted to lane G)
  out      (N*B, 8)    f32  — [acc, r, g, b, depth, block_chunks,
                              ray_chunks, 0]

``with_color=False`` is the density-only march (serve/README.md
"density-only march rule"): the color chain and lerp are skipped
entirely and rgb reads 0 — acc/depth/chunks keep full parity with the
reference density-only march.

VMEM accounting (ops.fused_march_vmem_bytes is the ledger) counts the
tables at 4 B per element: 64 MB for the full config's resident stack
(16 levels x 2^19 x 2), 8 MB for the streamed 2 x T x F pair.  That is
an undercount on a TPU, where the minor dimension F=2 is laid out in
128-lane tiles: each (T, 2) block occupies 64x its bytes, so even one
streamed level (T x 128 x 4 B = 256 MB) cannot sit in VMEM.
ops.fused_march_blocks auto-selects resident whenever the stack fits
the ledger (bit-identical to streamed — same gather math against the
same bytes, gated by tests), streamed otherwise.

TPU status: neither supply compiles (tests/test_tpu_compile.py pins
it).  Mosaic refuses (1) the VMEM vector gather ``table[idx]`` of the
shared ``HE.encode_level`` ("Shape mismatch in input, indices and
output") and (2) the streamed per-level DMA ``tables_ref.at[level]``
("Slice shape along dimension 2 must be aligned to tiling (128), but is
2").  Both paths run in interpret mode on the CPU only, and
``ops.fused_march_blocks`` raises on a TPU instead of interpreting.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import hash_encode as HE
from . import resolve_interpret

P = 128      # padded feature width (MXU lane width) — matches fused_mlp
PPAD = 8     # padded ray row [x, y, z, 0...]    — matches hash_encode
OUT_W = 8    # packed output lanes [acc, r, g, b, depth, chunks, ray_chunks, 0]

# per-core VMEM the auto-select lowers against (16 MB on current TPUs);
# tests shrink it via ops.py to force the streamed path on small shapes
VMEM_LIMIT_BYTES = 16 * 2 ** 20


def _relu(x):
    return jnp.maximum(x, 0.0)


def _trunc_exp(x):
    return jnp.exp(jnp.clip(x, -15.0, 15.0))


def _encode_points(flat, meta, read_level, n_levels):
    """In-register hash encode: (M, 3) points -> (M, L*F) features.

    Same per-level math as hash_encode (shared ``encode_level``), with
    the table source abstracted: ``read_level(l)`` returns level ``l``'s
    (T, F) block — a slice of the resident stack, or the streamed DMA
    ping/pong slot that was just waited on.
    """
    feats_per_level = []
    for level in range(n_levels):
        feats_per_level.append(HE.encode_level(
            flat, meta[level, 0], meta[level, 1], meta[level, 2],
            read_level(level)))
    return jnp.concatenate(feats_per_level, axis=-1)       # (M, L*F)


def _chains(x, w, n, final=None):
    for i in range(n):
        x = jnp.dot(x, w[i], preferred_element_type=jnp.float32)
        if i < n - 1:
            x = _relu(x)
    return final(x) if final is not None else x


def _anchor_select(C, A, group):
    """(A, C) one-hot anchor-pick matrix, built from 2-D iotas (the
    lane-shuffle idiom: a gather expressed as an MXU matmul, no
    C-way python unroll and no captured index-array constants)."""
    a_io = jax.lax.broadcasted_iota(jnp.int32, (A, C), 0)
    c_io = jax.lax.broadcasted_iota(jnp.int32, (A, C), 1)
    return (c_io == a_io * group).astype(jnp.float32)


def _lerp_expand(C, A, group):
    """(C, A) lerp-expansion matrix: row j carries weight (1 - t_j) on
    its left anchor and t_j on its right (clamped at the tail), so
    expanding anchors to all samples is one matmul —
    decouple.interpolate_group_colors as a lane shuffle."""
    j_io = jax.lax.broadcasted_iota(jnp.int32, (C, A), 0)
    a_io = jax.lax.broadcasted_iota(jnp.int32, (C, A), 1)
    gi = jnp.minimum(j_io // group, A - 1)
    ri = jnp.minimum(j_io // group + 1, A - 1)
    t = (j_io % group).astype(jnp.float32) / group
    return (jnp.where(a_io == gi, 1.0 - t, 0.0)
            + jnp.where(a_io == ri, t, 0.0))


def _march_impl(o_ref, d_ref, sh_ref, bud_ref, meta_ref, wd_ref, wc_ref,
                out_ref, encode, *, nd, nc, geo_dim, group, chunk,
                n_levels, near, far, log_eps_t, early_term, per_ray_exit,
                white_background, with_color):
    """The march body, table access abstracted behind ``encode(flat)``.

    Shared verbatim by the resident and streamed kernels — residency is
    a table-supply strategy, never a semantics change, which is what
    makes streamed-vs-resident bit-identity a testable contract.
    """
    B = o_ref.shape[0]
    C = chunk
    # read every ref up front: the loop body then touches only values
    # (weights stay resident; table refs are read through ``encode``)
    o = o_ref[...][:, :3]
    d = d_ref[...][:, :3]
    sh = sh_ref[...]
    budget = bud_ref[pl.program_id(0)]
    wd = wd_ref[...]
    wc = wc_ref[...]

    delta_t = (far - near) / budget.astype(jnp.float32)
    n_chunks = (budget + C - 1) // C
    A = len(range(0, C, group))

    def cond(state):
        ci, log_t = state[0], state[1]
        if not early_term:
            return ci < n_chunks
        return jnp.logical_and(ci < n_chunks, jnp.any(log_t > log_eps_t))

    def body(state):
        ci, log_t, rgb, acc, dep, ray_chunks = state
        # per-ray liveness at chunk start: saturated rays stop counting
        # toward ray_chunks, and — with per_ray_exit — stop contributing
        # sample work entirely (their sigma is masked, freezing log_t);
        # block-level exit timing is unchanged either way, because a
        # dead ray's log_t can never rise back above the threshold
        alive = log_t > log_eps_t
        idx = ci * C + jnp.arange(C)
        valid = idx < budget
        ts = near + (idx.astype(jnp.float32) + 0.5) * delta_t
        pts = o[:, None, :] + ts[None, :, None] * d[:, None, :]  # (B, C, 3)
        flat = pts.reshape(B * C, 3)

        enc = encode(flat)                                   # (M, L*F)
        enc = jnp.concatenate(
            [enc, jnp.zeros((B * C, P - enc.shape[-1]), jnp.float32)],
            axis=-1)
        dout = _chains(enc, wd, nd)                          # (M, P)
        sigma = _trunc_exp(dout[:, geo_dim]).reshape(B, C)
        inside = jnp.all((flat >= 0.0) & (flat <= 1.0),
                         axis=-1).reshape(B, C)
        sigma = jnp.where(inside & valid[None, :], sigma, 0.0)
        if per_ray_exit:
            sigma = jnp.where(alive[:, None], sigma, 0.0)

        if with_color:
            lane = jax.lax.broadcasted_iota(jnp.int32, dout.shape, 1)
            geo = jnp.where(lane < geo_dim, dout, 0.0)
            geo3 = geo.reshape(B, C, P)
            # anchor pick + lerp expansion as one-hot matmuls (the
            # lane-shuffle idiom) — no static C-way stack unrolls
            sel = _anchor_select(C, A, group)                # (A, C)
            geo_a = jnp.einsum("ac,bcp->bap", sel, geo3,
                               preferred_element_type=jnp.float32)
            cin = (geo_a + sh[:, None, :]).reshape(B * A, P)
            rgb_a = _chains(cin, wc, nc,
                            final=jax.nn.sigmoid)[:, :3].reshape(B, A, 3)
            lerp = _lerp_expand(C, A, group)                 # (C, A)
            colors = jnp.einsum("ca,bax->bcx", lerp, rgb_a,
                                preferred_element_type=jnp.float32)

        alphas = 1.0 - jnp.exp(-sigma * delta_t)
        one_m = jnp.clip(1.0 - alphas, 1e-10, 1.0)
        log_steps = jnp.log(one_m)
        intra = jnp.cumsum(log_steps, axis=-1) - log_steps   # exclusive
        trans = jnp.exp(log_t[:, None] + intra)
        w = trans * alphas
        if with_color:
            rgb = rgb + jnp.sum(w[..., None] * colors, axis=1)
        acc = acc + jnp.sum(w, axis=-1)
        dep = dep + jnp.sum(w * ts[None, :], axis=-1)
        log_t = log_t + jnp.sum(log_steps, axis=-1)
        ray_chunks = ray_chunks + alive.astype(jnp.int32)
        return ci + 1, log_t, rgb, acc, dep, ray_chunks

    state = (
        jnp.asarray(0, jnp.int32),
        jnp.zeros((B,)),
        jnp.zeros((B, 3)),
        jnp.zeros((B,)),
        jnp.zeros((B,)),
        jnp.zeros((B,), jnp.int32),
    )
    ci, _, rgb, acc, dep, ray_chunks = jax.lax.while_loop(cond, body, state)
    depth = dep + (1.0 - acc) * far
    if with_color and white_background:
        rgb = rgb + (1.0 - acc[:, None])
    out_ref[...] = jnp.concatenate(
        [acc[:, None], rgb, depth[:, None],
         jnp.broadcast_to(ci.astype(jnp.float32), (B,))[:, None],
         ray_chunks.astype(jnp.float32)[:, None],
         jnp.zeros((B, OUT_W - 7), jnp.float32)], axis=1)


def _march_kernel_resident(o_ref, d_ref, sh_ref, bud_ref, meta_ref,
                           tables_ref, wd_ref, wc_ref, out_ref, **kw):
    """All L levels VMEM-resident for the whole march (small configs)."""
    tables = tables_ref[...]
    _march_impl(o_ref, d_ref, sh_ref, bud_ref, meta_ref, wd_ref, wc_ref,
                out_ref,
                lambda flat: _encode_points(flat, meta_ref,
                                            lambda l: tables[l],
                                            kw["n_levels"]), **kw)


def _march_kernel_streamed(o_ref, d_ref, sh_ref, bud_ref, meta_ref,
                           tables_ref, wd_ref, wc_ref, out_ref,
                           tbuf, sem, **kw):
    """Production lowering: tables stay in HBM; each encode streams the
    stack through a double-buffered (2, T, F) VMEM scratch pair.

    Per level l the DMA for level l+1 is launched BEFORE waiting on
    level l, so the next copy is in flight while the current level's
    gathers and trilinear blend run — the §5.2 data-reuse dataflow with
    the table stream (not the sample stream) flowing past the compute.
    Ping/pong slot l % 2 is safe at any L (odd included): slot reuse is
    always two levels apart, and level l-1's slot was fully consumed
    before level l+1's copy into it starts.
    """
    def copy(level, slot):
        return pltpu.make_async_copy(
            tables_ref.at[level], tbuf.at[slot], sem.at[slot])

    def read_level(level):
        if level + 1 < kw["n_levels"]:
            copy(level + 1, (level + 1) % 2).start()
        copy(level, level % 2).wait()
        return tbuf[level % 2]

    def encode(flat):
        copy(0, 0).start()                    # warm-up: first level
        return _encode_points(flat, meta_ref, read_level, kw["n_levels"])

    _march_impl(o_ref, d_ref, sh_ref, bud_ref, meta_ref, wd_ref, wc_ref,
                out_ref, encode, **kw)


def fused_march_call(o, d, sh, budgets, meta, tables, wd, wc, *,
                     block_size, geo_dim, group, chunk, near, far,
                     log_eps_t, early_term, white_background,
                     with_color, stream_tables=False, per_ray_exit=False):
    """o/d (N*B, PPAD), sh (N*B, P), budgets (N,) i32, meta (L, 8) i32,
    tables (L, T, F), wd (nd,P,P), wc (nc,P,P) -> packed (N*B, OUT_W).

    ``stream_tables`` selects the table supply: False keeps the stack
    VMEM-resident (bit-identical baseline for configs that fit), True
    runs the double-buffered DMA streaming path (the only option at
    full-config table sizes — see ops.fused_march_vmem_bytes).
    """
    B = block_size
    n_blocks = budgets.shape[0]
    assert o.shape[0] == n_blocks * B, "one budget row per block"
    L, T, F = tables.shape
    kw = dict(nd=wd.shape[0], nc=wc.shape[0], geo_dim=geo_dim,
              group=group, chunk=chunk, n_levels=L, near=near, far=far,
              log_eps_t=log_eps_t, early_term=early_term,
              per_ray_exit=per_ray_exit,
              white_background=white_background, with_color=with_color)
    if stream_tables:
        kern = functools.partial(_march_kernel_streamed, **kw)
        tables_spec = pl.BlockSpec(memory_space=pl.ANY)
        scratch = [pltpu.VMEM((2, T, F), jnp.float32),
                   pltpu.SemaphoreType.DMA((2,))]
    else:
        kern = functools.partial(_march_kernel_resident, **kw)
        tables_spec = pl.BlockSpec((L, T, F), lambda i: (0, 0, 0))
        scratch = []
    return pl.pallas_call(
        kern,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((B, PPAD), lambda i: (i, 0)),
            pl.BlockSpec((B, PPAD), lambda i: (i, 0)),
            pl.BlockSpec((B, P), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            tables_spec,
            pl.BlockSpec((wd.shape[0], P, P), lambda i: (0, 0, 0)),
            pl.BlockSpec((wc.shape[0], P, P), lambda i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((B, OUT_W), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_blocks * B, OUT_W), jnp.float32),
        scratch_shapes=scratch,
        interpret=resolve_interpret(),
    )(o, d, sh, budgets, meta, tables, wd, wc)
