"""Flash-attention Pallas kernel for the LM stack (beyond-paper addition).

The prefill cells' residual roofline gap is attention intermediates
(scores materialized per KV chunk by the jnp path); this kernel keeps the
online-softmax state (m, l, acc) in VMEM registers across the KV sweep so
score tiles never reach HBM — the same VMEM-residency argument as the
fused render MLP (DESIGN.md §2), applied to the zoo side.

Layout: one block program per (batch*head, q_block); K/V for that head are
resident (BlockSpec row-select) and swept in KB-sized slices with
``lax.fori_loop`` + ``pl.dynamic_slice``-style indexing.  Causal +
sliding-window masking matches models/attention.py semantics exactly
(``ref`` oracle = attend_full).  Validated interpret=True on CPU; on real
TPU the same BlockSpecs tile Q into 128-row MXU passes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import resolve_interpret

NEG_INF = -1e30
QB = 128      # q rows per block program
KB = 128      # kv rows per inner step


def _flash_kernel(q_ref, k_ref, v_ref, out_ref, *, kv_len, window,
                  softcap, scale):
    q = q_ref[...].astype(jnp.float32) * scale          # (QB, Dh)
    qb = pl.program_id(1)
    q_pos = qb * QB + jax.lax.broadcasted_iota(jnp.int32, (QB, 1), 0)[:, 0]

    nk = kv_len // KB

    def body(i, carry):
        m_run, l_run, acc = carry
        k = jax.lax.dynamic_slice_in_dim(k_ref[...], i * KB, KB, 0)
        v = jax.lax.dynamic_slice_in_dim(v_ref[...], i * KB, KB, 0)
        s = q @ k.astype(jnp.float32).T                  # (QB, KB)
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        k_pos = i * KB + jax.lax.broadcasted_iota(
            jnp.int32, (1, KB), 1)[0]
        mask = q_pos[:, None] >= k_pos[None, :]
        if window:
            mask &= (q_pos[:, None] - k_pos[None, :]) < window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_run - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l_run * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[:, None] + p @ v.astype(jnp.float32)
        return m_new, l_new, acc

    init = (
        jnp.full((QB,), NEG_INF, jnp.float32),
        jnp.zeros((QB,), jnp.float32),
        jnp.zeros((QB, q.shape[-1]), jnp.float32),
    )
    m_run, l_run, acc = jax.lax.fori_loop(0, nk, body, init)
    out_ref[...] = (acc / jnp.maximum(l_run, 1e-30)[:, None]).astype(
        out_ref.dtype)


def flash_attention(q, k, v, window: int = 0, softcap: float = 0.0):
    """q (B, S, H, Dh); k/v (B, S, KV, Dh) with H % KV == 0 (GQA).
    Causal (+ optional sliding-window) attention. Returns (B, S, H, Dh)."""
    B, S, H, Dh = q.shape
    KV = k.shape[2]
    rep = H // KV
    assert S % QB == 0 and S % KB == 0, "pad sequence to 128"
    scale = Dh ** -0.5

    # lay out as (B*H, S, Dh); kv broadcast per GQA group
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, Dh)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), rep, axis=1).reshape(
        B * H, S, Dh)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), rep, axis=1).reshape(
        B * H, S, Dh)

    kern = functools.partial(
        _flash_kernel, kv_len=S, window=window, softcap=softcap, scale=scale)
    out = pl.pallas_call(
        kern,
        grid=(B * H, S // QB),
        in_specs=[
            pl.BlockSpec((None, QB, Dh), lambda bh, qb: (bh, qb, 0)),
            pl.BlockSpec((None, S, Dh), lambda bh, qb: (bh, 0, 0)),
            pl.BlockSpec((None, S, Dh), lambda bh, qb: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, QB, Dh), lambda bh, qb: (bh, qb, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, Dh), q.dtype),
        interpret=resolve_interpret(),
    )(qf, kf, vf)
    return out.reshape(B, H, S, Dh).transpose(0, 2, 1, 3)
