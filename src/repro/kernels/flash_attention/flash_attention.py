"""Causal GQA flash attention, Pallas/TPU.

Online-softmax tiling (Flash-2 style): grid (B, H, S/bq, T/bk) with the KV
axis innermost/sequential. Running (m, l, acc) live in VMEM scratch across
KV steps; the output block is written once on the last step. Fully-masked
(above-diagonal) KV blocks are skipped with ``pl.when`` — for causal
attention that's ~2x fewer MXU passes, the structural equivalent of
flash's "block sparsity on the diagonal".

GQA is handled in the index maps: query head h reads KV head ``h // g`` —
no materialized KV repetition in HBM or VMEM.

Layout: the wrapper folds the head axis into the lane axis (``(B, S, H,
hd) -> (B, S, H*hd)``, a free reshape), so each block is a ``(bq, hd)``
panel selected by a lane-block index. Mosaic requires the last two block
dims to be multiples of (8, 128) or the full array dims; a one-head block
over a ``(…, H, hd)`` layout puts 1 in the sublane position and is refused.
The compiled path therefore needs ``hd % 128 == 0`` (``can_flash_attend``).

VMEM per step: q (bq,hd) + k,v (bk,hd) + scores (bq,bk) + acc (bq,hd) fp32
≈ 0.5 MB at bq=bk=128, hd=128 — double-buffered comfortably on v5e.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import fit_block

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, nk: int, bq: int,
    bk: int, causal: bool, window: int, t_minus_s: int
):
    jk = pl.program_id(3)
    iq = pl.program_id(2)

    @pl.when(jk == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * bq + t_minus_s          # absolute position of first query
    k_start = jk * bk

    def compute():
        q = q_ref[0]                                # (bq, hd)
        k = k_ref[0]                                # (bk, hd)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) / (q.shape[-1] ** 0.5)                     # (bq, bk)
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            mask = kpos <= qpos
            if window:
                mask &= kpos > qpos - window
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[:, :1]                        # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    if causal:
        # Skip blocks entirely above the causal diagonal.
        pl.when(k_start <= q_start + bq - 1)(compute)
    else:
        compute()

    @pl.when(jk == nk - 1)
    def _():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,      # (B, S, H, hd)
    k: jax.Array,      # (B, T, K, hd)
    v: jax.Array,      # (B, T, K, hd)
    *,
    causal: bool = True,
    window: int = 0,
    bq: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    b, s, nh, hd = q.shape
    t, nkv = k.shape[1], k.shape[2]
    g = nh // nkv
    bq = fit_block(s, bq)
    bk = fit_block(t, bk)
    nk = t // bk
    grid = (b, nh, s // bq, nk)
    kernel = functools.partial(
        _flash_kernel,
        nk=nk,
        bq=bq,
        bk=bk,
        causal=causal,
        window=window,
        t_minus_s=t - s,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda bi, h, iq, jk: (bi, iq, h)),
            pl.BlockSpec((1, bk, hd), lambda bi, h, iq, jk: (bi, jk, h // g)),
            pl.BlockSpec((1, bk, hd), lambda bi, h, iq, jk: (bi, jk, h // g)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda bi, h, iq, jk: (bi, iq, h)),
        out_shape=jax.ShapeDtypeStruct((b, s, nh * hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),   # running max
            pltpu.VMEM((bq, 1), jnp.float32),     # running denom
            pltpu.VMEM((bq, hd), jnp.float32),    # running numerator
        ],
        interpret=interpret,
    )(
        q.reshape(b, s, nh * hd),
        k.reshape(b, t, nkv * hd),
        v.reshape(b, t, nkv * hd),
    )
    return out.reshape(b, s, nh, hd)
