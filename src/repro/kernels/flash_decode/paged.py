"""Paged flash-decode: block-table KV walk with scalar-prefetched pages.

The dense ``flash_decode`` streams the whole ``(B, max_seq, K, hd)`` cache
per step and masks invalid slots, so a short sequence in a long-``max_seq``
batch still pays full-cache HBM bandwidth. Here the cache is a shared
*page pool* ``(P, page_size, K, hd)`` plus per-request int32 metadata:

* ``block_tables (B, NB)`` — logical KV block ``j`` of request ``b`` lives
  in physical page ``block_tables[b, j]``;
* ``lengths (B,)`` — live context per request (no dense validity mask).

Both ride as **scalar-prefetch operands** (same mechanism as the ragged
GMM's per-bucket offsets), so the k/v *BlockSpec index maps* can read them:
grid step ``(b, kh, jb)`` fetches page ``block_tables[b, jb]`` straight
from the pool — the Pallas pipeline double-buffers those fetches like any
other block. Blocks past ``lengths[b]`` are clamped to the request's last
live page: consecutive grid steps with an identical block index elide the
copy, so HBM traffic tracks ``ceil(length / page_size)`` live pages, not
``max_seq``. The kernel body skips the MXU for dead blocks and masks the
final partial page with ``position < length``.

A ring-buffer sliding-window cache is the same kernel with a small block
table (``ceil(W / page_size)`` entries): ring validity is always a prefix
``min(pos + 1, W)`` of the logical slot space, which is exactly the
``lengths`` contract (softmax is permutation-invariant over the key set
and RoPE is applied at write time, so slot order never matters).

The pool keeps its ``(P, bs, K, hd)`` layout; the wrapper views it as
``(P, bs, K*hd)`` (a free reshape) so each fetched block is one page's
``(bs, hd)`` panel of one kv head — Mosaic's (8, 128) block rule then
holds for ``bs % 8 == 0`` and ``hd % 128 == 0`` (``can_flash_decode_paged``),
where a one-head block over ``(…, K, hd)`` would be refused.

``return_partials`` matches ``flash_decode``: fp32 ``(acc, m, l)`` for the
cross-shard LSE merge instead of locally-normalized output.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_decode.flash_decode import (
    output_layout,
    unpack_outputs,
    write_outputs,
)

NEG_INF = -1e30


def _paged_kernel(
    bt_ref, ln_ref, q_ref, k_ref, v_ref, *refs,
    bs: int, nb: int, partials: bool,
):
    if partials:
        o_ref, mo_ref, lo_ref, m_ref, l_ref, acc_ref = refs
        out_refs = (o_ref, mo_ref, lo_ref)
    else:
        o_ref, m_ref, l_ref, acc_ref = refs
        out_refs = (o_ref,)
    bi = pl.program_id(0)
    jb = pl.program_id(2)
    length = ln_ref[bi]
    live = jb * bs < length

    @pl.when(jb == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _():
        q = q_ref[0, 0]                         # (G, hd)
        k = k_ref[0]                            # (bs, hd) — one pool page
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) / (q.shape[-1] ** 0.5)                 # (G, bs)
        kpos = jb * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < length, s, NEG_INF)

        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(jb == nb - 1)
    def _():
        write_outputs(partials, out_refs, m_ref, l_ref, acc_ref)


def flash_decode_paged(
    q: jax.Array,             # (B, H, hd)
    pool_k: jax.Array,        # (P, bs, K, hd) shared page pool
    pool_v: jax.Array,        # (P, bs, K, hd)
    block_tables: jax.Array,  # (B, NB) int32 logical block -> physical page
    lengths: jax.Array,       # (B,) int32 live context per request
    *,
    return_partials: bool = False,
    interpret: bool = False,
):
    b, nh, hd = q.shape
    n_pages, bs, nkv = pool_k.shape[:3]
    nb = block_tables.shape[1]
    g = nh // nkv
    qg = q.reshape(b, nkv, g, hd)
    grid = (b, nkv, nb)

    def kv_map(bi, kh, jb, bt, ln):
        # Dead blocks clamp to the request's last live block: repeated
        # identical indices make the pipeline skip the page fetch.
        last = jnp.maximum(ln[bi] - 1, 0) // bs
        return (bt[bi, jnp.minimum(jb, last)], 0, kh)

    out_shape, out_specs = output_layout(
        return_partials, b, nkv, g, hd, q.dtype,
        lambda bi, kh, jb, bt, ln: (bi, kh, 0, 0),
    )
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, g, hd), lambda bi, kh, jb, bt, ln: (bi, kh, 0, 0)),
            pl.BlockSpec((1, bs, hd), kv_map),
            pl.BlockSpec((1, bs, hd), kv_map),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((g, 128), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, bs=bs, nb=nb, partials=return_partials),
        grid_spec=spec,
        out_shape=out_shape,
        interpret=interpret,
    )(
        block_tables.astype(jnp.int32),
        lengths.astype(jnp.int32),
        qg,
        pool_k.reshape(n_pages, bs, nkv * hd),
        pool_v.reshape(n_pages, bs, nkv * hd),
    )
    return unpack_outputs(return_partials, out, b, nh, hd)
