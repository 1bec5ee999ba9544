"""Flash-decode Pallas kernel: one query token vs a long KV cache.

Decode is HBM-bound (the whole KV cache streams through once), so the
kernel's job is to keep that stream dense: grid (B, K, T/bt) with the KV
axis sequential, online softmax in VMEM scratch, and — the GQA trick — all
``G = H/K`` query heads of a KV group processed *together* as a (G, hd)
panel, turning the per-block score computation into an MXU (G x hd) @
(hd x bt) matmul instead of G vector passes. Cache-slot validity arrives
as an int32 mask (ring buffers / partially filled caches).

Layout: the wrapper folds the kv-head axis into lanes (``(B, T, K, hd) ->
(B, T, K*hd)``, a free reshape) so each k/v block is a ``(bt, hd)`` panel
picked by a lane-block index, and the mask rides as ``(B, 1, T)``. Mosaic
refuses a block whose sublane dim is a lone head (or batch row) of a
larger axis; the compiled path needs ``hd % 128 == 0`` and ``T % 128 ==
0`` (``can_flash_decode``).

``return_partials=True`` skips the local normalization and emits the raw
online-softmax state ``(acc, m, l)`` instead — ``acc`` is the
*unnormalized* weighted value sum in fp32, ``m`` the running row max and
``l`` the running exp-sum. Two partials over disjoint key sets merge
exactly (the standard LSE merge)::

    m* = max(m1, m2);  l* = l1*e^(m1-m*) + l2*e^(m2-m*)
    acc* = acc1*e^(m1-m*) + acc2*e^(m2-m*);   out = acc* / l*

which is what the sequence-parallel decode path psums across shards
(`repro.parallel.collectives.seq_parallel_decode_attend`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import fit_block

NEG_INF = -1e30


def write_outputs(partials: bool, out_refs, m_ref, l_ref, acc_ref):
    """Final-block epilogue shared by the dense and paged decode kernels:
    either locally normalize, or emit the raw ``(acc, m, l)`` state (``l``
    broadcast across the 128-lane tile; column 0 is the value)."""
    if partials:
        o_ref, mo_ref, lo_ref = out_refs
        o_ref[0, 0] = acc_ref[...].astype(o_ref.dtype)
        mo_ref[0, 0] = m_ref[...].astype(mo_ref.dtype)
        lo_ref[0, 0] = jnp.broadcast_to(
            l_ref[:, :1], lo_ref.shape[2:]
        ).astype(lo_ref.dtype)
    else:
        (o_ref,) = out_refs
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def output_layout(partials: bool, b, nkv, g, hd, dtype, index_map):
    """(out_shape, out_specs) shared by the decode wrappers. Partials ride
    out as fp32 ``acc (…, g, hd)`` plus ``m``/``l`` through ``(…, g, 128)``
    lanes (min lane tile; the broadcast is free in VMEM)."""
    o_spec = pl.BlockSpec((1, 1, g, hd), index_map)
    if not partials:
        return jax.ShapeDtypeStruct((b, nkv, g, hd), dtype), o_spec
    ml_shape = jax.ShapeDtypeStruct((b, nkv, g, 128), jnp.float32)
    ml_spec = pl.BlockSpec((1, 1, g, 128), index_map)
    return (
        (jax.ShapeDtypeStruct((b, nkv, g, hd), jnp.float32), ml_shape, ml_shape),
        (o_spec, ml_spec, ml_spec),
    )


def unpack_outputs(partials: bool, out, b, nh, hd):
    """Reshape kernel outputs to the public ``(B, H, …)`` contract."""
    if not partials:
        return out.reshape(b, nh, hd)
    acc, m, l = out
    return (
        acc.reshape(b, nh, hd),
        m[..., 0].reshape(b, nh),
        l[..., 0].reshape(b, nh),
    )


def _decode_kernel(q_ref, k_ref, v_ref, m_in_ref, *refs, nt: int, partials: bool):
    if partials:
        o_ref, mo_ref, lo_ref, m_ref, l_ref, acc_ref = refs
        out_refs = (o_ref, mo_ref, lo_ref)
    else:
        o_ref, m_ref, l_ref, acc_ref = refs
        out_refs = (o_ref,)
    jt = pl.program_id(2)

    @pl.when(jt == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]                             # (G, hd)
    k = k_ref[0]                                # (bt, hd)
    v = v_ref[0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) / (q.shape[-1] ** 0.5)                     # (G, bt)
    valid = m_in_ref[0]                          # (1, bt)
    s = jnp.where(valid > 0, s, NEG_INF)

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

    @pl.when(jt == nt - 1)
    def _():
        write_outputs(partials, out_refs, m_ref, l_ref, acc_ref)


def flash_decode(
    q: jax.Array,       # (B, H, hd)
    k: jax.Array,       # (B, T, K, hd)
    v: jax.Array,       # (B, T, K, hd)
    valid: jax.Array,   # (B, T) int32
    *,
    bt: int = 512,
    return_partials: bool = False,
    interpret: bool = False,
):
    """Locally-normalized output ``(B, H, hd)``, or — with
    ``return_partials`` — the fp32 triple ``(acc, m, l)`` of shapes
    ``(B, H, hd)``, ``(B, H)``, ``(B, H)`` for a cross-shard LSE merge."""
    b, nh, hd = q.shape
    t, nkv = k.shape[1], k.shape[2]
    g = nh // nkv
    bt = fit_block(t, bt, 128)
    nt = t // bt
    qg = q.reshape(b, nkv, g, hd)
    grid = (b, nkv, nt)
    out_shape, out_specs = output_layout(
        return_partials, b, nkv, g, hd, q.dtype,
        lambda bi, kh, jt: (bi, kh, 0, 0),
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, nt=nt, partials=return_partials),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, g, hd), lambda bi, kh, jt: (bi, kh, 0, 0)),
            pl.BlockSpec((1, bt, hd), lambda bi, kh, jt: (bi, jt, kh)),
            pl.BlockSpec((1, bt, hd), lambda bi, kh, jt: (bi, jt, kh)),
            pl.BlockSpec((1, 1, bt), lambda bi, kh, jt: (bi, 0, jt)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((g, 128), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32),
        ],
        interpret=interpret,
    )(
        qg,
        k.reshape(b, t, nkv * hd),
        v.reshape(b, t, nkv * hd),
        valid.astype(jnp.int32).reshape(b, 1, t),
    )
    return unpack_outputs(return_partials, out, b, nh, hd)


def merge_partials(acc, m, l, axis_name: str):
    """LSE-merge flash-decode partials across a named mesh axis.

    ``acc (B, H, hd)``, ``m (B, H)``, ``l (B, H)`` — each shard's state over
    its disjoint KV slice. A fully-masked shard carries ``m = NEG_INF`` but
    *non-zero* ``l``/``acc`` (the online softmax computes ``exp(s - m)`` with
    both at ``NEG_INF``, so masked rows contribute ``exp(0) = 1`` until a
    live key raises ``m``); it still contributes nothing here because its
    weight ``exp(m - m_max)`` underflows to exactly 0 whenever *any* shard
    saw a live key. If every shard is fully masked the merge degenerates to
    the same uniform average over cache rows the dense masked softmax
    produces — callers must not treat ``l`` as a liveness signal.
    """
    m_max = jax.lax.pmax(m, axis_name)
    scale = jnp.exp(m - m_max)
    num = jax.lax.psum(acc * scale[..., None], axis_name)
    den = jax.lax.psum(l * scale, axis_name)
    return num / jnp.maximum(den, 1e-30)[..., None]
