"""Kernel dispatch layer: route hot-path math to Pallas or reference code.

One chokepoint decides, per call site, whether the Pallas kernels run and
how (compiled on TPU, interpret mode elsewhere), so model code never
hard-codes a backend:

* ``kernels_enabled(flag)``   — resolve a ``ParallelCtx.use_kernels`` value
  ("auto" -> TPU only) into a bool.
* ``default_interpret()``     — True off-TPU: kernel bodies execute via the
  Pallas interpreter so CPU tests cover the exact kernel code.
* ``expert_ffn(...)``         — count-aware grouped SwiGLU FFN. Kernel path
  = ``gmm_dual_act_ragged`` + ``gmm_ragged`` (FLOPs ~ sum(group_sizes));
  fallback = folded einsums. Differentiable: the kernel forward pairs with
  a reference-math backward via ``jax.custom_vjp``.
* ``attend(...)`` / ``can_flash_attend(...)``   — causal/bidirectional GQA
  flash attention with a chunked-reference backward.
* ``decode_attend(...)`` / ``can_flash_decode(...)`` — single-token decode
  against a (possibly partially valid) KV cache.

Fallback rules: a caller first asks the ``can_*`` predicate (shapes must
tile for the compiled path; interpret mode accepts anything), and keeps its
einsum reference for the "no" answer. Compiled-path gates are conservative
— last dims multiples of 128, row dims multiples of 8 — matching the MXU
native tiling the kernels were written for.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attention.ops import flash_attention_op
from repro.kernels.flash_decode.flash_decode import merge_partials
from repro.kernels.flash_decode.ops import (
    flash_decode_op,
    flash_decode_paged_op,
    flash_decode_partials_op,
)
from repro.kernels.gmm.ops import (
    expert_ffn_fused as _expert_ffn_fused_op,
)
from repro.kernels.gmm.ops import (
    expert_ffn_gather as _expert_ffn_gather_op,
)
from repro.kernels.gmm.ops import (
    expert_ffn_gather_compact as _expert_ffn_gather_compact_op,
)
from repro.kernels.gmm.ops import (
    expert_ffn_ragged as _expert_ffn_ragged_op,
)
from repro.kernels.gmm.ref import (
    expert_ffn_compact_ref,
    expert_ffn_fused_ref,
    expert_ffn_gather_ref,
    expert_ffn_ragged_ref,
)


# ---------------------------------------------------------------------------
# flag resolution
# ---------------------------------------------------------------------------

def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def default_interpret() -> bool:
    """Off-TPU the kernels run under the Pallas interpreter."""
    return not on_tpu()


def kernels_enabled(flag: str | bool = "auto") -> bool:
    """Resolve a ``use_kernels`` setting: "auto" means TPU-only (interpret
    mode is correct everywhere but too slow to be a default on CPU)."""
    if flag == "auto":
        return on_tpu()
    return bool(flag)


def parse_use_kernels(value: str) -> str | bool:
    """CLI tri-state ("auto"|"on"|"off") -> ``ParallelCtx.use_kernels``."""
    return {"on": True, "off": False}.get(value, "auto")


def _zero_ct(a):
    """float0 cotangent for integer primal inputs (custom_vjp contract)."""
    return np.zeros(a.shape, jax.dtypes.float0)


# ---------------------------------------------------------------------------
# grouped expert FFN (ragged / count-aware)
# ---------------------------------------------------------------------------

def can_gmm(c: int, d: int, f: int, interpret: bool) -> bool:
    """Can the grouped-matmul kernels take (·, c, d) @ (·, d, f)?"""
    if interpret:
        return True
    return c % 8 == 0 and d % 128 == 0 and f % 128 == 0


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _ffn_kernel(gpw: int, interpret: bool, x, wg, wu, wd, group_sizes):
    return _expert_ffn_ragged_op(
        x, wg, wu, wd, group_sizes,
        groups_per_weight=gpw, interpret=interpret,
    )


def _ffn_fwd(gpw, interpret, x, wg, wu, wd, group_sizes):
    y = _ffn_kernel(gpw, interpret, x, wg, wu, wd, group_sizes)
    return y, (x, wg, wu, wd, group_sizes)


def _ffn_bwd(gpw, interpret, res, ct):
    # Backward through the reference math (the standard flash-style trick:
    # kernel forward, recomputed reference backward — Pallas kernels with
    # VMEM scratch have no autodiff rule).
    x, wg, wu, wd, gs = res
    _, vjp = jax.vjp(
        lambda a, b, c, d: expert_ffn_ragged_ref(a, b, c, d, gs, gpw),
        x, wg, wu, wd,
    )
    return (*vjp(ct), _zero_ct(gs))


_ffn_kernel.defvjp(_ffn_fwd, _ffn_bwd)


def expert_ffn(
    x: jax.Array,                       # (G, C, D)
    wg: jax.Array,                      # (G/gpw, D, F)
    wu: jax.Array,                      # (G/gpw, D, F)
    wd: jax.Array,                      # (G/gpw, F, D)
    group_sizes: jax.Array | None = None,   # (G,) int32 valid-row counts
    *,
    groups_per_weight: int = 1,
    enabled: bool = True,
    interpret: bool | None = None,
) -> jax.Array:
    """Grouped SwiGLU expert FFN with optional raggedness.

    With ``group_sizes`` the kernel skips row-tiles past each group's count
    (and zeroes the tail), so expert FLOPs track tokens actually routed.
    ``groups_per_weight`` consecutive groups share one weight row (the
    flattened EP/ESP bucket layouts). Falls back to folded einsums when
    disabled or when shapes don't tile for the compiled kernel.
    """
    g, c, d = x.shape
    f = wg.shape[-1]
    interpret = default_interpret() if interpret is None else interpret
    if enabled and can_gmm(c, d, f, interpret) and can_gmm(c, f, d, interpret):
        gs = (
            group_sizes.astype(jnp.int32)
            if group_sizes is not None
            else jnp.full((g,), c, jnp.int32)
        )
        return _ffn_kernel(groups_per_weight, interpret, x, wg, wu, wd, gs)
    return expert_ffn_ragged_ref(
        x, wg, wu, wd, group_sizes, groups_per_weight
    )


# ---------------------------------------------------------------------------
# fused dispatch-gather expert FFN (flat rows + per-bucket offsets)
# ---------------------------------------------------------------------------

def can_gmm_gather(capacity: int, d: int, f: int, interpret: bool) -> bool:
    """Can the fused gather kernels take flat rows into (G, capacity) buckets
    with (d, f) expert dims? Same MXU-tiling gates as the ragged kernels
    (the flat array itself stays in ANY memory — no row-count constraint)."""
    return can_gmm(capacity, d, f, interpret) and can_gmm(capacity, f, d, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _ffn_gather_kernel(cap, gpw, interpret, x, wg, wu, wd, offsets, group_sizes):
    return _expert_ffn_gather_op(
        x, wg, wu, wd, offsets, group_sizes,
        capacity=cap, groups_per_weight=gpw, interpret=interpret,
    )


def _ffn_gather_fwd(cap, gpw, interpret, x, wg, wu, wd, offsets, group_sizes):
    y = _ffn_gather_kernel(cap, gpw, interpret, x, wg, wu, wd, offsets, group_sizes)
    return y, (x, wg, wu, wd, offsets, group_sizes)


def _ffn_gather_bwd(cap, gpw, interpret, res, ct):
    # Reference-math backward: the gather is a plain jnp take, so the
    # cotangent scatters back onto the flat rows for free.
    x, wg, wu, wd, offs, gs = res
    _, vjp = jax.vjp(
        lambda a, b, c, d: expert_ffn_gather_ref(a, b, c, d, offs, gs, cap, gpw),
        x, wg, wu, wd,
    )
    return (*vjp(ct), _zero_ct(offs), _zero_ct(gs))


_ffn_gather_kernel.defvjp(_ffn_gather_fwd, _ffn_gather_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _ffn_compact_kernel(cap, gpw, interpret, x, wg, wu, wd, offsets, group_sizes):
    return _expert_ffn_gather_compact_op(
        x, wg, wu, wd, offsets, group_sizes,
        capacity=cap, groups_per_weight=gpw, interpret=interpret,
    )


def _ffn_compact_fwd(cap, gpw, interpret, x, wg, wu, wd, offsets, group_sizes):
    y = _ffn_compact_kernel(cap, gpw, interpret, x, wg, wu, wd, offsets, group_sizes)
    return y, (x, wg, wu, wd, offsets, group_sizes)


def _ffn_compact_bwd(cap, gpw, interpret, res, ct):
    # Reference-math backward: gather + FFN + scatter are plain jnp ops, so
    # the cotangent flows back onto the flat rows through the same layout.
    # The kernel forward leaves rows outside live segments unspecified
    # while the reference zeroes them — consistent, because the reference
    # scatter's vjp reads the cotangent only at live (bucket, position)
    # pairs, exactly the rows downstream combines may touch.
    x, wg, wu, wd, offs, gs = res
    _, vjp = jax.vjp(
        lambda a, b, c, d: expert_ffn_compact_ref(a, b, c, d, offs, gs, cap, gpw),
        x, wg, wu, wd,
    )
    return (*vjp(ct), _zero_ct(offs), _zero_ct(gs))


_ffn_compact_kernel.defvjp(_ffn_compact_fwd, _ffn_compact_bwd)


# VMEM bound for the fully-fused kernel: it holds a (bm, d) fp32 output
# accumulator + a (bm, d) staging tile + a double-buffered (bf, d) w_down
# panel per step, all linear in d. Set from the TPU compiler: at bm = bf =
# 128 in bf16, a described v5e accepts d = 12288 and runs out of VMEM at
# d = 16384 (tests/test_tpu_compile.py keeps d = 4096 and 6144 compiling).
# Wider model dims fall back to the two-kernel gather+scatter pair (which
# blocks the down-projection's output columns).
FUSED_FFN_MAX_DOWN_DIM = 12288


def can_gmm_fused(
    capacity: int, d: int, f: int, interpret: bool, d_out: int | None = None
) -> bool:
    """Can the fully-fused single-kernel FFN (``gmm_fused_ffn``) take flat
    rows with (d, f, d_out) expert dims? Same MXU-tiling gates as the
    gather/scatter pair plus the VMEM bound on the output accumulator /
    staging tile / w_down panel — all of which scale with the
    *down-projection output* dim, so the bound is on ``d_out`` (== ``d``
    for the square expert-FFN contract, the default). The bound applies in
    interpret mode too, so CPU tests exercise the same dispatch decisions
    the compiled path makes."""
    d_out = d if d_out is None else d_out
    return (
        can_gmm(capacity, d, f, interpret)
        and can_gmm(capacity, f, d_out, interpret)
        and d_out <= FUSED_FFN_MAX_DOWN_DIM
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _ffn_fused_kernel(cap, gpw, interpret, x, wg, wu, wd, offsets, group_sizes):
    return _expert_ffn_fused_op(
        x, wg, wu, wd, offsets, group_sizes,
        capacity=cap, groups_per_weight=gpw, interpret=interpret,
    )


def _ffn_fused_fwd(cap, gpw, interpret, x, wg, wu, wd, offsets, group_sizes):
    y = _ffn_fused_kernel(cap, gpw, interpret, x, wg, wu, wd, offsets, group_sizes)
    return y, (x, wg, wu, wd, offsets, group_sizes)


def _ffn_fused_bwd(cap, gpw, interpret, res, ct):
    # Reference-math backward — identical to the compact pair's backward
    # (the fusion changes where the hidden tensor lives, not the math), so
    # the cotangent flows back onto the flat rows through the same
    # gather/FFN/scatter jnp composition.
    x, wg, wu, wd, offs, gs = res
    _, vjp = jax.vjp(
        lambda a, b, c, d: expert_ffn_fused_ref(a, b, c, d, offs, gs, cap, gpw),
        x, wg, wu, wd,
    )
    return (*vjp(ct), _zero_ct(offs), _zero_ct(gs))


_ffn_fused_kernel.defvjp(_ffn_fused_fwd, _ffn_fused_bwd)


def expert_ffn_from_rows(
    x: jax.Array,            # (R, D) flat token rows, bucket-contiguous
    wg: jax.Array,           # (G/gpw, D, F)
    wu: jax.Array,           # (G/gpw, D, F)
    wd: jax.Array,           # (G/gpw, F, D)
    offsets: jax.Array,      # (G,) int32 first-row index per bucket
    group_sizes: jax.Array,  # (G,) int32 rows per bucket
    *,
    capacity: int,
    groups_per_weight: int = 1,
    enabled: bool = True,
    compact_out: bool = False,
    fused: bool = False,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused dispatch-scatter grouped SwiGLU FFN.

    Bucket ``g``'s tokens are rows ``offsets[g] .. offsets[g]+count_g`` of
    the flat array; the kernel prologue gathers them tile-by-tile (dynamic-
    offset DMA), so the padded ``(G, capacity, D)`` dispatch buffer is never
    written to HBM. By default the output keeps the bucket-padded
    ``(G, capacity, D)`` contract of ``expert_ffn`` (zero tails). With
    ``compact_out=True`` the down-projection instead runs the
    ``gmm_scatter`` epilogue: result tiles are stored back at the *same*
    per-bucket offsets, emitting a flat rank-compacted ``(R, D)`` array —
    the padded FFN output buffer is never written to HBM either, and the
    caller combines through the dispatch metadata
    (``collectives.combine_from_rows``). Rows outside live segments are
    unspecified in the kernel output (zeroed by the reference path) and
    must never be read. Falls back to the reference gather + einsum math
    when disabled or when shapes don't tile.

    With ``fused=True`` (requires ``compact_out=True`` — the fusion's whole
    point is the compact layout on both sides) the three matmuls run as ONE
    kernel (``gmm_fused_ffn``): the SwiGLU hidden activations live entirely
    in VMEM accumulators, so the bucket-padded ``(G, capacity, F)`` hidden
    tensor — the last padded intermediate of the expert hot path — never
    touches HBM. Shape-gated by ``can_gmm_fused`` (the gather/scatter gates
    plus a VMEM bound on the model dim); ineligible shapes fall back to the
    two-kernel gather+scatter pair, then to the reference math.

    Per-chunk invocation (``ep_chunks > 1``): the chunked EP dispatch
    pipeline calls this once per chunk with *sliced* metadata and weights —
    the chunk's buckets' ``offsets``/``group_sizes`` rows and the matching
    weight-row slice — while ``x`` stays the full flat row array (chunk
    receive buffer on the mesh path, the whole compacted stream on the
    no-mesh path). Offsets index into ``x`` as usual; rows owned by buckets
    outside the slice are untouched/unspecified in the output and the
    caller selects each row from its owner chunk before the single final
    combine. The fallback chain above applies per chunk, so a shape that
    loses kernel eligibility after slicing degrades transparently for that
    chunk alone.
    """
    d = x.shape[-1]
    f = wg.shape[-1]
    interpret = default_interpret() if interpret is None else interpret
    if fused and not compact_out:
        raise ValueError(
            "expert_ffn_from_rows: fused=True requires compact_out=True — "
            "the single-kernel path always emits the flat compact layout"
        )
    if compact_out:
        if enabled and fused and can_gmm_fused(
            capacity, d, f, interpret, wd.shape[-1]
        ):
            return _ffn_fused_kernel(
                capacity, groups_per_weight, interpret,
                x, wg, wu, wd,
                offsets.astype(jnp.int32), group_sizes.astype(jnp.int32),
            )
        if enabled and can_gmm_gather(capacity, d, f, interpret):
            return _ffn_compact_kernel(
                capacity, groups_per_weight, interpret,
                x, wg, wu, wd,
                offsets.astype(jnp.int32), group_sizes.astype(jnp.int32),
            )
        return expert_ffn_compact_ref(
            x, wg, wu, wd, offsets, group_sizes, capacity, groups_per_weight
        )
    if enabled and can_gmm_gather(capacity, d, f, interpret):
        return _ffn_gather_kernel(
            capacity, groups_per_weight, interpret,
            x, wg, wu, wd,
            offsets.astype(jnp.int32), group_sizes.astype(jnp.int32),
        )
    return expert_ffn_gather_ref(
        x, wg, wu, wd, offsets, group_sizes, capacity, groups_per_weight
    )


# ---------------------------------------------------------------------------
# flash attention (prefill / train)
# ---------------------------------------------------------------------------

def can_flash_attend(
    s: int, t: int, nh: int, nkv: int, hd: int, interpret: bool
) -> bool:
    if nkv <= 0 or nh % nkv:
        return False
    if interpret:
        return True
    return hd % 128 == 0 and s % 8 == 0 and t % 128 == 0


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _attend_kernel(causal: bool, window: int, interpret: bool, q, k, v):
    return flash_attention_op(
        q, k, v, causal=causal, window=window, interpret=interpret
    )


def _attend_fwd(causal, window, interpret, q, k, v):
    return _attend_kernel(causal, window, interpret, q, k, v), (q, k, v)


def _attend_bwd(causal, window, interpret, res, ct):
    from repro.models.attention import chunked_gqa_attend  # import cycle

    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: chunked_gqa_attend(q_, k_, v_, causal, window),
        q, k, v,
    )
    return vjp(ct)


_attend_kernel.defvjp(_attend_fwd, _attend_bwd)


def attend(
    q: jax.Array,       # (B, S, H, hd)
    k: jax.Array,       # (B, T, K, hd)
    v: jax.Array,       # (B, T, K, hd)
    *,
    causal: bool = True,
    window: int = 0,
    interpret: bool | None = None,
) -> jax.Array:
    """Flash GQA attention (queries cover the tail of the key range). The
    caller is responsible for gating on ``can_flash_attend``."""
    interpret = default_interpret() if interpret is None else interpret
    return _attend_kernel(causal, window, interpret, q, k, v)


# ---------------------------------------------------------------------------
# flash decode (one token vs the KV cache)
# ---------------------------------------------------------------------------

def can_flash_decode(
    t: int, nh: int, nkv: int, hd: int, interpret: bool
) -> bool:
    if nkv <= 0 or nh % nkv:
        return False
    if interpret:
        return True
    return hd % 128 == 0 and t % 128 == 0


def decode_attend(
    q: jax.Array,        # (B, H, hd) — the single new token's queries
    k: jax.Array,        # (B, T, K, hd)
    v: jax.Array,        # (B, T, K, hd)
    valid: jax.Array,    # (B, T) int32/bool cache-slot validity
    *,
    interpret: bool | None = None,
) -> jax.Array:
    interpret = default_interpret() if interpret is None else interpret
    return flash_decode_op(q, k, v, valid.astype(jnp.int32), interpret=interpret)


def decode_attend_partials(
    q: jax.Array,        # (B, H, hd)
    k: jax.Array,        # (B, T, K, hd) — one shard's KV slice
    v: jax.Array,
    valid: jax.Array,    # (B, T)
    *,
    interpret: bool | None = None,
):
    """Unnormalized fp32 ``(acc, m, l)`` over this KV slice. Partials over
    disjoint slices LSE-merge exactly — ``merge_decode_partials`` does it
    across a named mesh axis (the sequence-parallel decode path)."""
    interpret = default_interpret() if interpret is None else interpret
    return flash_decode_partials_op(
        q, k, v, valid.astype(jnp.int32), interpret=interpret
    )


# the cross-shard LSE merge (psum/pmax over a named axis) — kernel partials
# ride the collective as-is, no per-shard normalization round-trip.
merge_decode_partials = merge_partials


# ---------------------------------------------------------------------------
# paged flash decode (block-table KV walk over a shared page pool)
# ---------------------------------------------------------------------------

def can_flash_decode_paged(
    page_size: int, nh: int, nkv: int, hd: int, interpret: bool
) -> bool:
    """Compiled paged decode streams (page_size, hd) k/v panels: last dims
    must hit the MXU/VPU native tiles. Interpret mode takes anything."""
    if nkv <= 0 or nh % nkv:
        return False
    if interpret:
        return True
    return hd % 128 == 0 and page_size % 128 == 0


def decode_attend_paged(
    q: jax.Array,             # (B, H, hd)
    pool_k: jax.Array,        # (P, page_size, K, hd) shared page pool
    pool_v: jax.Array,
    block_tables: jax.Array,  # (B, NB) int32 logical block -> physical page
    lengths: jax.Array,       # (B,) int32 live context per request
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """Paged decode: walks only ``ceil(lengths / page_size)`` live pages per
    request (dead blocks clamp to the last live page and skip the MXU), so
    decode HBM traffic tracks actual context, not the pool/max_seq size."""
    interpret = default_interpret() if interpret is None else interpret
    return flash_decode_paged_op(
        q, pool_k, pool_v,
        block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
        interpret=interpret,
    )
