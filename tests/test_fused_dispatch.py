"""Fused dispatch-gather/scatter GMM paths + EP capacity/placement bugfixes.

Covers:
* ``gmm_gather`` / ``gmm_dual_act_gather`` parity vs the gather oracles and
  vs the padded ragged kernels over the same buckets (the fused prologue
  must be a pure layout change, not a math change);
* ``gmm_scatter`` (compact combine leg): the scatter epilogue's live rows
  vs the padded-then-compacted oracle, the partial-tile spill overwrite
  contract, ``compact_out`` FFN parity + gradients, and the metadata-driven
  ``combine_from_rows`` vs ``bucket_combine`` (NaN-poisoned gap rows must
  never leak — balanced and heavily skewed routing, with capacity drops);
* ``gmm_fused_ffn`` (fully-fused single-kernel FFN): bit-closeness to the
  gather+scatter two-kernel composition and the einsum oracle on live rows
  (balanced, skewed with capacity drops, decode shapes, NaN-poisoned
  dropped rows), gradient parity through the custom_vjp, the
  fused-requires-compact contract, and the VMEM-bound fallback;
* ``validate_ep_token_split``: the prefill floor-truncation guard
  (non-divisible ``b*s`` used to under-size ``bucket_capacity`` or die
  inside shard_map with an opaque spec error);
* ``dispatch_metadata`` consistency with ``bucket_dispatch`` (same slots/
  keep/counts; rebuilding padded buffers from the metadata reproduces the
  scattered buffers bit-for-bit);
* the decode ownership sentinel (``total_slots + 1``) vs the dispatch trash
  row (``n_buckets``) off-by-one interplay — sentinels must never alias the
  trash row, leak into counts, or reach the combine;
* capacity **ceiling** regression: perfectly balanced routing at
  ``capacity_factor == 1.0`` drops zero copies (floor truncation used to);
* ``tiled_placement`` consistency: every default replica slot of expert e
  holds expert e's weight row under the ``jnp.tile`` slot expansion
  ``moe_ep`` uses for non-divisible ``n_rows / ep``;
* end-to-end MoE parity (EP and ESP, prefill and decode shapes) with the
  fused path on vs the reference paths, plus gradients through the fused
  ``custom_vjp``.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke
from repro.kernels import registry
from repro.kernels.gmm.ops import (
    expert_ffn_fused,
    expert_ffn_gather,
    expert_ffn_gather_compact,
    expert_ffn_ragged,
    gmm_gather_op,
    gmm_scatter_op,
)
from repro.kernels.gmm.ragged import gmm_dual_act_gather
from repro.kernels.gmm.ref import (
    expert_ffn_compact_ref,
    expert_ffn_fused_ref,
    expert_ffn_gather_ref,
    gather_buckets_ref,
    gmm_ragged_ref,
    gmm_ref,
    scatter_rows_ref,
)
from repro.kernels.tiling import ROW_ALIGN, round_up
from repro.models.moe import moe_dense, moe_ep, moe_esp, moe_init
from repro.parallel.collectives import (
    aligned_offsets,
    bucket_capacity,
    bucket_combine,
    bucket_dispatch,
    combine_from_rows,
    dispatch_metadata,
    kept_counts,
    tiled_placement,
    validate_ep_token_split,
)
from repro.parallel.ctx import ParallelCtx

RNG = jax.random.PRNGKey(0)

CTX_ON = ParallelCtx(capacity_factor=8.0, use_kernels=True)
CTX_OFF = ParallelCtx(capacity_factor=8.0, use_kernels=False)


def _segments(counts, pad_between=0):
    """Row count and offsets of bucket-contiguous segments, each starting
    on a ``ROW_ALIGN``-aligned row (the gather/scatter kernels' layout
    contract), with at least ``pad_between`` junk rows between segments."""
    counts = np.asarray(counts)
    offsets = np.zeros(len(counts), np.int32)
    pos = 0
    for i, c in enumerate(counts):
        offsets[i] = pos = round_up(pos)
        pos += int(c) + pad_between
    return pos, jnp.asarray(offsets, jnp.int32)


# ---------------------------------------------------------------------------
# gather kernels vs oracles and vs the padded ragged kernels
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "g,cap,d,f,counts",
    [
        (4, 16, 8, 12, [3, 0, 16, 5]),          # zero group, full group
        (3, 96, 64, 160, [1, 95, 40]),          # non-128 C/D/F
        (2, 128, 128, 256, [128, 17]),          # MXU-native tiles
        (5, 24, 48, 40, [24, 0, 0, 7, 2]),      # multiple empty groups
    ],
)
def test_gmm_gather_matches_ref(g, cap, d, f, counts):
    r, offsets = _segments(counts)
    ks = jax.random.split(RNG, 2)
    x = jax.random.normal(ks[0], (max(r, 1), d))
    w = jax.random.normal(ks[1], (g, d, f)) * 0.1
    gs = jnp.asarray(counts, jnp.int32)
    out = gmm_gather_op(x, w, offsets, gs, capacity=cap)
    buckets = gather_buckets_ref(x, offsets, gs, cap)
    ref = gmm_ragged_ref(buckets, w, gs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)
    # Rows past each group's count are exactly zero.
    outn = np.asarray(out)
    for gi, cnt in enumerate(counts):
        assert (outn[gi, cnt:] == 0).all()


def test_gmm_gather_noncontiguous_segments():
    """Offsets need not tile the array: junk rows between segments (and
    NaNs in them) must never reach the output — the prologue only gathers
    addressed rows, dead tiles skip the DMA entirely."""
    g, cap, d, f = 3, 16, 8, 12
    counts = [5, 0, 9]
    r, offsets = _segments(counts, pad_between=3)
    ks = jax.random.split(RNG, 2)
    x = jax.random.normal(ks[0], (r, d))
    # Poison every row not inside a live segment.
    live = np.zeros(r, bool)
    for off, cnt in zip(np.asarray(offsets), counts):
        live[off : off + cnt] = True
    x = jnp.where(jnp.asarray(live)[:, None], x, jnp.nan)
    w = jax.random.normal(ks[1], (g, d, f)) * 0.1
    gs = jnp.asarray(counts, jnp.int32)
    out = np.asarray(gmm_gather_op(x, w, offsets, gs, capacity=cap))
    # NaN rows CAN be touched by a partial tile over-read, but only the
    # masked tail — kept rows must be finite and exact.
    ref = np.asarray(
        gmm_ragged_ref(
            gather_buckets_ref(jnp.nan_to_num(x), offsets, gs, cap), w, gs
        )
    )
    for gi, cnt in enumerate(counts):
        assert np.isfinite(out[gi, :cnt]).all()
        np.testing.assert_allclose(out[gi, :cnt], ref[gi, :cnt], rtol=1e-5, atol=1e-5)


def test_gmm_gather_segment_at_array_end():
    """The last segment's partial tile over-reads past the end of the flat
    array — the wrapper's row padding must absorb it (regression for the
    clamped-DMA tile shift)."""
    g, cap, d, f = 2, 128, 16, 24
    counts = [100, 133 - 104]  # second segment (aligned start 104) ends at R
    r, offsets = _segments(counts)
    assert r == 133  # deliberately not a multiple of any tile size
    ks = jax.random.split(RNG, 2)
    x = jax.random.normal(ks[0], (r, d))
    w = jax.random.normal(ks[1], (g, d, f)) * 0.1
    gs = jnp.asarray(counts, jnp.int32)
    out = gmm_gather_op(x, w, offsets, gs, capacity=cap)
    ref = gmm_ragged_ref(gather_buckets_ref(x, offsets, gs, cap), w, gs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("gpw", [2, 3])
def test_gmm_gather_groups_per_weight(gpw):
    gw, cap, d, f = 2, 16, 24, 20
    g = gw * gpw
    counts = [(3 * i) % (cap + 1) for i in range(g)]
    r, offsets = _segments(counts)
    ks = jax.random.split(RNG, 2)
    x = jax.random.normal(ks[0], (r, d))
    w = jax.random.normal(ks[1], (gw, d, f)) * 0.1
    gs = jnp.asarray(counts, jnp.int32)
    out = gmm_gather_op(x, w, offsets, gs, capacity=cap, groups_per_weight=gpw)
    buckets = gather_buckets_ref(x, offsets, gs, cap)
    ref = gmm_ragged_ref(buckets, w, gs, groups_per_weight=gpw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_dual_act_gather_matches_ref():
    g, cap, d, f = 4, 32, 16, 24
    counts = [0, 32, 5, 19]
    r, offsets = _segments(counts)
    ks = jax.random.split(RNG, 3)
    x = jax.random.normal(ks[0], (r, d))
    wg = jax.random.normal(ks[1], (g, d, f)) * 0.1
    wu = jax.random.normal(ks[2], (g, d, f)) * 0.1
    gs = jnp.asarray(counts, jnp.int32)
    out = gmm_dual_act_gather(x, wg, wu, offsets, gs, capacity=cap, interpret=True)
    buckets = gather_buckets_ref(x, offsets, gs, cap)
    mask = (jnp.arange(cap)[None, :] < gs[:, None])[..., None]
    ref = (jax.nn.silu(gmm_ref(buckets, wg)) * gmm_ref(buckets, wu)) * mask
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_expert_ffn_gather_matches_padded_ragged_and_einsum():
    """The fused path must agree with BOTH the padded ragged kernel over the
    materialized buckets AND the pure einsum reference."""
    gw, gpw, cap, d, f = 2, 2, 16, 8, 12
    counts = [7, 0, 16, 2]
    r, offsets = _segments(counts)
    ks = jax.random.split(RNG, 4)
    x = jax.random.normal(ks[0], (r, d))
    wg = jax.random.normal(ks[1], (gw, d, f)) * 0.1
    wu = jax.random.normal(ks[2], (gw, d, f)) * 0.1
    wd = jax.random.normal(ks[3], (gw, f, d)) * 0.1
    gs = jnp.asarray(counts, jnp.int32)
    fused = expert_ffn_gather(
        x, wg, wu, wd, offsets, gs, capacity=cap, groups_per_weight=gpw
    )
    buckets = gather_buckets_ref(x, offsets, gs, cap)
    padded = expert_ffn_ragged(buckets, wg, wu, wd, gs, groups_per_weight=gpw)
    einsum = expert_ffn_gather_ref(x, wg, wu, wd, offsets, gs, cap, gpw)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(padded), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(einsum), rtol=1e-5, atol=1e-5)


def test_expert_ffn_from_rows_grad_matches_ref():
    """Kernel forward + reference backward (custom_vjp) through the fused
    gather — gradients must flow back onto the flat rows and the weights."""
    g, cap, d, f = 3, 16, 8, 12
    counts = [4, 16, 0]
    r, offsets = _segments(counts)
    ks = jax.random.split(RNG, 4)
    x = jax.random.normal(ks[0], (r, d))
    wg = jax.random.normal(ks[1], (g, d, f)) * 0.1
    wu = jax.random.normal(ks[2], (g, d, f)) * 0.1
    wd = jax.random.normal(ks[3], (g, f, d)) * 0.1
    gs = jnp.asarray(counts, jnp.int32)

    def loss(fn, x, wg, wu, wd):
        return (fn(x, wg, wu, wd) ** 2).sum()

    kern = lambda *a: registry.expert_ffn_from_rows(
        *a, offsets, gs, capacity=cap, enabled=True
    )
    ref = lambda *a: expert_ffn_gather_ref(*a, offsets, gs, cap)
    gk = jax.grad(loss, argnums=(1, 2, 3, 4))(kern, x, wg, wu, wd)
    gr = jax.grad(loss, argnums=(1, 2, 3, 4))(ref, x, wg, wu, wd)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# scatter-epilogue kernel (compact combine leg)
# ---------------------------------------------------------------------------

def _live_rows(counts, offsets, r):
    live = np.zeros(r, bool)
    for off, cnt in zip(np.asarray(offsets), np.asarray(counts)):
        live[off : off + cnt] = True
    return live


@pytest.mark.parametrize(
    "g,cap,d,f,counts",
    [
        (4, 16, 8, 12, [3, 0, 16, 5]),          # zero group, full group
        (3, 96, 64, 160, [1, 95, 40]),          # non-128 C/D/F
        (2, 128, 128, 256, [128, 17]),          # MXU-native tiles
        (5, 24, 48, 40, [24, 0, 0, 7, 2]),      # multiple empty groups
    ],
)
def test_gmm_scatter_matches_ref(g, cap, d, f, counts):
    """The scatter epilogue compacts the down-projection back to flat rows
    at the per-bucket offsets — live rows must match the padded ragged
    matmul scattered by the reference."""
    r, offsets = _segments(counts)
    ks = jax.random.split(RNG, 2)
    x = jax.random.normal(ks[0], (g, cap, d))
    w = jax.random.normal(ks[1], (g, d, f)) * 0.1
    gs = jnp.asarray(counts, jnp.int32)
    out_rows = max(r, 1)
    out = np.asarray(gmm_scatter_op(x, w, offsets, gs, out_rows=out_rows))
    ref = np.asarray(
        scatter_rows_ref(gmm_ragged_ref(x, w, gs), offsets, gs, out_rows)
    )
    live = _live_rows(counts, offsets, out_rows)
    np.testing.assert_allclose(out[live], ref[live], rtol=1e-5, atol=1e-5)


def test_gmm_scatter_partial_tile_spill_is_overwritten():
    """A partial tile's bm-row store spills masked zeros past its bucket's
    segment into the *next* bucket's rows; grid-ordered stores must
    overwrite the spill with the later bucket's real rows (the
    overlap-overwrite contract)."""
    g, cap, d, f = 3, 16, 8, 12
    counts = [5, 3, 7]  # none a multiple of the 16-row tile
    r, offsets = _segments(counts)
    # Aligned segments start 8 rows apart, inside the previous bucket's
    # 16-row tile: every later bucket's rows receive an earlier spill.
    assert (np.diff(np.asarray(offsets)) < cap).all()
    ks = jax.random.split(RNG, 2)
    x = jax.random.normal(ks[0], (g, cap, d))
    w = jax.random.normal(ks[1], (g, d, f)) * 0.1
    gs = jnp.asarray(counts, jnp.int32)
    out = np.asarray(gmm_scatter_op(x, w, offsets, gs, out_rows=r))
    ref = np.asarray(scatter_rows_ref(gmm_ragged_ref(x, w, gs), offsets, gs, r))
    live = _live_rows(counts, offsets, r)
    np.testing.assert_allclose(out[live], ref[live], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("gpw", [2, 3])
def test_gmm_scatter_groups_per_weight(gpw):
    gw, cap, d, f = 2, 16, 24, 20
    g = gw * gpw
    counts = [(3 * i) % (cap + 1) for i in range(g)]
    r, offsets = _segments(counts)
    ks = jax.random.split(RNG, 2)
    x = jax.random.normal(ks[0], (g, cap, d))
    w = jax.random.normal(ks[1], (gw, d, f)) * 0.1
    gs = jnp.asarray(counts, jnp.int32)
    out = np.asarray(
        gmm_scatter_op(x, w, offsets, gs, out_rows=r, groups_per_weight=gpw)
    )
    ref = np.asarray(
        scatter_rows_ref(
            gmm_ragged_ref(x, w, gs, groups_per_weight=gpw), offsets, gs, r
        )
    )
    live = _live_rows(counts, offsets, r)
    np.testing.assert_allclose(out[live], ref[live], rtol=1e-5, atol=1e-5)


def test_expert_ffn_compact_matches_padded_live_rows():
    """compact_out must be a pure layout change: live rows equal the padded
    gather path's bucket rows (and the pure-jnp compact oracle)."""
    gw, gpw, cap, d, f = 2, 2, 16, 8, 12
    counts = [7, 0, 16, 2]
    r, offsets = _segments(counts)
    ks = jax.random.split(RNG, 4)
    x = jax.random.normal(ks[0], (r, d))
    wg = jax.random.normal(ks[1], (gw, d, f)) * 0.1
    wu = jax.random.normal(ks[2], (gw, d, f)) * 0.1
    wd = jax.random.normal(ks[3], (gw, f, d)) * 0.1
    gs = jnp.asarray(counts, jnp.int32)
    compact = np.asarray(
        expert_ffn_gather_compact(
            x, wg, wu, wd, offsets, gs, capacity=cap, groups_per_weight=gpw
        )
    )
    padded = np.asarray(
        expert_ffn_gather(
            x, wg, wu, wd, offsets, gs, capacity=cap, groups_per_weight=gpw
        )
    )
    oracle = np.asarray(
        expert_ffn_compact_ref(x, wg, wu, wd, offsets, gs, cap, gpw)
    )
    for gi, cnt in enumerate(counts):
        off = int(np.asarray(offsets)[gi])
        np.testing.assert_allclose(
            compact[off : off + cnt], padded[gi, :cnt], rtol=1e-5, atol=1e-5
        )
        np.testing.assert_allclose(
            compact[off : off + cnt], oracle[off : off + cnt],
            rtol=1e-5, atol=1e-5,
        )


def test_expert_ffn_compact_grad_matches_ref():
    """Kernel forward + reference backward (custom_vjp) through the compact
    scatter epilogue — gradients flow back onto the flat rows/weights."""
    g, cap, d, f = 3, 16, 8, 12
    counts = [4, 16, 0]
    r, offsets = _segments(counts)
    ks = jax.random.split(RNG, 4)
    x = jax.random.normal(ks[0], (r, d))
    wg = jax.random.normal(ks[1], (g, d, f)) * 0.1
    wu = jax.random.normal(ks[2], (g, d, f)) * 0.1
    wd = jax.random.normal(ks[3], (g, f, d)) * 0.1
    gs = jnp.asarray(counts, jnp.int32)
    live = jnp.asarray(_live_rows(counts, offsets, r))[:, None]

    def loss(fn, x, wg, wu, wd):
        # Square only live rows: gap rows are unspecified kernel output.
        return ((fn(x, wg, wu, wd) * live) ** 2).sum()

    kern = lambda *a: registry.expert_ffn_from_rows(
        *a, offsets, gs, capacity=cap, enabled=True, compact_out=True
    )
    ref = lambda *a: expert_ffn_compact_ref(*a, offsets, gs, cap)
    gk = jax.grad(loss, argnums=(1, 2, 3, 4))(kern, x, wg, wu, wd)
    gr = jax.grad(loss, argnums=(1, 2, 3, 4))(ref, x, wg, wu, wd)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# fully-fused single-kernel FFN (gmm_fused_ffn: VMEM-resident hidden tile)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "g,cap,d,f,counts",
    [
        (4, 16, 8, 12, [16, 16, 16, 16]),       # balanced: every bucket full
        (4, 16, 8, 12, [3, 0, 16, 5]),          # skewed: zero + full groups
        (3, 96, 64, 160, [1, 95, 40]),          # non-128 C/D/F, partial tiles
        (2, 128, 128, 256, [128, 17]),          # MXU-native tiles
        (6, 8, 8, 12, [8, 8, 3, 0, 1, 2]),      # decode-ish: tiny capacity
    ],
    ids=["balanced", "skewed", "partial_tiles", "mxu_native", "decode"],
)
def test_gmm_fused_ffn_matches_pair_and_oracle(g, cap, d, f, counts):
    """The single-kernel fused FFN must be bit-close to the gather+scatter
    two-kernel composition AND the pure-jnp oracle on every live row — the
    VMEM-resident hidden tile is an execution-strategy change only."""
    r, offsets = _segments(counts)
    ks = jax.random.split(RNG, 4)
    x = jax.random.normal(ks[0], (max(r, 1), d))
    wg = jax.random.normal(ks[1], (g, d, f)) * 0.1
    wu = jax.random.normal(ks[2], (g, d, f)) * 0.1
    wd = jax.random.normal(ks[3], (g, f, d)) * 0.1
    gs = jnp.asarray(counts, jnp.int32)
    fused = np.asarray(
        expert_ffn_fused(x, wg, wu, wd, offsets, gs, capacity=cap)
    )
    pair = np.asarray(
        expert_ffn_gather_compact(x, wg, wu, wd, offsets, gs, capacity=cap)
    )
    oracle = np.asarray(expert_ffn_fused_ref(x, wg, wu, wd, offsets, gs, cap))
    for gi, cnt in enumerate(counts):
        off = int(np.asarray(offsets)[gi])
        np.testing.assert_allclose(
            fused[off : off + cnt], pair[off : off + cnt], rtol=1e-5, atol=1e-5
        )
        np.testing.assert_allclose(
            fused[off : off + cnt], oracle[off : off + cnt], rtol=1e-5, atol=1e-5
        )


@pytest.mark.parametrize("gpw", [2, 3])
def test_gmm_fused_ffn_groups_per_weight(gpw):
    """EP layout: gpw consecutive buckets (per-source-rank raggedness) share
    one weight row through all three fused matmuls."""
    gw, cap, d, f = 2, 16, 24, 20
    g = gw * gpw
    counts = [(3 * i) % (cap + 1) for i in range(g)]
    r, offsets = _segments(counts)
    ks = jax.random.split(RNG, 4)
    x = jax.random.normal(ks[0], (r, d))
    wg = jax.random.normal(ks[1], (gw, d, f)) * 0.1
    wu = jax.random.normal(ks[2], (gw, d, f)) * 0.1
    wd = jax.random.normal(ks[3], (gw, f, d)) * 0.1
    gs = jnp.asarray(counts, jnp.int32)
    fused = np.asarray(
        expert_ffn_fused(
            x, wg, wu, wd, offsets, gs, capacity=cap, groups_per_weight=gpw
        )
    )
    oracle = np.asarray(
        expert_ffn_fused_ref(x, wg, wu, wd, offsets, gs, cap, gpw)
    )
    live = _live_rows(counts, offsets, r)
    np.testing.assert_allclose(fused[live], oracle[live], rtol=1e-5, atol=1e-5)


def test_gmm_fused_ffn_nan_poisoned_gap_rows():
    """Junk rows between segments (dropped copies' would-be rows) may hold
    NaN; the fused kernel's gather prologue only addresses live segments, a
    partial tile's over-read of a NaN row must stay confined to masked tail
    rows, and every live output row stays finite and exact."""
    g, cap, d, f = 3, 16, 8, 12
    counts = [5, 0, 9]
    r, offsets = _segments(counts, pad_between=3)
    ks = jax.random.split(RNG, 4)
    x = jax.random.normal(ks[0], (r, d))
    live = _live_rows(counts, offsets, r)
    x = jnp.where(jnp.asarray(live)[:, None], x, jnp.nan)
    wg = jax.random.normal(ks[1], (g, d, f)) * 0.1
    wu = jax.random.normal(ks[2], (g, d, f)) * 0.1
    wd = jax.random.normal(ks[3], (g, f, d)) * 0.1
    gs = jnp.asarray(counts, jnp.int32)
    out = np.asarray(expert_ffn_fused(x, wg, wu, wd, offsets, gs, capacity=cap))
    ref = np.asarray(
        expert_ffn_fused_ref(
            jnp.nan_to_num(x), wg, wu, wd, offsets, gs, cap
        )
    )
    assert np.isfinite(out[live]).all(), "NaN gap rows leaked into live rows"
    np.testing.assert_allclose(out[live], ref[live], rtol=1e-5, atol=1e-5)


def test_expert_ffn_from_rows_fused_grad_matches_ref():
    """Kernel forward + reference backward (custom_vjp) through the fully-
    fused kernel — gradients flow back onto the flat rows and all three
    weight stacks exactly as through the compact oracle."""
    g, cap, d, f = 3, 16, 8, 12
    counts = [4, 16, 0]
    r, offsets = _segments(counts)
    ks = jax.random.split(RNG, 4)
    x = jax.random.normal(ks[0], (r, d))
    wg = jax.random.normal(ks[1], (g, d, f)) * 0.1
    wu = jax.random.normal(ks[2], (g, d, f)) * 0.1
    wd = jax.random.normal(ks[3], (g, f, d)) * 0.1
    gs = jnp.asarray(counts, jnp.int32)
    live = jnp.asarray(_live_rows(counts, offsets, r))[:, None]

    def loss(fn, x, wg, wu, wd):
        # Square only live rows: gap rows are unspecified kernel output.
        return ((fn(x, wg, wu, wd) * live) ** 2).sum()

    kern = lambda *a: registry.expert_ffn_from_rows(
        *a, offsets, gs, capacity=cap, enabled=True, compact_out=True, fused=True
    )
    ref = lambda *a: expert_ffn_fused_ref(*a, offsets, gs, cap)
    gk = jax.grad(loss, argnums=(1, 2, 3, 4))(kern, x, wg, wu, wd)
    gr = jax.grad(loss, argnums=(1, 2, 3, 4))(ref, x, wg, wu, wd)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


def test_fused_requires_compact_out():
    """fused=True without compact_out is a contract error (the single
    kernel always emits the flat layout), not a silent fallback."""
    x = jnp.zeros((8, 8))
    w = jnp.zeros((2, 8, 8))
    offs = jnp.zeros((2,), jnp.int32)
    gs = jnp.zeros((2,), jnp.int32)
    with pytest.raises(ValueError, match="compact_out"):
        registry.expert_ffn_from_rows(
            x, w, w, jnp.zeros((2, 8, 8)), offs, gs, capacity=8, fused=True
        )


def test_fused_vmem_gate_falls_back_to_pair(monkeypatch):
    """Shapes past the fused kernel's VMEM bound (large model dim) must
    fall back to the gather+scatter pair — same results, no error. The
    bound is shrunk so the test doesn't need a genuinely huge tensor."""
    assert not registry.can_gmm_fused(16, 16384, 128, True)
    g, cap, d, f = 3, 16, 8, 12
    counts = [4, 16, 0]
    r, offsets = _segments(counts)
    ks = jax.random.split(RNG, 4)
    x = jax.random.normal(ks[0], (r, d))
    wg = jax.random.normal(ks[1], (g, d, f)) * 0.1
    wu = jax.random.normal(ks[2], (g, d, f)) * 0.1
    wd = jax.random.normal(ks[3], (g, f, d)) * 0.1
    gs = jnp.asarray(counts, jnp.int32)
    want = registry.expert_ffn_from_rows(
        x, wg, wu, wd, offsets, gs, capacity=cap, compact_out=True, fused=True
    )
    monkeypatch.setattr(registry, "FUSED_FFN_MAX_DOWN_DIM", d - 1)
    assert not registry.can_gmm_fused(cap, d, f, True)
    got = registry.expert_ffn_from_rows(
        x, wg, wu, wd, offsets, gs, capacity=cap, compact_out=True, fused=True
    )
    live = _live_rows(counts, offsets, r)
    np.testing.assert_allclose(
        np.asarray(got)[live], np.asarray(want)[live], rtol=1e-5, atol=1e-5
    )


def test_fused_skewed_pipeline_parity_with_drops():
    """Full dispatch->fused-FFN->combine pipeline at heavily skewed routing
    with capacity overflow, single kernel vs the padded reference pipeline
    — the same cell as test_compact_combine_skewed_parity but through
    gmm_fused_ffn."""
    e, cap, d, f = 6, 8, 8, 12
    n, k = 40, 2
    ks = jax.random.split(RNG, 6)
    hot = jax.random.bernoulli(ks[0], 0.7, (n, k))
    ids = jnp.where(hot, 0, jax.random.randint(ks[1], (n, k), 0, 3))
    x = jax.random.normal(ks[2], (n, d))
    w = jax.random.uniform(ks[3], (n, k))
    wg = jax.random.normal(ks[4], (e, d, f)) * 0.1
    wu = jax.random.normal(ks[5], (e, d, f)) * 0.1
    wd = jax.random.normal(ks[0], (e, f, d)) * 0.1
    row_ids, offsets, counts, slots, keep = dispatch_metadata(ids, e, cap)
    assert int(counts[0]) == cap and not bool(keep.all())  # overflow happened
    bufs, slots_b, keep_b = bucket_dispatch(x, ids, e, cap)
    y_pad = expert_ffn_ragged(bufs, wg, wu, wd, counts)
    ref = bucket_combine(y_pad, ids, slots_b, keep_b, w)
    y_flat = registry.expert_ffn_from_rows(
        x[row_ids], wg, wu, wd, offsets, counts,
        capacity=cap, enabled=True, compact_out=True, fused=True,
    )
    out = combine_from_rows(y_flat, offsets[ids] + slots, keep, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# metadata-driven combine (combine_from_rows)
# ---------------------------------------------------------------------------

def test_combine_from_rows_matches_bucket_combine():
    """Gathering the compacted rows through offsets[bucket] + slot must
    reproduce the padded bucket_combine exactly — including capacity drops
    — even when every gap row of the flat array is NaN-poisoned (dropped
    copies select zero before any arithmetic)."""
    n, k, buckets, cap = 24, 2, 5, 4   # cap small -> real capacity drops
    ks = jax.random.split(RNG, 3)
    ids = jax.random.randint(ks[0], (n, k), 0, buckets)
    w = jax.random.uniform(ks[1], (n, k))
    row_ids, offsets, counts, slots, keep = dispatch_metadata(ids, buckets, cap)
    assert not bool(keep.all())  # the cell must exercise drops
    y_pad = jax.random.normal(ks[2], (buckets, cap, 8))
    # Build the compact array bucket_combine's padded buffer corresponds
    # to, poisoning every row outside a live segment.
    r = n * k
    live = _live_rows(np.asarray(counts), np.asarray(offsets), r)
    y_flat = jnp.full((r, 8), jnp.nan)
    for g in range(buckets):
        off, cnt = int(offsets[g]), int(counts[g])
        y_flat = y_flat.at[off : off + cnt].set(y_pad[g, :cnt])
    assert not bool(jnp.isnan(y_flat[jnp.asarray(live)]).any())
    ref = bucket_combine(y_pad, ids, slots, keep, w)
    out = combine_from_rows(y_flat, offsets[ids] + slots, keep, w)
    assert bool(jnp.isfinite(out).all()), "gap garbage leaked into combine"
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6, atol=1e-6)


def test_compact_combine_skewed_parity():
    """Full dispatch->FFN->combine pipeline parity, padded vs compact, at
    heavily skewed routing with capacity overflow — the regime the compact
    leg exists for."""
    e, cap, d, f = 6, 8, 8, 12
    n, k = 40, 2
    ks = jax.random.split(RNG, 6)
    # ~70% of copies hammer expert 0; a couple of experts stay empty.
    hot = jax.random.bernoulli(ks[0], 0.7, (n, k))
    ids = jnp.where(hot, 0, jax.random.randint(ks[1], (n, k), 0, 3))
    x = jax.random.normal(ks[2], (n, d))
    w = jax.random.uniform(ks[3], (n, k))
    wg = jax.random.normal(ks[4], (e, d, f)) * 0.1
    wu = jax.random.normal(ks[5], (e, d, f)) * 0.1
    wd = jax.random.normal(ks[0], (e, f, d)) * 0.1
    row_ids, offsets, counts, slots, keep = dispatch_metadata(ids, e, cap)
    assert int(counts[0]) == cap and not bool(keep.all())  # overflow happened
    # Padded pipeline (the fallback the fused path must match bit-for-bit).
    bufs, slots_b, keep_b = bucket_dispatch(x, ids, e, cap)
    y_pad = expert_ffn_ragged(bufs, wg, wu, wd, counts)
    ref = bucket_combine(y_pad, ids, slots_b, keep_b, w)
    # Compact pipeline: gather-prologue FFN + scatter epilogue + metadata
    # combine. No padded buffer on either side.
    y_flat = registry.expert_ffn_from_rows(
        x[row_ids], wg, wu, wd, offsets, counts,
        capacity=cap, enabled=True, compact_out=True,
    )
    out = combine_from_rows(y_flat, offsets[ids] + slots, keep, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# EP token-split validation (floor-truncation regression)
# ---------------------------------------------------------------------------

def test_validate_ep_token_split():
    # valid splits pass silently
    validate_ep_token_split(4, 8, 2, 4, decode=False)
    validate_ep_token_split(8, 1, 2, 4, decode=True)
    validate_ep_token_split(3, 4, 1, 4, decode=False)   # n_batch=1: any b
    # prefill: seq must divide the EP axis (b*s // (n_batch*ep) would
    # floor-truncate and under-size bucket_capacity)
    with pytest.raises(ValueError, match="seq=7 does not divide ep=4"):
        validate_ep_token_split(4, 7, 2, 4, decode=False)
    # batch must divide the batch axes, prefill and decode alike
    with pytest.raises(ValueError, match="batch=3"):
        validate_ep_token_split(3, 8, 2, 4, decode=False)
    with pytest.raises(ValueError, match="batch=5"):
        validate_ep_token_split(5, 1, 2, 4, decode=True)
    # decode never splits the sequence
    validate_ep_token_split(4, 1, 2, 4, decode=True)


# ---------------------------------------------------------------------------
# dispatch_metadata vs bucket_dispatch
# ---------------------------------------------------------------------------

def test_dispatch_metadata_matches_bucket_dispatch():
    n, k, buckets, cap = 20, 2, 6, 5
    ks = jax.random.split(RNG, 2)
    x = jax.random.normal(ks[0], (n, 8))
    ids = jax.random.randint(ks[1], (n, k), 0, buckets)
    bufs, slots_b, keep_b = bucket_dispatch(x, ids, buckets, cap)
    row_ids, offsets, counts, slots_m, keep_m = dispatch_metadata(ids, buckets, cap)
    np.testing.assert_array_equal(np.asarray(slots_b), np.asarray(slots_m))
    np.testing.assert_array_equal(np.asarray(keep_b), np.asarray(keep_m))
    np.testing.assert_array_equal(
        np.asarray(counts), np.asarray(kept_counts(ids, keep_b, buckets))
    )
    # Rebuilding the padded buffers from the compacted metadata reproduces
    # the scattered buffers exactly (same rows, same positions).
    rows = x[row_ids]
    rebuilt = np.asarray(gather_buckets_ref(rows, offsets, counts, cap))
    np.testing.assert_array_equal(rebuilt, np.asarray(bufs))


def test_dispatch_metadata_compacted_order_is_deterministic():
    """Within a bucket, earlier tokens come first in the compacted order —
    the same 'earlier tokens win' rule bucket_dispatch packs with."""
    ids = jnp.asarray([[1], [0], [1], [0], [1]], jnp.int32)
    row_ids, offsets, counts, _, _ = dispatch_metadata(ids, 2, 8)
    np.testing.assert_array_equal(np.asarray(counts), [2, 3])
    # Segments start on ROW_ALIGN-aligned rows (the gather/scatter DMA
    # contract): bucket 0 at row 0, bucket 1 at row 8.
    np.testing.assert_array_equal(np.asarray(offsets), [0, ROW_ALIGN])
    row_ids = np.asarray(row_ids)
    np.testing.assert_array_equal(row_ids[0:2], [1, 3])
    np.testing.assert_array_equal(row_ids[ROW_ALIGN : ROW_ALIGN + 3], [0, 2, 4])


# ---------------------------------------------------------------------------
# decode ownership sentinel vs trash row (off-by-one pin)
# ---------------------------------------------------------------------------

def test_decode_sentinel_never_aliases_trash_row():
    """The decode path marks unowned copies with ``total_slots + 1`` while
    ``bucket_dispatch`` keeps one sacrificial row at index ``n_buckets``
    and drops on ``flat_b < n_buckets``. Pin the interplay: the sentinel
    (and the trash index itself) must never land in a real bucket, never
    count toward kept_counts / metadata counts, and never reach combine."""
    n, k, buckets, cap = 8, 2, 4, 4
    ks = jax.random.split(RNG, 2)
    x = jax.random.normal(ks[0], (n, 8))
    base = jax.random.randint(ks[1], (n, k), 0, buckets)
    owned = (jnp.arange(n) % 2) == 0
    for sentinel in (buckets, buckets + 1):  # trash row itself + decode value
        ids = jnp.where(owned[:, None], base, sentinel)
        bufs, slots, keep = bucket_dispatch(x, ids, buckets, cap)
        # Unowned copies are dropped, owned copies under capacity kept.
        assert not bool(keep[~owned].any()), sentinel
        # Buffers only ever contain owned-token rows.
        ref_bufs, _, _ = bucket_dispatch(
            jnp.where(owned[:, None], x, 0.0), jnp.where(owned[:, None], base, sentinel),
            buckets, cap,
        )
        np.testing.assert_array_equal(np.asarray(bufs), np.asarray(ref_bufs))
        # Counts (both implementations) see only owned copies.
        counts_kept = kept_counts(ids, keep, buckets)
        _, _, counts_meta, _, keep_m = dispatch_metadata(ids, buckets, cap)
        owned_ids = base[owned]
        expect = np.minimum(
            np.bincount(np.asarray(owned_ids).reshape(-1), minlength=buckets), cap
        )
        np.testing.assert_array_equal(np.asarray(counts_kept), expect)
        np.testing.assert_array_equal(np.asarray(counts_meta), expect)
        np.testing.assert_array_equal(np.asarray(keep), np.asarray(keep_m))
        # Combine: sentinel copies contribute exactly zero.
        out = bucket_combine(bufs, ids, slots, keep, jnp.ones((n, k)))
        assert bool(jnp.all(out[~owned] == 0.0)), sentinel


# ---------------------------------------------------------------------------
# capacity ceiling regression
# ---------------------------------------------------------------------------

def test_bucket_capacity_uses_ceiling():
    # 100 copies over 3 buckets at factor 1.0: floor(33.3) = 33 dropped a
    # copy of a perfectly balanced batch; ceiling allocates 34.
    assert bucket_capacity(50, 2, 1.0, 3) == 34
    assert bucket_capacity(64, 2, 1.0, 4) == 32   # exact division unchanged
    assert bucket_capacity(2, 2, 1.0, 4) == 8     # floor-of-8 keeps smoke shapes


@pytest.mark.parametrize("n_tok,k,buckets", [(50, 2, 3), (33, 1, 5), (100, 2, 7)])
def test_balanced_routing_drops_nothing_at_capacity_one(n_tok, k, buckets):
    """Perfectly balanced routing at capacity_factor == 1.0 must drop zero
    token copies (regression: floor truncation under-allocated)."""
    cap = bucket_capacity(n_tok, k, 1.0, buckets)
    ids = (jnp.arange(n_tok * k) % buckets).reshape(n_tok, k)
    x = jax.random.normal(RNG, (n_tok, 4))
    _, _, keep = bucket_dispatch(x, ids, buckets, cap)
    assert bool(keep.all())
    _, _, counts, _, keep_m = dispatch_metadata(ids, buckets, cap)
    assert bool(keep_m.all())
    assert int(counts.sum()) == n_tok * k


# ---------------------------------------------------------------------------
# tiled placement consistency (non-divisible n_rows / ep)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("e,ep", [(6, 4), (3, 2), (5, 3), (7, 4)])
def test_tiled_placement_consistent_with_tiling(e, ep):
    """Every replica slot the default placement hands out must hold its
    expert's weight row under the ``jnp.tile`` expansion (slot s = row
    s % n_rows), and every physical slot must carry traffic."""
    n_rows = e
    spd = -(-n_rows // ep)
    n_slots = ep * spd
    slot_of, n_replicas = tiled_placement(e, n_rows, n_slots)
    slot_of, n_replicas = np.asarray(slot_of), np.asarray(n_replicas)
    covered = set()
    for eid in range(e):
        assert n_replicas[eid] >= 1
        for r in range(n_replicas[eid]):
            s = slot_of[eid, r]
            assert 0 <= s < n_slots
            assert s % n_rows == eid, (eid, r, s)
            covered.add(int(s))
        # Padding replica columns stay on valid slots for this expert too
        # (choose_slots never reads them, but a stale table must not alias).
        for r in range(n_replicas[eid], slot_of.shape[1]):
            assert slot_of[eid, r] % n_rows == eid
    assert covered == set(range(n_slots)), "idle shadow slots"


def test_tiled_placement_grows_replica_table():
    """More than r_max wrap-arounds (n_slots > 4 * n_rows) must widen the
    replica table, not truncate it — truncation would leave live tiled
    slots idle while they still inflate the capacity denominator."""
    n_experts = n_rows = 2
    n_slots = 10  # expert 0 -> slots {0,2,4,6,8}: 5 replicas > default 4
    slot_of, n_replicas = tiled_placement(n_experts, n_rows, n_slots)
    slot_of, n_replicas = np.asarray(slot_of), np.asarray(n_replicas)
    covered = set()
    for eid in range(n_experts):
        assert n_replicas[eid] == 5
        for r in range(n_replicas[eid]):
            assert slot_of[eid, r] % n_rows == eid
            covered.add(int(slot_of[eid, r]))
    assert covered == set(range(n_slots)), "idle shadow slots"


def test_moe_ep_rejects_underprovisioned_slots():
    """Fewer physical slots than weight rows would silently drop experts —
    moe_ep must refuse with a clear error, not truncate."""
    from repro.launch.mesh import make_mesh

    cfg = dataclasses.replace(
        smoke(get_config("dbrx-132b")), n_experts=3, experts_per_token=2
    )
    mesh = make_mesh((1, 1), ("data", "model"))
    rng = jax.random.PRNGKey(0)
    p = moe_init(rng, cfg)
    x = jax.random.normal(rng, (2, 8, cfg.d_model)) * 0.5
    ctx = ParallelCtx(mesh=mesh, capacity_factor=8.0, use_kernels=False)
    with mesh, pytest.raises(ValueError, match="physical"):
        moe_ep(p, x, cfg, ctx, slots_per_device=2)


def test_moe_ep_non_divisible_rows_single_device(monkeypatch):
    """moe_ep with n_rows % ep != 0 on a 1-device mesh: force the tiled
    branch by passing slots_per_device explicitly, then check parity with
    the dense oracle (tokens must land on slots holding their expert)."""
    from repro.launch.mesh import make_mesh

    cfg = dataclasses.replace(
        smoke(get_config("dbrx-132b")), n_experts=3, experts_per_token=2
    )
    mesh = make_mesh((1, 1), ("data", "model"))
    rng = jax.random.PRNGKey(0)
    p = moe_init(rng, cfg)
    x = jax.random.normal(rng, (2, 8, cfg.d_model)) * 0.5
    dense, _ = moe_dense(p, x, cfg, CTX_OFF)
    for uk in (False, True):
        ctx = ParallelCtx(mesh=mesh, capacity_factor=8.0, use_kernels=uk)
        with mesh:
            # slots_per_device=4 > n_rows=3: wrap-around shadow slots live.
            out, _ = jax.jit(
                lambda p_, x_: moe_ep(p_, x_, cfg, ctx, slots_per_device=4)
            )(p, x)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(dense), rtol=1e-5, atol=1e-5
        )


# ---------------------------------------------------------------------------
# end-to-end MoE parity through the fused path (prefill + decode shapes)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def moe_cfg():
    return dataclasses.replace(
        smoke(get_config("dbrx-132b")), n_experts=4, experts_per_token=2
    )


@pytest.mark.parametrize("shape", [(2, 8), (4, 1)], ids=["prefill", "decode"])
def test_moe_esp_fused_parity(moe_cfg, shape):
    b, s = shape
    rng = jax.random.PRNGKey(0)
    p = moe_init(rng, moe_cfg)
    x = jax.random.normal(rng, (b, s, moe_cfg.d_model)) * 0.5
    off, _ = moe_esp(p, x, moe_cfg, CTX_OFF)
    on, _ = moe_esp(p, x, moe_cfg, CTX_ON)   # mesh=None + kernels -> fused
    np.testing.assert_allclose(np.asarray(on), np.asarray(off), rtol=1e-5, atol=1e-5)
    dense, _ = moe_dense(p, x, moe_cfg, CTX_OFF)
    np.testing.assert_allclose(np.asarray(on), np.asarray(dense), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape", [(2, 8), (2, 1)], ids=["prefill", "decode"])
def test_moe_ep_fused_parity(moe_cfg, shape):
    """EP dispatch on a 1x1 mesh with kernels on takes the fused
    rank-compacted all_to_all path (interpret mode on CPU)."""
    from repro.launch.mesh import make_mesh

    b, s = shape
    mesh = make_mesh((1, 1), ("data", "model"))
    rng = jax.random.PRNGKey(0)
    p = moe_init(rng, moe_cfg)
    x = jax.random.normal(rng, (b, s, moe_cfg.d_model)) * 0.5
    outs = {}
    for name, uk in (("off", False), ("on", True)):
        ctx = ParallelCtx(mesh=mesh, capacity_factor=8.0, use_kernels=uk)
        with mesh:
            outs[name], _ = jax.jit(
                lambda p_, x_, c_=ctx: moe_ep(p_, x_, moe_cfg, c_)
            )(p, x)
    np.testing.assert_allclose(
        np.asarray(outs["on"]), np.asarray(outs["off"]), rtol=1e-5, atol=1e-5
    )
    dense, _ = moe_dense(p, x, moe_cfg, CTX_OFF)
    np.testing.assert_allclose(
        np.asarray(outs["on"]), np.asarray(dense), rtol=1e-5, atol=1e-5
    )


def test_moe_ep_fused_compact_grad(moe_cfg):
    """Gradients through the full fused EP path — compact scatter epilogue
    (custom_vjp), return all_to_all, and metadata combine — must match the
    dense oracle."""
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"))
    rng = jax.random.PRNGKey(0)
    p = moe_init(rng, moe_cfg)
    x = jax.random.normal(rng, (2, 8, moe_cfg.d_model)) * 0.5
    ctx = ParallelCtx(mesh=mesh, capacity_factor=8.0, use_kernels=True)
    gd = jax.grad(lambda p_: moe_dense(p_, x, moe_cfg, CTX_OFF)[0].sum())(p)
    with mesh:
        ge = jax.jit(
            jax.grad(lambda p_: moe_ep(p_, x, moe_cfg, ctx)[0].sum())
        )(p)
    for key in ("w_gate", "w_up", "w_down", "router"):
        np.testing.assert_allclose(
            np.asarray(gd[key]), np.asarray(ge[key]), rtol=1e-4, atol=1e-5
        )


# ---------------------------------------------------------------------------
# row-alignment contract of the dispatch layouts
# ---------------------------------------------------------------------------

def test_aligned_offsets_round_each_segment():
    counts = jnp.asarray([[5, 0, 8, 9], [1, 16, 3, 0]], jnp.int32)
    off = np.asarray(aligned_offsets(counts, axis=1))
    np.testing.assert_array_equal(off, [[0, 8, 8, 16], [0, 8, 24, 32]])


@pytest.mark.parametrize("path", ["esp_no_mesh", "ep_mesh_1x1"])
def test_dispatch_layouts_give_aligned_offsets(path, monkeypatch):
    """Both producers of flat-row offsets start every bucket segment on a
    ROW_ALIGN-aligned row — the promise the gather/scatter kernels'
    ``pl.multiple_of`` hints make to the TPU compiler — at skewed routing
    where the raw counts are not multiples of 8, and still match the dense
    oracle."""
    from repro.launch.mesh import make_mesh

    cfg = dataclasses.replace(
        smoke(get_config("dbrx-132b")), n_experts=4, experts_per_token=2
    )
    rng = jax.random.PRNGKey(3)
    p = moe_init(rng, cfg)
    p["router"] = p["router"].at[:, 0].multiply(6.0)   # expert 0 runs hot
    x = jax.random.normal(rng, (3, 7, cfg.d_model)) * 0.5
    dense, _ = moe_dense(p, x, cfg, CTX_OFF)

    seen = []
    orig = registry.expert_ffn_from_rows

    def spy(rows, wg, wu, wd, offsets, group_sizes, **kw):
        jax.debug.callback(
            lambda o, c: seen.append((np.asarray(o), np.asarray(c))),
            offsets, group_sizes,
        )
        return orig(rows, wg, wu, wd, offsets, group_sizes, **kw)

    monkeypatch.setattr(registry, "expert_ffn_from_rows", spy)
    if path == "esp_no_mesh":
        out, _ = moe_esp(p, x, cfg, CTX_ON)
    else:
        mesh = make_mesh((1, 1), ("data", "model"))
        ctx = ParallelCtx(mesh=mesh, capacity_factor=8.0, use_kernels=True)
        with mesh:
            out, _ = jax.jit(lambda p_, x_: moe_ep(p_, x_, cfg, ctx))(p, x)
    jax.block_until_ready(out)
    assert seen, "the flat-row FFN never ran"
    assert any((c % ROW_ALIGN).any() for _, c in seen)   # raw counts ragged
    for off, _ in seen:
        assert (off % ROW_ALIGN == 0).all(), off
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(dense), rtol=1e-5, atol=1e-5
    )
