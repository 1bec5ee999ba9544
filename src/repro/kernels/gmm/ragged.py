"""Count-aware (ragged) grouped matmul Pallas kernels.

``gmm_ragged`` / ``gmm_dual_act_ragged`` take bucket-padded ``(G, C, K)``
rows whose per-group token counts (``group_sizes``, int32 ``(G,)``) ride in
as a scalar-prefetch operand (SMEM). They stream weights, not tiles: the
grid is ``(G, C/bc, N/bn)`` with the column panels innermost, each step
multiplies the group's resident ``(bc, K)`` rows by full-contraction
``(K, bn)`` weight panels, and the live rows go through the MXU in
``bm``-row tiles (rows past the count are zero, tiles past it skip the
MXU). Blocks come from the operand shapes and a VMEM budget
(``ragged_blocks``): at DBRX widths a call is a few hundred grid steps
that each move megabytes, so the per-step overhead is noise and the call
runs at the HBM rate of one read of each live group's weights.

An empty group (or a row block past its count) moves nothing: the input
and weight index maps read the scalar-prefetched pins (``ragged_pins``)
and point a dead step at blocks the pipeline already holds or fetches next,
so Pallas issues no copy for it. An all-empty call reads one block. MXU
FLOPs scale with ``sum(ceil(count / bm) * bm)``, weight bytes with the
live groups: on the skewed routing the paper targets (fig. 6), and for a
prefill lane with no chunk, that is the bulk of the padded FFN cost.

``groups_per_weight`` (gpw) lets ``gpw`` consecutive x-groups share one
weight row — the layout both MoE paths produce after flattening:

* EP after the all_to_all: ``(slots_per_device, ep, cap, d)`` flattens to
  ``G = slots_per_device * ep`` groups, weight row ``gi // ep``;
* ESP local buckets: ``(E, n_batch_groups, cap, d)`` flattens to
  ``G = E * n_groups`` groups, weight row ``gi // n_groups``.

Consecutive live groups that share a weight row each fetch its panels:
the column loop restarts for every group.

``gmm_gather`` / ``gmm_dual_act_gather`` go one step further and fuse the
*dispatch* into the kernel prologue: instead of consuming pre-packed
``(G, capacity, d)`` buffers, they read token rows straight out of a flat
``(R, d)`` activations array in which bucket ``g``'s rows sit contiguously
at ``[offsets[g], offsets[g] + counts[g])`` (the compacted order
``dispatch_metadata`` emits). Both ``offsets`` and ``counts`` ride as
scalar-prefetch operands; each live row-tile issues one dynamic-offset DMA
(``pltpu.make_async_copy`` from the ANY-space flat array into a VMEM
scratch tile) and feeds the MXU from the scratch. The gathers are
**double-buffered** against the MXU: two scratch tiles + two DMA
semaphores, and every grid step starts the *next* live tile's copy before
waiting on its own, so the fetch for tile ``t+1`` overlaps tile ``t``'s
matmul (``_gather_pipeline``). The padded bucket tensor is never
materialized in HBM — that's the one dispatch round-trip per MoE layer the
fused path removes. Dead tiles skip the DMA *and* the MXU, so the ragged
FLOP/byte accounting is unchanged.

``gmm_fused_ffn`` chains all three of the above into **one** kernel: the
gather prologue reads flat ``(R, d)`` token rows, the dual-activation
SwiGLU front half produces per-tile hidden activations
``silu(x @ wg) * (x @ wu)`` — shape ``(bm, F)``, computed ``(bm, bf)``
block by block and consumed *immediately* by the down-projection into a
``(bm, d)`` VMEM output accumulator — and the scatter epilogue stores the
finished row-tile back at the same per-bucket offsets. The bucket-padded
``(G, capacity, F)`` hidden tensor between the front half and the down
projection, the last padded intermediate on the expert hot path, **never
exists in HBM**: the only HBM tensors the kernel touches are the flat
input rows, the three weight stacks, and the flat compact output. The
grid is ``(G, capacity/bm, F/bf, d/bk)``; for each row-tile the ``jf``
loop walks hidden blocks (each fully reduced over ``k`` before the next
starts) and ``out_acc += h_jf @ wd[jf]`` retires each hidden block the
step it is produced, so peak VMEM holds one ``(bm, bf)`` hidden block
plus the ``(bm, d)`` accumulator — independent of ``F``. The gather DMA
double-buffering, the store serialization, and the partial-tile
spill-overwrite contract (``capacity % bm == 0`` keeps padded spans
inside their rank segment) are inherited unchanged from the pieces below.

``gmm_scatter`` is the *combine*-leg mirror of the gather prologue: a
ragged grouped matmul (the expert down-projection) whose **epilogue writes
result tiles back at the same per-bucket offsets** — a dynamic-offset
store DMA from a VMEM staging tile into a flat ``(out_rows, d)`` ANY-space
output, so the bucket-padded ``(G, capacity, d)`` FFN *output* buffer is
never written to HBM either. Live tiles mask their tail rows to zero
before storing; a partial tile's ``bm``-row store may therefore spill
zeros past its bucket's segment, which is safe because (contract) each
bucket's padded span ``[offsets[g], offsets[g] + ceil(count/bm)*bm)`` may
only overlap rows of *later-in-grid* buckets — those overwrite the spill
with their real rows (stores are issued and completed in grid order: each
store waits for the previous one before starting, so a store is in flight
across all the MXU work until the next store point). Both layouts the MoE
paths produce satisfy the contract: offsets are non-decreasing in grid
order per rank segment and ``capacity % bm == 0`` keeps padded spans
inside their segment. Rows not covered by any live ``(bucket, position)``
pair are *unwritten garbage* — the metadata-driven combine
(``collectives.combine_from_rows``) never addresses them. Dead tiles skip
the MXU and the store, so at skewed routing the combine-leg HBM bytes
track routed tokens, exactly like the dispatch leg.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import ROW_ALIGN, fit_block, round_up


# Scoped VMEM the two ragged kernels ask Mosaic for (``vmem_limit_bytes``;
# the default scope is 16 MiB), and what the block rule may plan for. Set
# from the TPU compiler on a described v5e: it accepts a scope of the
# core's whole 128 MiB, and a plan that ``ragged_vmem_bytes`` puts 1 MiB
# under the scope (a 112 MiB plan compiles in a 120 MiB scope; a 144.6 MiB
# plan is refused, "Used 143.94M of 128.00M"). The budget keeps 16 MiB of
# the core for Mosaic's own scratch.
RAGGED_VMEM_LIMIT = 128 * 2**20
RAGGED_VMEM_BUDGET = 112 * 2**20
# Row tile of the MXU pass: live rows are computed in tiles of this height,
# so a bucket's rows past its count cost no MXU work beyond one tile.
RAGGED_ROW_TILE = 32
# Widest weight panel: at DBRX widths a (6144, 1536) bf16 panel is 18 MiB,
# tens of microseconds of HBM time a grid step, so the per-step overhead
# (~0.3 us) is noise, while the panels still double-buffer inside the
# budget at every bucket height. On a v5e the pair ran at 674-702 GB/s
# with panels of 512 or 1536 and row tiles of 32, 64 or 128; 1536 columns
# and 32 rows was the fastest by 2%.
RAGGED_MAX_COLS = 1536


def ragged_vmem_bytes(rows, k, bn, bm, itemsize, n_w):
    """VMEM a ragged kernel's step holds: the double-buffered ``(rows, k)``
    input block, ``n_w`` ``(k, bn)`` weight panels and the ``(rows, bn)``
    output block, plus a row tile and its fp32 products."""
    blocks = 2 * itemsize * (rows * k + n_w * k * bn + rows * bn)
    return blocks + itemsize * bm * k + 4 * bm * bn * (n_w + 1)


def ragged_blocks(c, k, n, itemsize, n_w):
    """Blocks ``(bm, bc, bn)`` for ``(G, c, k) @ n_w x (G', k, n)``.

    ``bm``: the row tile, the largest divisor of ``c`` up to
    ``RAGGED_ROW_TILE`` that fills whole packed sublane tiles (8 rows of
    fp32, 16 of bf16), else ``c``. ``bc``: the rows held resident per step,
    all ``c`` where they fit, so a group's rows are read once for all its
    column panels. ``bn``: the widest column panel up to
    ``RAGGED_MAX_COLS`` (``n`` itself, or a multiple of 128 dividing it)
    whose blocks fit ``RAGGED_VMEM_BUDGET``. The contraction is never split:
    each panel is a full ``(k, bn)`` slab, fetched once per live step.
    """
    align = 32 // itemsize
    bm = next(
        (t for t in range(min(c, RAGGED_ROW_TILE), 0, -1)
         if c % t == 0 and t % align == 0),
        c,
    )
    cols = [n] if n <= RAGGED_MAX_COLS else []
    cols += [t for t in range(RAGGED_MAX_COLS, 0, -128) if t < n and n % t == 0]
    cols = cols or [fit_block(n, RAGGED_MAX_COLS)]  # interpret-mode widths
    for bc in (t for t in range(c, 0, -bm) if c % t == 0):
        for bn in cols:
            if ragged_vmem_bytes(bc, k, bn, bm, itemsize, n_w) <= RAGGED_VMEM_BUDGET:
                return bm, bc, bn
    raise ValueError(f"no ragged GMM blocks fit VMEM for c={c} k={k} n={n}")


def ragged_pins(group_sizes, c, bc, nj):
    """Where each ``(group, row block)`` step reads its blocks.

    Step ``s = g * (c // bc) + r`` is live when ``r * bc < count[g]`` and
    reads its own blocks. A dead step reads the blocks the pipeline holds
    anyway, so Pallas issues no copy for it: the next live step's first
    blocks, or after the last live step that step's last blocks (one
    fixed block when nothing is live). Returns ``(src, col)``: the step
    whose input and weight blocks step ``s`` reads, and the weight panel
    it pins to (``-1``: a live step walks its own panels).
    """
    nr = c // bc
    s = group_sizes.shape[0] * nr
    idx = jnp.arange(s, dtype=jnp.int32)
    live = (idx % nr) * bc < jnp.repeat(group_sizes.astype(jnp.int32), nr)
    nxt = jax.lax.cummin(jnp.where(live, idx, s), reverse=True)
    prv = jax.lax.cummax(jnp.where(live, idx, -1))
    ahead = nxt < s
    src = jnp.where(ahead, nxt, jnp.maximum(prv, 0))
    col = jnp.where(live, -1, jnp.where(ahead | (prv < 0), 0, nj - 1))
    return src.astype(jnp.int32), col.astype(jnp.int32)


def ragged_block_maps(nr, gpw):
    """Index maps of the input block and the weight panels at grid step
    ``(gi, r, j)``, reading ``ragged_pins``' ``src`` and ``col``."""

    def x_map(gi, r, j, gs, src, col):
        s = src[gi * nr + r]
        return s // nr, s % nr, 0

    def w_map(gi, r, j, gs, src, col):
        s = gi * nr + r
        return src[s] // nr // gpw, 0, jnp.where(col[s] < 0, j, col[s])

    return x_map, w_map


def _fill_rows(gs_ref, o_ref, tile, *, bm):
    """Write one ``(bc, bn)`` output block of step ``(g, r)``: the live
    rows one ``bm`` tile at a time (``tile(r0)`` gives their fp32 values),
    rows past the group's count zero. Tiles past the count issue no MXU
    pass, and a dead step only writes zeros."""
    bc = o_ref.shape[1]
    rows = jnp.clip(gs_ref[pl.program_id(0)] - pl.program_id(1) * bc, 0, bc)

    @pl.when(rows < bc)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    def body(t, carry):
        r0 = pl.multiple_of(t * bm, bm)
        y = tile(r0)
        ids = r0 + jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
        o_ref[0, pl.ds(r0, bm), :] = jnp.where(ids < rows, y, 0.0).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, pl.cdiv(rows, bm), body, 0)


def _ragged_call(kernel, x, weights, group_sizes, gpw, interpret):
    """Shared ``pallas_call`` of the two ragged kernels: grid
    ``(G, c // bc, n // bn)`` with the column panels innermost; inputs and
    weight panels follow ``ragged_pins``; outputs ``(G, c, n)``."""
    g, c, k = x.shape
    n = weights[0].shape[-1]
    assert g == weights[0].shape[0] * gpw, (g, weights[0].shape, gpw)
    bm, bc, bn = ragged_blocks(c, k, n, x.dtype.itemsize, len(weights))
    nr, nj = c // bc, n // bn
    src, col = ragged_pins(group_sizes, c, bc, nj)

    x_map, w_map = ragged_block_maps(nr, gpw)
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(g, nr, nj),
        in_specs=[pl.BlockSpec((1, bc, k), x_map)]
        + [pl.BlockSpec((1, k, bn), w_map) for _ in weights],
        out_specs=pl.BlockSpec((1, bc, bn), lambda gi, r, j, *_: (gi, r, j)),
    )
    return pl.pallas_call(
        functools.partial(kernel, bm=bm),
        grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((g, c, n), x.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=RAGGED_VMEM_LIMIT),
        interpret=interpret,
    )(group_sizes.astype(jnp.int32), src, col, x, *weights)


def _dot(a, b):
    return jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def _ragged_kernel(gs_ref, src_ref, col_ref, x_ref, w_ref, o_ref, *, bm):
    _fill_rows(
        gs_ref, o_ref, lambda r0: _dot(x_ref[0, pl.ds(r0, bm), :], w_ref[0]),
        bm=bm,
    )


def gmm_ragged(
    x: jax.Array,            # (G, C, D)
    w: jax.Array,            # (G // gpw, D, F)
    group_sizes: jax.Array,  # (G,) int32 — valid leading rows per group
    *,
    groups_per_weight: int = 1,
    interpret: bool = False,
) -> jax.Array:
    """y[g, :count_g] = x[g, :count_g] @ w[g // gpw]; tail rows are zero."""
    return _ragged_call(
        _ragged_kernel, x, (w,), group_sizes, groups_per_weight, interpret
    )


def _ragged_dual_kernel(
    gs_ref, src_ref, col_ref, x_ref, wg_ref, wu_ref, o_ref, *, bm
):
    def tile(r0):
        xt = x_ref[0, pl.ds(r0, bm), :]
        return jax.nn.silu(_dot(xt, wg_ref[0])) * _dot(xt, wu_ref[0])

    _fill_rows(gs_ref, o_ref, tile, bm=bm)


def gmm_dual_act_ragged(
    x: jax.Array,
    wg: jax.Array,
    wu: jax.Array,
    group_sizes: jax.Array,
    *,
    groups_per_weight: int = 1,
    interpret: bool = False,
) -> jax.Array:
    """h[g] = silu(x@wg) * (x@wu) on the first count_g rows; tail is zero."""
    return _ragged_call(
        _ragged_dual_kernel, x, (wg, wu), group_sizes, groups_per_weight,
        interpret,
    )


# ---------------------------------------------------------------------------
# fused dispatch-gather variants (flat rows + per-bucket offsets)
# ---------------------------------------------------------------------------

def _pad_rows(x: jax.Array, bm: int) -> tuple[jax.Array, int]:
    """Pad the flat rows to a ``ROW_ALIGN`` multiple plus ``bm`` zero rows,
    so a tile DMA starting anywhere inside a valid segment never runs off
    the end of the array (partial tiles over-read up to ``bm - 1`` rows;
    the tail is masked in the epilogue) and the clamp bound ``r_pad - bm``
    is itself aligned."""
    r_pad = round_up(x.shape[0]) + bm
    return jnp.pad(x, ((0, r_pad - x.shape[0]), (0, 0))), r_pad


def _row_start(off_ref, gi, mi, bm, r_max):
    """First flat row of bucket ``gi``'s row-tile ``mi``. Layout contract:
    every ``offsets[g]`` is a multiple of ``ROW_ALIGN`` (the dispatch
    layouts start each segment on one — ``collectives.aligned_offsets``)
    and so are ``bm`` and ``r_max``, which lets Mosaic prove the DMA's
    tile alignment. An unaligned offset would read the wrong rows."""
    start = jnp.minimum(off_ref[gi] + mi * bm, r_max)
    return pl.multiple_of(start, ROW_ALIGN)


def _gather_dma(x_any, xbuf, sem, off_ref, gi, mi, k, slot, *, bm, bk, r_max):
    """Descriptor for the (bm, bk) row-tile DMA of bucket ``gi`` into
    double-buffer ``slot`` (start and wait happen at the call sites)."""
    start = _row_start(off_ref, gi, mi, bm, r_max)
    return pltpu.make_async_copy(
        x_any.at[pl.ds(start, bm), pl.ds(k * bk, bk)],
        xbuf.at[slot],
        sem.at[slot],
    )


def _gather_pipeline(gs_ref, *, g, nmi, nj, nk, bm):
    """Double-buffer bookkeeping shared by the gather kernels.

    Returns ``(live, t, nxt)``: this step's liveness, its linear step index
    (slot = ``t % 2``), and — for the *next* grid step in row-major order —
    ``(gi, mi, k, live)`` so its DMA can start before this step waits on
    its own (overlapping the copy with this step's MXU work)."""
    gi = pl.program_id(0)
    mi = pl.program_id(1)
    j = pl.program_id(2)
    k = pl.program_id(3)
    live = mi * bm < gs_ref[gi]
    t = ((gi * nmi + mi) * nj + j) * nk + k

    k1 = k + 1
    kr = (k1 == nk).astype(jnp.int32)
    k1 = k1 * (1 - kr)
    j1 = j + kr
    jr = (j1 == nj).astype(jnp.int32)
    j1 = j1 * (1 - jr)
    mi1 = mi + jr
    mr = (mi1 == nmi).astype(jnp.int32)
    mi1 = mi1 * (1 - mr)
    gi1 = gi + mr
    has_next = gi1 < g
    next_live = has_next & (mi1 * bm < gs_ref[jnp.minimum(gi1, g - 1)])
    return live, t, (gi1, mi1, k1, next_live)


def _gather_kernel(
    off_ref, gs_ref, x_any, w_ref, o_ref, acc_ref, xbuf, sem,
    *, g: int, nmi: int, nj: int, nk: int, bm: int, bk: int, r_max: int,
):
    gi = pl.program_id(0)
    mi = pl.program_id(1)
    k = pl.program_id(3)
    count = gs_ref[gi]
    live, t, (gi1, mi1, k1, next_live) = _gather_pipeline(
        gs_ref, g=g, nmi=nmi, nj=nj, nk=nk, bm=bm
    )
    dma = functools.partial(
        _gather_dma, x_any, xbuf, sem, off_ref, bm=bm, bk=bk, r_max=r_max
    )

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Warm-up: the very first grid step fetches its own tile.
    @pl.when((t == 0) & live)
    def _():
        dma(gi, mi, k, 0).start()

    # Pipeline: start the next live step's gather into the other buffer
    # before waiting on ours — the copy overlaps this step's matmul.
    @pl.when(next_live)
    def _():
        dma(gi1, mi1, k1, (t + 1) % 2).start()

    @pl.when(live)
    def _():
        dma(gi, mi, k, t % 2).wait()
        acc_ref[...] += jax.lax.dot_general(
            xbuf[t % 2],
            w_ref[0],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(k == nk - 1)
    def _():
        rows = mi * bm + jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape, 0)
        o_ref[0, ...] = jnp.where(rows < count, acc_ref[...], 0.0).astype(
            o_ref.dtype
        )


def gmm_gather(
    x: jax.Array,            # (R, D) flat token rows, bucket-contiguous
    w: jax.Array,            # (G // gpw, D, F)
    offsets: jax.Array,      # (G,) int32 — bucket g's first row in x
    group_sizes: jax.Array,  # (G,) int32 — bucket g's row count
    *,
    capacity: int,
    groups_per_weight: int = 1,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """y[g, :count_g] = x[offsets[g] : offsets[g]+count_g] @ w[g // gpw].

    Output is bucket-padded ``(G, capacity, F)`` with zero tails (identical
    contract to ``gmm_ragged``), but the input is the *flat* compacted rows
    — no ``(G, capacity, D)`` buffer ever exists.
    """
    r, d = x.shape
    f = w.shape[-1]
    gpw = groups_per_weight
    g = w.shape[0] * gpw
    assert offsets.shape == (g,), (offsets.shape, g)
    bm, bn, bk = fit_block(capacity, bm), fit_block(f, bn), fit_block(d, bk)
    x, r_pad = _pad_rows(x, bm)
    nk = d // bk
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(g, capacity // bm, f // bn, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, bk, bn), lambda gi, i, j, k, off, gs: (gi // gpw, k, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda gi, i, j, k, off, gs: (gi, i, j)),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((2, bm, bk), x.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _gather_kernel,
            g=g, nmi=capacity // bm, nj=f // bn, nk=nk,
            bm=bm, bk=bk, r_max=r_pad - bm,
        ),
        grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((g, capacity, f), x.dtype),
        interpret=interpret,
    )(offsets.astype(jnp.int32), group_sizes.astype(jnp.int32), x, w)


def _gather_dual_kernel(
    off_ref, gs_ref, x_any, wg_ref, wu_ref, o_ref, accg_ref, accu_ref, xbuf, sem,
    *, g: int, nmi: int, nj: int, nk: int, bm: int, bk: int, r_max: int,
):
    gi = pl.program_id(0)
    mi = pl.program_id(1)
    k = pl.program_id(3)
    count = gs_ref[gi]
    live, t, (gi1, mi1, k1, next_live) = _gather_pipeline(
        gs_ref, g=g, nmi=nmi, nj=nj, nk=nk, bm=bm
    )
    dma = functools.partial(
        _gather_dma, x_any, xbuf, sem, off_ref, bm=bm, bk=bk, r_max=r_max
    )

    @pl.when(k == 0)
    def _():
        accg_ref[...] = jnp.zeros_like(accg_ref)
        accu_ref[...] = jnp.zeros_like(accu_ref)

    @pl.when((t == 0) & live)
    def _():
        dma(gi, mi, k, 0).start()

    @pl.when(next_live)
    def _():
        dma(gi1, mi1, k1, (t + 1) % 2).start()

    @pl.when(live)
    def _():
        dma(gi, mi, k, t % 2).wait()
        dims = (((1,), (0,)), ((), ()))
        accg_ref[...] += jax.lax.dot_general(
            xbuf[t % 2], wg_ref[0], dims, preferred_element_type=jnp.float32
        )
        accu_ref[...] += jax.lax.dot_general(
            xbuf[t % 2], wu_ref[0], dims, preferred_element_type=jnp.float32
        )

    @pl.when(k == nk - 1)
    def _():
        rows = mi * bm + jax.lax.broadcasted_iota(jnp.int32, accg_ref.shape, 0)
        h = jax.nn.silu(accg_ref[...]) * accu_ref[...]
        o_ref[0, ...] = jnp.where(rows < count, h, 0.0).astype(o_ref.dtype)


def gmm_dual_act_gather(
    x: jax.Array,            # (R, D) flat token rows, bucket-contiguous
    wg: jax.Array,           # (G // gpw, D, F)
    wu: jax.Array,           # (G // gpw, D, F)
    offsets: jax.Array,      # (G,)
    group_sizes: jax.Array,  # (G,)
    *,
    capacity: int,
    groups_per_weight: int = 1,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """h[g] = silu(rows_g @ wg) * (rows_g @ wu) with the fused gather
    prologue; rows_g are read from the flat array via per-bucket offsets."""
    r, d = x.shape
    f = wg.shape[-1]
    gpw = groups_per_weight
    g = wg.shape[0] * gpw
    assert offsets.shape == (g,), (offsets.shape, g)
    bm, bn, bk = fit_block(capacity, bm), fit_block(f, bn), fit_block(d, bk)
    x, r_pad = _pad_rows(x, bm)
    nk = d // bk
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(g, capacity // bm, f // bn, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, bk, bn), lambda gi, i, j, k, off, gs: (gi // gpw, k, j)),
            pl.BlockSpec((1, bk, bn), lambda gi, i, j, k, off, gs: (gi // gpw, k, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda gi, i, j, k, off, gs: (gi, i, j)),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((2, bm, bk), x.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _gather_dual_kernel,
            g=g, nmi=capacity // bm, nj=f // bn, nk=nk,
            bm=bm, bk=bk, r_max=r_pad - bm,
        ),
        grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((g, capacity, f), x.dtype),
        interpret=interpret,
    )(offsets.astype(jnp.int32), group_sizes.astype(jnp.int32), x, wg, wu)


# ---------------------------------------------------------------------------
# fused compact-scatter variant (flat-row output at per-bucket offsets)
# ---------------------------------------------------------------------------

def _scatter_store(o_any, obuf, sem, off_ref, gi, mi, j, *, bm, bn, r_max):
    """Descriptor for the (bm, bn) result-tile store of bucket ``gi`` row-
    tile ``mi`` / column block ``j`` into the flat output (start and wait
    happen at the call sites; the clamp only guards bogus offsets — live
    tiles of a well-formed layout never hit it)."""
    start = _row_start(off_ref, gi, mi, bm, r_max)
    return pltpu.make_async_copy(
        obuf,
        o_any.at[pl.ds(start, bm), pl.ds(j * bn, bn)],
        sem,
    )


def _scatter_kernel(
    off_ref, gs_ref, x_ref, w_ref, o_any, acc_ref, obuf, pend, sem,
    *, nsteps: int, nk: int, bm: int, bn: int, r_max: int,
):
    gi = pl.program_id(0)
    mi = pl.program_id(1)
    j = pl.program_id(2)
    k = pl.program_id(3)
    count = gs_ref[gi]
    live = mi * bm < count
    t = ((gi * pl.num_programs(1) + mi) * pl.num_programs(2) + j) * nk + k
    store = functools.partial(
        _scatter_store, o_any, obuf, sem, off_ref, bm=bm, bn=bn, r_max=r_max
    )

    @pl.when(t == 0)
    def _():
        pend[0] = 0  # no store in flight yet

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _():
        acc_ref[...] += jax.lax.dot_general(
            x_ref[0],
            w_ref[0],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    # Epilogue: stage the masked tile in VMEM and DMA it to the flat
    # output at the bucket's offset. Stores are serialized against each
    # other (wait the previous store before reusing the staging tile),
    # which both frees the buffer and guarantees grid-order completion —
    # the overlap-overwrite contract in the module docstring — while each
    # store still overlaps all MXU work up to the next store point.
    @pl.when((k == nk - 1) & live)
    def _():
        @pl.when(pend[0] == 1)
        def _():
            store(pend[1], pend[2], pend[3]).wait()

        rows = mi * bm + jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape, 0)
        obuf[...] = jnp.where(rows < count, acc_ref[...], 0.0).astype(obuf.dtype)
        store(gi, mi, j).start()
        pend[0] = 1
        pend[1] = gi
        pend[2] = mi
        pend[3] = j

    # Drain: the final grid step waits out the last in-flight store.
    @pl.when((t == nsteps - 1) & (pend[0] == 1))
    def _():
        store(pend[1], pend[2], pend[3]).wait()
        pend[0] = 0


def gmm_scatter(
    x: jax.Array,            # (G, C, D) bucket-padded rows (ragged fill)
    w: jax.Array,            # (G // gpw, D, F)
    offsets: jax.Array,      # (G,) int32 — bucket g's first output row
    group_sizes: jax.Array,  # (G,) int32 — bucket g's live row count
    *,
    out_rows: int,
    groups_per_weight: int = 1,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """out[offsets[g] : offsets[g]+count_g] = x[g, :count_g] @ w[g // gpw].

    The compact mirror of ``gmm_gather``: same grouped matmul, but the
    epilogue scatters result tiles into a flat ``(out_rows, F)`` array at
    the scalar-prefetched per-bucket offsets instead of emitting the
    padded ``(G, capacity, F)`` tensor. Output rows outside every live
    segment are unspecified (zero where a partial tile spilled, garbage
    where never written) — callers gather exclusively through the
    dispatch metadata. See the module docstring for the non-overlap
    contract on ``offsets``.
    """
    g, c, d = x.shape
    f = w.shape[-1]
    gpw = groups_per_weight
    assert g == w.shape[0] * gpw, (g, w.shape, gpw)
    assert offsets.shape == (g,), (offsets.shape, g)
    bm, bn, bk = fit_block(c, bm), fit_block(f, bn), fit_block(d, bk)
    nk = d // bk
    nmi, nj = c // bm, f // bn
    # A partial tile's spill never runs off the end; the clamp bound
    # out_pad - bm stays ROW_ALIGN-aligned.
    out_pad = round_up(out_rows) + bm
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(g, nmi, nj, nk),
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda gi, i, j, k, off, gs: (gi, i, k)),
            pl.BlockSpec((1, bk, bn), lambda gi, i, j, k, off, gs: (gi // gpw, k, j)),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, bn), x.dtype),
            pltpu.SMEM((4,), jnp.int32),
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _scatter_kernel,
            nsteps=g * nmi * nj * nk, nk=nk,
            bm=bm, bn=bn, r_max=out_pad - bm,
        ),
        grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((out_pad, f), x.dtype),
        interpret=interpret,
    )(offsets.astype(jnp.int32), group_sizes.astype(jnp.int32), x, w)
    return out[:out_rows]


# ---------------------------------------------------------------------------
# fully-fused SwiGLU expert FFN (gather prologue + VMEM hidden + scatter)
# ---------------------------------------------------------------------------

def _fused_ffn_kernel(
    off_ref, gs_ref, x_any, wg_ref, wu_ref, wd_ref, o_any,
    accg_ref, accu_ref, out_ref, xbuf, gsem, obuf, pend, osem,
    *, g: int, nmi: int, nj: int, nk: int, nsteps: int,
    bm: int, bk: int, dn: int, r_max_in: int, r_max_out: int,
):
    gi = pl.program_id(0)
    mi = pl.program_id(1)
    jf = pl.program_id(2)
    k = pl.program_id(3)
    count = gs_ref[gi]
    live, t, (gi1, mi1, k1, next_live) = _gather_pipeline(
        gs_ref, g=g, nmi=nmi, nj=nj, nk=nk, bm=bm
    )
    gather = functools.partial(
        _gather_dma, x_any, xbuf, gsem, off_ref, bm=bm, bk=bk, r_max=r_max_in
    )
    store = functools.partial(
        _scatter_store, o_any, obuf, osem, off_ref, bm=bm, bn=dn, r_max=r_max_out
    )

    @pl.when(t == 0)
    def _():
        pend[0] = 0  # no store in flight yet

    # A fresh row-tile: reset the (bm, dn) output accumulator. It survives
    # the whole (jf, k) loop nest — one full hidden row per token row is
    # reduced into it without ever leaving VMEM.
    @pl.when((jf == 0) & (k == 0))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(k == 0)
    def _():
        accg_ref[...] = jnp.zeros_like(accg_ref)
        accu_ref[...] = jnp.zeros_like(accu_ref)

    # Gather prologue: identical double-buffered pipeline to the
    # stand-alone gather kernels (warm-up fetch + next-step prefetch).
    @pl.when((t == 0) & live)
    def _():
        gather(gi, mi, k, 0).start()

    @pl.when(next_live)
    def _():
        gather(gi1, mi1, k1, (t + 1) % 2).start()

    @pl.when(live)
    def _():
        gather(gi, mi, k, t % 2).wait()
        dims = (((1,), (0,)), ((), ()))
        accg_ref[...] += jax.lax.dot_general(
            xbuf[t % 2], wg_ref[0], dims, preferred_element_type=jnp.float32
        )
        accu_ref[...] += jax.lax.dot_general(
            xbuf[t % 2], wu_ref[0], dims, preferred_element_type=jnp.float32
        )

    # Hidden block jf is fully reduced: apply the dual activation and
    # retire it straight into the down-projection accumulator. The cast to
    # the I/O dtype reproduces the unfused pair bit-for-bit (there the
    # hidden tensor round-trips HBM at the I/O dtype); masked tail rows
    # stay exactly zero so the final store's spill contract holds.
    @pl.when((k == nk - 1) & live)
    def _():
        rows = mi * bm + jax.lax.broadcasted_iota(jnp.int32, accg_ref.shape, 0)
        h = jnp.where(
            rows < count, jax.nn.silu(accg_ref[...]) * accu_ref[...], 0.0
        ).astype(obuf.dtype)
        out_ref[...] += jax.lax.dot_general(
            h,
            wd_ref[0],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    # Scatter epilogue (last hidden block of a live row-tile): stage the
    # finished (bm, dn) tile and DMA it to the flat output at the bucket's
    # offset — same serialized-store bookkeeping as ``gmm_scatter`` (wait
    # the previous store before reusing the staging tile; completion order
    # == grid order, which is what makes partial-tile spills safe).
    @pl.when((jf == nj - 1) & (k == nk - 1) & live)
    def _():
        @pl.when(pend[0] == 1)
        def _():
            store(pend[1], pend[2], pend[3]).wait()

        rows = mi * bm + jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 0)
        obuf[...] = jnp.where(rows < count, out_ref[...], 0.0).astype(obuf.dtype)
        store(gi, mi, 0).start()
        pend[0] = 1
        pend[1] = gi
        pend[2] = mi
        pend[3] = 0

    # Drain: the final grid step waits out the last in-flight store.
    @pl.when((t == nsteps - 1) & (pend[0] == 1))
    def _():
        store(pend[1], pend[2], pend[3]).wait()
        pend[0] = 0


def gmm_fused_ffn(
    x: jax.Array,            # (R, D) flat token rows, bucket-contiguous
    wg: jax.Array,           # (G // gpw, D, F)
    wu: jax.Array,           # (G // gpw, D, F)
    wd: jax.Array,           # (G // gpw, F, D_out)
    offsets: jax.Array,      # (G,) int32 — bucket g's first row (in and out)
    group_sizes: jax.Array,  # (G,) int32 — bucket g's live row count
    *,
    capacity: int,
    out_rows: int | None = None,
    groups_per_weight: int = 1,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Fully-fused SwiGLU expert FFN over flat compacted rows.

    ``out[offsets[g] : offsets[g]+count_g] =
    (silu(rows_g @ wg) * (rows_g @ wu)) @ wd`` with ``rows_g =
    x[offsets[g] : offsets[g]+count_g]``. One kernel: the gather prologue
    reads each live ``(bm, bk)`` input tile by dynamic-offset DMA, the
    dual-activation front half reduces hidden blocks in VMEM, the
    down-projection retires each block into a ``(bm, D_out)`` accumulator,
    and the scatter epilogue stores the tile back at the same offsets.
    The padded ``(G, capacity, F)`` hidden tensor never touches HBM —
    hidden-leg HBM bytes are exactly zero. Output rows outside every live
    segment follow the ``gmm_scatter`` contract (zero where a partial tile
    spilled, unwritten garbage otherwise); callers combine through the
    dispatch metadata. Dead tiles skip the DMA, both MXU passes, and the
    store.
    """
    r, d = x.shape
    f = wg.shape[-1]
    dn = wd.shape[-1]
    gpw = groups_per_weight
    g = wg.shape[0] * gpw
    assert offsets.shape == (g,), (offsets.shape, g)
    assert wd.shape[-2] == f, (wd.shape, f)
    out_rows = r if out_rows is None else out_rows
    bm, bf, bk = fit_block(capacity, bm), fit_block(f, bn), fit_block(d, bk)
    x, r_pad = _pad_rows(x, bm)
    nk = d // bk
    nmi, nj = capacity // bm, f // bf
    # A partial tile's spill never runs off the end; the clamp bound
    # out_pad - bm stays ROW_ALIGN-aligned.
    out_pad = round_up(out_rows) + bm
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(g, nmi, nj, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, bk, bf), lambda gi, i, j, k, off, gs: (gi // gpw, k, j)),
            pl.BlockSpec((1, bk, bf), lambda gi, i, j, k, off, gs: (gi // gpw, k, j)),
            pl.BlockSpec((1, bf, dn), lambda gi, i, j, k, off, gs: (gi // gpw, j, 0)),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((bm, bf), jnp.float32),   # gate accumulator
            pltpu.VMEM((bm, bf), jnp.float32),   # up accumulator
            pltpu.VMEM((bm, dn), jnp.float32),   # down-proj accumulator
            pltpu.VMEM((2, bm, bk), x.dtype),    # gather double-buffer
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((bm, dn), x.dtype),       # store staging tile
            pltpu.SMEM((4,), jnp.int32),         # pending-store bookkeeping
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _fused_ffn_kernel,
            g=g, nmi=nmi, nj=nj, nk=nk, nsteps=g * nmi * nj * nk,
            bm=bm, bk=bk, dn=dn,
            r_max_in=r_pad - bm, r_max_out=out_pad - bm,
        ),
        grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((out_pad, dn), x.dtype),
        interpret=interpret,
    )(offsets.astype(jnp.int32), group_sizes.astype(jnp.int32), x, wg, wu, wd)
    return out[:out_rows]
