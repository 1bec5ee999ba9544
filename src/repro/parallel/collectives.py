"""Expert-parallel dispatch/combine and sequence-parallel decode attention.

``bucket_dispatch`` / ``bucket_combine`` are the static-shape, differentiable
building blocks: token copies are sorted into fixed-capacity buckets (one
per physical expert slot), moved with ``jax.lax.all_to_all`` across the EP
axis under ``shard_map``, computed, and combined back with router weights.
Capacity overflow drops copies (standard capacity-factor semantics).

Physical expert *slots* (= native experts + shadow replicas) are first-class:
the routing table ``slot_of[e, r]`` and replica counts ``n_replicas[e]`` are
traced int32 inputs, so the NI-Balancer can re-place experts between serving
steps without recompilation.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.kernels import registry
from repro.kernels.tiling import ROW_ALIGN, round_up
from repro.parallel.ctx import ParallelCtx
from repro.parallel.placement import PlacementTable
from repro.parallel.sharding import placement_specs


def bucket_capacity(n_tok: int, k: int, capacity_factor: float, n_buckets: int) -> int:
    """Per-bucket capacity for ``n_tok`` tokens x ``k`` copies over
    ``n_buckets`` buckets. **Ceiling** division: floor truncation silently
    under-allocates (e.g. 100 copies over 3 buckets at factor 1.0 floored to
    33 drops a copy even under perfectly balanced routing). Floored at 8 so
    tiny smoke shapes keep a usable bucket."""
    return max(math.ceil(n_tok * k * capacity_factor / n_buckets), 8)


# ---------------------------------------------------------------------------
# bucket dispatch (pure jnp, static shapes, differentiable in x / weights)
# ---------------------------------------------------------------------------

def dispatch_metadata(
    bucket_ids: jax.Array,  # (n, k) target bucket per token copy
    n_buckets: int,
    capacity: int,
):
    """Metadata-only dispatch: the sort/position math of ``bucket_dispatch``
    without writing the padded ``(n_buckets, capacity, d)`` buffers.

    Returns ``(row_ids, offsets, counts, slots, keep)``:

    * ``row_ids`` (R,) — source token index per flat row: ``x[row_ids]``
      holds bucket 0's kept copies from row ``offsets[0]``, bucket 1's from
      ``offsets[1]``, … (within a bucket, earlier tokens first — the same
      deterministic order ``bucket_dispatch`` packs). Every segment starts
      on a ``ROW_ALIGN``-aligned row (``aligned_offsets``), the contract of
      the gather/scatter kernels' DMAs; rows between segments repeat token
      0 and are never addressed. Copies past capacity and out-of-range
      bucket ids (e.g. the decode ownership sentinel) get no row.
    * ``offsets`` (n_buckets,) int32 — bucket g's first row.
    * ``counts`` (n_buckets,) int32 — bucket g's *kept* copies
      (== ``kept_counts``): rows ``offsets[g] .. offsets[g]+counts[g]`` of
      the flat array are exactly bucket g's surviving tokens.
    * ``slots`` (n, k) / ``keep`` (n, k) — identical to ``bucket_dispatch``
      (within-bucket position, capacity-survival mask) for the combine.

    This is the operand layout the fused gather kernels
    (``kernels.gmm.ragged.gmm_gather``) consume via scalar prefetch.
    """
    n, k = bucket_ids.shape
    flat_b = bucket_ids.reshape(-1)                       # (n*k,)

    order = jnp.argsort(flat_b, stable=True)
    b_sorted = flat_b[order]
    # Out-of-range ids are dropped by bincount, so valid-bucket counts and
    # offsets are sentinel-proof (sentinels sort past every real bucket).
    counts_all = jnp.bincount(flat_b, length=n_buckets)
    starts = jnp.cumsum(counts_all) - counts_all
    idx_sorted = jnp.arange(n * k) - starts[b_sorted]

    # Undo the sort to index by (token, k).
    slots = jnp.zeros(n * k, dtype=jnp.int32).at[order].set(
        idx_sorted.astype(jnp.int32)
    )
    keep = (slots < capacity) & (flat_b < n_buckets)  # drop out-of-range ids too
    counts = jnp.minimum(counts_all, capacity).astype(jnp.int32)
    offsets = aligned_offsets(counts)
    # Every kept copy plus each segment's rounding, and never more than a
    # full round_up(capacity) per bucket.
    r = min(
        round_up(n * k + n_buckets * (ROW_ALIGN - 1)),
        n_buckets * round_up(capacity),
    )
    dst = jnp.where(keep, offsets[jnp.minimum(flat_b, n_buckets - 1)] + slots, r)
    row_ids = (
        jnp.zeros(r, jnp.int32)
        .at[dst]
        .set(jnp.arange(n * k, dtype=jnp.int32) // k, mode="drop")
    )
    return row_ids, offsets, counts, slots.reshape(n, k), keep.reshape(n, k)


def bucket_dispatch(
    x: jax.Array,          # (n, d) token activations
    bucket_ids: jax.Array, # (n, k) target bucket per token copy
    n_buckets: int,
    capacity: int,
):
    """Pack token copies into (n_buckets, capacity, d) buffers.

    Returns ``(buffers, slots, keep)`` where ``slots[n, k]`` is the
    within-bucket position of each copy and ``keep[n, k]`` masks copies that
    fit under capacity. Deterministic: earlier tokens win bucket slots.

    This is the materialized fallback; the fused kernel path uses
    ``dispatch_metadata`` + the gather kernels and never writes the buffers.
    """
    n, k = bucket_ids.shape
    d = x.shape[-1]
    flat_b = bucket_ids.reshape(-1)                       # (n*k,)
    flat_src = jnp.repeat(jnp.arange(n), k)               # (n*k,)
    _, _, _, slots, keep = dispatch_metadata(bucket_ids, n_buckets, capacity)

    # Scatter kept copies; overflow goes to a sacrificial extra bucket row.
    flat_keep = keep.reshape(-1)
    flat_slots = slots.reshape(-1)
    slot_b = jnp.where(flat_keep, flat_b, n_buckets)
    slot_i = jnp.minimum(flat_slots, capacity - 1)
    buffers = jnp.zeros((n_buckets + 1, capacity, d), dtype=x.dtype)
    buffers = buffers.at[slot_b, slot_i].set(x[flat_src], mode="drop")
    return buffers[:n_buckets], slots, keep


def bucket_combine(
    y: jax.Array,            # (n_buckets, capacity, d) expert outputs
    bucket_ids: jax.Array,   # (n, k)
    slots: jax.Array,        # (n, k)
    keep: jax.Array,         # (n, k)
    weights: jax.Array,      # (n, k) router weights
) -> jax.Array:
    n, k = bucket_ids.shape
    vals = y[bucket_ids.reshape(-1), jnp.minimum(slots, y.shape[1] - 1).reshape(-1)]
    vals = vals.reshape(n, k, -1)
    w = (weights * keep).astype(vals.dtype)
    return jnp.einsum("nkd,nk->nd", vals, w)


def combine_from_rows(
    y: jax.Array,        # (R, d) flat compact expert outputs
    rows: jax.Array,     # (n, k) flat output row per copy (junk when dropped)
    keep: jax.Array,     # (n, k) capacity-survival mask
    weights: jax.Array,  # (n, k) router weights
) -> jax.Array:
    """Metadata-driven combine for the compact FFN output: gather each kept
    copy's row from the flat array and weighted-sum per token — the
    ``(n_buckets, capacity, d)`` receive buffer of ``bucket_combine`` never
    exists. Rows between live segments carry uninitialized garbage (the
    scatter epilogue never writes them), so dropped copies must select zero
    *before* any arithmetic: a ``where``, not a ``0 *`` weighting —
    ``0 * NaN`` would poison the token."""
    n, k = rows.shape
    safe = jnp.clip(rows.reshape(-1), 0, y.shape[0] - 1)
    vals = y[safe].reshape(n, k, -1)
    vals = jnp.where(keep[..., None], vals, jnp.zeros_like(vals))
    w = (weights * keep).astype(vals.dtype)
    return jnp.einsum("nkd,nk->nd", vals, w)


def aligned_offsets(counts: jax.Array, axis: int = -1) -> jax.Array:
    """Segment starts for bucket-contiguous flat rows: the exclusive cumsum
    of ``counts`` with every count rounded up to ``ROW_ALIGN``, so each
    bucket's first row is tile-aligned — the contract the gather/scatter
    kernels' dynamic-offset DMAs rely on (``kernels.gmm.ragged``). A
    segment then spans ``round_up(count)`` rows; with a per-bucket row
    budget of ``round_up(capacity)`` the aligned layout still fits."""
    padded = round_up(counts)
    return jnp.cumsum(padded, axis=axis) - padded


def scatter_counts(bucket_ids: jax.Array, n_buckets: int) -> jax.Array:
    """Per-bucket token counts (n, k) -> (n_buckets,); feeds the balancer."""
    return jnp.bincount(bucket_ids.reshape(-1), length=n_buckets)


def kept_counts(
    bucket_ids: jax.Array, keep: jax.Array, n_buckets: int
) -> jax.Array:
    """Per-bucket *kept* copy counts (capacity drops excluded), int32.

    ``bucket_dispatch`` packs kept copies into slots ``0..count-1`` of their
    bucket, so these counts are exactly the ``group_sizes`` the ragged GMM
    kernels consume. Implemented as a scatter-add (vmap-safe, unlike
    ``jnp.bincount``); out-of-range ids land in a sacrificial row.
    """
    b = jnp.where(keep, bucket_ids, n_buckets)
    return (
        jnp.zeros((n_buckets + 1,), jnp.int32)
        .at[b.reshape(-1)]
        .add(1, mode="drop")[:n_buckets]
    )


# ---------------------------------------------------------------------------
# replica routing
# ---------------------------------------------------------------------------

def choose_slots(
    expert_ids: jax.Array,   # (n, k) logical expert per copy
    slot_of: jax.Array,      # (E, R_max) physical slot table
    n_replicas: jax.Array,   # (E,) live replica count per expert
    sentinel: int | None = None,
) -> jax.Array:
    """Pick a physical slot per copy, round-robin over live replicas.

    ``sentinel`` handles out-of-range expert ids (>= E — the routing mask
    for empty serving slots): their copies map to ``sentinel`` (pick one
    past every real bucket) so dispatch drops them, instead of the default
    clip-gather silently stealing a live expert's slot and capacity."""
    n, k = expert_ids.shape
    e = slot_of.shape[0]
    safe = jnp.minimum(expert_ids, e - 1)
    copy_idx = (jnp.arange(n * k) % 997).reshape(n, k)  # cheap spread
    r = copy_idx % n_replicas[safe]
    slots = slot_of[safe, r]
    if sentinel is not None:
        slots = jnp.where(expert_ids < e, slots, sentinel)
    return slots


def uniform_placement(n_experts: int, n_slots: int, r_max: int = 4):
    """Initial placement: expert e -> slot e (native homes), one replica.

    Thin wrapper over :meth:`PlacementTable.uniform` kept for callers that
    want the bare ``(slot_of, n_replicas)`` device arrays without holding a
    table; the serving path holds the table itself."""
    return PlacementTable.uniform(n_experts, n_slots, r_max=r_max).device_view()


def tiled_placement(n_experts: int, n_rows: int, n_slots: int, r_max: int = 4):
    """Placement consistent with ``jnp.tile``-expanded slot weights.

    When ``moe_ep`` pads a non-divisible expert count up to ``n_slots``
    physical slots by tiling the weight rows, slot ``s`` holds weight row
    ``s % n_rows`` — i.e. expert ``s % n_rows`` for the identity rows
    (``row j == expert j`` for ``j < n_experts``, which is what ``moe_init``
    produces). The matching placement therefore gives expert ``e`` a replica
    at *every* slot ``s < n_slots`` with ``s % n_rows == e`` — so routed
    tokens provably land on slots holding their expert's weights, and the
    wrap-around shadow slots carry real traffic instead of sitting idle
    while still being counted in the capacity denominator.

    Thin wrapper over :meth:`PlacementTable.tiled` (which grows ``r_max`` so
    every wrap-around replica fits the table)."""
    table = PlacementTable.tiled(n_experts, n_rows, n_slots, r_max=r_max)
    return table.device_view()


# ---------------------------------------------------------------------------
# EP all-to-all under shard_map
# ---------------------------------------------------------------------------

def validate_ep_token_split(
    b: int, s: int, n_batch: int, ep: int, decode: bool
) -> None:
    """Up-front shape validation for ``ep_moe_shardmap``.

    The shard_map splits batch over the batch axes and (prefill) sequence
    over the EP axis; a non-dividing shape either dies inside shard_map
    with an opaque spec error or — worse — silently floor-truncates
    ``n_tok = b*s // (n_batch * ep)`` and under-sizes ``bucket_capacity``
    (the same failure class as the PR 2 capacity-floor bug). Fail loudly,
    naming the offending shapes."""
    if n_batch and b % n_batch:
        raise ValueError(
            f"ep_moe_shardmap: batch={b} does not divide the {n_batch}-way "
            f"batch axis (seq={s}, ep={ep}, decode={decode}) — pad the "
            f"batch or reshape the mesh"
        )
    if not decode and s % ep:
        raise ValueError(
            f"ep_moe_shardmap prefill splits the sequence over the EP "
            f"axis: seq={s} does not divide ep={ep} (batch={b}, "
            f"n_batch={n_batch}); b*s//(n_batch*ep) would floor-truncate "
            f"the per-device token count and under-size bucket_capacity — "
            f"pad the sequence to a multiple of {ep}"
        )


def validate_ep_chunks(ep_chunks, n_groups: int | None = None, where: str = "") -> int:
    """Validate the EP dispatch chunk count with a named error.

    ``ep_chunks`` must be a positive int; when ``n_groups`` (the expert-group
    count the chunking splits — ``slots_per_device`` on the mesh path, the
    total slot count on the local path, ``n_experts`` for ESP) is known it
    must divide it, or per-chunk buckets would be ragged across chunks and
    the shard_map/jit shapes would differ per chunk. Failing here names the
    offending values instead of dying inside shard_map with an opaque spec
    error. ``ep_chunks=1`` is always valid and means the single-shot path.
    Returns the validated count."""
    at = f" ({where})" if where else ""
    if not isinstance(ep_chunks, int) or isinstance(ep_chunks, bool) or ep_chunks < 1:
        raise ValueError(
            f"ep_chunks={ep_chunks!r}{at} must be a positive int "
            f"(1 = single-shot dispatch, K > 1 pipelines the all_to_all "
            f"legs in K expert-group chunks)"
        )
    if n_groups is not None and n_groups % ep_chunks:
        raise ValueError(
            f"ep_chunks={ep_chunks}{at} does not divide the expert-group "
            f"count {n_groups} — every chunk must carry the same number of "
            f"expert groups so the exchange buffers stay statically shaped; "
            f"pick a divisor of {n_groups} (or 1 for the single-shot path)"
        )
    return ep_chunks


def ep_moe_shardmap(
    x: jax.Array,                 # (B, S, d) — seq will be split over model axis
    expert_ids: jax.Array,        # (B, S, k)
    weights: jax.Array,           # (B, S, k)
    slot_weights: dict,           # expert slot params, leading dim = total slots
    slot_of: jax.Array,           # (E, R_max)
    n_replicas: jax.Array,        # (E,)
    ctx: ParallelCtx,
    capacity_factor: float,
    slots_per_device: int,
    decode: bool = False,
):
    """Expert-parallel MoE: dispatch -> all_to_all -> GMM -> all_to_all -> combine
    (named scopes ``dispatch``, ``expert_ffn``, ``combine``).

    ``slot_weights`` holds (n_total_slots, d, f) matrices sharded over the
    model axis (slot dim). Inside the per-device block each device sees its
    ``slots_per_device`` local experts and exchanges fixed-capacity buckets
    with every peer on the EP (= model) axis.

    Train/prefill mode splits the *sequence* over the EP axis (each TP rank
    dispatches a distinct token slice — the paper's retained-AG semantics).
    Decode mode (``s == 1``) keeps tokens replicated over the EP axis; each
    rank owns tokens with ``idx % ep == rank`` and a final psum restores
    replication.
    """
    mesh = ctx.mesh
    axis = ctx.model_axis
    ep = ctx.n_model
    total_slots = ep * slots_per_device
    use_kernels = ctx.kernels_on

    b, s, d = x.shape
    k = expert_ids.shape[-1]
    f = slot_weights["w_gate"].shape[-1]
    validate_ep_token_split(b, s, ctx.n_batch, ep, decode)
    if decode:
        n_tok = b // ctx.n_batch                   # distinct tokens per EP group
    else:
        n_tok = b * s // (ctx.n_batch * ep)        # tokens per device, seq split
    cap = bucket_capacity(n_tok, k, capacity_factor, total_slots)
    # Fused dispatch-gather path: token rows ship rank-compacted (packed
    # back-to-back per destination rank inside the statically-sized
    # exchange buffer — all_to_all needs equal splits, so wire bytes are
    # unchanged) and the gather GMM reads the received rows via per-bucket
    # offsets. The *combine* leg mirrors it: the scatter epilogue
    # (compact_out) writes the down-projection back at the same offsets,
    # the return all_to_all ships that compact buffer, and
    # combine_from_rows gathers through the dest/posr/keep metadata — no
    # (spd, ep, cap, d) transpose/repack and no padded FFN input *or*
    # output buffer is ever materialized on either leg. Padded
    # bucket_dispatch/bucket_combine remain the fallback when the kernels
    # are off or shapes don't tile for the compiled kernel.
    # Rows per bucket in the fused exchange layout: segments start on
    # ROW_ALIGN-aligned rows (aligned_offsets), so each bucket's budget is
    # the capacity rounded up to the alignment. Drops still happen at cap.
    seg = round_up(cap)
    fused = use_kernels and registry.can_gmm_gather(
        seg, d, f, registry.default_interpret()
    )
    spd = slots_per_device
    # EP dispatch pipelining (ctx.ep_chunks = K): the fused branch splits
    # each device's spd expert groups into K chunks of spc groups and
    # pipelines the per-chunk all_to_all legs against the per-chunk FFN.
    # Validated up front with a named error; the padded fallback branch
    # stays single-shot (its buffers are already the slow path).
    kc = validate_ep_chunks(
        getattr(ctx, "ep_chunks", 1), where="ep_moe_shardmap"
    )
    if kc > 1:
        validate_ep_chunks(kc, spd, where="ep_moe_shardmap slots_per_device")
    if not fused:
        kc = 1
    spc = spd // kc

    @jax.named_scope("dispatch")
    def dispatch_fused(xt, slots):
        """Per-chunk rank-compacted send buffers + per-bucket metadata (no
        padding between a chunk's buckets; bucket order within a rank
        preserved). ``dest``/``posr`` — each copy's destination rank and
        row inside that rank's compacted *chunk* block — also address the
        copy's row in the returned compact FFN output (the scatter epilogue
        writes results at the same offsets the prologue gathered from), so
        the combine gathers through them directly. The chunk split is
        metadata-only: a bucket's fill and internal order are per-bucket
        properties of the one global ``dispatch_metadata`` call, so slicing
        buckets by chunk changes nothing about any bucket's rows — no
        padded buffer reappears on either leg."""
        n = xt.shape[0]
        _, _, kept, pos, keep = dispatch_metadata(slots, total_slots, cap)
        # Within-segment row offset of each bucket: aligned exclusive
        # cumsum restarting at every (rank, chunk) boundary. kc == 1
        # degenerates to the whole-rank cumsum of the single-shot path.
        kept_ck = kept.reshape(ep, kc, spc)
        wro = aligned_offsets(kept_ck, axis=2)                # (ep, kc, spc)
        flat_b = slots.reshape(-1)
        safe_b = jnp.minimum(flat_b, total_slots - 1)
        dest = flat_b // spd                                  # >= ep for sentinels
        chunk_of = (safe_b % spd) // spc                      # owning chunk
        posr = wro.reshape(-1)[safe_b] + pos.reshape(-1)
        posr = jnp.where(keep.reshape(-1), posr, spc * seg)   # overflow -> drop
        src = xt[jnp.repeat(jnp.arange(n), k)]
        sends = []
        for c in range(kc):
            # Copies owned by other chunks scatter out of bounds and drop —
            # each kept copy lands in exactly one chunk's buffer.
            posr_c = jnp.where(chunk_of == c, posr, spc * seg)
            send = jnp.zeros((ep, spc * seg, d), dtype=xt.dtype)
            sends.append(send.at[dest, posr_c].set(src, mode="drop"))
        return sends, kept_ck, keep, chunk_of, dest, posr

    def body(x_blk, eid_blk, w_blk, wg, wu, wd, slot_of_, n_rep_):
        # x_blk: (B_loc, S_loc, d) — this device's token slice.
        bl, sl, _ = x_blk.shape
        xt = x_blk.reshape(bl * sl, d)
        eid = eid_blk.reshape(bl * sl, k)
        w = w_blk.reshape(bl * sl, k)

        # Physical slot per copy; masked tokens (expert id E sentinel from
        # moe_apply's token_mask) overflow out of every bucket.
        with jax.named_scope("dispatch"):
            slots = choose_slots(eid, slot_of_, n_rep_, sentinel=total_slots + 1)
            if decode:
                # Tokens are replicated across the EP axis: rank r owns
                # idx % ep == r; unowned copies overflow out of every bucket.
                rank = jax.lax.axis_index(axis)
                owned = (jnp.arange(bl * sl) % ep) == rank
                slots = jnp.where(owned[:, None], slots, total_slots + 1)

        if fused:
            sends, kept_ck, keep, chunk_of, dest, posr = dispatch_fused(xt, slots)

            @jax.named_scope("dispatch")
            def exchange(c):
                recv = jax.lax.all_to_all(
                    sends[c], axis, split_axis=0, concat_axis=0, tiled=False
                )
                cnt = jax.lax.all_to_all(
                    kept_ck[:, c], axis, split_axis=0, concat_axis=0, tiled=False
                )
                return recv, cnt

            def chunk_ffn(recv, cnt, c):
                # recv[r'] = my chunk's spc buckets' rows from source rank
                # r', bucket-compacted; cnt[r', s] = that segment's fill.
                roff = aligned_offsets(cnt, axis=1)           # (ep, spc)
                # Group gi = s*ep + r' (weight row = gi // ep, as the
                # padded layout) -> flat row offset r'*spc*seg + roff.
                base = jnp.arange(ep, dtype=jnp.int32)[:, None] * (spc * seg)
                offsets_g = (roff + base).transpose(1, 0).reshape(-1)
                counts_g = cnt.transpose(1, 0).reshape(-1)
                # compact_out: the scatter epilogue writes the down-
                # projection back at offsets_g, so the flat (ep*spc*seg, d)
                # result IS the return exchange buffer — segment r' goes
                # straight back to source rank r', still bucket-compacted
                # in *my* bucket order. fused=True: one kernel when
                # can_gmm_fused accepts the shapes; the registry falls back
                # to the gather+scatter pair (same layout contract) per
                # chunk when it doesn't.
                ws = slice(c * spc, (c + 1) * spc)
                with jax.named_scope("expert_ffn"):
                    y = registry.expert_ffn_from_rows(
                        recv.reshape(ep * spc * seg, d),
                        wg[ws],
                        wu[ws],
                        wd[ws],
                        offsets_g,
                        counts_g,
                        capacity=seg,
                        groups_per_weight=ep,
                        enabled=True,
                        compact_out=True,
                        fused=True,
                    )
                with jax.named_scope("combine"):
                    return jax.lax.all_to_all(
                        y.reshape(ep, spc * seg, d), axis,
                        split_axis=0, concat_axis=0, tiled=False,
                    )

            # Software pipeline over the chunks (trace-unrolled): chunk
            # c+1's dispatch all_to_all is issued *before* chunk c's FFN,
            # and chunk c's combine all_to_all right after it — neither
            # depends on the other's data, so async collectives run the
            # in-flight legs while gmm_fused_ffn executes. Double-buffer
            # contract: at most two receive buffers are live at once (the
            # chunk being computed and the one in flight). kc == 1 is the
            # original single-shot dispatch -> FFN -> combine sequence.
            recv = [None] * kc
            recv[0] = exchange(0)
            backs = []
            for c in range(kc):
                if c + 1 < kc:
                    recv[c + 1] = exchange(c + 1)
                backs.append(chunk_ffn(*recv[c], c))
                recv[c] = None                    # retire chunk c's buffer
            # ONE combine over the concatenated chunk outputs: each copy's
            # row is its chunk's block base + dest*(spc*seg) + posr — the
            # exact coordinates dispatch_fused scattered it to on the way
            # out. A single gather + einsum keeps the per-token k-copy
            # reduction order identical to the single-shot path (bit-
            # exactness); per-chunk partial combines would re-order it.
            with jax.named_scope("combine"):
                back = jnp.concatenate(backs, axis=0)
                rows = chunk_of * (ep * spc * seg) + dest * (spc * seg) + posr
                out = combine_from_rows(
                    back.reshape(kc * ep * spc * seg, d),
                    rows.reshape(bl * sl, k), keep, w,
                )
        else:
            with jax.named_scope("dispatch"):
                bufs, pos, keep = bucket_dispatch(xt, slots, total_slots, cap)
                # How full each outgoing bucket actually is — rides the same
                # all_to_all so every device knows its received buckets'
                # raggedness.
                counts = kept_counts(slots, keep, total_slots)
                # (total_slots, cap, d) -> exchange so each device gets its
                # slots.
                bufs = bufs.reshape(ep, spd, cap, d)
                recv = jax.lax.all_to_all(
                    bufs, axis, split_axis=0, concat_axis=0, tiled=False
                )
                cnt = jax.lax.all_to_all(
                    counts.reshape(ep, spd), axis,
                    split_axis=0, concat_axis=0, tiled=False,
                )
                # recv: (ep, spd, cap, d) — axis 0 now = source rank.
                recv = recv.transpose(1, 0, 2, 3)          # (spd, ep, cap, d)
                cnt = cnt.transpose(1, 0)                  # (spd, ep)

            # Local expert compute: bucket (slot e, source r) uses weight
            # row e; the ragged GMM kernels skip capacity rows past each
            # bucket's count, so FFN FLOPs track tokens actually routed
            # (fallback: folded einsums over the same layout).
            with jax.named_scope("expert_ffn"):
                y = registry.expert_ffn(
                    recv.reshape(spd * ep, cap, d),
                    wg,
                    wu,
                    wd,
                    group_sizes=cnt.reshape(-1),
                    groups_per_weight=ep,
                    enabled=use_kernels,
                )
            with jax.named_scope("combine"):
                y = y.reshape(spd, ep, cap, d).transpose(1, 0, 2, 3)
                back = jax.lax.all_to_all(
                    y, axis, split_axis=0, concat_axis=0, tiled=False
                )
                back = back.reshape(total_slots, cap, d)
                out = bucket_combine(back, slots, pos, keep, w)
        if decode:
            with jax.named_scope("combine"):
                out = jax.lax.psum(out, axis)  # gather owners' results everywhere
        return out.reshape(bl, sl, d)

    bspec = ctx.batch_spec
    seq_spec = None if decode else axis
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(bspec, seq_spec, None),      # x: sequence split over model axis
            P(bspec, seq_spec, None),
            P(bspec, seq_spec, None),
            P(axis, None, None),           # slot weights: slot dim over model
            P(axis, None, None),
            P(axis, None, None),
            *placement_specs(),            # routing tables replicated
        ),
        out_specs=P(bspec, seq_spec, None),
        check_vma=False,
    )(
        x,
        expert_ids,
        weights,
        slot_weights["w_gate"],
        slot_weights["w_up"],
        slot_weights["w_down"],
        slot_of,
        n_replicas,
    )


def ep_moe_local(
    x: jax.Array,            # (B, S, d)
    expert_ids: jax.Array,   # (B, S, k) — may carry the E sentinel (masked)
    weights: jax.Array,      # (B, S, k)
    slot_weights: dict,      # expert slot params, leading dim = total slots
    slot_of: jax.Array,      # (E, R_max)
    n_replicas: jax.Array,   # (E,)
    ctx: ParallelCtx,
    capacity_factor: float,
    total_slots: int,
):
    """Single-process EP dispatch (no mesh): the same slot-table routing,
    fixed-capacity bucketing and ragged grouped FFN as ``ep_moe_shardmap``,
    minus the all_to_all — every slot is local, so the exchange is the
    identity. This is what lets the NI-Balancer run for real on one
    process (``ServeConfig.virtual_ep``): replica routing, migrations and
    evacuations move actual weight rows between slot rows; only the
    inter-device hop is notional. Named scopes: ``dispatch``,
    ``expert_ffn``, ``combine``."""
    b, s, d = x.shape
    k = expert_ids.shape[-1]
    n = b * s
    xt = x.reshape(n, d)
    eid = expert_ids.reshape(n, k)
    w = weights.reshape(n, k)
    cap = bucket_capacity(n, k, capacity_factor, total_slots)
    with jax.named_scope("dispatch"):
        slots = choose_slots(eid, slot_of, n_replicas, sentinel=total_slots + 1)
        bufs, pos, keep = bucket_dispatch(xt, slots, total_slots, cap)
        counts = kept_counts(slots, keep, total_slots)
        # Bucket rows rounded up to ROW_ALIGN so the grouped kernels' row
        # tiles meet Mosaic's block rule (can_gmm); the extra rows are dead
        # (past every count) and sliced off before the combine.
        bufs = jnp.pad(bufs, ((0, 0), (0, round_up(cap) - cap), (0, 0)))
    # ep_chunks: the local path has no all_to_all to hide, but it is the
    # substrate the virtual-EP serving/chaos tests run on — chunking the
    # grouped FFN the same way keeps the chunked program on the hot path
    # there (per-bucket results are independent of how groups are batched,
    # so the concatenated output is bit-identical to the single call).
    kc = validate_ep_chunks(getattr(ctx, "ep_chunks", 1), where="ep_moe_local")
    if kc > 1:
        validate_ep_chunks(kc, total_slots, where="ep_moe_local total_slots")
    spt = total_slots // kc
    with jax.named_scope("expert_ffn"):
        ys = [
            registry.expert_ffn(
                bufs[c * spt : (c + 1) * spt],
                slot_weights["w_gate"][c * spt : (c + 1) * spt],
                slot_weights["w_up"][c * spt : (c + 1) * spt],
                slot_weights["w_down"][c * spt : (c + 1) * spt],
                group_sizes=counts[c * spt : (c + 1) * spt],
                enabled=ctx.kernels_on,
            )
            for c in range(kc)
        ]
        y = ys[0] if kc == 1 else jnp.concatenate(ys, axis=0)
    with jax.named_scope("combine"):
        out = bucket_combine(y[:, :cap], slots, pos, keep, w)
    return out.reshape(b, s, d)


# ---------------------------------------------------------------------------
# ESP expert FFN (kernel path)
# ---------------------------------------------------------------------------

def esp_expert_ffn(
    bufs: jax.Array,     # (G, E, cap, d) — per-group expert buckets
    counts: jax.Array,   # (G, E) kept-token count per bucket
    wg: jax.Array,       # (E, d, f)
    wu: jax.Array,       # (E, d, f)
    wd: jax.Array,       # (E, f, d)
    ctx: ParallelCtx,
) -> jax.Array:
    """Count-aware expert FFN for the ESP path (experts' hidden dim sharded
    over the model axis, bucket groups over the batch axes).

    Under a mesh the Pallas call must be device-local, so the compute runs
    under shard_map: each device takes its f-slice of every expert, runs the
    ragged GMM kernels over its bucket groups, and the partial down-
    projection sums reduce-scatter onto the d dim (the einsum path's GSPMD
    layout, §Perf iteration 3). Output is (G, E, cap, d), d sharded over
    the model axis. Caller gates on divisibility (see ``moe_esp``).
    """
    g, e, cap, d = bufs.shape

    def compute(xb, cb, wgb, wub, wdb):
        gl = xb.shape[0]
        # (gl, E, cap, d) -> (E*gl, cap, d): expert-major flatten so weight
        # row = group // gl (the ragged kernels' divisor mapping).
        xg = xb.transpose(1, 0, 2, 3).reshape(e * gl, cap, -1)
        y = registry.expert_ffn(
            xg,
            wgb,
            wub,
            wdb,
            group_sizes=cb.transpose(1, 0).reshape(-1),
            groups_per_weight=gl,
            enabled=True,
        )
        return y.reshape(e, gl, cap, -1).transpose(1, 0, 2, 3)

    if ctx.mesh is None:
        return compute(bufs, counts, wg, wu, wd)

    axis = ctx.model_axis
    bspec = ctx.batch_spec

    def body(xb, cb, wgb, wub, wdb):
        y = compute(xb, cb, wgb, wub, wdb)
        # Partial sums over the f-shards: reduce-scatter onto d.
        return jax.lax.psum_scatter(y, axis, scatter_dimension=3, tiled=True)

    return shard_map(
        body,
        mesh=ctx.mesh,
        in_specs=(
            P(bspec, None, None, None),
            P(bspec, None),
            P(None, None, axis),
            P(None, None, axis),
            P(None, axis, None),
        ),
        out_specs=P(bspec, None, None, axis),
        check_vma=False,
    )(bufs, counts, wg, wu, wd)


# ---------------------------------------------------------------------------
# sequence-parallel flash-decode merge
# ---------------------------------------------------------------------------

def seq_parallel_decode_kernel_eligible(
    t: int, nh: int, nkv: int, hd: int, ctx: ParallelCtx
) -> bool:
    """Can each shard's partials come from the flash-decode kernel? The
    kernel emits unnormalized ``(acc, m, l)`` (``return_partials``), so the
    cross-shard LSE merge rides the psum as-is — decode with
    ``seq_parallel_kv=True`` takes the kernel path."""
    if not ctx.kernels_on or ctx.force_dense_attn:
        return False
    t_local = t // ctx.n_model
    return registry.can_flash_decode(
        t_local, nh, nkv, hd, registry.default_interpret()
    )


def seq_parallel_decode_attend(
    q: jax.Array,        # (B, 1, H, hd) — replicated over model axis
    k_cache: jax.Array,  # (B, L, K, hd) — L sharded over model axis
    v_cache: jax.Array,
    mask: jax.Array,     # (L,) validity, sharded like the cache
    ctx: ParallelCtx,
) -> jax.Array:
    """Flash-decode across the model axis: each shard attends over its KV
    chunk, partial results LSE-merge with a psum.

    Kernel path (when eligible): per-shard partials come straight from
    ``flash_decode(..., return_partials=True)`` — unnormalized ``(acc, m,
    l)`` — and ``registry.merge_decode_partials`` does the cross-shard
    merge, so no per-shard normalization round-trip. Fallback: the einsum
    partials below (identical math, unfused)."""
    mesh = ctx.mesh
    axis = ctx.model_axis
    use_kernel = seq_parallel_decode_kernel_eligible(
        k_cache.shape[1], q.shape[2], k_cache.shape[2], q.shape[3], ctx
    )

    def kernel_body(q_blk, k_blk, v_blk, m_blk):
        b, t_local = q_blk.shape[0], k_blk.shape[1]
        valid = jnp.broadcast_to(m_blk[None, :], (b, t_local))
        acc, m, l = registry.decode_attend_partials(q_blk[:, 0], k_blk, v_blk, valid)
        out = registry.merge_decode_partials(acc, m, l, axis)
        return out[:, None].astype(q_blk.dtype)

    def body(q_blk, k_blk, v_blk, m_blk):
        b, _, nh, hd = q_blk.shape
        nk = k_blk.shape[2]
        g = nh // nk
        qg = q_blk.reshape(b, 1, nk, g, hd)
        s = jnp.einsum("bskgd,btkd->bkgst", qg, k_blk).astype(jnp.float32)
        s = s / jnp.sqrt(hd).astype(jnp.float32)
        s = jnp.where(m_blk[None, None, None, None, :], s, -1e30)
        m = jnp.max(s, axis=-1, keepdims=True)
        # Guard fully-masked chunks.
        m_safe = jnp.maximum(m, -1e29)
        e = jnp.exp(s - m_safe)
        num = jnp.einsum("bkgst,btkd->bskgd", e.astype(v_blk.dtype), v_blk)
        den = jnp.sum(e, axis=-1)[..., None]              # (b,k,g,1,1)->align
        den = den.transpose(0, 3, 1, 2, 4)                # (b,1,k,g,1)
        # Global LSE merge across shards.
        m_b = m.transpose(0, 3, 1, 2, 4)                  # (b,1,k,g,1)
        m_max = jax.lax.pmax(m_b, axis)
        scale = jnp.exp(m_b - m_max)
        num = jax.lax.psum(num * scale.astype(num.dtype), axis)
        den = jax.lax.psum(den * scale, axis)
        out = num / jnp.maximum(den, 1e-30).astype(num.dtype)
        return out.reshape(b, 1, nh, hd)

    bspec = ctx.batch_spec
    return shard_map(
        kernel_body if use_kernel else body,
        mesh=mesh,
        in_specs=(
            P(bspec, None, None, None),
            P(bspec, axis, None, None),
            P(bspec, axis, None, None),
            P(axis),
        ),
        out_specs=P(bspec, None, None, None),
        check_vma=False,
    )(q, k_cache, v_cache, mask)
