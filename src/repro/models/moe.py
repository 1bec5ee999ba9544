"""MoE layer: router, expert FFNs, and three parallel implementations.

* ``dense``  — reference oracle: every expert computed for every token,
  masked combine. Exact; used by tests and tiny smoke configs.
* ``ep``     — expert parallelism: shard_map all_to_all dispatch into
  fixed-capacity per-slot buckets (the paper's deployment; supports shadow
  replicas via the traced placement table).
* ``esp``    — expert-sharding parallelism (paper §VI-B5): every device
  holds a 1/tp slice of *all* experts' FFN dims; tokens are bucketed by
  expert locally (no all-to-all) and partial sums all-reduce over the model
  axis. Used when ``n_experts`` doesn't divide the EP axis (Mixtral/DBRX on
  wide meshes) — exactly the regime the paper assigns to ESP.

The auxiliary load-balancing loss (Switch-style) is returned alongside.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import registry
from repro.kernels.tiling import round_up
from repro.models.layers import normal_init
from repro.parallel.collectives import (
    bucket_capacity,
    bucket_combine,
    bucket_dispatch,
    combine_from_rows,
    dispatch_metadata,
    ep_moe_local,
    ep_moe_shardmap,
    esp_expert_ffn,
    kept_counts,
    tiled_placement,
    uniform_placement,
    validate_ep_chunks,
)
from repro.parallel.ctx import ParallelCtx
from repro.parallel.placement import PlacementTable


def moe_init(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    e, d, f = cfg.n_experts, cfg.d_model, cfg.moe_d_ff_
    kr, kg, ku, kd = jax.random.split(key, 4)
    return {
        "router": normal_init(kr, (d, e), dtype=jnp.float32),  # fp32 router
        "w_gate": normal_init(kg, (e, d, f), dtype=dtype),
        "w_up": normal_init(ku, (e, d, f), dtype=dtype),
        "w_down": normal_init(kd, (e, f, d), dtype=dtype),
    }


def route(
    p: dict, x: jax.Array, cfg: ModelConfig
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k routing (named scope ``router``). Returns (expert_ids,
    weights, aux_loss)."""
    with jax.named_scope("router"):
        logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32), p["router"])
        probs = jax.nn.softmax(logits, axis=-1)
        weights, ids = jax.lax.top_k(probs, cfg.experts_per_token)
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        # Switch-style aux loss: E * sum_e fraction_tokens_e * mean_prob_e.
        e = cfg.n_experts
        one_hot = jax.nn.one_hot(ids, e, dtype=jnp.float32)     # (..., k, E)
        frac = jnp.mean(jnp.sum(one_hot, axis=-2).reshape(-1, e), axis=0)
        mean_prob = jnp.mean(probs.reshape(-1, e), axis=0)
        aux = e * jnp.sum(frac * mean_prob)
        return ids, weights.astype(x.dtype), aux


def zero_aux(cfg: ModelConfig) -> dict:
    """Aux accumulator template (works for dense archs too)."""
    return {
        "loss": jnp.zeros((), jnp.float32),
        "counts": jnp.zeros((max(cfg.n_experts, 1),), jnp.float32),
    }


def _aux(loss, ids, cfg: ModelConfig) -> dict:
    counts = jnp.bincount(
        ids.reshape(-1), length=max(cfg.n_experts, 1)
    ).astype(jnp.float32)
    return {"loss": loss, "counts": counts}


# ---------------------------------------------------------------------------
# dense oracle
# ---------------------------------------------------------------------------

def _mask_ids(ids, token_mask, cfg: ModelConfig):
    """Route masked tokens (empty serving slots) to the out-of-range expert
    id E: every dispatch drops the sentinel (and ``one_hot`` zeroes it), so
    dead batch rows consume no bucket capacity, contribute zero output and
    never pollute the balancer's expert counts."""
    if token_mask is None:
        return ids
    return jnp.where(token_mask[..., None], ids, cfg.n_experts)


def moe_dense(
    p: dict, x: jax.Array, cfg: ModelConfig, ctx: ParallelCtx, token_mask=None
):
    ids, w, aux = route(p, x, cfg)
    ids = _mask_ids(ids, token_mask, cfg)
    h = jnp.einsum("...d,edf->...ef", x, p["w_gate"])
    u = jnp.einsum("...d,edf->...ef", x, p["w_up"])
    y = jnp.einsum("...ef,efd->...ed", jax.nn.silu(h) * u, p["w_down"])
    mask = jax.nn.one_hot(ids, cfg.n_experts, dtype=w.dtype)       # (...,k,E)
    comb = jnp.einsum("...ke,...k->...e", mask, w)
    out = jnp.einsum("...ed,...e->...d", y, comb)
    return out, _aux(aux, ids, cfg)


# ---------------------------------------------------------------------------
# ESP: expert-sharded FFN, local bucketing, all-reduce combine
# ---------------------------------------------------------------------------

def moe_esp(
    p: dict, x: jax.Array, cfg: ModelConfig, ctx: ParallelCtx, token_mask=None
):
    """Experts' hidden dims sharded over the model axis (GSPMD handles the
    partial-sum all-reduce of w_down). Tokens are bucketed per expert so
    FLOPs stay ~topk * capacity_factor, not n_experts.

    Dispatch is *group-local*: tokens are reshaped so the leading group dim
    aligns with the batch sharding, each data shard sorts/scatters only its
    own tokens, and every bucket tensor keeps the group dim sharded. Without
    this, GSPMD replicates the global buckets across all data rows —
    redundant expert FLOPs x n_batch and a giant dispatch all-gather (see
    EXPERIMENTS.md §Perf, mixtral hillclimb)."""
    ids, w, aux = route(p, x, cfg)
    ids = _mask_ids(ids, token_mask, cfg)
    b, s, d = x.shape
    k = cfg.experts_per_token
    e = cfg.n_experts
    # Validate up front so a bad chunk count fails loudly on every branch;
    # only the no-mesh fused branch below actually pipelines (the padded /
    # meshed layouts keep the single-shot grouped FFN).
    kc = validate_ep_chunks(getattr(ctx, "ep_chunks", 1), where="moe_esp")
    if kc > 1:
        validate_ep_chunks(kc, e, where="moe_esp n_experts")
    groups = ctx.n_batch if (ctx.mesh is not None and b % ctx.n_batch == 0) else 1
    n_loc = (b // groups) * s
    cap = bucket_capacity(n_loc, k, ctx.capacity_factor, e)

    f = cfg.moe_d_ff_
    if (
        ctx.mesh is None
        and ctx.kernels_on
        and registry.can_gmm_gather(
            round_up(cap), d, f, registry.default_interpret()
        )
    ):
        # Fused dispatch-gather path (single group, no mesh): the gather
        # GMM reads token rows straight from the flat activations via
        # per-expert offsets, and the scatter epilogue (compact_out) writes
        # the down-projection back at the same offsets — neither the
        # (E, cap, d) dispatch buffer nor the padded FFN output is ever
        # materialized; the combine gathers each kept copy's row through
        # the same metadata. fused=True additionally collapses the three
        # matmuls into one kernel when can_gmm_fused accepts the shapes,
        # keeping the (E, cap, F) hidden tensor in VMEM (registry falls
        # back to the gather+scatter pair otherwise).
        ids2 = ids.reshape(b * s, k)
        row_ids, offsets, counts, slots, keep = dispatch_metadata(ids2, e, cap)
        rows = x.reshape(b * s, d)[row_ids]
        # ep_chunks on the no-mesh path: split the experts into kc chunks
        # and run the fused row FFN per chunk over sliced offsets/counts/
        # weights — the offsets stay absolute into the one flat rows array,
        # so each chunk's call writes its buckets' segments at the same
        # coordinates the single-shot call would. The chunk outputs are
        # merged by each row's owning expert chunk (a select, no
        # arithmetic), and the ONE combine below is untouched — the result
        # is bit-identical to ep_chunks=1.
        epc = e // kc

        def chunk_ffn(c):
            ws = slice(c * epc, (c + 1) * epc)
            return registry.expert_ffn_from_rows(
                rows,
                p["w_gate"][ws],
                p["w_up"][ws],
                p["w_down"][ws],
                offsets[ws],
                counts[ws],
                capacity=round_up(cap),
                enabled=True,
                compact_out=True,
                fused=True,
            )

        y = chunk_ffn(0)
        if kc > 1:
            # Owning bucket of each compacted row (offsets are the buckets'
            # first rows); rows past the live span — sentinel copies — map
            # to the last chunk and are never addressed by the combine.
            r_idx = jnp.arange(rows.shape[0], dtype=jnp.int32)
            owner = jnp.searchsorted(offsets, r_idx, side="right") - 1
            owner_c = jnp.clip(owner, 0, e - 1) // epc
            for c in range(1, kc):
                y = jnp.where((owner_c == c)[:, None], chunk_ffn(c), y)
        out = combine_from_rows(
            y, offsets[ids2] + slots, keep, w.reshape(b * s, k)
        )
        return out.reshape(b, s, d), _aux(aux, ids, cfg)

    bspec = ctx.batch_spec
    xg = ctx.shard(x.reshape(groups, n_loc, d), bspec, None, None)
    idg = ids.reshape(groups, n_loc, k)
    wtg = w.reshape(groups, n_loc, k)
    bufs, slots, keep = jax.vmap(
        lambda xx, ii: bucket_dispatch(xx, ii, e, cap)
    )(xg, idg)
    bufs = ctx.shard(bufs, bspec, None, None, None)     # (G, E, cap, d)
    tp = ctx.n_model
    kernel_ok = ctx.kernels_on and (
        ctx.mesh is None
        or (d % tp == 0 and f % tp == 0 and groups % ctx.n_batch == 0)
    )
    if kernel_ok:
        # Count-aware kernel path: the ragged GMM skips capacity rows past
        # each bucket's fill, so FFN FLOPs track tokens actually routed.
        counts = jax.vmap(lambda ii, kk: kept_counts(ii, kk, e))(idg, keep)
        y = esp_expert_ffn(
            bufs, counts, p["w_gate"], p["w_up"], p["w_down"], ctx
        )
    else:
        wg = ctx.shard(p["w_gate"], None, None, ctx.model_axis)
        wu = ctx.shard(p["w_up"], None, None, ctx.model_axis)
        wd = ctx.shard(p["w_down"], None, ctx.model_axis, None)
        h = jnp.einsum("gecd,edf->gecf", bufs, wg)
        u = jnp.einsum("gecd,edf->gecf", bufs, wu)
        h = ctx.shard(jax.nn.silu(h) * u, bspec, None, None, ctx.model_axis)
        y = jnp.einsum("gecf,efd->gecd", h, wd)
    # Reduce-scatter (d-sharded) instead of a full all-reduce of the padded
    # buckets; the all-gather happens after combine, on the much smaller
    # per-token tensor (§Perf iteration 3).
    y = ctx.shard(y, bspec, None, None, ctx.model_axis)
    out = jax.vmap(bucket_combine)(y, idg, slots, keep, wtg)
    out = ctx.shard(out, bspec, None, None)
    return out.reshape(b, s, d), _aux(aux, ids, cfg)


# ---------------------------------------------------------------------------
# EP via shard_map (paper-faithful dispatch)
# ---------------------------------------------------------------------------

def moe_ep(
    p: dict,
    x: jax.Array,
    cfg: ModelConfig,
    ctx: ParallelCtx,
    placement: PlacementTable | tuple[jax.Array, jax.Array] | None = None,
    slot_weights: dict | None = None,
    slots_per_device: int | None = None,
    token_mask=None,
):
    """Expert-parallel dispatch over the model axis (or, with no mesh, the
    local single-process equivalent — see ``ep_moe_local``).

    ``placement`` is a :class:`PlacementTable` (the serving substrate; its
    committed :meth:`~PlacementTable.device_view` is what routes) or a bare
    ``(slot_of, n_replicas)`` pair; default = native homes. For serving
    with shadow slots the Server owns ``slot_weights`` (n_slots rows,
    possibly > n_experts) and updates replica rows out-of-band; the
    default materializes slots from the logical experts (slot i = expert
    i % E)."""
    ep = ctx.n_model
    e = cfg.n_experts
    n_rows = p["w_gate"].shape[0]  # physical slot rows (>= n_experts when
    # the Server pre-expanded shadow slots)
    tiled = False
    if slot_weights is None:
        slots_per_device = slots_per_device or max(-(-n_rows // ep), 1)
        n_slots = ep * slots_per_device
        if n_slots < n_rows:
            raise ValueError(
                f"slots_per_device={slots_per_device} gives {n_slots} physical "
                f"slots < {n_rows} weight rows — experts would be dropped; "
                f"need at least ceil(n_rows / ep) = {-(-n_rows // ep)}"
            )
        if n_slots == n_rows:
            slot_weights = p  # slot i holds weight row i (identity)
        else:
            # Wrap-around shadow slots: slot i holds weight row i % n_rows
            # (covers both n_rows % ep != 0 and an explicitly larger
            # slots_per_device).
            reps = -(-n_slots // n_rows)
            slot_weights = {
                k2: jnp.tile(p[k2], (reps, 1, 1))[:n_slots]
                for k2 in ("w_gate", "w_up", "w_down")
            }
            tiled = True
    n_slots = ep * slots_per_device
    if isinstance(placement, PlacementTable):
        placement = placement.device_view()   # committed routing view only
    if placement is None:
        if tiled:
            # The tile above put weight row ``s % n_rows`` on slot ``s`` —
            # the default placement must route expert e to exactly those
            # slots (every s with s % n_rows == e), or the wrap-around
            # shadow slots would hold live weights that never see traffic
            # while still inflating the capacity denominator.
            slot_of, n_replicas = tiled_placement(e, n_rows, n_slots)
        else:
            slot_of, n_replicas = uniform_placement(e, n_slots)
    else:
        slot_of, n_replicas = placement

    ids, w, aux = route(p, x, cfg)
    ids = _mask_ids(ids, token_mask, cfg)
    if ctx.mesh is None:
        out = ep_moe_local(
            x,
            ids,
            w,
            slot_weights,
            slot_of,
            n_replicas,
            ctx,
            ctx.capacity_factor,
            n_slots,
        )
    else:
        out = ep_moe_shardmap(
            x,
            ids,
            w,
            slot_weights,
            slot_of,
            n_replicas,
            ctx,
            ctx.capacity_factor,
            slots_per_device,
            decode=x.shape[1] == 1,
        )
    return out, _aux(aux, ids, cfg)


def moe_apply(
    p: dict,
    x: jax.Array,
    cfg: ModelConfig,
    ctx: ParallelCtx,
    placement=None,
    token_mask=None,
):
    """``token_mask`` (bool, broadcastable to ``x.shape[:-1]``): False rows
    are dead serving slots — they route nowhere (no bucket capacity spent,
    zero MoE output, excluded from the balancer counts)."""
    impl = ctx.moe_impl
    if impl == "auto":
        if ctx.mesh is None:
            impl = "dense"
        elif cfg.n_experts % ctx.n_model == 0:
            # E/D >= 1: expert parallelism (decode uses owned-token dispatch).
            impl = "ep"
        else:
            # E/D < 1: ESP — the paper's choice for few-large-expert models.
            impl = "esp"
    if impl == "dense":
        return moe_dense(p, x, cfg, ctx, token_mask=token_mask)
    if impl == "esp":
        return moe_esp(p, x, cfg, ctx, token_mask=token_mask)
    if impl == "ep":
        return moe_ep(p, x, cfg, ctx, placement, token_mask=token_mask)
    raise ValueError(f"unknown moe impl {impl!r}")
