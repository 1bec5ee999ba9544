"""GQA attention: full / sliding-window, train/prefill/decode, cross-attn.

Layout conventions: activations ``(batch, seq, d_model)``; q ``(B,S,H,hd)``;
k/v ``(B,S,K,hd)`` with ``K = n_kv_heads``. GQA is computed in grouped form
(no materialized head repetition). Softmax in fp32.

Decode caches:
* full attention — cache length = max seq, write at ``pos``;
* sliding window — ring buffer of length ``window``, write at ``pos % W``.

Sharding: heads (H and K) on the model axis, batch on the data axes. For
decode with ``seq_parallel_kv`` the cache's *sequence* dim rides the model
axis instead (flash-decode style) — see ``repro.parallel.collectives``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.kernels import registry
from repro.models.layers import apply_rope, normal_init
from repro.parallel.collectives import seq_parallel_decode_attend
from repro.parallel.ctx import ParallelCtx

NEG_INF = -1e30


def attn_init(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    d, h = cfg.d_model, cfg.head_dim_
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "wq": normal_init(kq, (d, cfg.n_heads * h), dtype=dtype),
        "wk": normal_init(kk, (d, cfg.n_kv_heads * h), dtype=dtype),
        "wv": normal_init(kv, (d, cfg.n_kv_heads * h), dtype=dtype),
        "wo": normal_init(ko, (cfg.n_heads * h, d), dtype=dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * h,), dtype=dtype)
        p["bk"] = jnp.zeros((cfg.n_kv_heads * h,), dtype=dtype)
        p["bv"] = jnp.zeros((cfg.n_kv_heads * h,), dtype=dtype)
    return p


def qkv_proj(
    p: dict, x: jax.Array, cfg: ModelConfig, ctx: ParallelCtx
) -> tuple[jax.Array, jax.Array, jax.Array]:
    b, s, _ = x.shape
    h = cfg.head_dim_
    q = jnp.einsum("bsd,de->bse", x, p["wq"])
    k = jnp.einsum("bsd,de->bse", x, p["wk"])
    v = jnp.einsum("bsd,de->bse", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, cfg.n_heads, h)
    k = k.reshape(b, s, cfg.n_kv_heads, h)
    v = v.reshape(b, s, cfg.n_kv_heads, h)
    q = ctx.shard(q, ctx.batch_spec, None, ctx.model_axis, None)
    k = ctx.shard(k, ctx.batch_spec, None, ctx.model_axis, None)
    v = ctx.shard(v, ctx.batch_spec, None, ctx.model_axis, None)
    return q, k, v


def out_proj(p: dict, o: jax.Array, ctx: ParallelCtx) -> jax.Array:
    b, s = o.shape[:2]
    o = o.reshape(b, s, -1)
    return jnp.einsum("bse,ed->bsd", o, p["wo"])


# ---------------------------------------------------------------------------
# reference attention math (grouped GQA, fp32 softmax)
# ---------------------------------------------------------------------------

def gqa_attend(
    q: jax.Array,      # (B, S, H, hd)
    k: jax.Array,      # (B, T, K, hd)
    v: jax.Array,      # (B, T, K, hd)
    mask: jax.Array | None,   # broadcastable to (B, 1, 1, S, T) or (S, T)
) -> jax.Array:
    b, s, nh, hd = q.shape
    nk = k.shape[2]
    g = nh // nk
    qg = q.reshape(b, s, nk, g, hd)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(hd).astype(jnp.float32)
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(b, s, nh, hd)


CHUNKED_KV_THRESHOLD = 2048  # switch to the online-softmax path beyond this


def chunked_gqa_attend(
    q: jax.Array,      # (B, S, H, hd)
    k: jax.Array,      # (B, T, K, hd)
    v: jax.Array,      # (B, T, K, hd)
    causal: bool,
    window: int,
    chunk: int = 1024,
) -> jax.Array:
    """Flash-style attention in pure jnp: scan over KV chunks with an online
    softmax, so the S x T score matrix is never materialized. This is the
    memory-feasible path for train_4k/prefill_32k at full scale (the Pallas
    kernel is the TPU-optimized equivalent; this one is backend-agnostic
    and differentiable)."""
    b, s, nh, hd = q.shape
    t, nk = k.shape[1], k.shape[2]
    g = nh // nk
    while t % chunk:
        chunk //= 2
    n_chunks = t // chunk
    qg = (q / jnp.sqrt(hd)).reshape(b, s, nk, g, hd)
    kc = k.reshape(b, n_chunks, chunk, nk, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, chunk, nk, hd).transpose(1, 0, 2, 3, 4)
    offset = t - s  # queries cover the tail of the key range

    def step(carry, inp):
        m_run, l_run, acc = carry
        k_blk, v_blk, j = inp
        sc = jnp.einsum("bskgd,btkd->bkgst", qg, k_blk).astype(jnp.float32)
        if causal:
            qpos = offset + jnp.arange(s)[:, None]
            kpos = j * chunk + jnp.arange(chunk)[None, :]
            mask = kpos <= qpos
            if window:
                mask = mask & (kpos > qpos - window)
            sc = jnp.where(mask[None, None, None], sc, NEG_INF)
        m_new = jnp.maximum(m_run, jnp.max(sc, axis=-1))
        p = jnp.exp(sc - m_new[..., None])
        alpha = jnp.exp(m_run - m_new)
        l_new = l_run * alpha + jnp.sum(p, axis=-1)
        upd = jnp.einsum("bkgst,btkd->bskgd", p.astype(v_blk.dtype), v_blk)
        acc = acc * alpha.transpose(0, 3, 1, 2)[..., None].astype(acc.dtype) + upd
        return (m_new, l_new, acc), None

    m0 = jnp.full((b, nk, g, s), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, nk, g, s), jnp.float32)
    acc0 = jnp.zeros((b, s, nk, g, hd), v.dtype)
    (m_f, l_f, acc), _ = jax.lax.scan(
        step, (m0, l0, acc0), (kc, vc, jnp.arange(n_chunks))
    )
    l_f = jnp.maximum(l_f, 1e-30).transpose(0, 3, 1, 2)[..., None]
    out = acc / l_f.astype(acc.dtype)
    return out.reshape(b, s, nh, hd)


def causal_mask(s: int, t: int | None = None, window: int = 0, offset: int = 0):
    """(S, T) boolean mask. ``offset`` = absolute position of query 0 minus
    position of key 0 (0 when q/k cover the same range)."""
    t = t or s
    qpos = jnp.arange(s)[:, None] + offset
    kpos = jnp.arange(t)[None, :]
    m = kpos <= qpos
    if window > 0:
        m &= kpos > qpos - window
    return m


# ---------------------------------------------------------------------------
# kernel dispatch (flash attention / flash decode via repro.kernels.registry)
# ---------------------------------------------------------------------------

def _flash_attend_eligible(q, k, ctx: ParallelCtx) -> bool:
    if not ctx.kernels_on or ctx.force_dense_attn:
        return False
    b, s, nh, hd = q.shape
    t, nkv = k.shape[1], k.shape[2]
    if not registry.can_flash_attend(
        s, t, nh, nkv, hd, registry.default_interpret()
    ):
        return False
    if ctx.mesh is None:
        return True
    # Under GSPMD the pallas_call must go through shard_map; the sharded
    # dims (batch, heads) have to divide their mesh axes. When kv heads
    # don't divide the model axis but the axis divides evenly *into* the
    # GQA groups (tp % nkv == 0 — Mixtral-style GQA on a wide TP axis),
    # the kv cache stays replicated and each rank slices the single kv
    # head its query-head block attends to (`_flash_attend` kv-rep body).
    tp = ctx.n_model
    if nh % tp or b % ctx.n_batch:
        return False
    return nkv % tp == 0 or tp % nkv == 0


def _flash_attend(q, k, v, causal: bool, window: int, ctx: ParallelCtx):
    if ctx.mesh is None:
        return registry.attend(q, k, v, causal=causal, window=window)
    tp = ctx.n_model
    nkv = k.shape[2]
    spec = P(ctx.batch_spec, None, ctx.model_axis, None)
    if nkv % tp == 0:
        return shard_map(
            lambda qb, kb, vb: registry.attend(
                qb, kb, vb, causal=causal, window=window
            ),
            mesh=ctx.mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )(q, k, v)

    # kv-head-replicated variant (tp % nkv == 0): q heads shard the model
    # axis; k/v stay replicated (qkv_proj's sharding constraint already
    # dropped the non-dividing head axis) and each rank slices out the one
    # kv head its contiguous query-head block maps to — rank r holds heads
    # [r*nh/tp, (r+1)*nh/tp), all inside GQA group r // (tp // nkv).
    def kv_rep_body(qb, kb, vb):
        r = jax.lax.axis_index(ctx.model_axis)
        i = r // (tp // nkv)
        kb = jax.lax.dynamic_slice_in_dim(kb, i, 1, axis=2)
        vb = jax.lax.dynamic_slice_in_dim(vb, i, 1, axis=2)
        return registry.attend(qb, kb, vb, causal=causal, window=window)

    kv_spec = P(ctx.batch_spec, None, None, None)
    return shard_map(
        kv_rep_body,
        mesh=ctx.mesh,
        in_specs=(spec, kv_spec, kv_spec),
        out_specs=spec,
        check_vma=False,
    )(q, k, v)


def _flash_decode_eligible(q, k_cache, ctx: ParallelCtx) -> bool:
    if not ctx.kernels_on or ctx.force_dense_attn:
        return False
    b, _, nh, hd = q.shape
    t, nkv = k_cache.shape[1], k_cache.shape[2]
    if not registry.can_flash_decode(
        t, nh, nkv, hd, registry.default_interpret()
    ):
        return False
    if ctx.mesh is None:
        return True
    if ctx.seq_parallel_kv:
        # Cache seq dim rides the model axis: decode goes through
        # ``seq_parallel_decode_attend`` (kernel partials + LSE-merge psum
        # when eligible) — see ``_seq_parallel_decode_eligible``.
        return False
    # Same eligibility shape as ``_flash_attend_eligible``: kv heads either
    # divide the model axis (head-sharded cache) or the axis divides into
    # the GQA groups (tp % nkv == 0 — kv cache replicated, each rank slices
    # its group's single kv head), so dense decode under wide TP no longer
    # requires nkv % tp == 0.
    tp = ctx.n_model
    if nh % tp or b % ctx.n_batch:
        return False
    return nkv % tp == 0 or tp % nkv == 0


def _flash_decode(q, k_cache, v_cache, valid, ctx: ParallelCtx):
    """q: (B, 1, H, hd); valid: (B, L) -> (B, 1, H, hd)."""
    q1 = q[:, 0]
    if ctx.mesh is None:
        o = registry.decode_attend(q1, k_cache, v_cache, valid)
        return o[:, None]
    bspec, ax = ctx.batch_spec, ctx.model_axis
    tp = ctx.n_model
    nkv = k_cache.shape[2]
    if nkv % tp == 0:
        o = shard_map(
            lambda qb, kb, vb, mb: registry.decode_attend(qb, kb, vb, mb),
            mesh=ctx.mesh,
            in_specs=(
                P(bspec, ax, None),
                P(bspec, None, ax, None),
                P(bspec, None, ax, None),
                P(bspec, None),
            ),
            out_specs=P(bspec, ax, None),
            check_vma=False,
        )(q1, k_cache, v_cache, valid)
        return o[:, None]

    # kv-head-replicated variant (tp % nkv == 0): mirrors ``_flash_attend``'s
    # kv-rep body — q heads shard the model axis (dim 1 of (B, H, hd)), the
    # kv cache stays replicated (``cache_specs`` already degraded the
    # non-dividing head axis to replication) and each rank slices out the
    # one kv head its contiguous query-head block attends to.
    def kv_rep_body(qb, kb, vb, mb):
        r = jax.lax.axis_index(ax)
        i = r // (tp // nkv)
        kb = jax.lax.dynamic_slice_in_dim(kb, i, 1, axis=2)
        vb = jax.lax.dynamic_slice_in_dim(vb, i, 1, axis=2)
        return registry.decode_attend(qb, kb, vb, mb)

    kv_spec = P(bspec, None, None, None)
    o = shard_map(
        kv_rep_body,
        mesh=ctx.mesh,
        in_specs=(P(bspec, ax, None), kv_spec, kv_spec, P(bspec, None)),
        out_specs=P(bspec, ax, None),
        check_vma=False,
    )(q1, k_cache, v_cache, valid)
    return o[:, None]


# ---------------------------------------------------------------------------
# train / prefill
# ---------------------------------------------------------------------------

def attention(
    p: dict,
    x: jax.Array,
    cfg: ModelConfig,
    ctx: ParallelCtx,
    positions: jax.Array | None = None,
    causal: bool = True,
    return_kv: bool = False,
):
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    q, k, v = qkv_proj(p, x, cfg, ctx)
    if cfg.rope_theta > 0:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    if _flash_attend_eligible(q, k, ctx):
        o = _flash_attend(q, k, v, causal, cfg.sliding_window if causal else 0, ctx)
    elif s > CHUNKED_KV_THRESHOLD and not ctx.force_dense_attn:
        o = chunked_gqa_attend(q, k, v, causal, cfg.sliding_window)
    else:
        mask = causal_mask(s, window=cfg.sliding_window) if causal else None
        o = gqa_attend(q, k, v, mask)
    o = ctx.shard(o, ctx.batch_spec, None, ctx.model_axis, None)
    out = out_proj(p, o, ctx)
    if return_kv:
        return out, (k, v)
    return out


def cross_attention(
    p: dict,
    x: jax.Array,
    kv: tuple[jax.Array, jax.Array],
    cfg: ModelConfig,
    ctx: ParallelCtx,
) -> jax.Array:
    """Decoder cross-attention over precomputed encoder k/v (no mask)."""
    b, s, _ = x.shape
    h = cfg.head_dim_
    q = jnp.einsum("bsd,de->bse", x, p["wq"])
    if cfg.qkv_bias:
        q = q + p["bq"]
    q = q.reshape(b, s, cfg.n_heads, h)
    q = ctx.shard(q, ctx.batch_spec, None, ctx.model_axis, None)
    o = gqa_attend(q, kv[0], kv[1], None)
    return out_proj(p, o, ctx)


def cross_kv(
    p: dict, memory: jax.Array, cfg: ModelConfig, ctx: ParallelCtx
) -> tuple[jax.Array, jax.Array]:
    b, t, _ = memory.shape
    h = cfg.head_dim_
    k = jnp.einsum("btd,de->bte", memory, p["wk"])
    v = jnp.einsum("btd,de->bte", memory, p["wv"])
    if cfg.qkv_bias:
        k, v = k + p["bk"], v + p["bv"]
    k = k.reshape(b, t, cfg.n_kv_heads, h)
    v = v.reshape(b, t, cfg.n_kv_heads, h)
    k = ctx.shard(k, ctx.batch_spec, None, ctx.model_axis, None)
    v = ctx.shard(v, ctx.batch_spec, None, ctx.model_axis, None)
    return k, v


# ---------------------------------------------------------------------------
# decode (single new token against a cache)
# ---------------------------------------------------------------------------

PAGE_SIZE = 128  # default logical KV page (rows per physical pool page)


def cache_len(cfg: ModelConfig, max_seq: int) -> int:
    """Logical KV slots a decode cache holds (ring length when windowed)."""
    w = cfg.sliding_window or 0
    return min(max_seq, w) if w else max_seq


def cache_init(
    cfg: ModelConfig, batch: int, max_seq: int, dtype=jnp.float32
) -> dict:
    length = cache_len(cfg, max_seq)
    shape = (batch, length, cfg.n_kv_heads, cfg.head_dim_)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def paged_layout(cfg: ModelConfig, max_seq: int, page_size: int = PAGE_SIZE):
    """``(page_size, n_blocks)`` for a paged cache of ``max_seq`` context.

    Full attention tolerates a partial tail block (prefix validity masks
    it), so any page size works. A sliding-window ring must be a whole
    number of pages — prefix validity over ``NB * bs`` logical slots *is*
    the ring's live set only when ``NB * bs == ring length`` — so the page
    shrinks to the largest divisor of the ring length ≤ ``page_size``
    (compiled-kernel eligibility may then fall back to the gather
    reference; see ``registry.can_flash_decode_paged``).
    """
    length = cache_len(cfg, max_seq)
    bs = max(min(page_size, length), 1)
    if cfg.sliding_window:
        while length % bs:
            bs -= 1
    return bs, -(-length // bs)


def paged_cache_init(
    cfg: ModelConfig,
    batch: int,
    max_seq: int,
    dtype=jnp.float32,
    page_size: int = PAGE_SIZE,
    n_pages: int | None = None,
) -> dict:
    """Paged decode cache: a shared page pool + per-request block tables.

    * ``pool_k`` / ``pool_v`` — ``(P, bs, K, hd)``: physical pages, shared
      across requests (``P`` defaults to ``batch * NB`` = fully backed);
    * ``tables`` — ``(B, NB)`` int32: logical block ``j`` of request ``b``
      lives in pool page ``tables[b, j]`` (identity layout by default; a
      serving-side allocator may remap freely);
    * ``lengths`` — ``(B,)`` int32: tokens *written* per request. The live
      context is ``min(lengths, NB * bs)`` (ring wraps in place).

    With an explicit ``n_pages`` (allocator mode, possibly oversubscribed:
    ``n_pages < batch * NB``) the pool gets **one extra write-off page** at
    index ``n_pages`` and every table entry starts there: scatters through
    unallocated entries land on the write-off page and are never read back
    (prefix validity stops before them; the dead-block clamp in the kernel
    only revisits live pages). A serving allocator (`runtime.serve.PagePool`)
    hands out pages ``0..n_pages-1`` per request and frees them on release.
    """
    bs, nb = paged_layout(cfg, max_seq, page_size)
    if n_pages is None:
        pool_pages = batch * nb
        tables = jnp.arange(pool_pages, dtype=jnp.int32).reshape(batch, nb)
    else:
        pool_pages = n_pages + 1   # + write-off page for unallocated entries
        tables = jnp.full((batch, nb), n_pages, jnp.int32)
    shape = (pool_pages, bs, cfg.n_kv_heads, cfg.head_dim_)
    return {
        "pool_k": jnp.zeros(shape, dtype),
        "pool_v": jnp.zeros(shape, dtype),
        "tables": tables,
        "lengths": jnp.zeros((batch,), jnp.int32),
    }


def is_paged(cache: dict) -> bool:
    return "pool_k" in cache


def paged_prefill_fill(
    cache: dict,
    k: jax.Array,              # (B, S, K, hd) — the prompt's keys
    v: jax.Array,
    s: int,
    lengths: jax.Array | None = None,   # (B,) true prompt lengths (<= S)
) -> dict:
    """Scatter a prefill's K/V into the page pool through the block tables.

    Token ``t`` of request ``b`` lands at logical slot ``t % cap``
    (identical to the decode write), so after writing ``L_b`` tokens, slot
    ``j`` holds position ``L_b - 1 - ((L_b - 1 - j) % cap)`` — a *per-
    request* gather, which handles ragged right-padded prompts and
    ring-wrapped prefills (``L_b > cap``) uniformly (negative positions =
    never written; they fall outside the ``min(L_b, cap)`` live prefix).
    Table entries may point at a write-off page (unallocated blocks of an
    oversubscribed pool — see ``runtime.serve.PagePool``); rows scattered
    there are never read back.
    """
    pool_k, pool_v, tables = cache["pool_k"], cache["pool_v"], cache["tables"]
    b, nb = tables.shape
    bs = pool_k.shape[1]
    cap = nb * bs
    written = (
        lengths.astype(jnp.int32)
        if lengths is not None
        else jnp.full((b,), s, jnp.int32)
    )
    j = jnp.arange(cap)[None, :]                       # (1, cap)
    last = written[:, None] - 1                        # (B, 1)
    pos = last - ((last - j) % cap)                    # (B, cap)
    idx = jnp.clip(pos, 0, s - 1)[:, :, None, None]
    kk = jnp.take_along_axis(k, idx, axis=1)           # (B, cap, K, hd)
    vv = jnp.take_along_axis(v, idx, axis=1)
    flat = tables.reshape(-1)
    page_shape = (b * nb, bs, *kk.shape[2:])
    pool_k = pool_k.at[flat].set(kk.reshape(page_shape))
    pool_v = pool_v.at[flat].set(vv.reshape(page_shape))
    return {"pool_k": pool_k, "pool_v": pool_v, "tables": tables, "lengths": written}


def cache_spec(cfg: ModelConfig, batch: int, max_seq: int, ctx: ParallelCtx):
    """PartitionSpec elements for one layer's k/v cache."""
    if ctx.seq_parallel_kv:
        return (ctx.batch_spec, ctx.model_axis, None, None)
    return (ctx.batch_spec, None, ctx.model_axis, None)


def decode_attention(
    p: dict,
    x: jax.Array,            # (B, 1, d)
    cache: dict,             # dense {"k","v"} or paged (see paged_cache_init)
    pos: jax.Array,          # scalar int32 — absolute position of new token
    cfg: ModelConfig,
    ctx: ParallelCtx,
) -> tuple[jax.Array, dict]:
    if is_paged(cache):
        return _paged_decode_attention(p, x, cache, cfg, ctx)
    b = x.shape[0]
    q, k_new, v_new = qkv_proj(p, x, cfg, ctx)
    posb = jnp.broadcast_to(pos, (b, 1))
    if cfg.rope_theta > 0:
        q = apply_rope(q, posb, cfg.rope_theta)
        k_new = apply_rope(k_new, posb, cfg.rope_theta)

    length = cache["k"].shape[1]
    w = cfg.sliding_window or 0
    if w > 0:
        slot = pos % length
    else:
        # Overflow (pos >= length): the cache is full. Freeze it — skip the
        # write (it would silently clobber the last slot's key) and clamp
        # the mask below, so slot j always holds position j. The serving
        # layer refuses such steps outright (Server.decode raises).
        slot = jnp.minimum(pos, length - 1)
        old_k = jax.lax.dynamic_slice_in_dim(cache["k"], slot, 1, axis=1)
        old_v = jax.lax.dynamic_slice_in_dim(cache["v"], slot, 1, axis=1)
        overflow = pos >= length
        k_new = jnp.where(overflow, old_k, k_new)
        v_new = jnp.where(overflow, old_v, v_new)
    k_cache = jax.lax.dynamic_update_slice(cache["k"], k_new, (0, slot, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(cache["v"], v_new, (0, slot, 0, 0))

    j = jnp.arange(length)
    if w > 0:
        # ring buffer: slot j holds absolute position pos - ((pos - j) % L);
        # negative => never written yet.
        slot_pos = pos - ((pos - j) % length)
        mask = slot_pos >= 0
    else:
        mask = j <= jnp.minimum(pos, length - 1)
    if _flash_decode_eligible(q, k_cache, ctx):
        valid = jnp.broadcast_to(mask[None, :], (b, length))
        o = _flash_decode(q, k_cache, v_cache, valid, ctx)
    elif _seq_parallel_decode_eligible(q, k_cache, ctx):
        o = seq_parallel_decode_attend(q, k_cache, v_cache, mask, ctx)
    else:
        o = gqa_attend(q, k_cache, v_cache, mask[None, None, None, None, :])
    o = ctx.shard(o, ctx.batch_spec, None, ctx.model_axis, None)
    out = out_proj(p, o, ctx)
    return out, {"k": k_cache, "v": v_cache}


def chunk_prefill_attention(
    p: dict,
    x: jax.Array,            # (1, C, d) — the chunk's hidden states
    cache: dict,             # paged per-layer cache (shared pool, post-decode-write)
    table: jax.Array,        # (NB,) int32 — the prefilling request's block table
    start: jax.Array,        # scalar int32 — absolute position of chunk token 0
    length: jax.Array,       # scalar int32 — valid tokens this chunk (0 = no-op)
    cfg: ModelConfig,
    ctx: ParallelCtx,
) -> tuple[jax.Array, dict]:
    """Prefill-lane attention for one chunk of one admitting request.

    Runs *inside* the fused decode step, against the same shared page pool
    the decode lane just wrote: the chunk's K/V scatter through ``table``
    at logical slots ``start + i`` (full attention only — slot j holds
    position j, so "causal within the chunk AND against already-written
    pages" is the single mask ``kpos <= start + i``). Pad rows
    (``i >= length``) scatter to the write-off page and attend to garbage;
    their outputs are masked out of MoE routing by the caller and never
    read. ``length = 0`` is the no-op chunk: one fused program serves
    idle, decode-only and decode+chunk ticks alike.

    The chunk's pages are disjoint from every live slot's table (the
    serving allocator hands them out from the same pool), so the decode
    lane never reads a half-written chunk and the chunk never perturbs a
    live request — the isolation the splice-admission path got from a
    separate batch-1 prefill, now without stalling the batch.
    """
    pool_k, pool_v = cache["pool_k"], cache["pool_v"]
    bs = pool_k.shape[1]
    nb = table.shape[0]
    cap = nb * bs
    c = x.shape[1]

    q, k, v = qkv_proj(p, x, cfg, ctx)
    pos = start + jnp.arange(c, dtype=jnp.int32)         # (C,) absolute
    if cfg.rope_theta > 0:
        q = apply_rope(q, pos[None, :], cfg.rope_theta)
        k = apply_rope(k, pos[None, :], cfg.rope_theta)

    # Scatter this chunk's K/V through the block table. Valid rows land at
    # logical slot == absolute position; pad rows go to the write-off page.
    slot = jnp.minimum(pos, cap - 1)
    valid = jnp.arange(c) < length
    trash = pool_k.shape[0] - 1
    page = jnp.where(valid, table[slot // bs], trash)    # (C,)
    row = slot % bs
    pool_k = pool_k.at[page, row].set(k[0])
    pool_v = pool_v.at[page, row].set(v[0])

    # Attend over everything written so far: previous chunks' pages plus
    # this chunk, causally. Masked (future / never-written) slots score
    # exactly zero after softmax, so the gather over the full table is
    # bit-identical to a tight prefill over the same prefix.
    from repro.kernels.flash_decode.ref import gather_pages

    k_all = gather_pages(pool_k, table[None, :])         # (1, cap, K, hd)
    v_all = gather_pages(pool_v, table[None, :])
    mask = jnp.arange(cap)[None, :] <= pos[:, None]      # (C, cap)
    o = gqa_attend(q, k_all, v_all, mask)
    o = ctx.shard(o, ctx.batch_spec, None, ctx.model_axis, None)
    out = out_proj(p, o, ctx)
    new_cache = {
        "pool_k": pool_k, "pool_v": pool_v,
        "tables": cache["tables"], "lengths": cache["lengths"],
    }
    return out, new_cache


def _seq_parallel_decode_eligible(q, k_cache, ctx: ParallelCtx) -> bool:
    """Sequence-parallel decode: the cache's seq dim rides the model axis
    and each shard runs flash-decode partials locally, LSE-merged with a
    psum (`seq_parallel_decode_attend`). The shard_map just needs the
    sharded dims to divide their axes; whether the *kernel* or the einsum
    computes the per-shard partials is decided inside the collective."""
    if not ctx.seq_parallel_kv or ctx.mesh is None or ctx.force_dense_attn:
        return False
    b, _, _, _ = q.shape
    t = k_cache.shape[1]
    return t % ctx.n_model == 0 and b % ctx.n_batch == 0


# ---------------------------------------------------------------------------
# paged decode (block-table KV walk over a shared page pool)
# ---------------------------------------------------------------------------

def _paged_decode_eligible(q, pool_k, ctx: ParallelCtx) -> bool:
    if not ctx.kernels_on or ctx.force_dense_attn:
        return False
    b, _, nh, hd = q.shape
    bs, nkv = pool_k.shape[1], pool_k.shape[2]
    if not registry.can_flash_decode_paged(
        bs, nh, nkv, hd, registry.default_interpret()
    ):
        return False
    if ctx.mesh is None:
        return True
    # Under a mesh the pool is replicated over the batch axes (pages are
    # dynamically owned — the page dim can't shard by request) and kv-heads
    # ride the model axis.
    return nh % ctx.n_model == 0 and nkv % ctx.n_model == 0 and b % ctx.n_batch == 0


def _paged_flash_decode(q, pool_k, pool_v, tables, lengths, ctx: ParallelCtx):
    """q: (B, 1, H, hd) -> (B, 1, H, hd) via the paged kernel."""
    q1 = q[:, 0]
    if ctx.mesh is None:
        o = registry.decode_attend_paged(q1, pool_k, pool_v, tables, lengths)
        return o[:, None]
    bspec, ax = ctx.batch_spec, ctx.model_axis
    o = shard_map(
        lambda qb, kb, vb, tb, lb: registry.decode_attend_paged(
            qb, kb, vb, tb, lb
        ),
        mesh=ctx.mesh,
        in_specs=(
            P(bspec, ax, None),
            P(None, None, ax, None),
            P(None, None, ax, None),
            P(bspec, None),
            P(bspec),
        ),
        out_specs=P(bspec, ax, None),
        check_vma=False,
    )(q1, pool_k, pool_v, tables, lengths)
    return o[:, None]


def _paged_decode_attention(
    p: dict,
    x: jax.Array,            # (B, 1, d)
    cache: dict,             # paged: pool_k/pool_v/tables/lengths
    cfg: ModelConfig,
    ctx: ParallelCtx,
) -> tuple[jax.Array, dict]:
    """One decode step against a paged cache.

    Per-request ``lengths`` replace the global scalar position: each
    request RoPEs and writes at its own absolute position, so batched
    requests of different context lengths decode together. The write is a
    pool scatter (page = ``tables[b, slot // bs]``, row = ``slot % bs``);
    the ring case wraps ``slot`` over the ``NB * bs`` logical slots and
    prefix validity ``min(written, NB*bs)`` is exactly the ring's live set
    (softmax is permutation-invariant; RoPE is applied at write time).
    """
    pool_k, pool_v = cache["pool_k"], cache["pool_v"]
    tables, written = cache["tables"], cache["lengths"]
    bs = pool_k.shape[1]
    cap = tables.shape[1] * bs
    w = cfg.sliding_window or 0

    q, k_new, v_new = qkv_proj(p, x, cfg, ctx)
    posb = written[:, None]  # (B, 1) — per-request position of the new token
    if cfg.rope_theta > 0:
        q = apply_rope(q, posb, cfg.rope_theta)
        k_new = apply_rope(k_new, posb, cfg.rope_theta)

    slot = written % cap if w > 0 else jnp.minimum(written, cap - 1)
    page = jnp.take_along_axis(tables, (slot // bs)[:, None], axis=1)[:, 0]
    row = slot % bs
    if w == 0:
        # Same freeze-on-overflow contract as the dense cache: a request
        # at capacity stops writing (serving refuses the step anyway).
        overflow = (written >= cap)[:, None, None, None]
        k_new = jnp.where(overflow, pool_k[page, row][:, None], k_new)
        v_new = jnp.where(overflow, pool_v[page, row][:, None], v_new)
    pool_k = pool_k.at[page, row].set(k_new[:, 0])
    pool_v = pool_v.at[page, row].set(v_new[:, 0])
    written = written + 1
    live = jnp.minimum(written, cap)

    if _paged_decode_eligible(q, pool_k, ctx):
        o = _paged_flash_decode(q, pool_k, pool_v, tables, live, ctx)
    else:
        from repro.kernels.flash_decode.ref import gather_pages

        k_all = gather_pages(pool_k, tables)
        v_all = gather_pages(pool_v, tables)
        mask = jnp.arange(cap)[None, :] < live[:, None]
        o = gqa_attend(q, k_all, v_all, mask[:, None, None, None, :])
    o = ctx.shard(o, ctx.batch_spec, None, ctx.model_axis, None)
    out = out_proj(p, o, ctx)
    new_cache = {
        "pool_k": pool_k, "pool_v": pool_v, "tables": tables, "lengths": written,
    }
    return out, new_cache
