"""Model assembly: init / forward (train) / prefill / decode for every
assigned architecture family.

Block patterns (``cfg.block_pattern``):

* ``attn``   — decoder-only transformer; per-layer FFN is dense SwiGLU or
  MoE (``cfg.n_experts > 0``). Layers are *stacked* and driven by
  ``lax.scan`` so HLO size is independent of depth.
* ``zamba``  — units of ``attn_every`` Mamba2 layers followed by one
  invocation of a single *shared* attention+MLP block (Zamba2 signature);
  trailing Mamba2 layers close the stack.
* ``xlstm``  — units of 3 mLSTM + 1 sLSTM blocks (requires depth % 4 == 0).
* ``encdec`` — bidirectional encoder over stub frontend embeddings +
  causal decoder with cross-attention (seamless-m4t).

Frontend stubs (``cfg.frontend_stub``): precomputed frame/patch embeddings
arrive as an input and are prepended (vlm) or encoded (audio).

Caches: a dict with per-pattern stacked leaves + scalar ``pos``; decode is
one token per call. SSM caches are O(1) in context length.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import ssm
from repro.models.attention import (
    PAGE_SIZE,
    attn_init,
    attention,
    cache_init,
    chunk_prefill_attention,
    cross_attention,
    cross_kv,
    decode_attention,
    is_paged,
    paged_cache_init,
    paged_prefill_fill,
)
from repro.models.layers import mlp_apply, mlp_init, normal_init, rms_norm
from repro.models.moe import moe_apply, moe_init, zero_aux
from repro.parallel.ctx import NO_MESH, ParallelCtx

XLSTM_UNIT_M = 3  # mLSTM blocks per unit (then 1 sLSTM)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _attn_block_init(key, cfg: ModelConfig, dtype) -> dict:
    k1, k2 = jax.random.split(key)
    p = {
        "ln1": jnp.ones((cfg.d_model,), dtype),
        "ln2": jnp.ones((cfg.d_model,), dtype),
        "attn": attn_init(k1, cfg, dtype),
    }
    if cfg.is_moe:
        p["moe"] = moe_init(k2, cfg, dtype)
    else:
        p["mlp"] = mlp_init(k2, cfg.d_model, cfg.d_ff, dtype)
    return p


def _encdec_block_init(key, cfg: ModelConfig, dtype, cross: bool) -> dict:
    ks = jax.random.split(key, 3)
    p = {
        "ln1": jnp.ones((cfg.d_model,), dtype),
        "ln2": jnp.ones((cfg.d_model,), dtype),
        "attn": attn_init(ks[0], cfg, dtype),
        "mlp": mlp_init(ks[1], cfg.d_model, cfg.d_ff, dtype),
    }
    if cross:
        p["ln_x"] = jnp.ones((cfg.d_model,), dtype)
        p["xattn"] = attn_init(ks[2], cfg, dtype)
    return p


def _stack(init_fn, key, n: int):
    keys = jax.random.split(key, max(n, 1))
    return jax.tree.map(lambda *xs: jnp.stack(xs), *[init_fn(k) for k in keys[:n]]) if n else None


def zamba_layout(cfg: ModelConfig) -> tuple[int, int]:
    """(n_units, n_trailing_mamba)."""
    u = cfg.n_layers // cfg.attn_every
    return u, cfg.n_layers - u * cfg.attn_every


def init_params(rng, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    keys = jax.random.split(rng, 8)
    d = cfg.d_model
    params: dict = {
        "embed": normal_init(keys[0], (cfg.vocab_size, d), dtype=dtype),
        "final_norm": jnp.ones((d,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = normal_init(keys[1], (d, cfg.vocab_size), dtype=dtype)

    pat = cfg.block_pattern
    if pat == "attn":
        params["layers"] = _stack(
            lambda k: _attn_block_init(k, cfg, dtype), keys[2], cfg.n_layers
        )
    elif pat == "zamba":
        u, r = zamba_layout(cfg)
        mamba_one = lambda k: {
            "ln": jnp.ones((d,), dtype),
            "mamba": ssm.mamba_init(k, cfg, dtype),
        }
        params["units"] = _stack(
            lambda k: _stack(mamba_one, k, cfg.attn_every), keys[2], u
        )
        params["trailing"] = _stack(mamba_one, keys[3], r)
        params["shared"] = _encdec_block_init(keys[4], cfg, dtype, cross=False)
    elif pat == "xlstm":
        assert cfg.n_layers % (XLSTM_UNIT_M + 1) == 0, "xlstm depth % 4 != 0"
        u = cfg.n_layers // (XLSTM_UNIT_M + 1)
        m_one = lambda k: {
            "ln": jnp.ones((d,), dtype),
            "m": ssm.mlstm_init(k, cfg, dtype),
        }
        s_one = lambda k: {
            "ln": jnp.ones((d,), dtype),
            "s": ssm.slstm_init(k, cfg, dtype),
        }
        params["units"] = {
            "m": _stack(lambda k: _stack(m_one, k, XLSTM_UNIT_M), keys[2], u),
            "s": _stack(s_one, keys[3], u),
        }
    elif pat == "encdec":
        params["encoder"] = _stack(
            lambda k: _encdec_block_init(k, cfg, dtype, cross=False),
            keys[2],
            cfg.n_encoder_layers,
        )
        params["layers"] = _stack(
            lambda k: _encdec_block_init(k, cfg, dtype, cross=True),
            keys[3],
            cfg.n_layers,
        )
        params["enc_norm"] = jnp.ones((d,), dtype)
    else:
        raise ValueError(pat)
    return params


# ---------------------------------------------------------------------------
# blocks (full-sequence)
# ---------------------------------------------------------------------------

def _attn_block(p, x, cfg, ctx, positions):
    sp = ctx.seq_spec  # seq-parallel residual stream (retained-AG pattern)
    x = ctx.shard(x, ctx.batch_spec, sp, None)
    o = attention(p["attn"], rms_norm(x, p["ln1"], cfg.norm_eps), cfg, ctx, positions)
    h = x + ctx.shard(o, ctx.batch_spec, sp, None)
    z = rms_norm(h, p["ln2"], cfg.norm_eps)
    if cfg.is_moe:
        y, aux = moe_apply(p["moe"], z, cfg, ctx)
    else:
        y, aux = mlp_apply(p["mlp"], z, ctx), zero_aux(cfg)
    return h + ctx.shard(y, ctx.batch_spec, sp, None), aux


def _enc_block(p, x, cfg, ctx):
    h = x + attention(
        p["attn"], rms_norm(x, p["ln1"], cfg.norm_eps), cfg, ctx, causal=False
    )
    return h + mlp_apply(p["mlp"], rms_norm(h, p["ln2"], cfg.norm_eps), ctx)


def _dec_block(p, x, kv, cfg, ctx, positions):
    h = x + attention(p["attn"], rms_norm(x, p["ln1"], cfg.norm_eps), cfg, ctx, positions)
    h = h + cross_attention(p["xattn"], rms_norm(h, p["ln_x"], cfg.norm_eps), kv, cfg, ctx)
    return h + mlp_apply(p["mlp"], rms_norm(h, p["ln2"], cfg.norm_eps), ctx)


def _scan_layers(body, x, stacked, ctx: ParallelCtx, aux0=None):
    """Scan ``body`` over stacked layer params, accumulating aux pytrees."""
    fn = jax.checkpoint(body) if ctx.remat else body
    if aux0 is None:
        aux0 = jnp.zeros((), jnp.float32)

    def f(carry, inp):
        y, aux = fn(inp, carry[0])
        return (y, jax.tree.map(jnp.add, carry[1], aux)), None

    (x, aux), _ = jax.lax.scan(f, (x, aux0), stacked, unroll=ctx.full_unroll)
    return x, aux


# ---------------------------------------------------------------------------
# forward (train): full causal sequence -> logits
# ---------------------------------------------------------------------------

def _embed(params, tokens, cfg: ModelConfig, ctx: ParallelCtx):
    x = jnp.take(params["embed"], tokens, axis=0)
    return ctx.shard(x, ctx.batch_spec, None, None)


def _logits(params, x, cfg: ModelConfig, ctx: ParallelCtx):
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    return ctx.shard(logits, ctx.batch_spec, None, ctx.model_axis)


def forward(
    params,
    tokens,
    cfg: ModelConfig,
    ctx: ParallelCtx = NO_MESH,
    embeds=None,
):
    """Full-sequence causal forward. ``embeds``: stub frontend embeddings —
    prepended (vlm) or encoded (audio enc-dec). Returns (logits, aux_loss);
    logits cover only the token positions."""
    x = _embed(params, tokens, cfg, ctx)
    b, s, _ = x.shape
    pat = cfg.block_pattern
    aux = zero_aux(cfg)

    if pat == "encdec":
        assert embeds is not None, "enc-dec needs frontend embeddings"
        mem = embeds
        for_enc = lambda p, m: (_enc_block(p, m, cfg, ctx), 0.0)
        mem, _ = _scan_layers(for_enc, mem, params["encoder"], ctx)
        mem = rms_norm(mem, params["enc_norm"], cfg.norm_eps)
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))

        def dec(p, h):
            kv = cross_kv(p["xattn"], mem, cfg, ctx)
            return _dec_block(p, h, kv, cfg, ctx, positions), zero_aux(cfg)

        x, aux = _scan_layers(dec, x, params["layers"], ctx, zero_aux(cfg))
        return _logits(params, x, cfg, ctx), aux

    n_front = 0
    if cfg.frontend_stub and embeds is not None:
        n_front = embeds.shape[1]
        x = jnp.concatenate([embeds.astype(x.dtype), x], axis=1)
        s = s + n_front
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))

    if pat == "attn":
        body = lambda p, h: _attn_block(p, h, cfg, ctx, positions)
        x, aux = _scan_layers(body, x, params["layers"], ctx, zero_aux(cfg))
    elif pat == "zamba":
        x, aux = _zamba_forward(params, x, cfg, ctx, positions)
    elif pat == "xlstm":
        x, aux = _xlstm_forward(params, x, cfg, ctx)
    else:
        raise ValueError(pat)

    if n_front:
        x = x[:, n_front:]
    return _logits(params, x, cfg, ctx), aux


def _zamba_forward(params, x, cfg, ctx, positions):
    shared = params["shared"]

    def unit(p_unit, h):
        def inner(pl, hh):
            out, _ = ssm.mamba_apply(pl["mamba"], rms_norm(hh, pl["ln"], cfg.norm_eps), cfg)
            return hh + out, 0.0

        h, _ = _scan_layers(inner, h, p_unit, ctx)
        h = h + attention(
            shared["attn"], rms_norm(h, shared["ln1"], cfg.norm_eps), cfg, ctx, positions
        )
        h = h + mlp_apply(shared["mlp"], rms_norm(h, shared["ln2"], cfg.norm_eps), ctx)
        return h, 0.0

    if params["units"] is not None:
        x, _ = _scan_layers(unit, x, params["units"], ctx)
    if params["trailing"] is not None:
        def inner_t(pl, hh):
            out, _ = ssm.mamba_apply(pl["mamba"], rms_norm(hh, pl["ln"], cfg.norm_eps), cfg)
            return hh + out, 0.0
        x, _ = _scan_layers(inner_t, x, params["trailing"], ctx)
    return x, zero_aux(cfg)


def _xlstm_forward(params, x, cfg, ctx):
    def unit(p_unit, h):
        def m_body(pl, hh):
            out, _ = ssm.mlstm_apply(pl["m"], rms_norm(hh, pl["ln"], cfg.norm_eps), cfg)
            return hh + out, 0.0

        h, _ = _scan_layers(m_body, h, p_unit["m"], ctx)
        ps = p_unit["s"]
        out, _ = ssm.slstm_apply(ps["s"], rms_norm(h, ps["ln"], cfg.norm_eps), cfg)
        return h + out, 0.0

    x, _ = _scan_layers(unit, x, params["units"], ctx)
    return x, zero_aux(cfg)


# ---------------------------------------------------------------------------
# decode: cache init / prefill / one-token step
# ---------------------------------------------------------------------------

def init_cache(
    cfg: ModelConfig,
    batch: int,
    max_seq: int,
    dtype=jnp.float32,
    paged: bool = False,
    page_size: int = PAGE_SIZE,
    n_pages: int | None = None,
) -> dict:
    """Decode cache sized for ``max_seq`` context.

    ``paged=True`` (``attn`` pattern only) swaps the dense per-layer
    ``(B, L, K, hd)`` k/v for a shared page pool + per-request block tables
    (`attention.paged_cache_init`): decode HBM traffic then tracks each
    request's live context, and an oversubscribed pool (``n_pages``) lets a
    serving-side allocator share pages across requests of varied lengths.
    """
    pat = cfg.block_pattern
    if paged and pat != "attn":
        raise ValueError(f"paged KV cache requires block_pattern='attn', got {pat}")
    cache: dict = {"pos": jnp.zeros((), jnp.int32)}
    if pat == "attn":
        one = (
            paged_cache_init(cfg, batch, max_seq, dtype, page_size, n_pages)
            if paged
            else cache_init(cfg, batch, max_seq, dtype)
        )
        cache["layers"] = jax.tree.map(
            lambda z: jnp.broadcast_to(z, (cfg.n_layers, *z.shape)).copy(), one
        )
    elif pat == "zamba":
        u, r = zamba_layout(cfg)
        st = ssm.mamba_state_init(cfg, batch)
        cache["units_ssm"] = jax.tree.map(
            lambda z: jnp.zeros((u, cfg.attn_every, *z.shape), z.dtype), st
        )
        cache["trailing_ssm"] = jax.tree.map(
            lambda z: jnp.zeros((r, *z.shape), z.dtype), st
        )
        one = cache_init(cfg, batch, max_seq, dtype)
        cache["shared_kv"] = jax.tree.map(
            lambda z: jnp.zeros((u, *z.shape), z.dtype), one
        )
    elif pat == "xlstm":
        u = cfg.n_layers // (XLSTM_UNIT_M + 1)
        ms = ssm.mlstm_state_init(cfg, batch)
        ss = ssm.slstm_state_init(cfg, batch)
        cache["m"] = jax.tree.map(
            lambda z: jnp.zeros((u, XLSTM_UNIT_M, *z.shape), z.dtype), ms
        )
        cache["s"] = jax.tree.map(lambda z: jnp.zeros((u, *z.shape), z.dtype), ss)
    elif pat == "encdec":
        one = cache_init(cfg, batch, max_seq, dtype)
        cache["layers"] = jax.tree.map(
            lambda z: jnp.broadcast_to(z, (cfg.n_layers, *z.shape)).copy(), one
        )
        h = cfg.head_dim_
        cache["cross_kv"] = (
            jnp.zeros((cfg.n_layers, batch, cfg.frontend_tokens, cfg.n_kv_heads, h), dtype),
            jnp.zeros((cfg.n_layers, batch, cfg.frontend_tokens, cfg.n_kv_heads, h), dtype),
        )
    return cache


def decode_step(
    params,
    token,                      # (B, 1) int32
    cache: dict,
    cfg: ModelConfig,
    ctx: ParallelCtx = NO_MESH,
    embeds=None,                # encdec: unused at decode (cross kv cached)
    placement=None,             # (slot_of, n_replicas) from the NI-Balancer
    slot_mask=None,             # (B,) bool — False = empty/released batch row
    chunk=None,                 # prefill-lane operand (see below); None = off
):
    """One serve step: consume one token, update the cache, emit logits.

    ``slot_mask`` marks live batch rows for continuous batching: masked
    rows still flow through the step (fixed shapes, no recompile) but are
    excluded from MoE routing, so a half-empty batch never spends expert
    bucket capacity on dead slots. Their logits are garbage by contract —
    the scheduler owns which rows mean anything.

    ``chunk`` adds the prefill lane (paged ``attn`` pattern only): a dict
    ``{"tokens": (1, C) int32, "table": (NB,) int32, "start": scalar,
    "length": scalar}`` carrying one fixed-size chunk of the admitting
    request's context. The chunk runs through every layer alongside the
    decode tokens — same weights, same placement, one compiled program —
    writing its K/V through ``table`` (see
    :func:`~repro.models.attention.chunk_prefill_attention`) and routing
    only its ``length`` valid rows through MoE. ``length = 0`` is the
    no-op chunk, so idle, decode-only and decode+chunk ticks all hit the
    same trace. ``stats["chunk_logits"]`` holds the last valid chunk
    position's logits ``(1, 1, V)``: on the final chunk these emit the
    request's first token, bit-identical to a whole-context prefill."""
    x = _embed(params, token, cfg, ctx)
    pos = cache["pos"]
    pat = cfg.block_pattern
    new_cache = dict(cache)
    if chunk is not None and pat != "attn":
        raise ValueError(
            f"chunked prefill requires block_pattern='attn', got {pat}"
        )

    aux = zero_aux(cfg)
    chunk_logits = None
    if pat == "attn":
        # Named scopes (op metadata only; the compiled program is the
        # same): ``decode/`` holds the decode rows' attention and MoE,
        # ``lane/`` the prefill lane's chunk through every layer and its
        # LM head, ``lm_head`` the decode rows' logits.
        if chunk is not None:
            with jax.named_scope("lane"):
                xc = _embed(params, chunk["tokens"], cfg, ctx)   # (1, C, d)
            n_chunk = chunk["tokens"].shape[1]
            cvalid = (jnp.arange(n_chunk) < chunk["length"])[None, :]

        def body(carry, inp):
            if chunk is None:
                h, a_sum = carry
            else:
                h, hc, a_sum = carry
            p_l, c_l = inp
            with jax.named_scope("decode"):
                z = rms_norm(h, p_l["ln1"], cfg.norm_eps)
                o, c_new = decode_attention(p_l["attn"], z, c_l, pos, cfg, ctx)
                h = h + o
                z2 = rms_norm(h, p_l["ln2"], cfg.norm_eps)
                if cfg.is_moe:
                    y, a = moe_apply(
                        p_l["moe"], z2, cfg, ctx, placement=placement,
                        token_mask=None if slot_mask is None else slot_mask[:, None],
                    )
                else:
                    y, a = mlp_apply(p_l["mlp"], z2, ctx), zero_aux(cfg)
                h = h + y
            if chunk is None:
                return (h, jax.tree.map(jnp.add, a_sum, a)), c_new
            # Prefill lane: the chunk flows through the same layer against
            # the pool the decode lane just wrote (disjoint pages).
            with jax.named_scope("lane"):
                zc = rms_norm(hc, p_l["ln1"], cfg.norm_eps)
                oc, c_new = chunk_prefill_attention(
                    p_l["attn"], zc, c_new, chunk["table"],
                    chunk["start"], chunk["length"], cfg, ctx,
                )
                hc = hc + oc
                z2c = rms_norm(hc, p_l["ln2"], cfg.norm_eps)
                if cfg.is_moe:
                    yc, ac = moe_apply(
                        p_l["moe"], z2c, cfg, ctx, placement=placement,
                        token_mask=cvalid,
                    )
                else:
                    yc, ac = mlp_apply(p_l["mlp"], z2c, ctx), zero_aux(cfg)
                hc = hc + yc
            a_sum = jax.tree.map(jnp.add, a_sum, jax.tree.map(jnp.add, a, ac))
            return (h, hc, a_sum), c_new

        if chunk is None:
            (x, aux), new_layers = jax.lax.scan(
                body,
                (x, zero_aux(cfg)),
                (params["layers"], cache["layers"]),
                unroll=ctx.full_unroll,
            )
        else:
            (x, xc, aux), new_layers = jax.lax.scan(
                body,
                (x, xc, zero_aux(cfg)),
                (params["layers"], cache["layers"]),
                unroll=ctx.full_unroll,
            )
            with jax.named_scope("lane"), jax.named_scope("lm_head"):
                last = jnp.clip(chunk["length"] - 1, 0, n_chunk - 1)
                xl = jax.lax.dynamic_slice_in_dim(xc, last, 1, axis=1)
                chunk_logits = _logits(params, xl, cfg, ctx)
        new_cache["layers"] = new_layers

    elif pat == "zamba":
        x, new_cache = _zamba_decode(params, x, cache, cfg, ctx, pos)
    elif pat == "xlstm":
        x, new_cache = _xlstm_decode(params, x, cache, cfg, ctx)
    elif pat == "encdec":

        def body(carry, inp):
            h = carry
            p_l, c_l, kv_l = inp
            z = rms_norm(h, p_l["ln1"], cfg.norm_eps)
            o, c_new = decode_attention(p_l["attn"], z, c_l, pos, cfg, ctx)
            h = h + o
            h = h + cross_attention(
                p_l["xattn"], rms_norm(h, p_l["ln_x"], cfg.norm_eps), kv_l, cfg, ctx
            )
            h = h + mlp_apply(p_l["mlp"], rms_norm(h, p_l["ln2"], cfg.norm_eps), ctx)
            return h, c_new

        x, new_layers = jax.lax.scan(
            body,
            x,
            (params["layers"], cache["layers"], cache["cross_kv"]),
            unroll=ctx.full_unroll,
        )
        new_cache["layers"] = new_layers

    new_cache["pos"] = pos + 1
    stats = {"expert_counts": aux["counts"]}
    if chunk_logits is not None:
        stats["chunk_logits"] = chunk_logits
    with jax.named_scope("lm_head"):
        logits = _logits(params, x, cfg, ctx)
    return logits, new_cache, stats


def _zamba_decode(params, x, cache, cfg, ctx, pos):
    shared = params["shared"]
    new_cache = dict(cache)

    def unit(carry, inp):
        h = carry
        p_unit, ssm_states, kv = inp

        def inner(hh, inp2):
            pl, st = inp2
            out, st_new = ssm.mamba_decode(
                pl["mamba"], rms_norm(hh, pl["ln"], cfg.norm_eps), st, cfg
            )
            return hh + out, st_new

        h, ssm_new = jax.lax.scan(inner, h, (p_unit, ssm_states))
        z = rms_norm(h, shared["ln1"], cfg.norm_eps)
        o, kv_new = decode_attention(shared["attn"], z, kv, pos, cfg, ctx)
        h = h + o
        h = h + mlp_apply(shared["mlp"], rms_norm(h, shared["ln2"], cfg.norm_eps), ctx)
        return h, (ssm_new, kv_new)

    if params["units"] is not None:
        x, (ssm_new, kv_new) = jax.lax.scan(
            unit,
            x,
            (params["units"], cache["units_ssm"], cache["shared_kv"]),
            unroll=ctx.full_unroll,
        )
        new_cache["units_ssm"] = ssm_new
        new_cache["shared_kv"] = kv_new
    if params["trailing"] is not None:

        def inner_t(hh, inp2):
            pl, st = inp2
            out, st_new = ssm.mamba_decode(
                pl["mamba"], rms_norm(hh, pl["ln"], cfg.norm_eps), st, cfg
            )
            return hh + out, st_new

        x, tr_new = jax.lax.scan(
            inner_t,
            x,
            (params["trailing"], cache["trailing_ssm"]),
            unroll=ctx.full_unroll,
        )
        new_cache["trailing_ssm"] = tr_new
    return x, new_cache


def _xlstm_decode(params, x, cache, cfg, ctx):
    new_cache = dict(cache)

    def unit(carry, inp):
        h = carry
        p_unit, m_states, s_state = inp

        def m_body(hh, inp2):
            pl, st = inp2
            out, st_new = ssm.mlstm_apply(
                pl["m"], rms_norm(hh, pl["ln"], cfg.norm_eps), cfg, st
            )
            return hh + out, st_new

        h, m_new = jax.lax.scan(m_body, h, (p_unit["m"], m_states))
        ps = p_unit["s"]
        out, s_new = ssm.slstm_apply(
            ps["s"], rms_norm(h, ps["ln"], cfg.norm_eps), cfg, s_state
        )
        return h + out, (m_new, s_new)

    x, (m_new, s_new) = jax.lax.scan(
        unit, x, (params["units"], cache["m"], cache["s"]), unroll=ctx.full_unroll
    )
    new_cache["m"] = m_new
    new_cache["s"] = s_new
    return x, new_cache


# ---------------------------------------------------------------------------
# prefill: full-sequence pass that also fills the decode cache
# ---------------------------------------------------------------------------

def prefill(
    params,
    tokens,
    cfg: ModelConfig,
    ctx: ParallelCtx = NO_MESH,
    embeds=None,
    max_seq: int | None = None,
    dtype=jnp.float32,
    paged: bool = False,
    page_size: int = PAGE_SIZE,
    n_pages: int | None = None,
    tables=None,               # (B, NB) int32 — allocator-provided block tables
    lengths=None,              # (B,) int32 — true per-request prompt lengths
):
    """Process the prompt; return (last-position logits, primed cache).

    Paged mode: ``tables`` lets a serving allocator place each request's
    blocks in a shared (possibly oversubscribed) pool; ``lengths`` marks
    true prompt lengths for right-padded ragged batches — pad positions
    fall outside each request's validity prefix and are overwritten as the
    request decodes.
    """
    b, s = tokens.shape
    pat = cfg.block_pattern
    x = _embed(params, tokens, cfg, ctx)
    if cfg.frontend_stub and embeds is not None and pat != "encdec":
        x = jnp.concatenate([embeds.astype(x.dtype), x], axis=1)
        s = x.shape[1]
    max_seq = max(max_seq or s, s)
    cache = init_cache(cfg, b, max_seq, dtype, paged, page_size, n_pages)
    if tables is not None:
        nl = cfg.n_layers
        cache["layers"]["tables"] = jnp.broadcast_to(
            tables.astype(jnp.int32), (nl, *tables.shape)
        ).copy()
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))

    if pat == "attn":

        def body(carry, inp):
            h, a_sum = carry
            p_l, c_l = inp
            z = rms_norm(h, p_l["ln1"], cfg.norm_eps)
            o, (k, v) = attention(p_l["attn"], z, cfg, ctx, positions, return_kv=True)
            h = h + o
            if is_paged(c_l):
                c_new = paged_prefill_fill(c_l, k, v, s, lengths)
            else:
                length = c_l["k"].shape[1]
                kk, vv = k[:, -length:], v[:, -length:]
                if cfg.sliding_window and s >= length:
                    # Align to the decode ring buffer: slot j holds pos%W == j.
                    kk = jnp.roll(kk, s % length, axis=1)
                    vv = jnp.roll(vv, s % length, axis=1)
                c_new = {
                    "k": jax.lax.dynamic_update_slice(c_l["k"], kk, (0, 0, 0, 0)),
                    "v": jax.lax.dynamic_update_slice(c_l["v"], vv, (0, 0, 0, 0)),
                }
            z2 = rms_norm(h, p_l["ln2"], cfg.norm_eps)
            if cfg.is_moe:
                y, a = moe_apply(p_l["moe"], z2, cfg, ctx)
            else:
                y, a = mlp_apply(p_l["mlp"], z2, ctx), zero_aux(cfg)
            return (h + y, jax.tree.map(jnp.add, a_sum, a)), c_new

        (x, _), new_layers = jax.lax.scan(
            body,
            (x, zero_aux(cfg)),
            (params["layers"], cache["layers"]),
            unroll=ctx.full_unroll,
        )
        cache["layers"] = new_layers

    elif pat in ("zamba", "xlstm"):
        x, cache = _ssm_prefill(params, x, cache, cfg, ctx, positions)
    elif pat == "encdec":
        assert embeds is not None
        mem = embeds
        for_enc = lambda p, m: (_enc_block(p, m, cfg, ctx), 0.0)
        mem, _ = _scan_layers(for_enc, mem, params["encoder"], ctx)
        mem = rms_norm(mem, params["enc_norm"], cfg.norm_eps)

        def body(carry, inp):
            h = carry
            p_l, c_l = inp
            kv = cross_kv(p_l["xattn"], mem, cfg, ctx)
            z = rms_norm(h, p_l["ln1"], cfg.norm_eps)
            o, (k, v) = attention(p_l["attn"], z, cfg, ctx, positions, return_kv=True)
            h = h + o
            c_new = {
                "k": jax.lax.dynamic_update_slice(c_l["k"], k, (0, 0, 0, 0)),
                "v": jax.lax.dynamic_update_slice(c_l["v"], v, (0, 0, 0, 0)),
            }
            h = h + cross_attention(
                p_l["xattn"], rms_norm(h, p_l["ln_x"], cfg.norm_eps), kv, cfg, ctx
            )
            h = h + mlp_apply(p_l["mlp"], rms_norm(h, p_l["ln2"], cfg.norm_eps), ctx)
            return h, (c_new, kv)

        x, (new_layers, kvs) = jax.lax.scan(
            body, x, (params["layers"], cache["layers"]), unroll=ctx.full_unroll
        )
        cache["layers"] = new_layers
        cache["cross_kv"] = kvs

    cache["pos"] = jnp.asarray(s, jnp.int32)
    if lengths is not None:
        # Ragged right-padded prompts: each request's next-token logits
        # live at its true last position, not the padded batch tail.
        last = jnp.clip(lengths.astype(jnp.int32) - 1, 0, s - 1)
        x = jnp.take_along_axis(x, last[:, None, None], axis=1)
    else:
        x = x[:, -1:]
    logits = _logits(params, x, cfg, ctx)
    return logits, cache


def _ssm_prefill(params, x, cache, cfg, ctx, positions):
    pat = cfg.block_pattern
    new_cache = dict(cache)
    if pat == "zamba":
        shared = params["shared"]

        def unit(carry, inp):
            h = carry
            p_unit, ssm_states, kv = inp

            def inner(hh, inp2):
                pl, st = inp2
                out, st_new = ssm.mamba_apply(
                    pl["mamba"], rms_norm(hh, pl["ln"], cfg.norm_eps), cfg, st
                )
                return hh + out, st_new

            h, ssm_new = jax.lax.scan(inner, h, (p_unit, ssm_states))
            z = rms_norm(h, shared["ln1"], cfg.norm_eps)
            o, (k, v) = attention(shared["attn"], z, cfg, ctx, positions, return_kv=True)
            h = h + o
            length = kv["k"].shape[1]
            kv_new = {
                "k": jax.lax.dynamic_update_slice(kv["k"], k[:, -length:], (0, 0, 0, 0)),
                "v": jax.lax.dynamic_update_slice(kv["v"], v[:, -length:], (0, 0, 0, 0)),
            }
            h = h + mlp_apply(shared["mlp"], rms_norm(h, shared["ln2"], cfg.norm_eps), ctx)
            return h, (ssm_new, kv_new)

        if params["units"] is not None:
            x, (ssm_new, kv_new) = jax.lax.scan(
                unit,
                x,
                (params["units"], cache["units_ssm"], cache["shared_kv"]),
                unroll=ctx.full_unroll,
            )
            new_cache["units_ssm"] = ssm_new
            new_cache["shared_kv"] = kv_new
        if params["trailing"] is not None:

            def inner_t(hh, inp2):
                pl, st = inp2
                out, st_new = ssm.mamba_apply(
                    pl["mamba"], rms_norm(hh, pl["ln"], cfg.norm_eps), cfg, st
                )
                return hh + out, st_new

            x, tr_new = jax.lax.scan(
                inner_t,
                x,
                (params["trailing"], cache["trailing_ssm"]),
                unroll=ctx.full_unroll,
            )
            new_cache["trailing_ssm"] = tr_new
        return x, new_cache

    # xlstm
    def unit(carry, inp):
        h = carry
        p_unit, m_states, s_state = inp

        def m_body(hh, inp2):
            pl, st = inp2
            out, st_new = ssm.mlstm_apply(
                pl["m"], rms_norm(hh, pl["ln"], cfg.norm_eps), cfg, st
            )
            return hh + out, st_new

        h, m_new = jax.lax.scan(m_body, h, (p_unit["m"], m_states))
        ps = p_unit["s"]
        out, s_new = ssm.slstm_apply(
            ps["s"], rms_norm(h, ps["ln"], cfg.norm_eps), cfg, s_state
        )
        return h + out, (m_new, s_new)

    x, (m_new, s_new) = jax.lax.scan(
        unit, x, (params["units"], cache["m"], cache["s"]), unroll=ctx.full_unroll
    )
    new_cache["m"] = m_new
    new_cache["s"] = s_new
    return x, new_cache
