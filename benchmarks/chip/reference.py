"""Plain reference of the served model, and the comparison that decides
``correct``.

The reference imports nothing of the program. It reads the model's sizes
from the configuration file's ``model`` block and its weights from
``weights.make`` (the benchmark's own seeded generator), and computes a
pre-norm decoder in straightforward ``jax.numpy``: RMSNorm, grouped-query
attention with rotary positions (rotate-half), a softmax router over all
experts whose top-k weights are renormalised, SwiGLU experts, a final norm
and the LM head. Everything is float32 at ``highest`` matmul precision (the
bf16 weights are exact in float32). It runs one request at a time, layer
by layer and one expert at a time, with attention in blocks of query rows,
so it fits beside nothing else on one chip.

``served_gaps`` scores what the program served: teacher-forced over the
prompt and the served tokens, at each position that emitted a token, the
gap by which the served token's logit lies below the reference's best, and
the reference's logits at a seeded sample of vocabulary ids (to set beside
the program's own logits at those ids). With ``controls`` it also reads
the control: the same forward with every matmul computed in a lower
precision (``int8``: weights per output channel rounded to int8, the step
below bf16 that v5e runs natively; ``fp8``: weights per output channel and
activations per row rounded to float8 e4m3), its logits at the sampled
ids, and the gap of the token the control puts first.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512            # query rows per attention block
FP8_MAX = 448.0          # largest finite float8 e4m3fn
INT8_MAX = 127.0


def _q(x, axis, kind):
    """``x`` rounded to ``kind`` with a symmetric scale per slice along
    ``axis`` (the reduction axis of the matmul), returned in float32."""
    x = x.astype(jnp.float32)
    if kind is None:
        return x
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    if kind == "fp8":
        s = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    if kind == "int8":
        s = jnp.where(amax > 0, amax / INT8_MAX, 1.0)
        return jnp.clip(jnp.round(x / s), -INT8_MAX, INT8_MAX) * s
    raise ValueError(f"unknown precision {kind!r}")


def _mm(a, w, kind):
    """``a @ w`` for activations ``a`` (..., k) and weights ``w`` (k, n)."""
    act = None if kind == "int8" else kind       # int8 is weight-only
    return jnp.matmul(_q(a, -1, act), _q(w, 0, kind), precision=HI)


def _rms(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(jnp.float32)


def _rope(x, pos, theta):
    """Rotate-half rotary embedding of ``x`` (T, heads, hd) at ``pos`` (T,)."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


@functools.partial(jax.jit, static_argnames=("m", "kind"))
def _attn(x, ln, wq, wk, wv, wo, *, m, kind):
    """``x + attention(norm(x))`` over the whole (padded) sequence."""
    t = x.shape[0]
    hd, nh, nk = m["head_dim"], m["n_heads"], m["n_kv_heads"]
    g = nh // nk
    z = _rms(x, ln, m["norm_eps"])
    pos = jnp.arange(t)
    q = _rope(_mm(z, wq, kind).reshape(t, nh, hd), pos, m["rope_theta"])
    k = _rope(_mm(z, wk, kind).reshape(t, nk, hd), pos, m["rope_theta"])
    v = _mm(z, wv, kind).reshape(t, nk, hd)
    outs = []
    for lo in range(0, t, Q_BLOCK):
        qb = q[lo:lo + Q_BLOCK].reshape(-1, nk, g, hd)
        s = jnp.einsum("skgd,tkd->kgst", qb, k, precision=HI) / np.sqrt(hd)
        mask = jnp.arange(t)[None, :] <= (lo + jnp.arange(qb.shape[0]))[:, None]
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("kgst,tkd->skgd", p, v, precision=HI).reshape(-1, nh * hd))
    return x + _mm(jnp.concatenate(outs), wo, kind)


@functools.partial(jax.jit, static_argnames=("m", "kind"))
def _route(x, ln, router, *, m, kind):
    """Normed MoE input, each token's gate per expert (T, E), and how far
    its last chosen expert's probability lies above the first one left out
    (a near tie is where a rounding can change the route)."""
    z = _rms(x, ln, m["norm_eps"])
    probs = jax.nn.softmax(_mm(z, router, kind), axis=-1)
    top, ids = jax.lax.top_k(probs, m["top_k"] + 1)
    margin = top[:, -2] - top[:, -1]
    top, ids = top[:, :-1], ids[:, :-1]
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    gates = jnp.sum(jax.nn.one_hot(ids, m["n_experts"]) * top[..., None], axis=-2)
    return z, gates, margin


@functools.partial(jax.jit, static_argnames=("kind",))
def _expert(z, gate, wg, wu, wd, *, kind):
    """One expert's gated SwiGLU output for every token (zero gate where the
    token did not pick it)."""
    h = jax.nn.silu(_mm(z, wg, kind)) * _mm(z, wu, kind)
    return gate[:, None] * _mm(h, wd, kind)


@functools.partial(jax.jit, static_argnames=("m", "kind"))
def _head(x, ln, lm_head, targets, ids, *, m, kind):
    """Per row: the best logit, the logit of ``targets``, the argmax, and
    the logits at the vocabulary ids ``ids``."""
    z = _rms(x, ln, m["norm_eps"])
    logits = _mm(z, lm_head, kind)
    at = jnp.take_along_axis(logits, targets[:, None], axis=1)[:, 0]
    return (jnp.max(logits, axis=1), at, jnp.argmax(logits, axis=1).astype(jnp.int32),
            logits[:, ids])


def forward(w, m: dict, tokens: np.ndarray, kind=None):
    """Final hidden states (T, d) of ``tokens`` (already padded to the
    bucket; causal, so padding at the end changes no earlier row), and each
    row's smallest router margin over the layers."""
    mh = _hashable(m)
    margin = None
    lay = w["layers"]
    x = jnp.take(w["embed"], jnp.asarray(tokens), axis=0).astype(jnp.float32)
    for l in range(m["n_layers"]):
        a = lay["attn"]
        x = _attn(x, lay["ln1"][l], a["wq"][l], a["wk"][l], a["wv"][l], a["wo"][l],
                  m=mh, kind=kind)
        z, gates, mg = _route(x, lay["ln2"][l], lay["moe"]["router"][l], m=mh, kind=kind)
        margin = mg if margin is None else jnp.minimum(margin, mg)
        moe = lay["moe"]
        for e in range(m["n_experts"]):
            x = x + _expert(z, gates[:, e], moe["w_gate"][l, e], moe["w_up"][l, e],
                            moe["w_down"][l, e], kind=kind)
    return x, margin


def _rows(w, m, x, targets, ids, kind):
    outs = [[], [], [], []]
    mh = _hashable(m)
    for lo in range(0, x.shape[0], Q_BLOCK):
        got = _head(x[lo:lo + Q_BLOCK], w["final_norm"], w["lm_head"],
                    jnp.asarray(targets[lo:lo + Q_BLOCK]), ids, m=mh, kind=kind)
        for out, g in zip(outs, got):
            out.append(np.asarray(g))
    return tuple(np.concatenate(o) for o in outs)


def served_gaps(w, m: dict, prompt, served, bucket: int, controls=(), ids=None) -> dict:
    """Gaps of one request's served tokens below the reference's best logit,
    and the reference's logits at the vocabulary ids ``ids`` (and, for each
    precision in ``controls``, its logits at ``ids`` and the gaps of the
    tokens it would have put first, at the same positions).

    ``served[i]`` was emitted after the prompt and ``served[:i]``; it is
    scored at position ``len(prompt) - 1 + i`` of the teacher-forced
    sequence ``prompt + served[:-1]``."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    seq = np.concatenate([prompt, served[:-1]])
    if len(seq) > bucket:
        raise ValueError(f"request of {len(seq)} tokens exceeds the bucket {bucket}")
    pad = -(-bucket // Q_BLOCK) * Q_BLOCK
    toks = np.zeros(pad, np.int32)
    toks[: len(seq)] = seq
    at_pos = np.arange(len(prompt) - 1, len(seq))
    tgt = np.zeros(pad, np.int32)
    tgt[at_pos] = served
    ids = jnp.asarray(np.zeros(1, np.int32) if ids is None else ids)
    firsts, c_logits = {}, {}
    for kind in controls:
        xc, _ = forward(w, m, toks, kind=kind)
        _, _, firsts[kind], lc = _rows(w, m, xc, tgt, ids, kind)
        c_logits[kind] = lc[at_pos]
        del xc
    x, margin = forward(w, m, toks)
    best, at, _, logits = _rows(w, m, x, tgt, ids, None)
    out = {"gap": (best - at)[at_pos], "router_margin": np.asarray(margin)[at_pos],
           "logits": logits[at_pos], "control_gap": {}, "control_logits": c_logits}
    for kind, first in firsts.items():
        _, at_c, _, _ = _rows(w, m, x, first, ids, None)
        out["control_gap"][kind] = (best - at_c)[at_pos]
    return out


class _Frozen(dict):
    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def _hashable(m: dict) -> _Frozen:
    keys = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim", "n_experts",
            "top_k", "expert_d_ff", "vocab", "rope_theta", "norm_eps")
    return _Frozen({k: m[k] for k in keys})
