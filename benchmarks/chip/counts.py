"""Useful operations and bytes of the served step, from shapes and routing.

Shared by every per-layer metric. Work is counted from what the step really
had to do — tokens that were live, the copies the router really sent, the
context each token really attended — so the count is the same whatever
kernel implements it, and padded bucket rows, masked batch rows and
recomputed work never count. ``m`` is the ``model`` block of a
configuration file.
"""

from __future__ import annotations

import numpy as np

BF16 = 2


def attn_proj_params(m: dict) -> int:
    """Weights of one layer's q, k, v and output projections."""
    d, hd = m["d_model"], m["head_dim"]
    return d * hd * (2 * m["n_heads"] + 2 * m["n_kv_heads"])


def expert_params(m: dict) -> int:
    """Weights of one expert's SwiGLU FFN (gate, up, down)."""
    return 3 * m["d_model"] * m["expert_d_ff"]


def expert_ffn_flops(m: dict, copies) -> float:
    """Operations of the expert FFN for ``copies`` routed token-copies
    (summed over layers): 2 x 3 x d x F each."""
    return 2.0 * expert_params(m) * float(np.sum(copies))


def expert_ffn_bytes(m: dict, counts, n_replicas, itemsize: int = BF16) -> float:
    """HBM bytes the expert FFN has to move in one step: the weights of
    every slot that received a token, once, plus each copy's activation in
    and out. ``counts`` is copies per logical expert summed over layers;
    ``n_replicas`` the committed replicas per expert. The router spreads an
    expert's copies round-robin over its replicas, so a layer touches
    ``min(replicas, copies in that layer)`` slots of it; copies are taken
    to split evenly over layers (a count the step does not report)."""
    counts = np.asarray(counts, np.float64)
    reps = np.ones_like(counts) if n_replicas is None else np.asarray(n_replicas, np.float64)
    L = m["n_layers"]
    touched = L * np.minimum(reps, np.ceil(counts / L))
    weights = float(np.sum(touched)) * expert_params(m) * itemsize
    acts = 2.0 * float(np.sum(counts)) * m["d_model"] * itemsize
    return weights + acts


def tick_flops(m: dict, decode_keys, chunk_start: int, chunk_len: int,
               chunk_final: bool) -> float:
    """Useful operations of one step: one token for each decoding row
    (``decode_keys`` holds each row's attended context), plus the prefill
    lane's ``chunk_len`` tokens from position ``chunk_start`` (the LM head
    only for the last token of a final chunk)."""
    keys = np.asarray(decode_keys, np.float64)
    d, L = m["d_model"], m["n_layers"]
    dense = attn_proj_params(m) + d * m["n_experts"] + m["top_k"] * expert_params(m)
    per_tok = 2.0 * L * dense + 2.0 * d * m["vocab"]
    attn = 4.0 * L * m["n_heads"] * m["head_dim"]
    total = keys.size * per_tok + attn * float(keys.sum())
    if chunk_len:
        # token i of the chunk attends positions 0 .. chunk_start + i
        ck = chunk_len * chunk_start + chunk_len * (chunk_len + 1) / 2
        total += chunk_len * 2.0 * L * dense + attn * ck
        if chunk_final:
            total += 2.0 * d * m["vocab"]
    return total


def roofline_s(flops: float, nbytes: float, peak: dict, chips: int = 1) -> tuple[float, str]:
    """The least time ``chips`` chips could take for the work, and which
    bound sets it (``compute`` or ``hbm``)."""
    t_c = flops / (peak["bf16_flops"] * chips)
    t_m = nbytes / (peak["hbm_bytes_per_s"] * chips)
    return (t_c, "compute") if t_c >= t_m else (t_m, "hbm")
