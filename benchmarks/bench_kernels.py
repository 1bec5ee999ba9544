"""Kernel-path benchmark: dispatch + expert-FFN + combine (einsum vs padded
vs ragged vs fused-gather vs fused-compact), plus dense-vs-paged decode
attention KV-byte accounting.

Each shape cell drives the full MoE expert hot path *including token
dispatch and the combine leg* (both HBM round-trips the fused paths exist
to remove) — every path ends in the per-token weighted combine so outputs
are directly comparable:

* ``einsum_padded_dispatch``  — ``bucket_dispatch`` into ``(G, C, d)``
  buffers + the XLA einsum FFN + ``bucket_combine`` (the pre-kernel
  reference);
* ``gmm_padded_dispatch``     — ``bucket_dispatch`` + the padded Pallas
  kernels (``gmm_dual_act`` + ``gmm``): every capacity row hits the MXU;
* ``gmm_ragged_padded_dispatch`` — ``bucket_dispatch`` + the count-aware
  kernels: row-tiles past each bucket's fill skip the MXU, but the padded
  buffers are still written/read through HBM on both legs;
* ``gmm_gather_fused_dispatch``  — ``dispatch_metadata`` + the fused gather
  kernels (``gmm_dual_act_gather`` + ``gmm_ragged``): token rows stay in a
  flat compacted array and the kernel prologue gathers them via
  scalar-prefetched per-bucket offsets — the ``(G, C, d)`` *input* buffer
  never exists, but the FFN output is still bucket-padded and the combine
  reads it;
* ``gmm_compact_fused_combine`` — the gather prologue **plus the
  ``gmm_scatter`` epilogue**: the down-projection writes result tiles back
  at the same per-bucket offsets, so neither the padded input nor the
  padded output buffer exists; ``combine_from_rows`` gathers each kept
  copy through the dispatch metadata — but the ``(G, C, F)`` *hidden*
  tensor between the two kernels still round-trips HBM;
* ``gmm_fused_ffn_combine`` — **one kernel for all three matmuls**
  (``gmm_fused_ffn``): gather prologue, SwiGLU hidden tiles held in VMEM
  accumulators, down-projection, scatter epilogue. The padded hidden
  tensor never exists — its HBM-byte column is exactly zero.

Besides wall-clock, each row reports the FLOP accounting (``padded_gflop``
= what a capacity-padded pass must execute, ``achieved_gflop`` = useful
work at the measured routing, ``exec_gflop`` = what the path actually
runs at tile granularity), ``dispatch_hbm_mb`` — the bytes the dispatch
stage moves through HBM (padded: write + read of ``G*C*d``; fused: a
row-granular write of the ``R = sum(counts)`` compacted rows + a
tile-granular gather-DMA read, ``sum(ceil(count/bm)*bm)`` rows — the same
ceil-tile convention as ``exec_gflop``), ``combine_hbm_mb``, the
mirror accounting for the combine leg (padded paths write + read the
``G*C*d`` FFN output; the compact path's scatter epilogue writes
tile-granular rows and the metadata combine gathers the ``R`` live rows),
and ``hidden_hbm_mb`` — the bytes the ``(G, C, F)`` hidden tensor between
the SwiGLU front half and the down-projection moves (write + read for
every two-kernel path; **0** for ``gmm_fused_ffn_combine``, where the
hidden tile never leaves VMEM). ``utilization`` = achieved/executed FLOPs.

Shape cells cover balanced routing (every bucket full — the fused paths
must not lose here) and zipf-skewed routing (fig. 6 imbalance — where
tile-skipping plus the smaller dispatch *and* combine footprints win).

Usage::

    PYTHONPATH=src python benchmarks/bench_kernels.py [--out BENCH_kernels.json]
    PYTHONPATH=src python benchmarks/bench_kernels.py --smoke   # CI gate
    PYTHONPATH=src python benchmarks/bench_kernels.py --check BENCH_kernels.json

``--smoke`` runs one tiny FFN cell + one tiny decode cell + one tiny
stepped-migration cell + one tiny chunked-admission cell + one tiny
chunked-EP-dispatch cell with 2 iterations (interpret mode on CPU) and
exits non-zero on any parity failure — a kernel-dispatch, paged-decode,
sliced-copy, prefill-lane or chunk-pipeline regression fails the gate
even when the full parity suite isn't run.

``--check BASELINE.json`` recomputes every **deterministic** column (shape
metadata, FLOP accounting, per-leg HBM-byte accounting — not wall-clock,
not backend) from the same seeded routing draws and fails with a readable
diff if any drifts from the committed baseline — a PR that silently
re-pads a leg (or re-materializes the hidden tensor) turns CI red without
running a single kernel.

On CPU the Pallas paths execute in interpret mode (kernel *semantics*, not
kernel speed) — wall-clock comparisons are only meaningful on TPU, and the
JSON records backend + interpret so numbers aren't misread. The FLOP and
dispatch-byte accounting is backend-independent.
"""

from __future__ import annotations

import argparse
import json
import math
import platform
import sys
import time
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_decode.ops import flash_decode_op, flash_decode_paged_op
from repro.kernels.flash_decode.ref import decode_ref
from repro.kernels.gmm.gmm import gmm, gmm_dual_act
from repro.kernels.tiling import fit_block
from repro.kernels.gmm.ops import (
    expert_ffn_fused,
    expert_ffn_gather,
    expert_ffn_gather_compact,
    expert_ffn_ragged,
)
from repro.kernels.gmm.ref import expert_ffn_ref
from repro.kernels.registry import default_interpret, expert_ffn_from_rows
from repro.parallel.collectives import (
    bucket_combine,
    bucket_dispatch,
    combine_from_rows,
    dispatch_metadata,
    kept_counts,
)

# (name, G, C, D, F, balanced) — G buckets of capacity C, d_model D, expert
# hidden F. Mirrors smoke-to-midsize EP cells (slots x capacity after
# dispatch); balanced cells fill every bucket, skewed cells draw zipf counts.
SHAPES = [
    ("smoke_4x64", 4, 64, 64, 128, False),
    ("balanced_8x128", 8, 128, 128, 256, True),
    ("ep_16x128", 16, 128, 128, 512, False),
    ("skewed_32x64", 32, 64, 128, 256, False),
]
SMOKE_SHAPES = [("smoke_4x16", 4, 16, 16, 32, False)]

BM = 128  # row-tile the ragged kernels mask at (see kernels/gmm/ragged.py)

# Decode cells: (name, B, max_seq, lengths, K, H, hd, page_size). Dense
# flash-decode streams the whole (B, max_seq) cache and masks; paged decode
# walks only each request's live pages — `kv_hbm_mb` is the bandwidth story.
DECODE_SHAPES = [
    # hd/page multiples of 128 so the cells stay compiled-eligible on TPU
    # (can_flash_decode / can_flash_decode_paged gates).
    ("decode_short_balanced", 4, 1024, [256, 256, 256, 256], 2, 8, 128, 128),
    ("decode_long_balanced", 4, 1024, [1024, 1024, 1024, 1024], 2, 8, 128, 128),
    ("decode_ragged", 4, 2048, [128, 256, 512, 1024], 2, 8, 128, 128),
]
DECODE_SMOKE_SHAPES = [("decode_smoke", 2, 64, [20, 48], 2, 4, 16, 16)]

# Live stepped migration cells: (name, L, n_slots, D, F, n_slices, n_tok).
# One cell = one in-flight expert migration sliced over n_slices decode
# ticks (the MigrationDriver's per-tick _copy_row_slice on all three slot
# tensors) riding a decode-step-sized expert FFN. The accounting columns
# (slice/expert bytes, tick counts) are deterministic and CI-gated; the
# wall columns — including migration_exposed_ms, the per-tick cost the
# decode step cannot hide = (step + slice) − step — are not.
MIGRATION_SHAPES = [
    ("mig_smoke_4x64", 2, 4, 64, 128, 4, 64),
    ("mig_ep_8x128", 4, 8, 128, 256, 4, 128),
    ("mig_finegrain_8x128", 4, 8, 128, 256, 8, 128),
]
MIGRATION_SMOKE_SHAPES = [("mig_smoke", 2, 4, 16, 32, 4, 16)]

# Chunked-admission interleave cells: (name, model, B, chunk, prompt_len,
# page_size, max_seq). One cell = the fused two-lane decode step
# (runtime/serve.py with ServeConfig(prefill_chunk=C)) admitting a
# prompt_len prompt one C-token chunk per tick while a B-slot decode batch
# rides the same program. The deterministic columns — `ttft_ticks` (chunk
# ticks to the first token) and `chunk_hbm_mb` (KV bytes the prefill lane
# moves over the whole admission) — are CI-gated; the wall columns,
# including chunk_exposed_ms = wall(decode + live chunk) − wall(decode +
# no-op chunk), are not.
PREFILL_SHAPES = [
    ("prefill_interleave_c8", "llama3.2-1b", 3, 8, 40, 8, 64),
    ("prefill_interleave_c16", "llama3.2-1b", 3, 16, 48, 8, 64),
]
PREFILL_SMOKE_SHAPES = [("prefill_smoke", "llama3.2-1b", 2, 8, 16, 8, 32)]

# Chunked EP dispatch cells: (name, EP, SPD, CAP, D, F, chunk_counts,
# balanced). One cell = one EP step's expert hot path — EP ranks x SPD
# expert groups per rank at capacity CAP — run single-shot (ep_chunks=1)
# and chunked (each K in chunk_counts): the per-chunk fused row-FFN over
# K contiguous slices of the rank-compacted row layout, exactly the
# per-chunk `expert_ffn_from_rows` calls the pipelined
# `ep_moe_shardmap` fused branch issues between its all_to_all legs.
# Deterministic gated columns per K: per-chunk dispatch/combine HBM
# bytes (same ceil-tile convention as the FFN cells — per-chunk offsets
# keep BOTH legs compact, so the K lists sum to the single-shot
# numbers), per-chunk exchange wire bytes (the statically shaped
# all_to_all buffer splits exactly K ways), and `exposed_comm_ms` — the
# analytic pipeline schedule's wall(step) − wall(overlapped ideal):
# with D_i/B_i the dispatch/combine leg times and C_i the chunk-i
# compute time, chunk i's compute slot must cover chunk i−1's combine
# and chunk i+1's dispatch, so
#   exposed(K) = D_0 + B_{K-1} + sum_i max(0, B_{i-1} + D_{i+1} - C_i)
# (absent terms at the boundaries). K=1 degenerates to D + B — the two
# synchronous walls — and every K>1 is strictly below it (asserted).
# The model uses the fixed EP_WIRE_GBPS / EP_MODEL_TFLOPS constants so
# the column is seed-deterministic and --check-gated; `wall_ms` per K
# is measured (interpret semantics off-TPU) and NOT gated.
EP_CHUNK_SHAPES = [
    ("epchunk_balanced_8x64", 4, 2, 64, 64, 128, (1, 2), True),
    ("epchunk_skewed_16x64", 4, 4, 64, 128, 256, (1, 2, 4), False),
    ("epchunk_skewed_32x32", 8, 4, 32, 128, 256, (1, 2, 4), False),
]
EP_CHUNK_SMOKE_SHAPES = [("epchunk_smoke", 2, 2, 16, 16, 32, (1, 2), False)]

# Fixed analytic-model constants for the exposed-comm schedule: a
# mid-range per-device all_to_all leg bandwidth and MXU throughput.
# Deliberately NOT measured — the exposed_comm_ms column is a
# deterministic schedule property (what the pipeline hides at a given
# comm:compute ratio), not a backend benchmark; changing these changes
# the committed baseline.
EP_WIRE_GBPS = 40.0
EP_MODEL_TFLOPS = 20.0


def _skewed_counts(g: int, c: int, seed: int) -> np.ndarray:
    """Zipf-ish routing skew: a few hot experts near capacity, a long tail
    (incl. empties) — the fig. 6 imbalance regime."""
    rng = np.random.default_rng(seed)
    raw = rng.zipf(1.5, size=g).astype(np.float64)
    counts = np.floor(c * raw / raw.max()).astype(np.int64)
    counts[rng.permutation(g)[: max(g // 8, 1)]] = 0  # idle slots
    return np.clip(counts, 0, c)


def _ids_from_counts(counts: np.ndarray) -> np.ndarray:
    """A token stream whose per-bucket histogram is exactly ``counts``,
    in a seeded random order so dispatch never sees pre-sorted input."""
    ids = np.concatenate([np.full(c, g, np.int32) for g, c in enumerate(counts)])
    rng = np.random.default_rng(int(counts.sum()))
    return rng.permutation(ids)


def ffn_cell_accounting(name, g, c, d, f, balanced):
    """Deterministic columns of one FFN shape cell — seeded routing draw,
    FLOP model, and per-leg HBM-byte model. No kernels run; this is what
    ``--check`` recomputes against the committed baseline."""
    counts = (
        np.full(g, c, np.int64) if balanced else _skewed_counts(g, c, seed=g * c)
    )
    n_tok = int(counts.sum())
    flop_per_row = 6 * d * f  # 3 matmuls, 2 flop/MAC
    padded_gf = g * c * flop_per_row / 1e9
    achieved_gf = n_tok * flop_per_row / 1e9
    # The kernels' actual row tile: the largest divisor of the capacity
    # <= BM (min(BM, c) agrees only when that happens to divide c).
    bm = fit_block(c, BM)
    ragged_rows = sum(math.ceil(cnt / bm) * bm for cnt in counts)
    ragged_exec_gf = ragged_rows * flop_per_row / 1e9
    row_bytes = d * np.dtype(np.float32).itemsize
    hidden_row_bytes = f * np.dtype(np.float32).itemsize
    # Padded legs: scatter out + read in of the full (G, C, ·) buffer.
    padded_leg_mb = 2 * g * c * row_bytes / 1e6
    # Fused legs are half row-granular (XLA scatter/gather of the
    # compacted rows), half tile-granular (the kernel's dynamic-offset
    # DMAs move whole (bm, ·) tiles, padding included — same ceil-tile
    # convention as exec_gflop): dispatch writes n_tok rows and the
    # gather prologue reads ragged_rows; the scatter epilogue writes
    # ragged_rows and the combine gathers n_tok.
    fused_dispatch_mb = (n_tok + ragged_rows) * row_bytes / 1e6
    compact_combine_mb = (ragged_rows + n_tok) * row_bytes / 1e6
    # Hidden leg: every two-kernel path writes the (G, C, F) SwiGLU output
    # and the down-projection reads it back (the Pallas pipeline moves all
    # blocks of a BlockSpec-driven operand, dead tiles included, so this
    # leg is full-size even for the ragged kernels). The single-kernel
    # fused path keeps the hidden tile in VMEM: exactly zero.
    hidden_mb = 2 * g * c * hidden_row_bytes / 1e6

    def acc(exec_gf, dispatch_mb, combine_mb, hidden):
        return {
            "exec_gflop": round(exec_gf, 4),
            "utilization": round(achieved_gf / exec_gf, 4) if exec_gf else 1.0,
            "dispatch_hbm_mb": round(dispatch_mb, 4),
            "combine_hbm_mb": round(combine_mb, 4),
            "hidden_hbm_mb": round(hidden, 4),
        }

    meta = {
        "shape": name,
        "G": g,
        "C": c,
        "D": d,
        "F": f,
        "routing": "balanced" if balanced else "skewed",
        "tokens_routed": n_tok,
        "tokens_padded": g * c,
        "group_sizes": counts.tolist(),
        "padded_gflop": round(padded_gf, 4),
        "achieved_gflop": round(achieved_gf, 4),
    }
    paths = {
        "einsum_padded_dispatch": acc(
            padded_gf, padded_leg_mb, padded_leg_mb, hidden_mb
        ),
        "gmm_padded_dispatch": acc(
            padded_gf, padded_leg_mb, padded_leg_mb, hidden_mb
        ),
        "gmm_ragged_padded_dispatch": acc(
            ragged_exec_gf, padded_leg_mb, padded_leg_mb, hidden_mb
        ),
        "gmm_gather_fused_dispatch": acc(
            ragged_exec_gf, fused_dispatch_mb, padded_leg_mb, hidden_mb
        ),
        "gmm_compact_fused_combine": acc(
            ragged_exec_gf, fused_dispatch_mb, compact_combine_mb, hidden_mb
        ),
        "gmm_fused_ffn_combine": acc(
            ragged_exec_gf, fused_dispatch_mb, compact_combine_mb, 0.0
        ),
    }
    return counts, meta, paths


def decode_cell_accounting(name, b, max_seq, lengths, kv, h, hd, bs):
    """Deterministic columns of one decode cell (KV HBM-byte model)."""
    nb = -(-max_seq // bs)
    row_bytes = 2 * kv * hd * np.dtype(np.float32).itemsize  # k + v
    dense_mb = b * nb * bs * row_bytes / 1e6
    live_pages = sum(-(-l // bs) for l in lengths)
    paged_mb = live_pages * bs * row_bytes / 1e6
    meta = {
        "shape": name,
        "B": b,
        "max_seq": max_seq,
        "page_size": bs,
        "lengths": list(lengths),
        "tokens_live": int(sum(lengths)),
        "tokens_streamed_dense": b * nb * bs,
        "tokens_streamed_paged": live_pages * bs,
    }
    paths = {
        "flash_decode_dense_masked": {"kv_hbm_mb": round(dense_mb, 4)},
        "flash_decode_paged": {"kv_hbm_mb": round(paged_mb, 4)},
    }
    ratio = round(dense_mb / paged_mb, 3)
    return meta, paths, ratio


def migration_cell_accounting(name, layers, s, d, f, n_slices, n_tok):
    """Deterministic columns of one stepped-migration cell: the byte/tick
    schedule the MigrationDriver produces for one expert move. Gated by
    ``--check``; the wall columns are not."""
    itemsize = np.dtype(np.float32).itemsize
    # Rows axis is axis 2 of every slot tensor: d for w_gate/w_up, f for
    # w_down — the driver chunks each tensor independently.
    chunks = {
        "w_gate": (-(-d // n_slices), f),
        "w_up": (-(-d // n_slices), f),
        "w_down": (-(-f // n_slices), d),
    }
    expert_bytes = layers * (2 * d * f + f * d) * itemsize
    slice_bytes = sum(
        layers * rows * cols * itemsize for rows, cols in chunks.values()
    )
    return {
        "shape": name,
        "L": layers,
        "n_slots": s,
        "D": d,
        "F": f,
        "tokens_per_step": n_tok,
        "n_slices": n_slices,
        "slice_rows": {k: rows for k, (rows, _) in chunks.items()},
        "expert_mb": round(expert_bytes / 1e6, 4),
        "slice_mb": round(slice_bytes / 1e6, 4),
        # one commit tick after the last slice tick (the atomic table swap
        # happens at the next step boundary).
        "ticks_to_commit": n_slices + 1,
    }


def prefill_cell_accounting(name, model, b, chunk, prompt_len, bs, max_seq):
    """Deterministic columns of one chunked-admission cell: the tick and
    KV-byte schedule the decode step's prefill lane pays to admit one
    prompt. Gated by ``--check``; the wall columns are not."""
    from repro.configs import get_config, smoke

    cfg = smoke(get_config(model))
    kv_row_bytes = 2 * cfg.n_kv_heads * cfg.head_dim_ * np.dtype(np.float32).itemsize
    ticks = -(-prompt_len // chunk)
    # Every tick writes the full padded chunk (padding rows land on the
    # write-off page — still a write) and the lane's attention gathers the
    # request's whole capacity table (max_seq rows of k + v), per layer.
    rows_written = ticks * chunk
    rows_streamed = ticks * max_seq
    return {
        "shape": name,
        "model": model,
        "B": b,
        "chunk": chunk,
        "prompt_len": prompt_len,
        "page_size": bs,
        "max_seq": max_seq,
        "L": cfg.n_layers,
        "kv_heads": cfg.n_kv_heads,
        "head_dim": cfg.head_dim_,
        # first token lands on the final chunk's tick; live decode slots
        # never stall (they share the one fused program).
        "ttft_ticks": ticks,
        "decode_stall_ticks": 0,
        "chunk_rows_written": rows_written,
        "chunk_rows_streamed": rows_streamed,
        "chunk_hbm_mb": round(
            cfg.n_layers * (rows_written + rows_streamed) * kv_row_bytes / 1e6, 4
        ),
    }


def ep_chunk_cell_accounting(name, ep, spd, cap, d, f, chunk_counts, balanced):
    """Deterministic columns of one chunked-EP cell: seeded routing draw,
    per-chunk HBM/wire-byte model, and the analytic ``exposed_comm_ms``
    pipeline schedule. Gated by ``--check``; the wall columns are not.
    Raises if any chunked schedule fails to beat the single-shot one —
    the overlap property itself is part of the gate."""
    g = ep * spd
    counts = (
        np.full(g, cap, np.int64) if balanced else _skewed_counts(g, cap, seed=g * cap)
    )
    n_tok = int(counts.sum())
    row_bytes = d * np.dtype(np.float32).itemsize
    flop_per_row = 6 * d * f
    bm = fit_block(cap, BM)
    # One all_to_all leg moves the full statically shaped exchange buffer:
    # EP * SPD buckets of CAP rows per device. Chunking splits it exactly
    # K ways (the per-chunk buffers are (EP, SPD/K * CAP, D)).
    wire_total = g * cap * row_bytes

    def leg_ms(nbytes):
        return nbytes / (EP_WIRE_GBPS * 1e9) * 1e3

    def compute_ms(nflop):
        return nflop / (EP_MODEL_TFLOPS * 1e12) * 1e3

    per_k = {}
    exposed_by_k = {}
    for kk in chunk_counts:
        assert g % kk == 0, f"{name}: ep_chunks={kk} does not divide {g} groups"
        gpc = g // kk
        t_leg = leg_ms(wire_total / kk)
        disp, comb, exec_gf, comp = [], [], [], []
        for cc in range(kk):
            cnts = counts[cc * gpc : (cc + 1) * gpc]
            tok_c = int(cnts.sum())
            ragged_c = sum(math.ceil(cnt / bm) * bm for cnt in cnts)
            disp.append(round((tok_c + ragged_c) * row_bytes / 1e6, 4))
            comb.append(round((ragged_c + tok_c) * row_bytes / 1e6, 4))
            exec_gf.append(round(ragged_c * flop_per_row / 1e9, 4))
            comp.append(compute_ms(ragged_c * flop_per_row))
        # Pipeline schedule: chunk i's compute slot must cover chunk i-1's
        # combine and chunk i+1's dispatch; the first dispatch and last
        # combine have nothing to hide behind.
        exposed = t_leg + t_leg
        for i in range(kk):
            net = (t_leg if i > 0 else 0.0) + (t_leg if i < kk - 1 else 0.0)
            exposed += max(0.0, net - comp[i])
        exposed_by_k[kk] = exposed
        per_k[str(kk)] = {
            "groups_per_chunk": gpc,
            "wire_mb_per_chunk": round(wire_total / kk / 1e6, 4),
            "exec_gflop": exec_gf,
            "dispatch_hbm_mb": disp,
            "combine_hbm_mb": comb,
            "exposed_comm_ms": round(exposed, 6),
        }
    for kk, exp in exposed_by_k.items():
        if kk > 1 and not exp < exposed_by_k[1]:
            raise AssertionError(
                f"{name}: exposed_comm_ms(K={kk})={exp:.6f} is not strictly "
                f"below the single-shot baseline {exposed_by_k[1]:.6f} — the "
                "chunked schedule stopped hiding the all_to_all legs"
            )
    meta = {
        "shape": name,
        "EP": ep,
        "SPD": spd,
        "CAP": cap,
        "D": d,
        "F": f,
        "routing": "balanced" if balanced else "skewed",
        "tokens_routed": n_tok,
        "tokens_padded": g * cap,
        "group_sizes": counts.tolist(),
        "wire_mb_per_leg": round(wire_total / 1e6, 4),
    }
    return counts, meta, per_k


def _time(fn, *args, iters: int = 20, warmup: int = 3) -> float:
    """Best-of-N wall time: the minimum is the standard noise-robust
    estimator on shared/virtualized hosts (medians here swing 2-3x with
    CPU steal; the floor is what the code costs)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return float(np.min(times))


def run(iters: int = 20, smoke: bool = False) -> list[dict]:
    interpret = default_interpret()
    dtype = jnp.float32
    rows = []
    for name, g, c, d, f, balanced in SMOKE_SHAPES if smoke else SHAPES:
        ks = jax.random.split(jax.random.PRNGKey(zlib.crc32(name.encode())), 4)
        counts, meta, path_acc = ffn_cell_accounting(name, g, c, d, f, balanced)
        n_tok = int(counts.sum())
        ids = jnp.asarray(_ids_from_counts(counts))[:, None]        # (n, 1)
        xt = jax.random.normal(ks[0], (n_tok, d), dtype)            # token stream
        wg = jax.random.normal(ks[1], (g, d, f), dtype) * 0.1
        wu = jax.random.normal(ks[2], (g, d, f), dtype) * 0.1
        wd = jax.random.normal(ks[3], (g, f, d), dtype) * 0.1

        wt = jnp.ones(ids.shape, dtype)  # router weights (k = 1)

        @jax.jit
        def einsum_fn(xt, ids, wg, wu, wd):
            bufs, slots, keep = bucket_dispatch(xt, ids, g, c)
            y = expert_ffn_ref(bufs, wg, wu, wd)
            return bucket_combine(y, ids, slots, keep, wt)

        @jax.jit
        def padded_fn(xt, ids, wg, wu, wd):
            bufs, slots, keep = bucket_dispatch(xt, ids, g, c)
            h = gmm_dual_act(bufs, wg, wu, interpret=interpret)
            return bucket_combine(gmm(h, wd, interpret=interpret), ids, slots, keep, wt)

        @jax.jit
        def ragged_fn(xt, ids, wg, wu, wd):
            bufs, slots, keep = bucket_dispatch(xt, ids, g, c)
            gs = kept_counts(ids, keep, g)
            y = expert_ffn_ragged(bufs, wg, wu, wd, gs, interpret=interpret)
            return bucket_combine(y, ids, slots, keep, wt)

        @jax.jit
        def fused_fn(xt, ids, wg, wu, wd):
            row_ids, offsets, gs, slots, keep = dispatch_metadata(ids, g, c)
            y = expert_ffn_gather(
                xt[row_ids], wg, wu, wd, offsets, gs,
                capacity=c, interpret=interpret,
            )
            return bucket_combine(y, ids, slots, keep, wt)

        @jax.jit
        def compact_fn(xt, ids, wg, wu, wd):
            row_ids, offsets, gs, slots, keep = dispatch_metadata(ids, g, c)
            y = expert_ffn_gather_compact(
                xt[row_ids], wg, wu, wd, offsets, gs,
                capacity=c, interpret=interpret,
            )
            return combine_from_rows(y, offsets[ids] + slots, keep, wt)

        @jax.jit
        def fused_ffn_fn(xt, ids, wg, wu, wd):
            row_ids, offsets, gs, slots, keep = dispatch_metadata(ids, g, c)
            y = expert_ffn_fused(
                xt[row_ids], wg, wu, wd, offsets, gs,
                capacity=c, interpret=interpret,
            )
            return combine_from_rows(y, offsets[ids] + slots, keep, wt)

        # Cross-check all paths before timing — the outputs are per-token
        # combined results, so padded-vs-compact divergence on *either* leg
        # (dispatch or combine) fails here.
        ref = np.asarray(einsum_fn(xt, ids, wg, wu, wd))
        for label, fn in (
            ("ragged", ragged_fn),
            ("fused", fused_fn),
            ("compact", compact_fn),
            ("fused_ffn", fused_ffn_fn),
        ):
            np.testing.assert_allclose(
                np.asarray(fn(xt, ids, wg, wu, wd)), ref,
                rtol=2e-4, atol=2e-4, err_msg=f"{name}:{label} parity",
            )

        walls = {
            "einsum_padded_dispatch": _time(einsum_fn, xt, ids, wg, wu, wd, iters=iters),
            "gmm_padded_dispatch": _time(padded_fn, xt, ids, wg, wu, wd, iters=iters),
            "gmm_ragged_padded_dispatch": _time(ragged_fn, xt, ids, wg, wu, wd, iters=iters),
            "gmm_gather_fused_dispatch": _time(fused_fn, xt, ids, wg, wu, wd, iters=iters),
            "gmm_compact_fused_combine": _time(compact_fn, xt, ids, wg, wu, wd, iters=iters),
            "gmm_fused_ffn_combine": _time(fused_ffn_fn, xt, ids, wg, wu, wd, iters=iters),
        }
        rows.append(
            {
                **meta,
                "paths": {
                    pname: {"wall_ms": round(walls[pname] * 1e3, 3), **acc}
                    for pname, acc in path_acc.items()
                },
            }
        )
    return rows


def run_decode(iters: int = 20, smoke: bool = False) -> list[dict]:
    """Dense vs paged decode attention: parity + KV HBM-byte accounting.

    Bytes model (fp32, k + v): dense reads ``B * max_seq`` cache rows per
    step regardless of context; paged reads ``sum_b ceil(len_b / page) *
    page`` rows (the dead-block clamp elides everything past each request's
    live pages). Wall-clock is interpret-mode semantics off-TPU.
    """
    interpret = default_interpret()
    rows = []
    for name, b, max_seq, lengths, kv, h, hd, bs in (
        DECODE_SMOKE_SHAPES if smoke else DECODE_SHAPES
    ):
        ks = jax.random.split(jax.random.PRNGKey(zlib.crc32(name.encode())), 3)
        nb = -(-max_seq // bs)
        q = jax.random.normal(ks[0], (b, h, hd))
        k = jax.random.normal(ks[1], (b, nb * bs, kv, hd))
        v = jax.random.normal(ks[2], (b, nb * bs, kv, hd))
        ln = jnp.asarray(lengths, jnp.int32)
        valid = (jnp.arange(nb * bs)[None, :] < ln[:, None]).astype(jnp.int32)
        pool_k = k.reshape(b * nb, bs, kv, hd)
        pool_v = v.reshape(b * nb, bs, kv, hd)
        tables = jnp.arange(b * nb, dtype=jnp.int32).reshape(b, nb)

        dense_fn = lambda q, k, v, m: flash_decode_op(q, k, v, m, interpret=interpret)
        paged_fn = lambda q, pk, pv, t, l: flash_decode_paged_op(
            q, pk, pv, t, l, interpret=interpret
        )

        ref = np.asarray(decode_ref(q, k, v, valid))
        np.testing.assert_allclose(
            np.asarray(dense_fn(q, k, v, valid)), ref,
            rtol=2e-4, atol=2e-4, err_msg=f"{name}:dense parity",
        )
        np.testing.assert_allclose(
            np.asarray(paged_fn(q, pool_k, pool_v, tables, ln)), ref,
            rtol=2e-4, atol=2e-4, err_msg=f"{name}:paged parity",
        )

        meta, path_acc, ratio = decode_cell_accounting(
            name, b, max_seq, lengths, kv, h, hd, bs
        )
        walls = {
            "flash_decode_dense_masked": _time(dense_fn, q, k, v, valid, iters=iters),
            "flash_decode_paged": _time(
                paged_fn, q, pool_k, pool_v, tables, ln, iters=iters
            ),
        }
        rows.append(
            {
                **meta,
                "paths": {
                    pname: {"wall_ms": round(walls[pname] * 1e3, 3), **acc}
                    for pname, acc in path_acc.items()
                },
                "kv_bytes_ratio_dense_over_paged": ratio,
            }
        )
    return rows


def run_migration(iters: int = 20, smoke: bool = False) -> list[dict]:
    """Stepped-migration overlap cells: per-tick weight-slice copy riding a
    decode-step-sized expert FFN.

    ``migration_exposed_ms`` = wall(step + slice copies) − wall(step): the
    per-tick migration cost the decode compute does *not* hide. On TPU the
    copy overlaps the step's MXU work and this approaches 0; interpret/CPU
    numbers are semantics-only, like every other wall column here."""
    dtype = jnp.float32
    rows = []
    for name, layers, s, d, f, n_slices, n_tok in (
        MIGRATION_SMOKE_SHAPES if smoke else MIGRATION_SHAPES
    ):
        ks = jax.random.split(jax.random.PRNGKey(zlib.crc32(name.encode())), 4)
        wg = jax.random.normal(ks[0], (layers, s, d, f), dtype) * 0.1
        wu = jax.random.normal(ks[1], (layers, s, d, f), dtype) * 0.1
        wd = jax.random.normal(ks[2], (layers, s, f, d), dtype) * 0.1
        x = jax.random.normal(ks[3], (n_tok, d), dtype)
        meta = migration_cell_accounting(name, layers, s, d, f, n_slices, n_tok)
        src, dst = 0, s - 1
        chunks = {"w_gate": -(-d // n_slices), "w_down": -(-f // n_slices)}
        chunks["w_up"] = chunks["w_gate"]

        def ffn(x, wg, wu, wd):
            # decode-step stand-in: the batch's tokens through one slot's
            # SwiGLU FFN per layer (what one EP rank computes per tick).
            h = jnp.einsum("td,ldf->ltf", x, wg[:, src])
            u = jnp.einsum("td,ldf->ltf", x, wu[:, src])
            return jnp.einsum("ltf,lfd->ltd", jax.nn.silu(h) * u, wd[:, src])

        def one_slice(i, wg, wu, wd):
            # Mirrors migration_driver._copy_row_slice (undonated here so
            # the timed function can be re-invoked on the same buffers).
            out = []
            for w, rows_ in ((wg, chunks["w_gate"]), (wu, chunks["w_up"]),
                             (wd, chunks["w_down"])):
                total = w.shape[2]
                lo = max(0, min(i * rows_, total - rows_))
                blk = jax.lax.dynamic_slice(
                    w, (0, src, lo, 0), (w.shape[0], 1, rows_, w.shape[3])
                )
                out.append(jax.lax.dynamic_update_slice(w, blk, (0, dst, lo, 0)))
            return tuple(out)

        step_fn = jax.jit(ffn)
        step_plus_slice_fn = jax.jit(
            lambda x, wg, wu, wd: (ffn(x, wg, wu, wd), one_slice(0, wg, wu, wd))
        )

        # Parity: n_slices slice copies must land the whole expert exactly.
        cg, cu, cd = wg, wu, wd
        for i in range(n_slices):
            cg, cu, cd = jax.jit(lambda g, u, dn, i=i: one_slice(i, g, u, dn))(
                cg, cu, cd
            )
        for full, copied, label in ((wg, cg, "w_gate"), (wu, cu, "w_up"),
                                    (wd, cd, "w_down")):
            np.testing.assert_array_equal(
                np.asarray(copied[:, dst]), np.asarray(full[:, src]),
                err_msg=f"{name}:{label} sliced copy != whole expert",
            )

        step_ms = _time(step_fn, x, wg, wu, wd, iters=iters) * 1e3
        both_ms = _time(step_plus_slice_fn, x, wg, wu, wd, iters=iters) * 1e3
        rows.append(
            {
                **meta,
                "step_wall_ms": round(step_ms, 3),
                "step_plus_slice_wall_ms": round(both_ms, 3),
                "migration_exposed_ms": round(max(0.0, both_ms - step_ms), 3),
            }
        )
    return rows


def run_prefill(iters: int = 20, smoke_mode: bool = False) -> list[dict]:
    """Chunked-admission interleave cells: the fused two-lane decode step
    with a live prefill chunk vs the no-op chunk.

    Parity first: the decode lane's logits must be bitwise identical
    whether the prefill lane is off (``chunk=None``), idling (the no-op
    chunk) or mid-chunk — the lane must be invisible to its batchmates.
    ``chunk_exposed_ms`` = wall(decode + live chunk) − wall(decode + no-op
    chunk): the per-tick cost of interleaving admission, which on TPU the
    step's existing compute largely hides."""
    from repro.configs import get_config, smoke
    from repro.models import transformer as T
    from repro.parallel.ctx import ParallelCtx

    ctx = ParallelCtx()
    rows = []
    for name, model, b, chunk, prompt_len, bs, max_seq in (
        PREFILL_SMOKE_SHAPES if smoke_mode else PREFILL_SHAPES
    ):
        meta = prefill_cell_accounting(name, model, b, chunk, prompt_len, bs, max_seq)
        cfg = smoke(get_config(model))
        params = T.init_params(jax.random.PRNGKey(zlib.crc32(name.encode())), cfg)
        cache = T.init_cache(cfg, b, max_seq, paged=True, page_size=bs)
        nb = -(-max_seq // bs)
        token = jnp.zeros((b, 1), jnp.int32)
        rng = np.random.default_rng(zlib.crc32(name.encode()))
        buf = np.zeros(chunk, np.int32)
        buf[:] = rng.integers(0, cfg.vocab_size, size=chunk)
        live_chunk = {
            "tokens": jnp.asarray(buf[None, :]),
            "table": jnp.arange(nb, dtype=jnp.int32),
            "start": jnp.zeros((), jnp.int32),
            "length": jnp.asarray(chunk, jnp.int32),
        }
        trash = cache["layers"]["pool_k"].shape[1] - 1
        noop_chunk = {
            "tokens": jnp.zeros((1, chunk), jnp.int32),
            "table": jnp.full((nb,), trash, jnp.int32),
            "start": jnp.zeros((), jnp.int32),
            "length": jnp.zeros((), jnp.int32),
        }

        @jax.jit
        def lane_off(token, cache):
            return T.decode_step(params, token, cache, cfg, ctx)[0]

        @jax.jit
        def fused(token, cache, chunk_op):
            return T.decode_step(
                params, token, cache, cfg, ctx, chunk=chunk_op
            )[0]

        ref = np.asarray(lane_off(token, cache))
        for label, op in (("noop", noop_chunk), ("live", live_chunk)):
            np.testing.assert_array_equal(
                np.asarray(fused(token, cache, op)), ref,
                err_msg=f"{name}: chunk lane ({label}) leaked into decode lane",
            )

        decode_ms = _time(fused, token, cache, noop_chunk, iters=iters) * 1e3
        both_ms = _time(fused, token, cache, live_chunk, iters=iters) * 1e3
        rows.append(
            {
                **meta,
                "decode_wall_ms": round(decode_ms, 3),
                "decode_plus_chunk_wall_ms": round(both_ms, 3),
                "chunk_exposed_ms": round(max(0.0, both_ms - decode_ms), 3),
            }
        )
    return rows


def run_ep_chunk(iters: int = 20, smoke: bool = False) -> list[dict]:
    """Chunked EP dispatch cells: the per-chunk fused row-FFN schedule the
    pipelined ``ep_moe_shardmap`` runs between its all_to_all legs.

    Parity first, and it is *bitwise*: the chunked path slices the
    per-bucket offsets/counts of ONE global ``dispatch_metadata`` call, so
    every bucket's rows, keep mask, and FP combine order are unchanged —
    ``ep_chunks`` must be a pure schedule knob. ``wall_ms`` per K is the
    measured chunked FFN (interpret semantics off-TPU, not gated);
    ``exposed_comm_ms`` is the gated analytic schedule column."""
    dtype = jnp.float32
    rows = []
    for name, ep, spd, cap, d, f, chunk_counts, balanced in (
        EP_CHUNK_SMOKE_SHAPES if smoke else EP_CHUNK_SHAPES
    ):
        g = ep * spd
        counts, meta, per_k = ep_chunk_cell_accounting(
            name, ep, spd, cap, d, f, chunk_counts, balanced
        )
        n_tok = int(counts.sum())
        ks = jax.random.split(jax.random.PRNGKey(zlib.crc32(name.encode())), 4)
        ids = jnp.asarray(_ids_from_counts(counts))[:, None]
        xt = jax.random.normal(ks[0], (n_tok, d), dtype)
        wg = jax.random.normal(ks[1], (g, d, f), dtype) * 0.1
        wu = jax.random.normal(ks[2], (g, d, f), dtype) * 0.1
        wd = jax.random.normal(ks[3], (g, f, d), dtype) * 0.1
        wt = jnp.ones(ids.shape, dtype)

        def make_fn(kk):
            gpc = g // kk

            @jax.jit
            def fn(xt, ids, wg, wu, wd):
                row_ids, offsets, gs, slots, keep = dispatch_metadata(ids, g, cap)
                rows_in = xt[row_ids]

                def chunk_ffn(cc):
                    ws = slice(cc * gpc, (cc + 1) * gpc)
                    return expert_ffn_from_rows(
                        rows_in, wg[ws], wu[ws], wd[ws], offsets[ws], gs[ws],
                        capacity=cap, groups_per_weight=1, enabled=True,
                        compact_out=True, fused=True,
                    )

                y = chunk_ffn(0)
                if kk > 1:
                    # Rows outside a chunk's buckets are unspecified in its
                    # output — select each row from its owner chunk (same
                    # merge as the chunked moe_esp fused branch).
                    r_idx = jnp.arange(rows_in.shape[0], dtype=jnp.int32)
                    owner = jnp.searchsorted(offsets, r_idx, side="right") - 1
                    owner_c = jnp.clip(owner, 0, g - 1) // gpc
                    for cc in range(1, kk):
                        y = jnp.where((owner_c == cc)[:, None], chunk_ffn(cc), y)
                return combine_from_rows(y, offsets[ids] + slots, keep, wt)

            return fn

        fns = {kk: make_fn(kk) for kk in chunk_counts}
        base = np.asarray(fns[1](xt, ids, wg, wu, wd))
        for kk in chunk_counts:
            if kk == 1:
                continue
            np.testing.assert_array_equal(
                np.asarray(fns[kk](xt, ids, wg, wu, wd)), base,
                err_msg=f"{name}: ep_chunks={kk} is not bit-identical to "
                "the single-shot path",
            )

        chunks_out = {}
        for kk in chunk_counts:
            wall = _time(fns[kk], xt, ids, wg, wu, wd, iters=iters)
            chunks_out[str(kk)] = {
                "wall_ms": round(wall * 1e3, 3),
                **per_k[str(kk)],
            }
        rows.append({**meta, "chunks": chunks_out})
    return rows


# ---------------------------------------------------------------------------
# baseline regression gate (--check)
# ---------------------------------------------------------------------------

def check_baseline(baseline_path: str) -> list[str]:
    """Recompute every deterministic column from the same seeded draws and
    diff against the committed baseline. Returns human-readable failure
    lines (empty == green). Wall-clock, backend, and version fields are
    deliberately ignored — only the accounting the fused kernels exist to
    improve is gated."""
    with open(baseline_path) as fh:
        base = json.load(fh)
    failures: list[str] = []

    def cmp(cell: str, key: str, want, got) -> None:
        if want != got:
            failures.append(
                f"{cell}.{key}: baseline {want!r} != recomputed {got!r}"
            )

    base_shapes = {r.get("shape"): r for r in base.get("shapes", [])}
    expected = []
    for name, g, c, d, f, balanced in SHAPES:
        expected.append(name)
        _, meta, path_acc = ffn_cell_accounting(name, g, c, d, f, balanced)
        row = base_shapes.get(name)
        if row is None:
            failures.append(f"shapes[{name}]: missing from baseline")
            continue
        for key, val in meta.items():
            cmp(f"shapes[{name}]", key, row.get(key), val)
        for pname, acc in path_acc.items():
            prow = (row.get("paths") or {}).get(pname)
            if prow is None:
                failures.append(f"shapes[{name}].paths.{pname}: missing from baseline")
                continue
            for key, val in acc.items():
                cmp(f"shapes[{name}].paths.{pname}", key, prow.get(key), val)
    for name in set(base_shapes) - set(expected):
        failures.append(f"shapes[{name}]: in baseline but no longer benchmarked")

    base_dec = {r.get("shape"): r for r in base.get("decode_shapes", [])}
    expected = []
    for name, b, max_seq, lengths, kv, h, hd, bs in DECODE_SHAPES:
        expected.append(name)
        meta, path_acc, ratio = decode_cell_accounting(
            name, b, max_seq, lengths, kv, h, hd, bs
        )
        row = base_dec.get(name)
        if row is None:
            failures.append(f"decode_shapes[{name}]: missing from baseline")
            continue
        for key, val in meta.items():
            cmp(f"decode_shapes[{name}]", key, row.get(key), val)
        cmp(
            f"decode_shapes[{name}]", "kv_bytes_ratio_dense_over_paged",
            row.get("kv_bytes_ratio_dense_over_paged"), ratio,
        )
        for pname, acc in path_acc.items():
            prow = (row.get("paths") or {}).get(pname)
            if prow is None:
                failures.append(
                    f"decode_shapes[{name}].paths.{pname}: missing from baseline"
                )
                continue
            for key, val in acc.items():
                cmp(f"decode_shapes[{name}].paths.{pname}", key, prow.get(key), val)
    for name in set(base_dec) - set(expected):
        failures.append(f"decode_shapes[{name}]: in baseline but no longer benchmarked")

    base_mig = {r.get("shape"): r for r in base.get("migration_shapes", [])}
    expected = []
    for name, layers, s, d, f, n_slices, n_tok in MIGRATION_SHAPES:
        expected.append(name)
        meta = migration_cell_accounting(name, layers, s, d, f, n_slices, n_tok)
        row = base_mig.get(name)
        if row is None:
            failures.append(f"migration_shapes[{name}]: missing from baseline")
            continue
        for key, val in meta.items():
            cmp(f"migration_shapes[{name}]", key, row.get(key), val)
    for name in set(base_mig) - set(expected):
        failures.append(
            f"migration_shapes[{name}]: in baseline but no longer benchmarked"
        )

    base_pf = {r.get("shape"): r for r in base.get("prefill_shapes", [])}
    expected = []
    for name, model, b, chunk, prompt_len, bs, max_seq in PREFILL_SHAPES:
        expected.append(name)
        meta = prefill_cell_accounting(name, model, b, chunk, prompt_len, bs, max_seq)
        row = base_pf.get(name)
        if row is None:
            failures.append(f"prefill_shapes[{name}]: missing from baseline")
            continue
        for key, val in meta.items():
            cmp(f"prefill_shapes[{name}]", key, row.get(key), val)
    for name in set(base_pf) - set(expected):
        failures.append(
            f"prefill_shapes[{name}]: in baseline but no longer benchmarked"
        )

    base_ec = {r.get("shape"): r for r in base.get("ep_chunk_shapes", [])}
    expected = []
    for name, ep, spd, cap, d, f, chunk_counts, balanced in EP_CHUNK_SHAPES:
        expected.append(name)
        _, meta, per_k = ep_chunk_cell_accounting(
            name, ep, spd, cap, d, f, chunk_counts, balanced
        )
        row = base_ec.get(name)
        if row is None:
            failures.append(f"ep_chunk_shapes[{name}]: missing from baseline")
            continue
        for key, val in meta.items():
            cmp(f"ep_chunk_shapes[{name}]", key, row.get(key), val)
        base_chunks = row.get("chunks") or {}
        for kk, acc in per_k.items():
            crow = base_chunks.get(kk)
            if crow is None:
                failures.append(
                    f"ep_chunk_shapes[{name}].chunks[{kk}]: missing from baseline"
                )
                continue
            for key, val in acc.items():
                cmp(f"ep_chunk_shapes[{name}].chunks[{kk}]", key, crow.get(key), val)
        for kk in set(base_chunks) - set(per_k):
            failures.append(
                f"ep_chunk_shapes[{name}].chunks[{kk}]: in baseline but no "
                "longer benchmarked"
            )
    for name in set(base_ec) - set(expected):
        failures.append(
            f"ep_chunk_shapes[{name}]: in baseline but no longer benchmarked"
        )
    return failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_kernels.json")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument(
        "--smoke",
        action="store_true",
        help="tiny FFN + decode cells, 2 iters: fast kernel regression gate",
    )
    ap.add_argument(
        "--check",
        metavar="BASELINE_JSON",
        help="recompute the deterministic columns (FLOP + HBM-byte "
        "accounting, not wall-clock) and fail on any drift from the "
        "committed baseline",
    )
    args = ap.parse_args()

    if args.check:
        failures = check_baseline(args.check)
        if failures:
            print(
                f"BENCH BASELINE DRIFT vs {args.check} "
                f"({len(failures)} mismatches):",
                file=sys.stderr,
            )
            for line in failures:
                print(f"  {line}", file=sys.stderr)
            print(
                "If the change is intentional, regenerate the baseline: "
                "PYTHONPATH=src python benchmarks/bench_kernels.py --out "
                "BENCH_kernels.json",
                file=sys.stderr,
            )
            raise SystemExit(1)
        print(f"BENCH BASELINE OK ({args.check}: deterministic columns match)")
        return

    iters = 2 if args.smoke else args.iters
    try:
        rows = run(iters=iters, smoke=args.smoke)
        decode_rows = run_decode(iters=iters, smoke=args.smoke)
        migration_rows = run_migration(iters=iters, smoke=args.smoke)
        prefill_rows = run_prefill(iters=iters, smoke_mode=args.smoke)
        ep_chunk_rows = run_ep_chunk(iters=iters, smoke=args.smoke)
    except AssertionError as e:  # parity failure must fail the gate loudly
        print(f"KERNEL PARITY FAILURE: {e}", file=sys.stderr)
        raise SystemExit(1)
    doc = {
        "bench": "kernels_expert_ffn_dispatch",
        "backend": jax.default_backend(),
        "interpret": default_interpret(),
        "jax": jax.__version__,
        "host": platform.machine(),
        "smoke": args.smoke,
        "note": (
            "wall_ms on non-TPU backends runs the Pallas paths in interpret "
            "mode (semantics, not speed); FLOP and byte accounting is "
            "backend-independent. utilization = achieved/executed FLOPs; "
            "dispatch_hbm_mb / combine_hbm_mb / hidden_hbm_mb = HBM bytes "
            "each leg moves; fused-path DMA sides are counted at ceil-tile "
            "granularity (the kernels move whole bm-row tiles), matching "
            "exec_gflop (the fused gather path never materializes the "
            "padded input buckets; the compact path's gmm_scatter epilogue "
            "never materializes the padded FFN output either, and "
            "combine_from_rows reads only live rows; gmm_fused_ffn_combine "
            "runs all three matmuls in ONE kernel with the (G, C, F) "
            "SwiGLU hidden tile resident in VMEM, so its hidden_hbm_mb is "
            "exactly 0 where every two-kernel path pays the full padded "
            "write + read). All paths end in the per-token combine, so "
            "parity covers both legs. This bench drives the local/ESP-style "
            "dispatch; the EP all_to_all path keeps statically-sized "
            "exchange buffers on both legs (equal splits), where the "
            "fusion instead removes the receive-side repack + padded FFN "
            "input/output. decode_shapes compare dense masked flash-decode "
            "(streams B*max_seq KV rows/step) against the paged "
            "block-table kernel (streams only live pages): kv_hbm_mb "
            "tracks context length, not max_seq. migration_shapes measure "
            "live stepped expert migration: one per-tick weight-row slice "
            "copy (dynamic_slice/dynamic_update_slice per tensor, the same "
            "program runtime.migration_driver issues) dispatched alongside a "
            "decode-step-sized expert FFN; migration_exposed_ms = "
            "wall(step + slice) - wall(step) is the per-tick cost decode "
            "compute does not hide, and slice_mb / expert_mb / "
            "ticks_to_commit are the deterministic accounting. "
            "prefill_shapes measure the chunked-admission prefill lane "
            "(ServeConfig(prefill_chunk=C)): the fused two-lane decode "
            "step with a live chunk vs the no-op chunk; ttft_ticks, "
            "decode_stall_ticks and chunk_hbm_mb (KV bytes the lane "
            "writes + streams over one admission) are deterministic, and "
            "chunk_exposed_ms = wall(decode + live chunk) - wall(decode + "
            "no-op chunk) is the per-tick interleave cost. ep_chunk_shapes "
            "measure the chunked EP dispatch pipeline "
            "(ServeConfig(ep_chunks=K)): per-K bitwise parity of the "
            "chunked fused row-FFN against the single-shot path, per-chunk "
            "dispatch/combine HBM bytes (the K lists sum to the "
            "single-shot columns — per-chunk offset slices keep both legs "
            "compact), per-chunk exchange wire bytes, and exposed_comm_ms "
            "— the analytic schedule's wall(step) - wall(overlapped "
            "ideal) at the fixed EP_WIRE_GBPS/EP_MODEL_TFLOPS model point "
            "(K=1 = the two synchronous all_to_all walls; every K>1 must "
            "be strictly below it, asserted at generation AND re-checked "
            "by --check). The deterministic columns are CI-gated: "
            "bench_kernels.py --check BENCH_kernels.json recomputes them "
            "and fails on drift."
        ),
        "shapes": rows,
        "decode_shapes": decode_rows,
        "migration_shapes": migration_rows,
        "prefill_shapes": prefill_rows,
        "ep_chunk_shapes": ep_chunk_rows,
    }
    if args.smoke:
        print(json.dumps(doc, indent=2))
        print("BENCH SMOKE OK")
        return
    with open(args.out, "w") as fh:
        json.dump(doc, fh, indent=2)
        fh.write("\n")
    print(json.dumps(doc, indent=2))


if __name__ == "__main__":
    main()
