"""Grouped matmul Pallas kernels (TPU target, MXU-aligned tiling).

The MoE hot spot: after capacity dispatch, expert inputs sit in dense
buckets ``(G, C, D)`` with per-group weights ``(G, D, F)``. Two kernels:

* ``gmm``         — y[g] = x[g] @ w[g], K-accumulated in VMEM scratch.
* ``gmm_dual_act``— h[g] = silu(x[g] @ wg[g]) * (x[g] @ wu[g]) — the fused
  SwiGLU first half; saves one HBM round-trip of the (G, C, F) hidden
  tensor versus two separate gmm calls + an elementwise pass.

Tiling: grid (G, C/bm, F/bn, D/bk); block shapes default to the MXU-native
128x128 (shrunk to divisors for small inputs). The K dimension is the
innermost (sequential) grid axis; the fp32 accumulator lives in VMEM
scratch and flushes on the last K step. VMEM working set per step:
bm*bk + bk*bn (+bk*bn) inputs + bm*bn fp32 accumulator(s) — ~0.3 MB at the
defaults, far under the ~16 MB v5e VMEM budget, leaving headroom for
Pallas' input double-buffering.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import fit_block


def _gmm_kernel(x_ref, w_ref, o_ref, acc_ref, *, nk: int):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[0],
        w_ref[0],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == nk - 1)
    def _():
        o_ref[0, ...] = acc_ref[...].astype(o_ref.dtype)


def gmm(
    x: jax.Array,
    w: jax.Array,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """y[g] = x[g] @ w[g]; x: (G, C, D), w: (G, D, F) -> (G, C, F)."""
    g, c, d = x.shape
    f = w.shape[-1]
    bm, bn, bk = fit_block(c, bm), fit_block(f, bn), fit_block(d, bk)
    nk = d // bk
    grid = (g, c // bm, f // bn, nk)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda gi, i, j, k: (gi, i, k)),
            pl.BlockSpec((1, bk, bn), lambda gi, i, j, k: (gi, k, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda gi, i, j, k: (gi, i, j)),
        out_shape=jax.ShapeDtypeStruct((g, c, f), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, w)


def _gmm_dual_kernel(x_ref, wg_ref, wu_ref, o_ref, accg_ref, accu_ref, *, nk: int):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _():
        accg_ref[...] = jnp.zeros_like(accg_ref)
        accu_ref[...] = jnp.zeros_like(accu_ref)

    dims = (((1,), (0,)), ((), ()))
    accg_ref[...] += jax.lax.dot_general(
        x_ref[0], wg_ref[0], dims, preferred_element_type=jnp.float32
    )
    accu_ref[...] += jax.lax.dot_general(
        x_ref[0], wu_ref[0], dims, preferred_element_type=jnp.float32
    )

    @pl.when(k == nk - 1)
    def _():
        h = jax.nn.silu(accg_ref[...]) * accu_ref[...]
        o_ref[0, ...] = h.astype(o_ref.dtype)


def gmm_dual_act(
    x: jax.Array,
    wg: jax.Array,
    wu: jax.Array,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """h[g] = silu(x@wg) * (x@wu); fused SwiGLU front half."""
    g, c, d = x.shape
    f = wg.shape[-1]
    bm, bn, bk = fit_block(c, bm), fit_block(f, bn), fit_block(d, bk)
    nk = d // bk
    grid = (g, c // bm, f // bn, nk)
    return pl.pallas_call(
        functools.partial(_gmm_dual_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda gi, i, j, k: (gi, i, k)),
            pl.BlockSpec((1, bk, bn), lambda gi, i, j, k: (gi, k, j)),
            pl.BlockSpec((1, bk, bn), lambda gi, i, j, k: (gi, k, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda gi, i, j, k: (gi, i, j)),
        out_shape=jax.ShapeDtypeStruct((g, c, f), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, bn), jnp.float32),
        ],
        interpret=interpret,
    )(x, wg, wu)
