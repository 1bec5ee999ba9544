"""Ragged (count-aware) GMM kernels and the kernel-dispatch layer.

Covers:
* ``gmm_ragged`` / ``gmm_dual_act_ragged`` parity vs the einsum oracles
  across uneven group counts (zero-token groups, full groups, counts that
  don't hit tile boundaries) and non-MXU-aligned C/D/F;
* tile-skip semantics — garbage (NaN) rows past a group's count must never
  leak into kept rows or the output tail;
* the ``groups_per_weight`` divisor mapping both MoE layouts rely on;
* differentiability of the registry ops (kernel forward, reference-math
  backward via custom_vjp);
* end-to-end parity of ``moe_esp`` / ``moe_ep`` / prefill / decode
  attention with kernels on vs off (interpret mode on CPU).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke
from repro.kernels import registry
from repro.kernels.gmm.ops import expert_ffn_ragged, gmm_ragged_op
from repro.kernels.gmm.ragged import gmm_dual_act_ragged
from repro.kernels.gmm.ref import (
    expert_ffn_ragged_ref,
    gmm_ragged_ref,
    gmm_ref,
)
from repro.kernels.tiling import fit_block
from repro.models import attention as A
from repro.models.moe import moe_dense, moe_ep, moe_esp, moe_init
from repro.parallel.ctx import ParallelCtx

RNG = jax.random.PRNGKey(0)

CTX_ON = ParallelCtx(capacity_factor=8.0, use_kernels=True)
CTX_OFF = ParallelCtx(capacity_factor=8.0, use_kernels=False)


@pytest.fixture(scope="module")
def moe_cfg():
    return dataclasses.replace(
        smoke(get_config("dbrx-132b")), n_experts=4, experts_per_token=2
    )


# ---------------------------------------------------------------------------
# gmm_ragged vs oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "g,c,d,f,counts",
    [
        (4, 16, 8, 12, [0, 5, 16, 3]),          # zero group, full group
        (3, 96, 64, 160, [1, 95, 40]),          # non-128 C/D/F
        (2, 128, 128, 256, [128, 17]),          # MXU-native tiles
        (5, 24, 48, 40, [24, 0, 0, 7, 2]),      # multiple empty groups
    ],
)
def test_gmm_ragged_matches_ref(g, c, d, f, counts):
    ks = jax.random.split(RNG, 2)
    x = jax.random.normal(ks[0], (g, c, d))
    w = jax.random.normal(ks[1], (g, d, f)) * 0.1
    gs = jnp.asarray(counts, jnp.int32)
    out = gmm_ragged_op(x, w, gs)
    ref = gmm_ragged_ref(x, w, gs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)
    # Kept rows agree with a dense oracle bit-for-bit when the K dim fits
    # one accumulation tile and the oracle issues the kernel's own dots:
    # one (C, D) @ (D, bn) product per output column block. (A single
    # (C, D) @ (D, F) einsum is free to pick another fp32 summation order
    # on the CPU backend, so it is not a bitwise oracle.)
    if d <= 128:
        bn = fit_block(f, 128)
        dense = np.concatenate(
            [
                np.asarray(gmm_ref(x, w[:, :, j : j + bn]))
                for j in range(0, f, bn)
            ],
            axis=2,
        )
        outn = np.asarray(out)
        for gi, cnt in enumerate(counts):
            np.testing.assert_array_equal(outn[gi, :cnt], dense[gi, :cnt])
    # Rows past each group's count are exactly zero.
    outn = np.asarray(out)
    for gi, cnt in enumerate(counts):
        assert (outn[gi, cnt:] == 0).all()


def test_gmm_ragged_skips_dead_rows():
    """NaNs planted past each group's count must not reach the output —
    dead row-tiles are skipped (no MXU pass), partial tiles are masked.
    This is the semantic footprint of FLOPs ~ sum(group_sizes)."""
    g, c, d, f = 3, 64, 32, 48
    counts = jnp.asarray([10, 0, 33], jnp.int32)
    ks = jax.random.split(RNG, 2)
    x = jax.random.normal(ks[0], (g, c, d))
    rows = jnp.arange(c)[None, :, None]
    x = jnp.where(rows < counts[:, None, None], x, jnp.nan)
    w = jax.random.normal(ks[1], (g, d, f)) * 0.1
    out = np.asarray(gmm_ragged_op(x, w, counts))
    assert np.isfinite(out).all()
    ref = gmm_ragged_ref(jnp.nan_to_num(x), w, counts)
    np.testing.assert_allclose(out, np.asarray(ref), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("gpw", [2, 3])
def test_gmm_ragged_groups_per_weight(gpw):
    """gpw consecutive groups share one weight row — the flattened EP
    (slot-major) and ESP (expert-major) bucket layouts."""
    gw, c, d, f = 2, 16, 24, 20
    g = gw * gpw
    ks = jax.random.split(RNG, 2)
    x = jax.random.normal(ks[0], (g, c, d))
    w = jax.random.normal(ks[1], (gw, d, f)) * 0.1
    gs = jnp.arange(g, dtype=jnp.int32) * 2  # 0, 2, 4, ...
    out = gmm_ragged_op(x, w, gs, groups_per_weight=gpw)
    ref = gmm_ragged_ref(x, w, gs, groups_per_weight=gpw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_dual_act_ragged_matches_ref():
    g, c, d, f = 4, 32, 16, 24
    ks = jax.random.split(RNG, 3)
    x = jax.random.normal(ks[0], (g, c, d))
    wg = jax.random.normal(ks[1], (g, d, f)) * 0.1
    wu = jax.random.normal(ks[2], (g, d, f)) * 0.1
    gs = jnp.asarray([0, 32, 5, 19], jnp.int32)
    out = gmm_dual_act_ragged(x, wg, wu, gs, interpret=True)
    mask = (jnp.arange(c)[None, :] < gs[:, None])[..., None]
    ref = (jax.nn.silu(gmm_ref(x, wg)) * gmm_ref(x, wu)) * mask
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_expert_ffn_ragged_end_to_end():
    gw, gpw, c, d, f = 2, 2, 16, 8, 12
    g = gw * gpw
    ks = jax.random.split(RNG, 4)
    x = jax.random.normal(ks[0], (g, c, d))
    wg = jax.random.normal(ks[1], (gw, d, f)) * 0.1
    wu = jax.random.normal(ks[2], (gw, d, f)) * 0.1
    wd = jax.random.normal(ks[3], (gw, f, d)) * 0.1
    gs = jnp.asarray([7, 0, 16, 2], jnp.int32)
    out = expert_ffn_ragged(x, wg, wu, wd, gs, groups_per_weight=gpw)
    ref = expert_ffn_ragged_ref(x, wg, wu, wd, gs, gpw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# registry: dispatch decisions + differentiability
# ---------------------------------------------------------------------------

def test_registry_compiled_gates():
    """The compiled (non-interpret) path only takes MXU-tileable shapes;
    interpret mode takes anything."""
    assert registry.can_gmm(128, 128, 256, interpret=False)
    assert not registry.can_gmm(128, 96, 256, interpret=False)
    assert registry.can_gmm(7, 5, 3, interpret=True)
    assert registry.can_flash_attend(128, 128, 8, 2, 128, interpret=False)
    assert not registry.can_flash_attend(128, 128, 8, 3, 128, interpret=False)
    assert not registry.can_flash_attend(100, 100, 8, 2, 64, interpret=False)
    assert registry.can_flash_decode(256, 8, 2, 128, interpret=False)
    assert not registry.can_flash_decode(100, 8, 2, 64, interpret=False)


def test_registry_expert_ffn_grad_matches_ref():
    """Kernel forward + reference backward (custom_vjp) must match the
    all-reference gradients."""
    g, c, d, f = 4, 16, 8, 12
    ks = jax.random.split(RNG, 4)
    x = jax.random.normal(ks[0], (g, c, d))
    wg = jax.random.normal(ks[1], (g, d, f)) * 0.1
    wu = jax.random.normal(ks[2], (g, d, f)) * 0.1
    wd = jax.random.normal(ks[3], (g, f, d)) * 0.1
    gs = jnp.asarray([3, 16, 0, 9], jnp.int32)

    def loss(fn, x, wg, wu, wd):
        return (fn(x, wg, wu, wd) ** 2).sum()

    kern = lambda *a: registry.expert_ffn(*a, group_sizes=gs, enabled=True)
    ref = lambda *a: expert_ffn_ragged_ref(*a, gs)
    gk = jax.grad(loss, argnums=(1, 2, 3, 4))(kern, x, wg, wu, wd)
    gr = jax.grad(loss, argnums=(1, 2, 3, 4))(ref, x, wg, wu, wd)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


def test_registry_attend_grad_matches_ref():
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (1, 32, 4, 16))
    k = jax.random.normal(ks[1], (1, 32, 2, 16))
    v = jax.random.normal(ks[2], (1, 32, 2, 16))
    f_kern = lambda q_: registry.attend(q_, k, v, causal=True).sum()
    f_ref = lambda q_: A.gqa_attend(q_, k, v, A.causal_mask(32)).sum()
    np.testing.assert_allclose(
        np.asarray(jax.grad(f_kern)(q)),
        np.asarray(jax.grad(f_ref)(q)),
        rtol=1e-4,
        atol=1e-4,
    )


# ---------------------------------------------------------------------------
# end-to-end: kernels on vs off
# ---------------------------------------------------------------------------

def test_moe_esp_kernels_on_off_parity(moe_cfg):
    rng = jax.random.PRNGKey(0)
    p = moe_init(rng, moe_cfg)
    x = jax.random.normal(rng, (2, 8, moe_cfg.d_model)) * 0.5
    off, _ = moe_esp(p, x, moe_cfg, CTX_OFF)
    on, _ = moe_esp(p, x, moe_cfg, CTX_ON)
    np.testing.assert_allclose(np.asarray(on), np.asarray(off), rtol=1e-5, atol=1e-5)
    dense, _ = moe_dense(p, x, moe_cfg, CTX_OFF)
    np.testing.assert_allclose(np.asarray(on), np.asarray(dense), rtol=1e-5, atol=1e-5)


def test_moe_esp_kernels_grad_parity(moe_cfg):
    rng = jax.random.PRNGKey(1)
    p = moe_init(rng, moe_cfg)
    x = jax.random.normal(rng, (2, 8, moe_cfg.d_model)) * 0.5
    g_on = jax.grad(lambda p_: moe_esp(p_, x, moe_cfg, CTX_ON)[0].sum())(p)
    g_off = jax.grad(lambda p_: moe_esp(p_, x, moe_cfg, CTX_OFF)[0].sum())(p)
    for k in ("w_gate", "w_up", "w_down", "router"):
        np.testing.assert_allclose(
            np.asarray(g_on[k]), np.asarray(g_off[k]), rtol=1e-4, atol=1e-5
        )


def test_moe_ep_kernels_on_off_parity(moe_cfg):
    """EP dispatch (shard_map all_to_all) on a 1x1 mesh: the kernel path
    runs inside the shard_map body exactly as on a real EP axis."""
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"))
    rng = jax.random.PRNGKey(0)
    p = moe_init(rng, moe_cfg)
    x = jax.random.normal(rng, (2, 8, moe_cfg.d_model)) * 0.5
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


@pytest.mark.parametrize("window", [0, 8])
def test_attention_kernels_on_off_parity(window):
    cfg = dataclasses.replace(smoke(get_config("dbrx-132b")), sliding_window=window)
    rng = jax.random.PRNGKey(0)
    p = A.attn_init(rng, cfg)
    x = jax.random.normal(rng, (2, 16, cfg.d_model)) * 0.5
    off = A.attention(p, x, cfg, CTX_OFF)
    on = A.attention(p, x, cfg, CTX_ON)
    np.testing.assert_allclose(np.asarray(on), np.asarray(off), rtol=1e-5, atol=1e-5)


def test_decode_attention_kernels_on_off_parity():
    cfg = smoke(get_config("dbrx-132b"))
    rng = jax.random.PRNGKey(0)
    p = A.attn_init(rng, cfg)
    cache = A.cache_init(cfg, 2, 32)
    x = jax.random.normal(rng, (2, 1, cfg.d_model)) * 0.5
    for pos in (0, 5, 31):
        o_off, c_off = A.decode_attention(p, x, cache, jnp.int32(pos), cfg, CTX_OFF)
        o_on, c_on = A.decode_attention(p, x, cache, jnp.int32(pos), cfg, CTX_ON)
        np.testing.assert_allclose(
            np.asarray(o_on), np.asarray(o_off), rtol=1e-5, atol=1e-5
        )
        np.testing.assert_array_equal(np.asarray(c_on["k"]), np.asarray(c_off["k"]))
