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
from repro.kernels.gmm import ragged
from repro.kernels.gmm.ragged import (
    gmm_dual_act_ragged,
    ragged_block_maps,
    ragged_blocks,
    ragged_pins,
)
from repro.kernels.gmm.ref import (
    expert_ffn_ragged_ref,
    gmm_ragged_ref,
    gmm_ref,
)
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
    # Kept rows agree with a dense oracle bit-for-bit when the oracle issues
    # the kernel's own dots: one (bm, D) @ (D, bn) product per row tile and
    # column panel, with the blocks the kernel's rule picks. (A single
    # (C, D) @ (D, F) einsum is free to pick another fp32 summation order
    # on the CPU backend, so it is not a bitwise oracle.)
    bm, _, bn = ragged_blocks(c, d, f, x.dtype.itemsize, 1)
    dense = np.concatenate(
        [
            np.concatenate(
                [
                    np.asarray(gmm_ref(x[:, r : r + bm], w[:, :, j : j + bn]))
                    for j in range(0, f, bn)
                ],
                axis=2,
            )
            for r in range(0, c, bm)
        ],
        axis=1,
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
# the weight-streaming schedule: row tiles, empty groups, the block rule
# ---------------------------------------------------------------------------

def _run(kind, x, ws, gs, gpw=1):
    if kind == "gmm":
        return ragged.gmm_ragged(x, ws[0], gs, groups_per_weight=gpw, interpret=True)
    return gmm_dual_act_ragged(
        x, ws[0], ws[1], gs, groups_per_weight=gpw, interpret=True
    )


def _oracle(kind, x, ws, gs, gpw=1):
    if kind == "gmm":
        return gmm_ragged_ref(x, ws[0], gs, groups_per_weight=gpw)
    wg, wu = (jnp.repeat(w, gpw, axis=0) for w in ws)
    mask = (jnp.arange(x.shape[1])[None, :] < gs[:, None])[..., None]
    return (jax.nn.silu(gmm_ref(x, wg)) * gmm_ref(x, wu)) * mask


def _operands(g, c, d, f, gpw=1, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(ks[0], (g, c, d))
    ws = tuple(jax.random.normal(k, (g // gpw, d, f)) * 0.1 for k in ks[1:])
    return x, ws


@pytest.mark.parametrize("kind", ["gmm", "dual"])
@pytest.mark.parametrize(
    "counts",
    [[96, 0, 33, 1], [32, 64, 0, 95], [0, 0, 31, 0]],
    ids=["full-empty-partial", "tile-edges", "one-partial"],
)
def test_ragged_dead_tiles_among_live(kind, counts, monkeypatch):
    """Buckets of three 32-row tiles: tiles past each count are skipped and
    the partial tile's tail is zeroed, whatever mix of live and dead."""
    monkeypatch.setattr(ragged, "RAGGED_ROW_TILE", 32)
    x, ws = _operands(4, 96, 40, 48)
    assert ragged_blocks(96, 40, 48, 4, len(ws))[0] == 32
    gs = jnp.asarray(counts, jnp.int32)
    out = np.asarray(_run(kind, x, ws, gs))
    np.testing.assert_allclose(
        out, np.asarray(_oracle(kind, x, ws, gs)), rtol=1e-5, atol=1e-5
    )
    for gi, cnt in enumerate(counts):
        assert (out[gi, cnt:] == 0).all()


@pytest.mark.parametrize("kind", ["gmm", "dual"])
@pytest.mark.parametrize("gpw", [1, 2])
def test_ragged_all_empty_call_is_zero(kind, gpw):
    """The no-op prefill lane: every count 0 gives exactly zeros."""
    x, ws = _operands(4, 64, 32, 48, gpw)
    out = np.asarray(_run(kind, x, ws, jnp.zeros((4,), jnp.int32), gpw))
    assert out.shape == (4, 64, 48) and (out == 0).all()


@pytest.mark.parametrize("gpw", [2, 4])
def test_dual_act_ragged_groups_per_weight(gpw):
    x, ws = _operands(8, 64, 24, 40, gpw)
    gs = jnp.asarray([0, 64, 5, 33, 0, 0, 17, 40], jnp.int32)
    np.testing.assert_allclose(
        np.asarray(_run("dual", x, ws, gs, gpw)),
        np.asarray(_oracle("dual", x, ws, gs, gpw)),
        rtol=1e-5, atol=1e-5,
    )


@pytest.mark.parametrize("kind", ["gmm", "dual"])
@pytest.mark.parametrize("row_blocks", [1, 2], ids=["resident", "row-blocked"])
def test_ragged_dead_row_nans_never_reach_output(kind, row_blocks, monkeypatch):
    """NaNs planted past each count stay out of the output, with the
    group's rows resident in one block and, under a budget too small for
    that, split over two row blocks (the second dead for short groups)."""
    g, c, d, f = 4, 64, 32, 48
    n_w = 1 if kind == "gmm" else 2
    monkeypatch.setattr(ragged, "RAGGED_ROW_TILE", 32)
    if row_blocks == 2:
        monkeypatch.setattr(
            ragged, "RAGGED_VMEM_BUDGET",
            ragged.ragged_vmem_bytes(c // 2, d, f, 32, 4, n_w),
        )
    assert ragged_blocks(c, d, f, 4, n_w)[1] == c // row_blocks
    x, ws = _operands(g, c, d, f)
    counts = jnp.asarray([10, 0, 33, 64], jnp.int32)
    rows = jnp.arange(c)[None, :, None]
    x = jnp.where(rows < counts[:, None, None], x, jnp.nan)
    out = np.asarray(_run(kind, x, ws, counts))
    assert np.isfinite(out).all()
    np.testing.assert_allclose(
        out, np.asarray(_oracle(kind, jnp.nan_to_num(x), ws, counts)),
        rtol=1e-5, atol=1e-5,
    )


def _fetches(seq):
    """Copies a Pallas pipeline issues for a block sequence: one for the
    first step, then one per change of block index."""
    return 1 + sum(a != b for a, b in zip(seq, seq[1:]))


@pytest.mark.parametrize(
    "counts,gpw,nr",
    [
        ([5, 0, 0, 9, 0], 1, 1),      # dead groups between and after
        ([0, 0, 3, 8], 1, 1),         # leading dead groups
        ([0, 0, 0, 0], 1, 1),         # the no-op lane
        ([16, 3, 0, 9], 1, 2),        # row blocks past the count
        ([4, 0, 0, 7, 0, 2], 2, 1),   # groups sharing a weight row
        ([0, 0, 0, 0, 0, 0], 3, 2),
    ],
)
def test_ragged_fetches_each_live_step_once(counts, gpw, nr):
    """Walk the grid through the kernels' own index maps: every live step
    reads its own blocks, each live step's weight panels are copied once,
    dead steps copy nothing, and an all-empty call copies one block."""
    nj, bc = 3, 8
    gs = jnp.asarray(counts, jnp.int32)
    src, col = (np.asarray(a) for a in ragged_pins(gs, bc * nr, bc, nj))
    x_map, w_map = ragged_block_maps(nr, gpw)
    xs, ws, live = [], [], 0
    for gi in range(len(counts)):
        for r in range(nr):
            on = r * bc < counts[gi]
            live += on
            for j in range(nj):
                xi = tuple(int(v) for v in x_map(gi, r, j, gs, src, col))
                wi = tuple(int(v) for v in w_map(gi, r, j, gs, src, col))
                if on:
                    assert xi == (gi, r, 0) and wi == (gi // gpw, 0, j)
                xs.append(xi)
                ws.append(wi)
    assert _fetches(ws) == max(live * nj, 1)
    assert _fetches(xs) == max(live, 1)


@pytest.mark.parametrize("c", [8, 16, 24, 32, 40, 64, 96, 128, 200, 256, 384, 512])
@pytest.mark.parametrize(
    "d,f", [(6144, 10752), (6144, 16384)], ids=["dbrx", "mixtral-8x22b"]
)
def test_ragged_block_rule_fits_v5e(d, f, c):
    """At published widths and every bucket height up to 512, both kernels'
    blocks meet Mosaic's (8, 128) rule, divide their axes, fit the VMEM
    budget, and keep a 20-slot call to a few hundred grid steps."""
    for k, n, n_w in ((d, f, 2), (f, d, 1)):
        bm, bc, bn = ragged_blocks(c, k, n, 2, n_w)
        assert c % bc == 0 and bc % bm == 0 and n % bn == 0
        assert bm % 16 == 0 or bm == c    # whole packed bf16 sublane tiles
        assert bn % 128 == 0 or bn == n
        assert (
            ragged.ragged_vmem_bytes(bc, k, bn, bm, 2, n_w)
            <= ragged.RAGGED_VMEM_BUDGET
            <= ragged.RAGGED_VMEM_LIMIT
        )
        assert 20 * (c // bc) * (n // bn) <= 1000


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
