"""Chunked EP dispatch (``ep_chunks=K``): the pipelined dispatch/combine
schedule must be a pure performance knob.

The contract under test everywhere: chunking slices the per-bucket
offsets/counts of ONE global ``dispatch_metadata`` call, so every bucket's
rows, keep mask, and FP combine order are unchanged — outputs are
*bit-identical* to the single-shot path for every K, on the mesh
(``ep_moe_shardmap``), no-mesh (``moe_esp``) and local-loopback paths,
with kernels on or off, under balanced and skewed routing, and with
capacity drops in play. Bad chunk counts fail loudly with named errors at
``ServeConfig`` construction and at every collectives entry point.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke
from repro.launch.mesh import make_mesh
from repro.models import moe as M
from repro.models import transformer as T
from repro.parallel.collectives import validate_ep_chunks
from repro.parallel.ctx import ParallelCtx
from repro.runtime.serve import Server, ServeConfig

RNG = jax.random.PRNGKey(0)


def _cfg(**kw):
    base = dataclasses.replace(
        smoke(get_config("dbrx-132b")), n_experts=4, experts_per_token=2
    )
    return dataclasses.replace(base, **kw)


def _skewed_params(cfg, hot=(0, 1), scale=8.0):
    params = M.moe_init(RNG, cfg)
    router = np.asarray(params["router"])
    s = np.ones(router.shape[-1], router.dtype)
    s[list(hot)] = scale
    params = dict(params)
    params["router"] = jnp.asarray(router * s)
    return params


# ---------------------------------------------------------------------------
# validation
# ---------------------------------------------------------------------------

def test_validate_ep_chunks_named_errors():
    assert validate_ep_chunks(1) == 1
    assert validate_ep_chunks(4, 8) == 4
    for bad in (0, -1, 2.0, True, "2"):
        with pytest.raises((ValueError, TypeError), match="ep_chunks"):
            validate_ep_chunks(bad)
    with pytest.raises(ValueError, match="does not divide"):
        validate_ep_chunks(3, 8, where="test")


def test_serve_config_validates_ep_chunks():
    ok = ServeConfig(max_seq=32, batch=2, slots_per_device=2, ep_chunks=2)
    assert ok.ep_chunks == 2
    # virtual_ep multiplies the group count: 3 slots x 4 virtual ranks = 12
    ok = ServeConfig(max_seq=32, batch=2, slots_per_device=3, virtual_ep=4,
                     ep_chunks=3)
    assert ok.ep_chunks == 3
    with pytest.raises(ValueError, match="ep_chunks"):
        ServeConfig(max_seq=32, batch=2, slots_per_device=3, ep_chunks=2)
    with pytest.raises(ValueError, match="ep_chunks"):
        ServeConfig(max_seq=32, batch=2, slots_per_device=2, ep_chunks=0)
    # ep_chunks=1 (the single-shot path) never needs divisibility
    assert ServeConfig(max_seq=32, batch=2, slots_per_device=3,
                       ep_chunks=1).ep_chunks == 1


def test_serve_config_ep_chunks_round_trips_via_asdict():
    # The crash-safe snapshot stores ServeConfig as dataclasses.asdict and
    # restores with ServeConfig(**d) — the new field must survive the trip
    # (and re-validate on the way back in).
    scfg = ServeConfig(max_seq=32, batch=2, slots_per_device=3, virtual_ep=4,
                       ep_chunks=3)
    back = ServeConfig(**dataclasses.asdict(scfg))
    assert back.ep_chunks == 3
    d = dataclasses.asdict(scfg)
    d["slots_per_device"], d["virtual_ep"] = 4, None   # 3 does not divide 4
    with pytest.raises(ValueError, match="ep_chunks"):
        ServeConfig(**d)


# ---------------------------------------------------------------------------
# bit parity: no-mesh paths
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["esp", "ep"])
@pytest.mark.parametrize("use_kernels", [False, True])
def test_no_mesh_chunked_parity(impl, use_kernels):
    """Single-process esp/ep: chunked output must be bit-identical to
    ep_chunks=1 under balanced routing, skewed routing, and a tight
    capacity that actually drops copies."""
    cfg = _cfg()
    for label, params, cf in (
        ("balanced", M.moe_init(RNG, cfg), 8.0),
        ("skewed", _skewed_params(cfg), 8.0),
        ("capacity_drop", _skewed_params(cfg), 1.0),
    ):
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model))
        base = None
        for K in (1, 2, 4):
            ctx = ParallelCtx(moe_impl=impl, capacity_factor=cf,
                              use_kernels=use_kernels, ep_chunks=K)
            out, _ = M.moe_apply(params, x, cfg, ctx)
            out = np.asarray(out)
            assert np.all(np.isfinite(out))
            if base is None:
                base = out
            else:
                np.testing.assert_array_equal(
                    out, base,
                    err_msg=f"{impl} uk={use_kernels} {label} K={K}")


def test_no_mesh_bad_chunk_count_fails_on_every_branch():
    # Validation runs at moe entry, not inside the fused branch: a bad
    # count must fail loudly even when kernels are off (padded branch).
    cfg = _cfg()   # 4 experts: 3 does not divide
    params = M.moe_init(RNG, cfg)
    x = jax.random.normal(RNG, (2, 4, cfg.d_model))
    for uk in (False, True):
        ctx = ParallelCtx(moe_impl="esp", capacity_factor=4.0,
                          use_kernels=uk, ep_chunks=3)
        with pytest.raises(ValueError, match="ep_chunks"):
            M.moe_apply(params, x, cfg, ctx)


# ---------------------------------------------------------------------------
# bit parity + grads: 1x1 mesh (shard_map path without multidevice cost)
# ---------------------------------------------------------------------------

def test_mesh_chunked_parity_and_grads():
    cfg = _cfg()
    params = _skewed_params(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    for shape in ((2, 8), (4, 1)):   # prefill and decode shapes
        x = jax.random.normal(jax.random.PRNGKey(2), (*shape, cfg.d_model))
        base = None
        for K in (1, 2, 4):
            ctx = ParallelCtx(mesh=mesh, moe_impl="ep", capacity_factor=1.0,
                              use_kernels=True, ep_chunks=K)
            out, _ = M.moe_apply(params, x, cfg, ctx)
            out = np.asarray(out)
            if base is None:
                base = out
            else:
                np.testing.assert_array_equal(out, base,
                                              err_msg=f"{shape} K={K}")

    # Gradients flow through the chunked custom_vjp identically.
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 8, cfg.d_model))

    def loss(p, K):
        ctx = ParallelCtx(mesh=mesh, moe_impl="ep", capacity_factor=2.0,
                          use_kernels=True, ep_chunks=K)
        out, _ = M.moe_apply(p, x, cfg, ctx)
        return jnp.sum(out * out)

    g1 = jax.grad(loss)(params, 1)
    g2 = jax.grad(loss)(params, 2)
    for key in ("w_gate", "w_up", "w_down", "router"):
        np.testing.assert_allclose(np.asarray(g1[key]), np.asarray(g2[key]),
                                   rtol=1e-6, atol=1e-6, err_msg=key)


# ---------------------------------------------------------------------------
# serving: one compiled program, bit-identical streams
# ---------------------------------------------------------------------------

def test_server_chunked_generation_parity_one_program():
    """A chunked server must generate bit-identical tokens to the
    single-shot server, from ONE compiled step program (the chunk count is
    static, baked into the jitted closures — no traced switch)."""
    cfg = _cfg()
    params = T.init_params(RNG, cfg)
    prompt = jnp.ones((2, 6), jnp.int32)

    def gen(ep_chunks):
        srv = Server(cfg, ParallelCtx(capacity_factor=8.0),
                     jax.tree.map(jnp.copy, params),
                     ServeConfig(max_seq=32, batch=2, slots_per_device=3,
                                 virtual_ep=4, ep_chunks=ep_chunks))
        out = np.asarray(srv.generate(prompt, 8))
        return srv, out

    srv1, base = gen(1)
    for K in (2, 3):
        srv, out = gen(K)
        np.testing.assert_array_equal(out, base, err_msg=f"ep_chunks={K}")
        assert srv.ctx.ep_chunks == K          # config landed on the ctx
        assert srv._decode._cache_size() == 1  # still one compiled program
    assert srv1._decode._cache_size() == 1
