"""Multi-device parity checks (8 forced host devices, run in subprocesses
so the main pytest process keeps its single real device)."""

import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow  # subprocess + forced-device tests: full tier only

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, devices: int = 8, timeout: int = 420):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True,
        text=True,
        env=env,
        timeout=timeout,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    return r.stdout


def test_ep_esp_decode_parity_8dev():
    out = _run(
        """
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from repro.configs import get_config, smoke
        from repro.models.moe import moe_dense, moe_ep, moe_esp, moe_init
        from repro.parallel.ctx import ParallelCtx
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        ctx = ParallelCtx(mesh=mesh, capacity_factor=8.0)
        cfg = dataclasses.replace(smoke(get_config("dbrx-132b")),
                                  n_experts=4, experts_per_token=2)
        rng = jax.random.PRNGKey(0)
        p = moe_init(rng, cfg)
        # train-shape parity (seq split over EP axis)
        x = jax.random.normal(rng, (4, 8, cfg.d_model)) * 0.5
        ref, _ = moe_dense(p, x, cfg, ctx)
        with mesh:
            ep, _ = jax.jit(lambda p, x: moe_ep(p, x, cfg, ctx))(p, x)
            esp, _ = jax.jit(lambda p, x: moe_esp(p, x, cfg, ctx))(p, x)
        assert float(jnp.max(jnp.abs(ep - ref))) < 1e-5, "ep train parity"
        assert float(jnp.max(jnp.abs(esp - ref))) < 1e-5, "esp train parity"
        # decode-shape parity (owned-token dispatch + psum)
        xd = jax.random.normal(rng, (8, 1, cfg.d_model)) * 0.5
        refd, _ = moe_dense(p, xd, cfg, ctx)
        with mesh:
            epd, _ = jax.jit(lambda p, x: moe_ep(p, x, cfg, ctx))(p, xd)
        assert float(jnp.max(jnp.abs(epd - refd))) < 1e-5, "ep decode parity"
        print("PARITY_OK")
        """
    )
    assert "PARITY_OK" in out


def test_ep_fused_dispatch_parity_8dev():
    """Fused rank-compacted dispatch + compact combine (kernels on,
    interpret mode) across a real 4-way all_to_all: both legs ship the
    compact exchange buffer and the combine gathers through dest/posr/keep
    metadata. Prefill + decode (ownership sentinel + psum) + a
    non-divisible expert count (tiled shadow slots), all vs the dense
    oracle."""
    out = _run(
        """
        import jax, jax.numpy as jnp, dataclasses
        from repro.configs import get_config, smoke
        from repro.models.moe import moe_dense, moe_ep, moe_init
        from repro.parallel.ctx import ParallelCtx
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        ctx = ParallelCtx(mesh=mesh, capacity_factor=8.0, use_kernels=True)
        ref_ctx = ParallelCtx(capacity_factor=8.0, use_kernels=False)
        rng = jax.random.PRNGKey(0)
        for n_exp in (4, 6):  # 6 % ep(4) != 0 -> tiled shadow slots
            cfg = dataclasses.replace(smoke(get_config("dbrx-132b")),
                                      n_experts=n_exp, experts_per_token=2)
            p = moe_init(rng, cfg)
            x = jax.random.normal(rng, (4, 8, cfg.d_model)) * 0.5
            ref, _ = moe_dense(p, x, cfg, ref_ctx)
            with mesh:
                ep, _ = jax.jit(lambda p, x: moe_ep(p, x, cfg, ctx))(p, x)
            err = float(jnp.max(jnp.abs(ep - ref)))
            assert err < 1e-5, ("prefill", n_exp, err)
            xd = jax.random.normal(rng, (8, 1, cfg.d_model)) * 0.5
            refd, _ = moe_dense(p, xd, cfg, ref_ctx)
            with mesh:
                epd, _ = jax.jit(lambda p, x: moe_ep(p, x, cfg, ctx))(p, xd)
            err = float(jnp.max(jnp.abs(epd - refd)))
            assert err < 1e-5, ("decode", n_exp, err)
        print("FUSED_OK")
        """
    )
    assert "FUSED_OK" in out


def test_ep_fused_ffn_single_kernel_8dev():
    """The fully-fused single-kernel FFN (gmm_fused_ffn) must actually
    engage inside ep_moe_shardmap's shard_map body over a real 4-way
    all_to_all — and match both the two-kernel gather+scatter pair (VMEM
    gate forced shut) and the dense oracle, prefill and decode."""
    out = _run(
        """
        import jax, jax.numpy as jnp, dataclasses
        from repro.configs import get_config, smoke
        from repro.kernels import registry
        from repro.models.moe import moe_dense, moe_ep, moe_init
        from repro.parallel.ctx import ParallelCtx
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        ctx = ParallelCtx(mesh=mesh, capacity_factor=8.0, use_kernels=True)
        ref_ctx = ParallelCtx(capacity_factor=8.0, use_kernels=False)
        cfg = dataclasses.replace(smoke(get_config("dbrx-132b")),
                                  n_experts=4, experts_per_token=2)
        rng = jax.random.PRNGKey(0)
        p = moe_init(rng, cfg)
        # Record whether the fused gate was consulted AND said yes.
        orig = registry.can_gmm_fused
        verdicts = []
        def spy(*a, **kw):
            v = orig(*a, **kw)
            verdicts.append(v)
            return v
        registry.can_gmm_fused = spy
        for shape in ((4, 8), (8, 1)):
            x = jax.random.normal(rng, (*shape, cfg.d_model)) * 0.5
            ref, _ = moe_dense(p, x, cfg, ref_ctx)
            verdicts.clear()
            with mesh:
                fused, _ = jax.jit(lambda p, x: moe_ep(p, x, cfg, ctx))(p, x)
            assert verdicts and all(verdicts), ("fused gate never engaged", shape)
            err = float(jnp.max(jnp.abs(fused - ref)))
            assert err < 1e-5, ("fused vs dense", shape, err)
            # Force the VMEM gate shut: the registry must fall back to the
            # two-kernel pair with identical results over the same exchange.
            registry.can_gmm_fused = lambda *a, **kw: False
            with mesh:
                pair, _ = jax.jit(lambda p, x: moe_ep(p, x, cfg, ctx))(p, x)
            registry.can_gmm_fused = spy
            err = float(jnp.max(jnp.abs(fused - pair)))
            assert err < 1e-6, ("fused vs pair", shape, err)
        print("FUSED_FFN_OK")
        """
    )
    assert "FUSED_FFN_OK" in out


def test_ep_compact_combine_skewed_and_validation_8dev():
    """Combine-leg coverage the dense-oracle cells can't give: (1) fused
    vs padded ep_moe_shardmap parity under *heavily skewed* hand-crafted
    routing (capacity drops on both paths must agree bit-for-bit over a
    real 4-way all_to_all); (2) the prefill token-split validation raises
    a clear error instead of floor-truncating bucket_capacity."""
    out = _run(
        """
        import jax, jax.numpy as jnp
        from repro.parallel.collectives import ep_moe_shardmap, uniform_placement
        from repro.parallel.ctx import ParallelCtx
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        ep = 4
        e, d, f, k = 8, 8, 16, 2
        b, s = 4, 8
        rng = jax.random.PRNGKey(0)
        ks = jax.random.split(rng, 6)
        x = jax.random.normal(ks[0], (b, s, d)) * 0.5
        # ~75% of copies hammer expert 0 (one device's slots); rest spread.
        hot = jax.random.bernoulli(ks[1], 0.75, (b, s, k))
        ids = jnp.where(hot, 0, jax.random.randint(ks[2], (b, s, k), 0, e))
        w = jax.random.uniform(ks[3], (b, s, k))
        w = w / w.sum(-1, keepdims=True)
        slot_weights = {
            "w_gate": jax.random.normal(ks[4], (e, d, f)) * 0.1,
            "w_up": jax.random.normal(ks[5], (e, d, f)) * 0.1,
            "w_down": jax.random.normal(ks[0], (e, f, d)) * 0.1,
        }
        slot_of, n_rep = uniform_placement(e, e)
        outs = {}
        for name, uk in (("padded", False), ("fused", True)):
            ctx = ParallelCtx(mesh=mesh, use_kernels=uk)
            with mesh:
                outs[name] = jax.jit(lambda x_, i_, w_: ep_moe_shardmap(
                    x_, i_, w_, slot_weights, slot_of, n_rep, ctx,
                    capacity_factor=1.0,  # tight capacity -> real drops
                    slots_per_device=e // ep))(x, ids, w)
        err = float(jnp.max(jnp.abs(outs["fused"] - outs["padded"])))
        assert err < 1e-5, ("skewed fused-vs-padded", err)
        # decode-shape ownership psum under the same skew
        xd = jax.random.normal(ks[0], (8, 1, d)) * 0.5
        idd = jnp.where(jax.random.bernoulli(ks[1], 0.75, (8, 1, k)), 0,
                        jax.random.randint(ks[2], (8, 1, k), 0, e))
        wd_ = jax.random.uniform(ks[3], (8, 1, k))
        for name, uk in (("padded", False), ("fused", True)):
            ctx = ParallelCtx(mesh=mesh, use_kernels=uk)
            with mesh:
                outs[name] = jax.jit(lambda x_, i_, w_: ep_moe_shardmap(
                    x_, i_, w_, slot_weights, slot_of, n_rep, ctx,
                    capacity_factor=1.0, slots_per_device=e // ep,
                    decode=True))(xd, idd, wd_)
        err = float(jnp.max(jnp.abs(outs["fused"] - outs["padded"])))
        assert err < 1e-5, ("skewed decode fused-vs-padded", err)
        # token-split validation: seq not divisible by ep must raise the
        # named error, not die inside shard_map / silently floor-truncate
        ctx = ParallelCtx(mesh=mesh, use_kernels=True)
        xbad = jax.random.normal(rng, (4, 7, d))
        try:
            with mesh:
                ep_moe_shardmap(xbad, ids[:, :7], w[:, :7], slot_weights,
                                slot_of, n_rep, ctx, 1.0, e // ep)
        except ValueError as exc:
            assert "seq=7 does not divide ep=4" in str(exc), exc
        else:
            raise AssertionError("non-divisible seq did not raise")
        print("SKEWED_OK")
        """
    )
    assert "SKEWED_OK" in out


def test_ep_chunked_dispatch_parity_8dev():
    """The pipelined chunked dispatch over a REAL 4-way all_to_all:
    ep_chunks=2 splits each rank's exchange into per-chunk buffers and
    interleaves the legs with the per-chunk fused FFN — and must stay
    bit-identical to the single-shot path under heavy skew at tight
    capacity (real drops), on prefill and decode shapes, with kernels on
    and off (the fallback path ignores the knob but must still accept
    it)."""
    out = _run(
        """
        import numpy as np
        import jax, jax.numpy as jnp
        from repro.parallel.collectives import ep_moe_shardmap, uniform_placement
        from repro.parallel.ctx import ParallelCtx
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        ep = 4
        e, d, f, k = 8, 8, 16, 2    # spd = 2 -> ep_chunks in {1, 2}
        rng = jax.random.PRNGKey(0)
        ks = jax.random.split(rng, 6)
        slot_weights = {
            "w_gate": jax.random.normal(ks[4], (e, d, f)) * 0.1,
            "w_up": jax.random.normal(ks[5], (e, d, f)) * 0.1,
            "w_down": jax.random.normal(ks[0], (e, f, d)) * 0.1,
        }
        slot_of, n_rep = uniform_placement(e, e)
        for (b, s), decode in (((4, 8), False), ((8, 1), True)):
            x = jax.random.normal(ks[0], (b, s, d)) * 0.5
            hot = jax.random.bernoulli(ks[1], 0.75, (b, s, k))
            ids = jnp.where(hot, 0, jax.random.randint(ks[2], (b, s, k), 0, e))
            w = jax.random.uniform(ks[3], (b, s, k))
            w = w / w.sum(-1, keepdims=True)
            for uk in (True, False):
                base = None
                for K in (1, 2):
                    ctx = ParallelCtx(mesh=mesh, use_kernels=uk, ep_chunks=K)
                    with mesh:
                        out = jax.jit(lambda x_, i_, w_: ep_moe_shardmap(
                            x_, i_, w_, slot_weights, slot_of, n_rep, ctx,
                            capacity_factor=1.0, slots_per_device=e // ep,
                            decode=decode))(x, ids, w)
                    out = np.asarray(out)
                    assert np.all(np.isfinite(out))
                    if base is None:
                        base = out
                    else:
                        np.testing.assert_array_equal(
                            out, base,
                            err_msg=f"decode={decode} uk={uk} K={K}")
        # non-dividing chunk count: named error before any collective runs
        ctx = ParallelCtx(mesh=mesh, use_kernels=True, ep_chunks=3)
        try:
            with mesh:
                x = jax.random.normal(rng, (4, 8, d))
                ids = jax.random.randint(rng, (4, 8, k), 0, e)
                w = jnp.ones((4, 8, k)) / k
                ep_moe_shardmap(x, ids, w, slot_weights, slot_of, n_rep,
                                ctx, 1.0, e // ep)
        except ValueError as exc:
            assert "ep_chunks" in str(exc), exc
        else:
            raise AssertionError("non-dividing ep_chunks did not raise")
        print("CHUNKED_OK")
        """
    )
    assert "CHUNKED_OK" in out


def test_gqa_kv_replicated_flash_attention_8dev():
    """Mixtral-style GQA on a wide TP axis (n_kv_heads=2 < tp=4,
    tp % nkv == 0): flash attention must take the kv-head-replicated
    shard_map variant instead of silently falling back to einsum, and
    match the einsum fallback. nkv=3 (tp % nkv != 0) must stay on the
    fallback."""
    out = _run(
        """
        import jax, jax.numpy as jnp, dataclasses
        from repro.configs import get_config, smoke
        from repro.models import attention as A
        from repro.parallel.ctx import ParallelCtx
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = dataclasses.replace(smoke(get_config("llama3.2-1b")),
                                  n_heads=8, n_kv_heads=2)
        p = A.attn_init(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model)) * 0.3
        ctx_on = ParallelCtx(mesh=mesh, use_kernels=True)
        ctx_off = ParallelCtx(mesh=mesh, use_kernels=False)
        q = jnp.zeros((4, 16, 8, cfg.head_dim_))
        kk = jnp.zeros((4, 16, 2, cfg.head_dim_))
        assert A._flash_attend_eligible(q, kk, ctx_on), "kv-rep not eligible"
        with mesh:
            on = jax.jit(lambda p, x: A.attention(p, x, cfg, ctx_on))(p, x)
            off = jax.jit(lambda p, x: A.attention(p, x, cfg, ctx_off))(p, x)
        err = float(jnp.max(jnp.abs(on - off)))
        assert err < 2e-5, ("kv-rep parity", err)
        # tp not a multiple of nkv: ineligible, einsum fallback unchanged
        q3 = jnp.zeros((4, 16, 12, cfg.head_dim_))
        k3 = jnp.zeros((4, 16, 3, cfg.head_dim_))
        assert not A._flash_attend_eligible(q3, k3, ctx_on)
        print("KVREP_OK")
        """
    )
    assert "KVREP_OK" in out


def test_gqa_kv_replicated_flash_decode_8dev():
    """Dense-cache flash decode under wide TP with non-dividing kv heads
    (nkv=2 < tp=4, tp % nkv == 0): must take the kv-head-replicated
    shard_map variant (prefill already had one) and match the einsum
    fallback across a multi-step decode. nkv=3 stays ineligible."""
    out = _run(
        """
        import jax, jax.numpy as jnp, dataclasses
        from repro.configs import get_config, smoke
        from repro.models import attention as A
        from repro.parallel.ctx import ParallelCtx
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = dataclasses.replace(smoke(get_config("llama3.2-1b")),
                                  n_heads=8, n_kv_heads=2)
        ctx_on = ParallelCtx(mesh=mesh, use_kernels=True, seq_parallel_kv=False)
        ctx_off = ParallelCtx(mesh=mesh, use_kernels=False, seq_parallel_kv=False)
        p = A.attn_init(jax.random.PRNGKey(0), cfg)
        b, max_seq = 4, 32
        q = jnp.zeros((b, 1, 8, cfg.head_dim_))
        kc = jnp.zeros((b, max_seq, 2, cfg.head_dim_))
        assert A._flash_decode_eligible(q, kc, ctx_on), "kv-rep decode not eligible"
        cache_on = A.cache_init(cfg, b, max_seq)
        cache_off = A.cache_init(cfg, b, max_seq)
        x0 = jax.random.normal(jax.random.PRNGKey(1), (b, 1, cfg.d_model)) * 0.3
        pos = jnp.asarray(0, jnp.int32)
        with mesh:
            for step in range(6):
                x = x0 * (step % 3 + 1) / 3
                o_on, cache_on = jax.jit(lambda p,x,c,t: A.decode_attention(
                    p, x, c, t, cfg, ctx_on))(p, x, cache_on, pos)
                o_off, cache_off = jax.jit(lambda p,x,c,t: A.decode_attention(
                    p, x, c, t, cfg, ctx_off))(p, x, cache_off, pos)
                err = float(jnp.max(jnp.abs(o_on - o_off)))
                assert err < 2e-5, ("kv-rep decode parity", step, err)
                pos = pos + 1
        # tp not a multiple of nkv: ineligible, fallback unchanged
        q3 = jnp.zeros((b, 1, 12, cfg.head_dim_))
        k3 = jnp.zeros((b, max_seq, 3, cfg.head_dim_))
        assert not A._flash_decode_eligible(q3, k3, ctx_on)
        print("KVREP_DECODE_OK")
        """
    )
    assert "KVREP_DECODE_OK" in out


def test_ep_gradient_parity_8dev():
    """EP dispatch must be differentiable and match dense gradients — on
    both the padded fallback (kernels off) and the fused compact path
    (kernels on: gather prologue + scatter epilogue custom_vjp, return
    all_to_all adjoint, combine_from_rows gather vjp across real rank
    segments — a 1x1 mesh can't exercise any of that)."""
    out = _run(
        """
        import jax, jax.numpy as jnp, dataclasses
        from repro.configs import get_config, smoke
        from repro.models.moe import moe_dense, moe_ep, moe_init
        from repro.parallel.ctx import ParallelCtx
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = dataclasses.replace(smoke(get_config("dbrx-132b")),
                                  n_experts=4, experts_per_token=2)
        rng = jax.random.PRNGKey(0)
        p = moe_init(rng, cfg)
        x = jax.random.normal(rng, (4, 8, cfg.d_model)) * 0.5
        gd = jax.grad(lambda p: moe_dense(
            p, x, cfg, ParallelCtx(capacity_factor=8.0))[0].sum())(p)
        for uk in (False, True):
            ctx = ParallelCtx(mesh=mesh, capacity_factor=8.0, use_kernels=uk)
            loss_e = lambda p: moe_ep(p, x, cfg, ctx)[0].sum()
            with mesh:
                ge = jax.jit(jax.grad(loss_e))(p)
            for k in ("w_gate", "w_up", "w_down", "router"):
                err = float(jnp.max(jnp.abs(gd[k] - ge[k])))
                assert err < 1e-4, (uk, k, err)
        print("GRAD_OK")
        """
    )
    assert "GRAD_OK" in out


def test_seq_parallel_decode_and_compressed_sync_8dev():
    out = _run(
        """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.parallel.collectives import (
            seq_parallel_decode_attend, seq_parallel_decode_kernel_eligible)
        from repro.models.attention import gqa_attend
        from repro.parallel.ctx import ParallelCtx
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        ctx = ParallelCtx(mesh=mesh)
        q = jax.random.normal(jax.random.PRNGKey(1), (2, 1, 8, 16))
        k = jax.random.normal(jax.random.PRNGKey(2), (2, 16, 4, 16))
        v = jax.random.normal(jax.random.PRNGKey(3), (2, 16, 4, 16))
        mask = jnp.arange(16) <= 9
        ref = gqa_attend(q, k, v, mask[None, None, None, None, :])
        with mesh:
            out = jax.jit(lambda q,k,v,m: seq_parallel_decode_attend(q,k,v,m,ctx))(q,k,v,mask)
        assert float(jnp.max(jnp.abs(out - ref))) < 1e-5
        # kernelized merge: flash-decode partials + psum LSE merge must take
        # the kernel path (eligibility) and match the einsum reference.
        ctx_k = ParallelCtx(mesh=mesh, use_kernels=True)
        assert seq_parallel_decode_kernel_eligible(16, 8, 4, 16, ctx_k)
        assert not seq_parallel_decode_kernel_eligible(16, 8, 4, 16, ctx)
        with mesh:
            out_k = jax.jit(lambda q,k,v,m: seq_parallel_decode_attend(q,k,v,m,ctx_k))(q,k,v,mask)
        assert float(jnp.max(jnp.abs(out_k - ref))) < 1e-5, "kernelized merge parity"
        # compressed cross-pod sync: mean preserved within int8 error
        mesh2 = make_mesh((2, 2, 2), ("pod", "data", "model"))
        from repro.parallel.grad_compress import compressed_pod_mean
        tree = {"w": jax.random.normal(jax.random.PRNGKey(5), (64, 33))}
        with mesh2:
            out2 = jax.jit(lambda t: compressed_pod_mean(t, mesh2))(tree)
        rel = float(jnp.max(jnp.abs(out2["w"] - tree["w"])) / jnp.max(jnp.abs(tree["w"])))
        assert rel < 0.03, rel
        print("SP_OK")
        """
    )
    assert "SP_OK" in out


def test_paged_decode_under_mesh_8dev():
    """Paged decode under a mesh (pool kv-heads on the model axis, pool
    replicated over batch): parity with the no-mesh dense cache, kernel
    path on (interpret). Also: seq_parallel_kv decode rides the kernelized
    merge inside full decode_attention."""
    out = _run(
        """
        import jax, jax.numpy as jnp, dataclasses
        from repro.configs import get_config, smoke
        from repro.models import attention as A
        from repro.parallel.ctx import ParallelCtx
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = dataclasses.replace(smoke(get_config("llama3.2-1b")),
                                  n_heads=8, n_kv_heads=4)
        ctx0 = ParallelCtx()
        ctx_p = ParallelCtx(mesh=mesh, use_kernels=True, seq_parallel_kv=False)
        ctx_sp = ParallelCtx(mesh=mesh, use_kernels=True)  # seq_parallel_kv
        p = A.attn_init(jax.random.PRNGKey(0), cfg)
        b, max_seq = 4, 32
        dense = A.cache_init(cfg, b, max_seq)
        dense_sp = A.cache_init(cfg, b, max_seq)
        paged = A.paged_cache_init(cfg, b, max_seq, page_size=8)
        x0 = jax.random.normal(jax.random.PRNGKey(1), (b, 1, cfg.d_model)) * 0.3
        pos = jnp.asarray(0, jnp.int32)
        with mesh:
            for step in range(10):
                x = x0 * (step % 4 + 1) / 4
                o_ref, dense = A.decode_attention(p, x, dense, pos, cfg, ctx0)
                o_p, paged = jax.jit(lambda p,x,c,t: A.decode_attention(
                    p, x, c, t, cfg, ctx_p))(p, x, paged, pos)
                o_sp, dense_sp = jax.jit(lambda p,x,c,t: A.decode_attention(
                    p, x, c, t, cfg, ctx_sp))(p, x, dense_sp, pos)
                err_p = float(jnp.max(jnp.abs(o_ref - o_p)))
                err_sp = float(jnp.max(jnp.abs(o_ref - o_sp)))
                assert err_p < 2e-5, ("paged", step, err_p)
                assert err_sp < 2e-5, ("seq_parallel", step, err_sp)
                pos = pos + 1
        print("PAGED_MESH_OK")
        """
    )
    assert "PAGED_MESH_OK" in out


def test_server_migration_preserves_outputs_8dev():
    """Expert migration is semantics-preserving: generation with shadow
    replicas equals generation without any balancing."""
    out = _run(
        """
        import jax, jax.numpy as jnp, dataclasses, numpy as np
        from repro.configs import get_config, smoke
        from repro.models import transformer as T
        from repro.runtime.serve import Server, ServeConfig
        from repro.parallel.ctx import ParallelCtx
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        ctx = ParallelCtx(mesh=mesh, capacity_factor=8.0)
        cfg = dataclasses.replace(smoke(get_config("dbrx-132b")),
                                  n_experts=8, experts_per_token=2)
        params = T.init_params(jax.random.PRNGKey(1), cfg)
        prompt = jnp.ones((4, 8), jnp.int32)
        with mesh:
            s_off = Server(cfg, ctx, jax.tree.map(jnp.copy, params),
                           ServeConfig(max_seq=64, batch=4, slots_per_device=3,
                                       alpha=1e9))  # never triggers
            out_off = s_off.generate(prompt, 10)
            s_on = Server(cfg, ctx, jax.tree.map(jnp.copy, params),
                          ServeConfig(max_seq=64, batch=4, slots_per_device=3,
                                      alpha=0.1))   # triggers eagerly
            out_on = s_on.generate(prompt, 10)
        assert s_on.migrations > 0, "balancer should have migrated"
        assert np.array_equal(np.asarray(out_off), np.asarray(out_on)), \
            "migration changed outputs"
        print("MIG_OK", s_on.migrations)
        """
    )
    assert "MIG_OK" in out


def test_dryrun_machinery_small_mesh():
    """lower_cell + collective parser on a small forced mesh (2x4)."""
    out = _run(
        """
        import jax, dataclasses
        import repro.launch.dryrun as D
        from repro.configs import get_config
        from repro.configs.base import ShapeConfig
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = dataclasses.replace(get_config("llama3.2-1b"), n_layers=2)
        shape = ShapeConfig("t", 256, 8, "train")
        with mesh:
            lowered, compiled, tl, tc = D.lower_cell(cfg, shape, mesh, False)
            coll = D.collective_bytes(compiled.as_text())
            ma = compiled.memory_analysis()
        assert coll["total"] > 0, coll
        assert ma.temp_size_in_bytes > 0
        print("DRYRUN_OK", coll["total"])
        """
    )
    assert "DRYRUN_OK" in out


def test_er_mesh_device_permutation():
    out = _run(
        """
        import jax, numpy as np
        from repro.launch.mesh import make_er_mesh
        from repro.core.er_mapping import er_mapping
        from repro.core.topology import MeshTopology
        mesh = make_er_mesh()
        assert mesh.shape == {"data": 16, "model": 16}
        ids = np.array([[d.id for d in row] for row in mesh.devices])
        m = er_mapping(MeshTopology(16, 16), 16, 16)
        assert np.array_equal(ids, m.device_order())
        # logical row g (= TP group) lands one member per physical tile
        print("ERMESH_OK")
        """,
        devices=512,
    )
    assert "ERMESH_OK" in out
