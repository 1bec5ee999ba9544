#!/usr/bin/env python3
"""Chip smoke run: serve a DBRX-width MoE through the normal serving path.

Drives ``RequestScheduler`` -> ``Server`` decode step -> Pallas kernels once,
at the published widths of ``dbrx-132b`` (d=6144, 48 q / 8 kv heads of 128,
16 experts top-4 with hidden 10752, vocab 100352) in bf16 with random
weights made from ``--seed``. Only the depth is cut, to one layer; every
layer of the model is MoE, so one layer is one whole period.

One chip (the default): the expert slots are spread over 4 virtual EP
devices x 5 slots (16 native experts + 4 spare), so ``ep_moe_local``, the
NI-Balancer and the stepped ``MigrationDriver`` all run. The KV cache is
paged (128-token pages, 1024 context) and admission rides the decode
step's 256-token prefill lane. Eight seeded requests (prompts of 128-512
tokens, 32 new tokens each) must reach FINISHED.

``--chips 4``: the same model on a (1, 4) mesh, 5 slots per chip, through
``ep_moe_shardmap``'s fused branch over a real all_to_all; nothing else.

Checks, any of which fails the run (non-zero exit, no result line):

* JAX sees a TPU (and at least ``--chips`` of them);
* every request FINISHED;
* exactly one compiled step program;
* the step contains the expected Pallas kernels (``tpu_custom_call``s);
* each request's first-token logits agree with a plain reference — the
  same parameters through ``ParallelCtx(use_kernels=False,
  moe_impl="dense")`` with a dense cache, on device 0 — within
  ``max|served - ref| <= LOGIT_TOL * max|ref|``.

The last line of standard output is the JSON result, e.g.
``{"ok": true, "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}``.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four-chip EP path only
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "dbrx-132b"
N_LAYERS = 1
N_REQUESTS = 8
PROMPT_LENS = (128, 512)
NEW_TOKENS = 32
SLOTS_PER_DEVICE = 5
VIRTUAL_EP = 4
# bf16 agreement bound on first-token logits, relative to the reference's
# largest logit: 0.02 is about five bf16 ulps (2^-8 each) at that scale —
# room for the served path's different reduction shapes (bucketed expert
# FFN, paged attention, chunked prefill) without admitting a wrong layer.
LOGIT_TOL = 0.02

# Pallas kernel function name (``kernel_name`` in the lowered step) -> kernel.
KERNELS = {
    "_paged_kernel": "flash_decode_paged",
    "_decode_kernel": "flash_decode",
    "_flash_kernel": "flash_attention",
    "_ragged_dual_kernel": "gmm_dual_act_ragged",
    "_ragged_kernel": "gmm_ragged",
    "_gather_dual_kernel": "gmm_dual_act_gather",
    "_gather_kernel": "gmm_gather",
    "_scatter_kernel": "gmm_scatter",
    "_fused_ffn_kernel": "gmm_fused_ffn",
}
EXPECTED = {
    1: {"flash_decode_paged", "gmm_dual_act_ragged", "gmm_ragged"},
    4: {"flash_decode_paged", "gmm_fused_ffn"},
}


class SmokeFailure(RuntimeError):
    """A phase of the smoke run failed its check."""


def step_kernels(server, sched) -> list[str]:
    """Pallas kernels in the server's decode step, read off its lowering."""
    import jax.numpy as jnp

    b = server.scfg.batch
    lowered = server._decode.lower(
        server.params,
        jnp.asarray(sched.next_tok),
        sched.cache,
        placement=server.table.device_view() if server.use_balancer else None,
        slot_mask=jnp.ones((b,), bool),
        chunk=server.noop_chunk() if server.scfg.prefill_chunk else None,
    )
    names = set(re.findall(r'kernel_name = "([^"]+)"', lowered.as_text()))
    return sorted(KERNELS.get(n, n) for n in names)


def hbm(devices) -> str:
    """Device memory in use and its peak so far (max over ``devices``)."""
    stats = [d.memory_stats() or {} for d in devices]
    if not any(stats):
        return "HBM not reported"
    use = max(s.get("bytes_in_use", 0) for s in stats) / 2**30
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats) / 2**30
    return f"HBM {use:.2f} GiB in use, peak {peak:.2f} GiB"


def reference_logits(cfg, params, prompts):
    """First-token logits of each prompt through the plain path: dense MoE
    oracle, einsum attention, dense cache, no kernels."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import transformer as T
    from repro.parallel.ctx import ParallelCtx

    ctx = ParallelCtx(use_kernels=False, moe_impl="dense")
    prefill = jax.jit(
        functools.partial(T.prefill, cfg=cfg, ctx=ctx, dtype=jnp.bfloat16)
    )
    width = max(len(p) for p in prompts)   # one compiled program
    out = []
    for p in prompts:
        buf = np.zeros((1, width), np.int32)
        buf[0, : len(p)] = p
        logits, _ = prefill(
            params, jnp.asarray(buf), lengths=jnp.asarray([len(p)], jnp.int32)
        )
        out.append(np.asarray(logits[0, -1], np.float32))
    return out


def run(
    cfg,
    *,
    seed: int = 0,
    n_requests: int = N_REQUESTS,
    prompt_lens: tuple[int, int] = PROMPT_LENS,
    new_tokens: int = NEW_TOKENS,
    serve_kw: dict | None = None,
    mesh=None,
    use_kernels="auto",
    log=print,
) -> dict:
    """Serve ``n_requests`` seeded requests through the scheduler and
    compare their first-token logits with the plain reference. Returns a
    report; raises nothing on a failed check (see :func:`check`)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import transformer as T
    from repro.parallel.ctx import ParallelCtx
    from repro.runtime.scheduler import RequestScheduler
    from repro.runtime.serve import Server, ServeConfig

    devices = [jax.devices()[0]] if mesh is None else list(mesh.devices.flat)
    t0 = time.perf_counter()
    params = jax.jit(
        functools.partial(T.init_params, cfg=cfg, dtype=jnp.bfloat16)
    )(jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    lens = rng.integers(prompt_lens[0], prompt_lens[1] + 1, size=n_requests)
    prompts = [
        rng.integers(0, cfg.vocab_size, size=int(n)).astype(np.int32)
        for n in lens
    ]
    n_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    log(f"params: {n_bytes / 1e9:.2f} GB bf16 ({time.perf_counter() - t0:.1f} s; "
        f"{hbm(devices)}); prompt lengths {lens.tolist()}")

    t1 = time.perf_counter()
    ref = reference_logits(cfg, params, prompts)
    log(f"reference: {len(ref)} first-token logit rows "
        f"({time.perf_counter() - t1:.1f} s; {hbm(devices)})")

    scfg = ServeConfig(**(serve_kw or {}))
    n_dev = 1 if mesh is None else mesh.shape["model"]
    n_slots = scfg.slots_per_device * (scfg.virtual_ep or n_dev)
    # A copy is dropped only past a bucket's capacity; at this factor the
    # capacity covers every token of a dispatch, so no copy ever is (the
    # scheduler's determinism contract) and the dense oracle is exact.
    cap_factor = n_slots / cfg.experts_per_token
    if mesh is None:
        ctx = ParallelCtx(capacity_factor=cap_factor, use_kernels=use_kernels)
    else:
        from repro.parallel.sharding import params_specs, to_shardings

        ctx = ParallelCtx(
            mesh=mesh, capacity_factor=cap_factor, use_kernels=use_kernels
        )
        params = jax.device_put(
            params, to_shardings(mesh, params_specs(cfg, params, ctx))
        )

    t2 = time.perf_counter()
    server = Server(cfg, ctx, params, scfg)
    del params   # the server's dict is the only holder of the weights now
    log(f"server: {n_slots} expert slots built ({hbm(devices)})")
    sched = RequestScheduler(server)
    reqs = [sched.submit(p, max_new_tokens=new_tokens) for p in prompts]
    first: dict[int, np.ndarray] = {}
    limit = n_requests * (-(-prompt_lens[1] // (scfg.prefill_chunk or 1)) + new_tokens) + 16
    while not all(r.done for r in reqs) and sched.step_no < limit:
        sched.step()
        for r in reqs:
            if r.first_token_step == sched.step_no - 1 and r.rid not in first:
                # Chunked admission: the final chunk's logits emit token one.
                first[r.rid] = np.asarray(
                    server.last_chunk_logits[0, -1], np.float32
                )
    jax.block_until_ready(sched.cache)
    serve_s = time.perf_counter() - t2
    log(f"served: {hbm(devices)}")

    rel = []
    agree = 0
    for r, want in zip(reqs, ref):
        got = first.get(r.rid)
        if got is None:
            rel.append(float("inf"))
            continue
        rel.append(float(np.max(np.abs(got - want)) / np.max(np.abs(want))))
        agree += int(np.argmax(got) == np.argmax(want))
    return {
        "states": [r.state for r in reqs],
        "ticks": sched.step_no,
        "serve_s": serve_s,
        "step_programs": server._decode._cache_size(),
        "kernels": step_kernels(server, sched),
        "migrations": server.migrations,
        "rel_err": rel,
        "argmax_agree": agree,
        "n_slots": n_slots,
        "capacity_factor": cap_factor,
    }


def check(report: dict, expected_kernels: set[str] | None) -> None:
    """Raise :class:`SmokeFailure` naming every failed check."""
    bad = []
    if any(s != "FINISHED" for s in report["states"]):
        bad.append(f"requests not FINISHED: {report['states']}")
    if report["step_programs"] != 1:
        bad.append(f"{report['step_programs']} compiled step programs, want 1")
    if expected_kernels is not None:
        missing = expected_kernels - set(report["kernels"])
        if missing:
            bad.append(f"step lacks kernels {sorted(missing)}")
    worst = max(report["rel_err"])
    if not worst <= LOGIT_TOL:
        bad.append(f"first-token logits off the reference: {worst:.4g} > {LOGIT_TOL}")
    if bad:
        raise SmokeFailure("; ".join(bad))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    devs = jax.devices()
    dev = devs[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(devs)}; "
          f"compile cache {cache_dir}")
    if dev.platform != "tpu":
        print(f"FAIL: no TPU (JAX platform is {dev.platform!r})", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"FAIL: --chips {args.chips} but JAX sees {len(devs)}", file=sys.stderr)
        return 2

    from repro.configs import get_config

    full = get_config(ARCH)
    cfg = dataclasses.replace(full, n_layers=N_LAYERS)
    print(f"model: {ARCH} at published widths (d={cfg.d_model}, "
          f"{cfg.n_heads}q/{cfg.n_kv_heads}kv heads x {cfg.head_dim_}, "
          f"{cfg.n_experts} experts top-{cfg.experts_per_token} x "
          f"{cfg.moe_d_ff_}, vocab {cfg.vocab_size}), bf16, random weights "
          f"(seed {args.seed})")
    print(f"cut: n_layers {full.n_layers} -> {N_LAYERS} (depth only; every "
          f"layer is MoE, so one layer is one whole period)")

    serve_kw = dict(
        max_seq=1024, batch=8, paged=True, page_size=128, prefill_chunk=256,
        slots_per_device=SLOTS_PER_DEVICE,
    )
    mesh = None
    if args.chips == 1:
        serve_kw["virtual_ep"] = VIRTUAL_EP
        path = (f"ep_moe_local over {VIRTUAL_EP} virtual EP devices x "
                f"{SLOTS_PER_DEVICE} slots")
    else:
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((1, args.chips), ("data", "model"))
        path = (f"ep_moe_shardmap over a (1, {args.chips}) mesh x "
                f"{SLOTS_PER_DEVICE} slots, real all_to_all")
    print(f"serving: {path}; paged KV (128-token pages, 1024 context), "
          f"256-token prefill lane, 8 batch slots; {N_REQUESTS} requests, "
          f"prompts {PROMPT_LENS[0]}-{PROMPT_LENS[1]}, {NEW_TOKENS} new tokens")

    try:
        if mesh is None:
            report = run(cfg, seed=args.seed, serve_kw=serve_kw)
        else:
            with mesh:
                report = run(cfg, seed=args.seed, serve_kw=serve_kw, mesh=mesh)
    except Exception as e:  # noqa: BLE001 — any phase failure fails the run
        import traceback

        traceback.print_exc()
        print(f"FAIL: serving run raised {type(e).__name__}: {e}", file=sys.stderr)
        return 1

    peak = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in devs[: args.chips]
    )
    print(f"requests: {report['states'].count('FINISHED')}/{len(report['states'])} "
          f"FINISHED in {report['ticks']} ticks ({report['serve_s']:.1f} s, "
          f"compilation included)")
    print(f"peak HBM: {peak / 2**30:.2f} GiB (max over {args.chips} device(s))")
    print(f"compiled step programs: {report['step_programs']}")
    print(f"step kernels (tpu_custom_call): {', '.join(report['kernels']) or 'none'}")
    print(f"expert slots: {report['n_slots']}; capacity factor "
          f"{report['capacity_factor']:g}; migrations committed: "
          f"{report['migrations']}")
    print("reference agreement: max|served-ref|/max|ref| per request "
          + " ".join(f"{x:.4f}" for x in report["rel_err"])
          + f" (tolerance {LOGIT_TOL}); argmax agrees "
          f"{report['argmax_agree']}/{len(report['rel_err'])}")
    try:
        check(report, EXPECTED[args.chips])
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(devs),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
