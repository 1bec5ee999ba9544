"""Serving driver: batched generation with the NI-Balancer active.

Example (CPU, 8 fake devices):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.serve --arch dbrx-132b --smoke \
      --requests 4 --prompt-len 16 --gen 32
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, smoke as smoke_cfg
from repro.kernels.registry import parse_use_kernels
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.core.topology import MeshTopology
from repro.models import transformer as T
from repro.parallel.ctx import ParallelCtx
from repro.runtime.data import request_stream
from repro.runtime.serve import ServeConfig, Server


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--batches", type=int, default=3)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--alpha", type=float, default=0.5)
    ap.add_argument(
        "--use-kernels", default="auto", choices=("auto", "on", "off"),
        help="Pallas kernel dispatch: auto=TPU only, on=everywhere "
        "(interpret off-TPU), off=einsum reference paths",
    )
    ap.add_argument(
        "--paged", action="store_true",
        help="paged KV cache: shared page pool + per-request block tables "
        "(decode HBM tracks live context, not max_seq)",
    )
    ap.add_argument("--page-size", type=int, default=128)
    ap.add_argument(
        "--pool-pages", type=int, default=None,
        help="oversubscribe the page pool (default: fully backed)",
    )
    ap.add_argument(
        "--ep-chunks", type=int, default=1,
        help="pipeline the EP dispatch/combine all_to_all legs against the "
        "fused expert FFN in this many expert-group chunks (must divide "
        "slots-per-device; 1 = single-shot dispatch)",
    )
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_cfg(cfg)

    uk = parse_use_kernels(args.use_kernels)
    n_dev = len(jax.devices())
    if n_dev > 1:
        m = max(d for d in (2, 4, 8, 16) if n_dev % d == 0 and d <= n_dev)
        mesh = make_mesh((n_dev // m, m), ("data", "model"))
        ctx = ParallelCtx(mesh=mesh, capacity_factor=4.0, use_kernels=uk)
        # ER-Mapping hop distance on a model-axis ring mesh (for Algorithm 1).
        rows = int(np.sqrt(m)) if int(np.sqrt(m)) ** 2 == m else 1
        topo = MeshTopology(rows, m // rows)
        dist = lambda a, b: topo.hops(topo.coord(a), topo.coord(b))
    else:
        mesh = None
        ctx = ParallelCtx(use_kernels=uk)
        dist = None

    # bf16 residency, as served: fp32 experts do not fit a chip's HBM at
    # published widths (one DBRX layer's 16 experts alone are 12.7 GB).
    params = T.init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16)
    scfg = ServeConfig(
        max_seq=args.max_seq,
        batch=args.requests,
        slots_per_device=args.slots,
        alpha=args.alpha,
        paged=args.paged,
        page_size=args.page_size,
        pool_pages=args.pool_pages,
        ep_chunks=args.ep_chunks,
    )
    cm = mesh if mesh is not None else _null()
    with cm:
        server = Server(cfg, ctx, params, scfg, distance=dist)
        stream = request_stream(cfg.vocab_size, args.requests, args.prompt_len)
        for i, prompt in zip(range(args.batches), stream):
            embeds = None
            if cfg.frontend_stub:
                embeds = (
                    jax.random.normal(
                        jax.random.PRNGKey(i),
                        (args.requests, cfg.frontend_tokens, cfg.d_model),
                    )
                    * 0.02
                )
            t0 = time.time()
            out = server.generate(prompt, args.gen, embeds=embeds)
            dt = time.time() - t0
            tps = args.requests * args.gen / dt
            print(
                f"batch {i}: generated {out.shape} in {dt:.2f}s "
                f"({tps:.1f} tok/s), migrations so far: {server.migrations}"
            )
    print("done")


class _null:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


if __name__ == "__main__":
    main()
