"""Batched MoE serving with expert parallelism and the NI-Balancer active.

Needs multiple devices for real EP — run with forced host devices:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python examples/serve_moe.py
"""

import dataclasses
import time

import jax

from repro.configs import get_config, smoke
from repro.core.topology import MeshTopology
from repro.models import transformer as T
from repro.parallel.ctx import ParallelCtx
from repro.runtime.data import request_stream
from repro.runtime.elastic import drill_failure
from repro.runtime.serve import ServeConfig, Server

n_dev = len(jax.devices())
if n_dev >= 8:
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((n_dev // 4, 4), ("data", "model"))
    ctx = ParallelCtx(mesh=mesh, capacity_factor=4.0)
    topo = MeshTopology(2, 2)
    dist = lambda a, b: topo.hops(topo.coord(a), topo.coord(b))
else:
    print(f"only {n_dev} device(s) — running the dense fallback")
    mesh, ctx, dist = None, ParallelCtx(), None

cfg = dataclasses.replace(
    smoke(get_config("dbrx-132b")), n_experts=8, experts_per_token=2
)
params = T.init_params(jax.random.PRNGKey(0), cfg)


class _null:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


with (mesh if mesh is not None else _null()):
    server = Server(
        cfg, ctx, params,
        ServeConfig(max_seq=128, batch=4, slots_per_device=3, alpha=0.3),
        distance=dist,
    )
    for i, prompt in zip(range(3), request_stream(cfg.vocab_size, 4, 12)):
        t0 = time.time()
        out = server.generate(prompt, 24)
        dt = time.time() - t0
        print(
            f"batch {i}: {out.shape} in {dt:.2f}s "
            f"({4 * 24 / dt:.1f} tok/s), migrations={server.migrations}"
        )
    if server.state is not None:
        print("failure drill:", drill_failure(server, device=1))
