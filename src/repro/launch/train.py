"""Training driver: config-driven, fault-tolerant, mesh-aware.

Runs any ``--arch`` (full or ``--smoke`` reduction) on whatever devices
exist: single CPU for local runs, a forced host-device mesh for rehearsal,
or a real pod slice. Features wired in:

* deterministic resumable data pipeline (cursor in the checkpoint),
* async checkpointing every ``--ckpt-every`` steps + restore-on-start
  (elastic: restoring onto a different mesh re-places host arrays),
* straggler watch via StepTimer,
* optional DiLoCo-style compressed cross-pod sync every ``--pod-sync``
  steps when the mesh has a "pod" axis.

Example (CPU):
  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
      --smoke --steps 100 --batch 8 --seq 128
"""

from __future__ import annotations

import argparse

import jax

from repro.configs import get_config, smoke as smoke_cfg
from repro.kernels.registry import parse_use_kernels
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.parallel.ctx import ParallelCtx
from repro.parallel.sharding import state_specs, to_shardings
from repro.runtime.checkpoint import CheckpointManager
from repro.runtime.data import DataConfig, SyntheticLM
from repro.runtime.elastic import StepTimer
from repro.runtime.optimizer import AdamWConfig
from repro.runtime.train import init_state, make_train_step


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--pod-sync", type=int, default=0)
    ap.add_argument("--mesh", default="auto", help="auto|DxM e.g. 2x4")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument(
        "--use-kernels", default="auto", choices=("auto", "on", "off"),
        help="Pallas kernel dispatch: auto=TPU only, on=everywhere "
        "(interpret off-TPU), off=einsum reference paths",
    )
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_cfg(cfg)

    uk = parse_use_kernels(args.use_kernels)
    n_dev = len(jax.devices())
    if args.mesh != "auto":
        d, m = (int(x) for x in args.mesh.split("x"))
        mesh = make_mesh((d, m), ("data", "model"))
        ctx = ParallelCtx(mesh=mesh, use_kernels=uk)
    elif n_dev > 1:
        m = 1
        while n_dev % (m * 2) == 0 and m * 2 <= 8:
            m *= 2
        mesh = make_mesh((n_dev // m, m), ("data", "model"))
        ctx = ParallelCtx(mesh=mesh, use_kernels=uk)
    else:
        mesh = None
        ctx = ParallelCtx(use_kernels=uk)

    opt = AdamWConfig(lr=args.lr, warmup_steps=20, total_steps=args.steps)
    state = init_state(jax.random.PRNGKey(0), cfg)
    step_fn = make_train_step(cfg, ctx, opt)
    if mesh is not None:
        specs = state_specs(cfg, state, ctx)
        state = jax.device_put(state, to_shardings(mesh, specs))
        step_fn = jax.jit(step_fn, donate_argnums=(0,))
    else:
        step_fn = jax.jit(step_fn, donate_argnums=(0,))

    data = SyntheticLM(DataConfig(cfg.vocab_size, args.batch, args.seq))
    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    start = 0
    if mgr and mgr.latest() is not None:
        state, meta = mgr.restore(state)
        start = meta.get("data_step", meta["step"]) or 0
        print(f"[restore] resumed from step {start}")

    timer = StepTimer()
    ctxmgr = mesh if mesh is not None else _null()
    with ctxmgr:
        for step in range(start, args.steps):
            batch = data.batch_at(step)
            with timer:
                state, met = step_fn(state, batch)
                jax.block_until_ready(met["loss"])
            if timer.is_straggling:
                print(f"[straggler] step {step} took {timer.ratio:.2f}x EMA")
            if args.pod_sync and mesh is not None and "pod" in mesh.shape:
                if (step + 1) % args.pod_sync == 0:
                    from repro.parallel.grad_compress import compressed_pod_mean

                    state["params"] = compressed_pod_mean(state["params"], mesh)
            if step % args.log_every == 0 or step == args.steps - 1:
                print(
                    f"step {step:5d} loss {float(met['loss']):.4f} "
                    f"ce {float(met['ce']):.4f} gnorm {float(met['grad_norm']):.3f} "
                    f"lr {float(met['lr']):.2e} {timer.last:.2f}s"
                )
            if mgr and (step + 1) % args.ckpt_every == 0:
                mgr.async_save(step + 1, state, extra={"data_step": step + 1})
    if mgr:
        mgr.wait()
        mgr.save(args.steps, state, extra={"data_step": args.steps})
        print(f"[ckpt] final at {args.steps}")


class _null:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


if __name__ == "__main__":
    main()
