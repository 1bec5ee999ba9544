#!/usr/bin/env python3
"""Chip benchmark of the served MoE path: one cell, one seed, one window.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Runs on the machine it is started on, and only there: without a TPU, or
with fewer chips than the cell asks for, it exits non-zero and prints no
result. Set-up (weights from the seed, the server, the cell's own traffic
until every shape has run) is timed as ``setup_s``; then the window runs
for ``--seconds``; then the plain reference scores a seeded sample of what
the window served. The last lines of standard error give each compared
number beside its limit; the last line of standard output is the JSON
result. ``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1``
traces the window and reports its per-layer metrics.

JAX's persistent compilation cache lives at ``$JAX_COMPILATION_CACHE_DIR``
when that is set, else at ``.jax_cache`` in the checkout's root, so only a
checkout's first run of a cell compiles.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]


def log(*a):
    print(*a, flush=True)


def enable_cache() -> str:
    """JAX's persistent compilation cache at its fixed place; every
    program, however small, is kept, so only a checkout's first run of a
    cell compiles."""
    import jax

    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    cache = enable_cache()
    import bench

    spec = bench.load_spec(args.workload)
    chips = spec.cell["chips"]
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"FAIL: no TPU (JAX platform is {devs[0].platform!r})", file=sys.stderr)
        return 2
    if len(devs) < chips:
        print(f"FAIL: cell {args.workload} needs {chips} chips, JAX sees {len(devs)}",
              file=sys.stderr)
        return 2
    peak = bench.peak_of(devs[0].device_kind)
    log(f"device: {devs[0].platform} {devs[0].device_kind} x{chips}; compile cache {cache}")
    res = bench.run_cell(spec, args.seed, args.seconds, bool(args.trace),
                         t_start=T_START, peak=peak, devices=devs[:chips], log=log)
    print(f"correct: {res['correct']}", file=sys.stderr)
    for name, c in res["checks"].items():     # the compared numbers, last
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
