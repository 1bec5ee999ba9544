#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's and the control's.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1,2,... \
        --seconds <s> [--controls int8,fp8]

For each seed, in one process: the cell's whole run (set-up, window at the
cell's own load, the reference's check), and for the same prompts and
served tokens the control's reading, i.e. the reference put in the
program's place at a lower precision: at each position its logits at the
sampled ids and the gap of the token that precision puts first. int8
(weights per output channel) is the step below bf16 that v5e runs
natively; fp8 is the coarser one. Every number the check can compare
(``bench.gap_stats``) is read. Prints one JSON line per seed and a summary
(each number's largest reading by the program, smallest by the control).
The benchmark's own runs never run this; the limits in
``limits/<cell>.json`` come from its readings. Needs the chips the cell
asks for.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run as entry  # noqa: E402  (sets the import paths and the cache)


def readings(spec, seeds, seconds, controls, *, devices, peak, use_kernels="auto",
             log=print) -> dict:
    """Every number the check can compare, for the program and for each
    control precision, per seed; and over the seeds, the program's largest
    and each control's smallest."""
    import bench

    rows = []
    for seed in seeds:
        t0 = time.perf_counter()
        res = bench.run_cell(spec, seed, seconds, False, t_start=t0, peak=peak,
                             devices=devices, use_kernels=use_kernels,
                             controls=controls, log=log)
        c = res["control"]
        row = {"seed": seed, "correct": res["correct"], "program": c["program"],
               "control": c["control"], "reference_s": c["reference_s"],
               "metrics": {k: v["value"] for k, v in res["metrics"].items()}}
        log(json.dumps(row))
        rows.append(row)
    names = rows[0]["program"]
    return {
        "program_max": {n: max(r["program"][n] for r in rows) for n in names},
        "control_min": {k: {n: min(r["control"][k][n] for r in rows) for n in names}
                        for k in controls},
        "rows": rows,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--controls", default="int8,fp8")
    args = ap.parse_args(argv)
    import jax

    entry.enable_cache()
    import bench

    spec = bench.load_spec(args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < spec.cell["chips"]:
        print(f"FAIL: needs {spec.cell['chips']} TPU chips", file=sys.stderr)
        return 2
    out = readings(spec, [int(s) for s in args.seeds.split(",")], args.seconds,
                   tuple(args.controls.split(",")), devices=devs[: spec.cell["chips"]],
                   peak=bench.peak_of(devs[0].device_kind), log=entry.log)
    print(json.dumps({k: v for k, v in out.items() if k != "rows"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
