"""The benchmark is data found by name: a new traffic mix, limits file or
per-layer metric needs no edit of the harness; the entry fails, printing
no result, without a TPU or without the program beside it."""

import json
import shutil
import subprocess
import sys

import tiny
import bench

ROOT = tiny.CHIP.parents[1]


def _copy_bench(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    dst = tmp_path / "benchmarks" / "chip"
    shutil.copytree(tiny.CHIP, dst, ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return dst


def test_every_cell_resolves_to_its_files():
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    for cell in b["workloads"]:
        spec = bench.load_spec(cell["name"])
        assert spec.config["chips"] == cell["chips"]
        assert spec.config["name"] == cell["config"]
        bench.program_config(spec.config)           # the program agrees on sizes
        for m in spec.per_layer:
            assert callable(bench.load_metric(m["name"]))
    for conf in b["configs"]:
        data = json.loads((ROOT / conf["file"]).read_text())
        for key in ("source", "reduced", "assumed", "deployment"):
            assert data[key], key
        assert set(conf["reduced"]) == set(data["reduced"])
    for mix in {c["traffic"] for c in b["workloads"]}:
        data = json.loads((tiny.CHIP / "traffic" / f"{mix}.json").read_text())
        assert data["why"] and data["users"]


def test_new_cell_files_are_found_by_name(tmp_path):
    base = _copy_bench(tmp_path)
    (base / "traffic" / "short_poisson.json").write_text(json.dumps(
        dict(json.loads((base / "traffic" / "mixed_poisson.json").read_text()),
             prompt={"dist": "loguniform", "min": 32, "max": 256})))
    (base / "limits" / "dbrx1l.short_poisson.json").write_text(
        json.dumps({"compare": {"served_gap_mean": 0.05}, "min_requests": 1}))
    (base / "metrics" / "sched.preemptions.py").write_text(
        "def read(c):\n    return 7.0\n")
    b = json.loads((tmp_path / "BENCHMARK.json").read_text())
    b["workloads"].append({"name": "dbrx1l.short_poisson", "config": "dbrx-132b.1l",
                           "traffic": "short_poisson", "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "sched.preemptions", "unit": "count", "better": "lower",
                           "source": "program_counter", "layer": "scheduler",
                           "moves": "ttft_p95_ms", "workloads": ["dbrx1l.short_poisson"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    spec = bench.load_spec("dbrx1l.short_poisson", root=tmp_path, base=base)
    assert spec.mix["prompt"]["max"] == 256
    assert "sched.preemptions" in [m["name"] for m in spec.per_layer]
    assert bench.load_metric("sched.preemptions", base=base)(None) == 7.0


def test_missing_peaks_are_an_error():
    import pytest

    with pytest.raises(KeyError, match="no peaks"):
        bench.peak_of("TPU v9 imaginary")
    assert bench.peak_of("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def _run(cwd, env_extra=None):
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin", **(env_extra or {})}
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", "dbrx1l.decode_closed",
         "--seed", "4294967311", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(out: str) -> bool:
    lines = out.strip().splitlines()
    return not lines or not lines[-1].startswith("{")


def test_run_fails_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode != 0 and _no_result(p.stdout)
    assert "no TPU" in p.stderr


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    _copy_bench(tmp_path)
    p = _run(tmp_path)
    assert p.returncode != 0 and _no_result(p.stdout)
