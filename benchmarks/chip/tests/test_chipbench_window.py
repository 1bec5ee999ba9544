"""The harness window over ``RequestScheduler`` at smoke widths on the
CPU, through the same entry the chip runs use (``bench.run_cell``)."""

import time

import jax
import numpy as np
import pytest

import tiny
import bench
import devtrace as tr

OPEN_E2E = [{"name": "ttft_p95_ms", "unit": "ms"}, {"name": "itl_p95_ms", "unit": "ms"},
            {"name": "setup_s", "unit": "s"}]


def _run(spec, seconds=1.5, seed=2**32 + 9):
    return bench.run_cell(spec, seed, seconds, False, t_start=time.perf_counter(),
                          peak=tiny.PEAK, devices=jax.devices()[:1], use_kernels=False,
                          log=lambda *a: None)


def test_closed_loop_window_is_correct_and_compiles_nothing(monkeypatch):
    seen = {}
    window = bench.Run.window

    def spy(self, *a, **k):
        window(self, *a, **k)
        seen["compiles"] = self.compiles

    monkeypatch.setattr(bench.Run, "window", spy)
    res = _run(tiny.spec())
    assert res["correct"], res["checks"]
    assert seen["compiles"] == 0
    assert set(res["metrics"]) == {"tokens_per_s", "itl_p95_ms", "setup_s"}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res["checks"]) == ["served_gap_mean", "served_logit_err_median",
                                   "min_requests_scored", "failed_requests",
                                   "requests_without_logits"]
    assert res["checks"]["requests_without_logits"]["value"] == 0


def test_open_loop_window_times_requests_from_when_due():
    res = _run(tiny.spec(tiny.OPEN, end_to_end=OPEN_E2E))
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"ttft_p95_ms", "itl_p95_ms", "setup_s"}
    assert res["metrics"]["ttft_p95_ms"]["value"] > 0


def test_per_layer_readers_on_a_cpu_window():
    spec = tiny.spec()
    system = bench.System(spec, 5, use_kernels=False, log=lambda *a: None)
    run = bench.Run(system, 5, log=lambda *a: None)
    run.warm(True)
    run.window(1.0, True)
    ticks = run.window_ticks()
    # a synthetic device trace over the same window: busy half the time
    lo, hi = 0.0, 1e9
    iv = np.array([[0, 2.5e8], [5e8, 7.5e8]])
    red = tr.Reduced(lo, hi, [tr.Device(["expert_ffn_ragged.1", "fusion.2"], iv, [])],
                     [], len(ticks))
    ctr = bench.Counters(run, spec, tiny.PEAK, 1, red, system.n_replicas())
    got = {n: bench.load_metric(n)(ctr) for n in (
        "sched.occupancy", "mfu", "mfu.rate", "device.idle_share", "expert_ffn_roofline",
        "step.lane_tick_ms", "balancer.migrations")}
    assert 0 < got["sched.occupancy"] <= 100
    assert 0 < got["mfu"] <= got["mfu.rate"]
    assert got["device.idle_share"] == pytest.approx(50.0)
    assert got["expert_ffn_roofline"] > 0
    assert got["balancer.migrations"] >= 0
    # a reader with nothing to read returns nothing
    assert bench.load_metric("step.host_gap_ms")(ctr) is None
    system.free()
