"""A tiny cell for the CPU tests: DBRX's block at smoke widths, built the
way the chip cells are (same engine, same files), small enough for the
CPU in a few seconds. It is served in float32 (at these widths a bf16
program and an fp8 control do not separate: one routing flip moves a logit
as much as fp8 does); served so, it matches the reference's argmax at
every position read and its logits to float32 rounding, and the int8 and
fp8 controls do not."""

from __future__ import annotations

import copy
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
CHIP = HERE.parent
sys.path[:0] = [str(CHIP), str(CHIP.parents[1] / "src")]

import bench  # noqa: E402

MODEL = {
    "n_layers": 1, "d_model": 64, "n_heads": 4, "n_kv_heads": 2, "head_dim": 16,
    "n_experts": 4, "top_k": 2, "expert_d_ff": 96, "vocab": 256,
    "rope_theta": 500000.0, "norm_eps": 1e-05, "dtype": "float32",
    # 0.2 * sqrt(64) = 0.02 * sqrt(6144)/1.2: activations of the scale the
    # real widths give at 0.02, so attention and experts shape the logits
    "init_scale": 0.2,
}
CONFIG = {
    "name": "tiny",
    "arch": "dbrx-132b",
    "overrides": {"n_layers": 1, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
                  "head_dim": 16, "d_ff": 128, "n_experts": 4, "experts_per_token": 2,
                  "moe_d_ff": 96, "vocab_size": 256, "rope_theta": 500000.0},
    "model": MODEL,
    "serve": {"slots_per_device": 2, "virtual_ep": 4, "ep_chunks": 1, "migration_slices": 4},
    "mesh": None,
    "chips": 1,
}
CLOSED = {
    "loop": "closed", "clients": 8,
    "prompt": {"dist": "loguniform", "min": 8, "max": 24},
    "output": {"dist": "lognormal", "median": 6, "sigma": 0.5, "min": 3, "max": 12},
    "batch": 4, "max_seq": 48, "page_size": 8, "prefill_chunk": 16, "pool": 64,
    "check": {"max_requests": 3, "tokens": 20},
}
OPEN = dict(CLOSED, loop="open", rate=20.0, warm_s=0.3)
del OPEN["clients"]
# Readings (CPU, 12 seeds): served_gap_mean of the float32 program 0.0 on
# every seed, of the fp8 control 0.0116 at least (int8 reads 0.0 on half of
# the seeds); served_logit_err_median of the program 5.5e-7 to 7.6e-7, of
# the int8 control 0.0195 at least, of the fp8 control 0.120 at least.
LIMITS = {"compare": {"served_gap_mean": 0.003, "served_logit_err_median": 1e-3},
          "min_requests": 1}
E2E = [
    {"name": "tokens_per_s", "unit": "tokens/s"}, {"name": "itl_p95_ms", "unit": "ms"},
    {"name": "setup_s", "unit": "s"},
]
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def spec(mix=CLOSED, per_layer=(), end_to_end=None) -> "bench.Spec":
    return bench.Spec(
        cell={"name": "tiny", "chips": 1},
        config=copy.deepcopy(CONFIG),
        mix=copy.deepcopy(mix),
        limits=dict(LIMITS),
        end_to_end=list(E2E if end_to_end is None else end_to_end),
        per_layer=[{"name": n, "unit": "%"} for n in per_layer],
    )
