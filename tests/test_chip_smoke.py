"""``chip_smoke.py`` at smoke widths on the CPU (kernels in interpret mode).

The script itself refuses to run without a TPU; this drives its serving
run and its checks with the same code, so a change that breaks the smoke
run's logic fails here before it costs chip time.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import os

import pytest

from repro.configs import get_config, smoke

ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_run_matches_reference_at_smoke_widths(chip_smoke):
    cfg = dataclasses.replace(smoke(get_config("dbrx-132b")), n_layers=1)
    report = chip_smoke.run(
        cfg,
        n_requests=3,
        prompt_lens=(8, 24),
        new_tokens=4,
        serve_kw=dict(
            max_seq=64, batch=4, paged=True, page_size=8, prefill_chunk=16,
            slots_per_device=2, virtual_ep=4,
        ),
        use_kernels=True,
        log=lambda *_: None,
    )
    assert report["n_slots"] == 8
    # Interpret-mode kernels lower to plain ops, so no kernel names here.
    chip_smoke.check(report, expected_kernels=None)
    assert report["argmax_agree"] == 3


def test_smoke_checks_name_each_failure(chip_smoke):
    report = {
        "states": ["FINISHED", "DECODING"],
        "step_programs": 2,
        "kernels": ["gmm_ragged"],
        "rel_err": [0.0, float("inf")],
    }
    with pytest.raises(chip_smoke.SmokeFailure) as e:
        chip_smoke.check(report, {"gmm_ragged", "flash_decode_paged"})
    msg = str(e.value)
    for part in ("not FINISHED", "2 compiled step programs",
                 "flash_decode_paged", "off the reference"):
        assert part in msg
