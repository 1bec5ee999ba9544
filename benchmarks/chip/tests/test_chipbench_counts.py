"""Operation and byte counts of the served step, by hand at small sizes."""

import numpy as np
import pytest

import tiny
import counts

M = tiny.MODEL   # d=64, 4 q / 2 kv heads of 16, 4 experts top-2 of 96, vocab 256


def test_expert_ffn_flops_count_routed_copies():
    assert counts.expert_ffn_flops(M, [3, 0, 1, 2]) == 2 * 3 * 64 * 96 * 6


def test_expert_ffn_bytes_touch_each_used_slot_once():
    # expert 1 got nothing; expert 0 has 2 replicas and 3 copies -> 2 slots
    nbytes = counts.expert_ffn_bytes(M, [3, 0, 1, 2], [2, 1, 1, 1])
    slots = 2 + 0 + 1 + 1
    assert nbytes == slots * 3 * 64 * 96 * 2 + 2 * 6 * 64 * 2


def test_tick_flops_by_hand():
    d, hd, h, k, v = 64, 16, 4, 2, 256
    dense = d * hd * (2 * h + 2 * k) + d * 4 + 2 * 3 * d * 96
    per_tok = 2 * dense + 2 * d * v
    attn = 4 * h * hd
    # two decode rows attending 5 and 7 keys, no chunk
    assert counts.tick_flops(M, [5, 7], 0, 0, False) == pytest.approx(
        2 * per_tok + attn * 12)
    # a final chunk of 3 tokens from position 10: keys 11 + 12 + 13, one LM head
    got = counts.tick_flops(M, [], 10, 3, True)
    assert got == pytest.approx(3 * 2 * dense + attn * 36 + 2 * d * v)


def test_roofline_names_its_bound():
    peak = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.roofline_s(1000.0, 10.0, peak) == (10.0, "compute")
    assert counts.roofline_s(10.0, 1000.0, peak, chips=2) == (50.0, "hbm")
    assert np.isfinite(counts.roofline_s(0.0, 0.0, peak)[0])
