"""The trace reduction on a small synthetic trace: busy union, idle gaps,
kernel sums, exposed all-to-all, program gaps and the breakdown."""

import numpy as np
import pytest

import tiny  # noqa: F401  (import paths)
import devtrace as tr


def _dev(ops, modules=()):
    names = [n for n, _, _ in ops]
    iv = np.array([(s, e) for _, s, e in ops], np.float64).reshape(-1, 2)
    return tr.Device(names, iv, list(modules))


@pytest.fixture
def red():
    # window [0, 100] ns; chip 0: two step programs with a 20 ns gap
    d0 = _dev(
        [("fusion.1", 0, 10), ("expert_ffn_ragged.8", 10, 30), ("all-to-all.2", 25, 40),
         ("expert_ffn_ragged.9", 60, 70), ("all-to-all.3", 70, 80)],
        [("jit__decode_step", 0, 40), ("jit_copy", 45, 47), ("jit__decode_step", 60, 80)],
    )
    d1 = _dev([("expert_ffn_ragged.8", 0, 50), ("all-to-all.2", 40, 60)])
    host = [("bench.tick", 0, 50), ("bench.tick", 50, 100), ("server.decode", 40, 45)]
    return tr.Reduced(0.0, 100.0, [d0, d1], host, 2)


def test_union_and_gaps():
    iv = np.array([[0, 10], [5, 20], [30, 40]], float)
    assert tr.union(iv) == 30
    assert tr.gaps(iv, 0, 50).tolist() == [[20, 30], [40, 50]]
    assert tr.minus(np.array([[0, 10.0]]), np.array([[2, 4.0], [8, 20]])) == 6


def test_busy_and_window(red):
    assert red.window_s == pytest.approx(100e-9)
    # chip 0 busy 0-40 and 60-80 = 60; chip 1 busy 0-60 = 60
    assert red.busy_s() == pytest.approx(60e-9)


def test_kernel_sum_and_exposed_collective(red):
    # chip 0: 20 + 10, chip 1: 50 -> mean 40
    assert red.kernel_s(r"^expert_ffn") == pytest.approx(40e-9)
    # chip 0: a2a 25-40 (30-40 exposed) + 70-80 exposed = 20; chip 1: 50-60 = 10
    assert red.exposed_s(r"all-to-all") == pytest.approx(15e-9)


def test_program_gaps_and_breakdown(red):
    assert red.step_program() == "jit__decode_step"
    assert red.step_gaps_s().tolist() == pytest.approx([20e-9])
    top = red.top_ops(2)
    assert [n for n, _ in top] == ["expert_ffn_ragged.8", "all-to-all.2"]
    idle = dict(red.idle_by_host())
    # chip 0 idle 40-60 (mid 50: both ticks touch it, the shorter one wins
    # ties by order) and 80-100
    assert sum(idle.values()) == pytest.approx(40e-9)
    assert set(idle) <= {"bench.tick", "server.decode"}


def test_op_names_are_cut_from_the_instruction_text():
    text = "%expert_ffn_ragged.10 = bf16[20,256,10752]{2,1,0} custom-call(s32[20]{0} %b)"
    assert tr.OP_NAME.match(text).group(1) == "expert_ffn_ragged.10"
    assert tr.OP_NAME.match("fusion.3").group(1) == "fusion.3"


def test_reduce_needs_a_device_plane(tmp_path):
    import jax

    f = jax.jit(lambda x: x * 2)
    f(np.ones(4, np.float32))
    with tr.capture(True) as h:
        with jax.profiler.TraceAnnotation(tr.TICK_SPAN):
            f(np.ones(4, np.float32)).block_until_ready()
    try:
        with pytest.raises(ValueError, match="device plane"):
            tr.reduce(h.path)
    finally:
        tr.discard(h)
