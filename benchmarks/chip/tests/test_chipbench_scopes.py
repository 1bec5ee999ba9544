"""Device time by named scope and host time by span (``scopes.py``), and
the four readers built on them, on a small synthetic trace; then the op
to scope map taken from the tiny cell's compiled step."""

import numpy as np
import pytest

import tiny
import bench
import devtrace as tr
import scopes

STEP = "jit__unknown(7)"
HLO = """HloModule jit__unknown, is_scheduled=true

ENTRY %main (p: f32[4]) -> f32[4] {
  %fusion.1 = f32[4] fusion(%p), kind=kLoop, metadata={op_name="jit(<unknown>)/while/body/closed_call/decode/router/dot_general" source_line=1}
  %expert_ffn_ragged.8 = f32[4] custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(<unknown>)/while/body/closed_call/decode/expert_ffn/jit(expert_ffn_ragged)/pallas_call"}
  %expert_ffn_ragged.10 = f32[4] custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(<unknown>)/while/body/closed_call/lane/expert_ffn/jit(expert_ffn_ragged)/pallas_call"}
  %multiply_reduce_fusion = f32[4] fusion(%p), kind=kLoop, metadata={op_name="jit(<unknown>)/lane/lm_head/dot_general"}
  %copy.3 = f32[4] copy(%p)
  ROOT %convolution_bitcast_fusion = f32[4] fusion(%p), metadata={op_name="jit(<unknown>)/lm_head/dot_general"}
}
"""
COPY = """HloModule jit__copy_row_slice, is_scheduled=true

ENTRY %main (w: bf16[8]) -> bf16[8] {
  ROOT %fusion.1 = bf16[8] fusion(%w), kind=kLoop, metadata={op_name="jit(_copy_row_slice)/migration/dynamic_update_slice"}
}
"""


def _dev(ops, modules):
    iv = np.array([(s, e) for _, s, e in ops], np.float64).reshape(-1, 2)
    return tr.Device([n for n, _, _ in ops], iv, list(modules))


def _reduced(scoped=True):
    """Window [0, 100] ns, two ticks. Chip 0 runs the step twice (0-40,
    50-80), a slice copy (42-46) and another program (46-48) whose op is
    called ``fusion.1`` like ops of the step and of the copy; chip 1 runs
    the step once (0-50)."""
    d0 = _dev(
        [("fusion.1", 0, 2), ("expert_ffn_ragged.8", 2, 12), ("expert_ffn_ragged.10", 12, 32),
         ("multiply_reduce_fusion", 32, 36), ("convolution_bitcast_fusion", 36, 40),
         ("fusion.1", 42, 46), ("fusion.1", 46, 48),
         ("expert_ffn_ragged.8", 50, 60), ("expert_ffn_ragged.10", 60, 76), ("copy.3", 76, 80)],
        [(STEP, 0, 40), ("jit__copy_row_slice(9)", 41, 46), ("jit_pick(3)", 46, 48),
         (STEP, 50, 80)],
    )
    d1 = _dev([("expert_ffn_ragged.10", 0, 30), ("expert_ffn_ragged.8", 30, 50)],
              [(STEP, 0, 50)])
    host = [("bench.tick", 0, 50), ("bench.tick", 50, 100), ("sched.step", 0, 49),
            ("sched.sample", 40, 47), ("sched.sample", 96, 104),   # clipped to 96-100
            ("sched.admit", 48, 49), ("server.prepare", 49, 50), ("server.dispatch", 50, 52),
            ("server.counts", 52, 80)]
    red = tr.Reduced(0.0, 100.0, [d0, d1], host if scoped else host[:2], 2)
    maps = ({"jit__copy_row_slice": scopes.op_names(COPY)}) if scoped else {}
    return red, scopes.Scopes(red, scopes.op_names(HLO) if scoped else {}, maps)


class _Counters:
    """What a reader takes: the trace and, prepared, its scopes."""

    def __init__(self, scoped=True):
        self.trace, self._scopes = _reduced(scoped)


def test_hlo_text_maps_instructions_to_their_name_stacks():
    m = scopes.op_names(HLO)
    assert m["expert_ffn_ragged.10"].endswith("/lane/expert_ffn/jit(expert_ffn_ragged)/pallas_call")
    assert m["convolution_bitcast_fusion"] == "jit(<unknown>)/lm_head/dot_general"
    assert "copy.3" not in m                        # no metadata, no scope
    assert scopes.module_name(HLO) == "jit__unknown"
    assert scopes.module_name(COPY) == "jit__copy_row_slice"


def test_each_op_takes_the_stack_of_the_program_that_ran_it():
    red, s = _reduced()
    st = s.stacks(red.devices[0])
    assert st[0].endswith("decode/router/dot_general")          # the step's fusion.1
    assert st[5] == "jit(_copy_row_slice)/migration/dynamic_update_slice"
    assert st[6] == "" and st[9] == ""                           # unmapped program, no metadata


@pytest.mark.parametrize("component,ns", [
    ("lane", ((20 + 4 + 16) + 30) / 2),       # lane FFN + lane LM head, chip 0; lane FFN, chip 1
    ("decode", ((2 + 10 + 10) + 20) / 2),
    ("expert_ffn", ((10 + 20 + 10 + 16) + 50) / 2),
    ("lm_head", (4 + 4) / 2),
    ("router", 2 / 2),
    ("migration", 4 / 2),
    ("lan", 0.0),                              # a whole part only
    ("expert", 0.0),
])
def test_scope_time_sums_ops_under_a_scope_averaged_over_chips(component, ns):
    _, s = _reduced()
    assert s.scope_s(component) == pytest.approx(ns * 1e-9)


def test_span_time_is_clipped_to_the_window():
    red, _ = _reduced()
    assert scopes.span_s(red, "sched.sample") == pytest.approx(11e-9)
    assert scopes.span_s(red, "sched.admit", "server.prepare") == pytest.approx(2e-9)
    assert scopes.span_s(red, "server.balance") is None
    assert scopes.per_tick_ms(red, "sched.sample") == pytest.approx(1e3 * 11e-9 / 2)


@pytest.mark.parametrize("metric,value", [
    # busy: chip 0 covers 0-40, 42-48 and 50-80 = 76, chip 1 0-50 -> mean 63
    ("lane.device_share", 100.0 * 35 / 63),
    ("host.sample_ms", 1e3 * 11e-9 / 2),
    ("host.prepare_ms", 1e3 * (1 + 1 + 2) * 1e-9 / 2),
    ("migration.copy_ms", 1e3 * 2e-9),
])
def test_readers_on_a_scoped_trace(metric, value):
    assert bench.load_metric(metric)(_Counters()) == pytest.approx(value)


@pytest.mark.parametrize("metric", [
    "lane.device_share", "host.sample_ms", "host.prepare_ms", "migration.copy_ms"])
def test_readers_return_nothing_for_a_program_without_spans_or_scopes(metric):
    assert bench.load_metric(metric)(_Counters(scoped=False)) is None


def test_scopes_of_the_tiny_cells_compiled_step():
    spec = tiny.spec()
    system = bench.System(spec, 3, use_kernels=False, log=lambda *a: None)
    run = bench.Run(system, 3, log=lambda *a: None)
    run.warm(True)
    red = tr.Reduced(0.0, 1.0, [tr.Device([], np.zeros((0, 2)), [])], [], 1)
    ctr = bench.Counters(run, spec, tiny.PEAK, 1, red, system.n_replicas())
    s = scopes.of(ctr)
    assert scopes.of(ctr) is s                   # built once per run
    stacks = list(s.step.values())
    for path in ("lane", "decode", "expert_ffn", "lm_head", "router"):
        assert any(scopes.in_scope(x, path) for x in stacks), path
    copies = [x for m in s.others.values() for x in m.values() if "/" in x]
    assert copies and all(scopes.in_scope(x, "migration") for x in copies)
    system.free()


def test_copy_map_is_compiled_anew_not_taken_from_memory(monkeypatch):
    """A slice-copy executable already in memory whose ops carry no
    ``migration`` scope (as one that a build with other scopes left in a
    shared compile cache) does not hide the scope from the map."""
    import contextlib

    import jax
    from repro.runtime import migration_driver as md

    class Unscoped:
        def __getattr__(self, name):
            return getattr(jax, name)

        @staticmethod
        def named_scope(_name):
            return contextlib.nullcontext()

    system = bench.System(tiny.spec(), 4, use_kernels=False, log=lambda *a: None)
    for name in md.MOE_WEIGHTS:
        w = system.server._moe()[name]
        rows = min(w.shape[2], -(-w.shape[2] // system.server.driver.min_slices))
        with monkeypatch.context() as m:
            m.setattr(md, "jax", Unscoped())
            md._copy_row_slice(jax.numpy.array(w), md._i32(0), md._i32(0), md._i32(0), rows=rows)
    stacks = [x for hlo in scopes.copy_hlo(system) for x in scopes.op_names(hlo).values()
              if "/" in x]
    assert stacks and all(scopes.in_scope(x, "migration") for x in stacks)
    system.free()
