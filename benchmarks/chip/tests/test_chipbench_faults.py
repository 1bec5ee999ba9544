"""``correct`` comes out false when the timed path is broken underneath,
and the controls (the reference in a lower precision: int8 weights, fp8)
fail the limits.
Drives the whole run at smoke widths on the CPU, past the look for a chip.

The faults a served one-chip cell can have: a step that returns its state
(the paged KV cache) unchanged; half of the batch left out of the expert
layer; a token altered where it is produced. (The exchange between chips
exists only in the four-chip cell.)"""

import time

import jax
import jax.numpy as jnp
import pytest

import tiny
import bench
import control


def _run(spec, seed=77):
    return bench.run_cell(spec, seed, 1.0, False, t_start=time.perf_counter(),
                          peak=tiny.PEAK, devices=jax.devices()[:1], use_kernels=False,
                          log=lambda *a: None)


def _state_unchanged(monkeypatch):
    from repro.runtime import serve

    build = serve.Server.__init__

    def init(self, *a, **k):
        build(self, *a, **k)
        step = self._decode

        def stale(params, token, cache, **kw):
            old = jax.tree.map(jnp.copy, cache)      # the step donates its input
            logits, _new, stats = step(params, token, cache, **kw)
            return logits, old, stats

        self._decode = stale

    monkeypatch.setattr(serve.Server, "__init__", init)


def _half_batch(monkeypatch):
    from repro.models import transformer

    moe_apply = transformer.moe_apply

    def half(p, x, cfg, ctx, placement=None, token_mask=None):
        keep = (jnp.arange(x.shape[0] * x.shape[1]) % 2 == 0).reshape(x.shape[:2])
        token_mask = keep if token_mask is None else token_mask & keep
        return moe_apply(p, x, cfg, ctx, placement=placement, token_mask=token_mask)

    monkeypatch.setattr(transformer, "moe_apply", half)


def _token_altered(monkeypatch):
    from repro.runtime import scheduler

    push = scheduler.RequestScheduler._push_token

    def altered(self, req, tok):
        if len(req.tokens_out) == 2:
            tok = (tok + 1) % self.server.cfg.vocab_size
        return push(self, req, tok)

    monkeypatch.setattr(scheduler.RequestScheduler, "_push_token", altered)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch, _token_altered])
def test_broken_step_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    jax.clear_caches()            # the step must be traced again, broken
    res = _run(tiny.spec())
    assert not res["correct"]
    gap = res["checks"]["served_gap_mean"]
    assert gap["value"] > gap["limit"]


def test_control_fails_the_limit():
    out = control.readings(tiny.spec(), [3, 5, 2**40 + 1], 1.0, ("int8", "fp8"),
                           devices=jax.devices()[:1], peak=tiny.PEAK, use_kernels=False,
                           log=lambda *a: None)
    limits = tiny.LIMITS["compare"]
    for name, limit in limits.items():
        assert out["program_max"][name] <= limit
    assert out["control_min"]["fp8"]["served_gap_mean"] > limits["served_gap_mean"]
    for kind in ("int8", "fp8"):
        assert out["control_min"][kind]["served_logit_err_median"] > \
            limits["served_logit_err_median"]
    assert all(r["correct"] for r in out["rows"])
