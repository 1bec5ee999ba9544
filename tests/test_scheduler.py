"""Continuous-batching scheduler: lifecycle, admission control, preemption,
and the chaos parity acceptance test.

The determinism yardstick everywhere: a request's tokens must be
bit-identical to a sequential, fault-free, one-request-at-a-time run of the
same scheduler (greedy argmax; capacity_factor high enough that routing
never drops a copy).
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke
from repro.models import transformer as T
from repro.parallel.ctx import ParallelCtx
from repro.runtime.faults import (
    CRASH_RESTART,
    NAN_LOGITS,
    POOL_PRESSURE,
    POOL_RELEASE,
    Fault,
    FaultPlan,
    SimulatedCrash,
)
from repro.runtime.scheduler import (
    FAILED,
    FINISHED,
    PREFILLING,
    RequestScheduler,
    SchedulerConfig,
)
from repro.runtime.serve import Server, ServeConfig

RNG = jax.random.PRNGKey(0)


def _dense_cfg(**kw):
    return dataclasses.replace(smoke(get_config("llama3.2-1b")), **kw)


def _moe_cfg(**kw):
    base = dataclasses.replace(
        smoke(get_config("dbrx-132b")), n_experts=4, experts_per_token=2
    )
    return dataclasses.replace(base, **kw)


def _server(cfg, params, **scfg):
    ctx = ParallelCtx(capacity_factor=8.0)
    defaults = dict(max_seq=64, paged=True, page_size=8)
    defaults.update(scfg)
    return Server(cfg, ctx, jax.tree.map(jnp.copy, params),
                  ServeConfig(**defaults))


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
            for n in lens]


def _reference(cfg, params, prompts, max_new, **scfg):
    """Sequential oracle: each request alone in a fresh server with an
    ample pool and no faults."""
    out = []
    for p in prompts:
        srv = _server(cfg, params, batch=1, pool_pages=64, **scfg)
        sched = RequestScheduler(srv)
        req = sched.submit(p, max_new_tokens=max_new)
        sched.run()
        assert req.state == FINISHED, (req.state, req.error)
        out.append(np.asarray(req.tokens_out, np.int32))
    return out


# ---------------------------------------------------------------------------
# guards
# ---------------------------------------------------------------------------

def test_scheduler_requires_paged_server():
    cfg = _dense_cfg()
    srv = Server(cfg, ParallelCtx(), T.init_params(RNG, cfg),
                 ServeConfig(max_seq=32, batch=1))
    with pytest.raises(ValueError, match="paged=True"):
        RequestScheduler(srv)


def test_oversized_request_fails_at_submit():
    cfg = _dense_cfg()
    srv = _server(cfg, T.init_params(RNG, cfg), batch=1, pool_pages=8)
    sched = RequestScheduler(srv)
    req = sched.submit(np.arange(40, dtype=np.int32) % cfg.vocab_size,
                       max_new_tokens=100)
    assert req.state == FAILED and "capacity" in req.error
    assert not sched.queue      # never enqueued, can't wedge the loop
    bad = sched.submit(np.arange(3, dtype=np.int32), max_new_tokens=0)
    assert bad.state == FAILED


def test_starved_pool_fails_head_instead_of_hanging():
    cfg = _dense_cfg()
    params = T.init_params(RNG, cfg)
    srv = _server(cfg, params, batch=2, pool_pages=4)
    # An external tenant steals the whole pool at step 0 and never releases.
    plan = FaultPlan([Fault(step=0, kind=POOL_PRESSURE, pages=4)])
    sched = RequestScheduler(srv, faults=plan)
    req = sched.submit(_prompts(cfg, [6])[0], max_new_tokens=4)
    sched.run(max_steps=50)
    assert req.state == FAILED and "pool" in req.error


# ---------------------------------------------------------------------------
# lifecycle
# ---------------------------------------------------------------------------

def test_ragged_arrivals_all_complete_with_parity():
    """More requests than batch slots, ragged lengths and staggered
    arrivals: every request finishes and matches its sequential run."""
    cfg = _dense_cfg()
    params = T.init_params(RNG, cfg)
    prompts = _prompts(cfg, [5, 11, 3, 8, 14])
    ref = _reference(cfg, params, prompts, max_new=6)
    srv = _server(cfg, params, batch=3, pool_pages=14)
    sched = RequestScheduler(srv)
    reqs = [sched.submit(p, max_new_tokens=6, arrival=i) for i, p in
            enumerate(prompts)]
    res = sched.run()
    for i, r in enumerate(reqs):
        assert r.state == FINISHED, (i, r.state, r.error)
        np.testing.assert_array_equal(res[r.rid], ref[i])
    admits = [e for e in sched.events if e[1] == "admit"]
    assert len(admits) == 5
    # arrival gating: nothing admitted before its arrival step
    by_rid = {r.rid: r for r in reqs}
    assert all(step >= by_rid[rid].arrival for step, _, rid in admits)


def test_eos_retires_mid_flight_and_slot_is_reused():
    cfg = _dense_cfg()
    params = T.init_params(RNG, cfg)
    prompts = _prompts(cfg, [5, 9, 7])
    ref = _reference(cfg, params, prompts, max_new=8)
    eos = int(ref[0][0])   # request 0 stops after its very first token
    srv = _server(cfg, params, batch=2, pool_pages=10)
    sched = RequestScheduler(srv)
    r0 = sched.submit(prompts[0], max_new_tokens=8, eos_id=eos)
    r1 = sched.submit(prompts[1], max_new_tokens=8)
    r2 = sched.submit(prompts[2], max_new_tokens=8)
    sched.run()
    # r0: EOS truncation, exact prefix of the no-EOS reference
    cut = int(np.argmax(ref[0] == eos)) + 1
    np.testing.assert_array_equal(np.asarray(r0.tokens_out), ref[0][:cut])
    np.testing.assert_array_equal(np.asarray(r1.tokens_out), ref[1])
    np.testing.assert_array_equal(np.asarray(r2.tokens_out), ref[2])
    # r2 only fits because r0's retirement freed a slot mid-flight:
    events = {(k, d if k != "preempt" else d[0]): s
              for s, k, d in sched.events}
    assert events[("admit", r2.rid)] >= events[("retire", r0.rid)]


def test_watermark_backpressure_defers_admission():
    cfg = _dense_cfg()
    params = T.init_params(RNG, cfg)
    prompts = _prompts(cfg, [16, 16])
    ref = _reference(cfg, params, prompts, max_new=4)
    srv = _server(cfg, params, batch=2, pool_pages=6)
    # watermark 0.5: 3 of 6 pages; each request needs 2 pages up front, so
    # the second must wait for the first's retirement even though the pool
    # could physically hold both.
    sched = RequestScheduler(srv, SchedulerConfig(admit_watermark=0.5))
    r0 = sched.submit(prompts[0], max_new_tokens=4)
    r1 = sched.submit(prompts[1], max_new_tokens=4)
    sched.run()
    events = {(k, d): s for s, k, d in sched.events if k in ("admit", "retire")}
    assert events[("admit", r1.rid)] >= events[("retire", r0.rid)]
    np.testing.assert_array_equal(np.asarray(r0.tokens_out), ref[0])
    np.testing.assert_array_equal(np.asarray(r1.tokens_out), ref[1])


def test_preemption_recomputes_bit_identical():
    """A pool-pressure window mid-decode evicts the youngest request; on
    re-admission it recomputes from prompt + emitted tokens and its final
    output is indistinguishable from a run that was never preempted."""
    cfg = _dense_cfg()
    params = T.init_params(RNG, cfg)
    prompts = _prompts(cfg, [7, 10, 6])
    ref = _reference(cfg, params, prompts, max_new=10)
    srv = _server(cfg, params, batch=3, pool_pages=9)
    plan = FaultPlan([
        Fault(step=2, kind=POOL_PRESSURE, pages=4),
        Fault(step=8, kind=POOL_RELEASE, pages=4),
    ])
    sched = RequestScheduler(srv, faults=plan)
    reqs = [sched.submit(p, max_new_tokens=10) for p in prompts]
    res = sched.run()
    assert sched.n_preempted > 0, "pressure window should force eviction"
    for i, r in enumerate(reqs):
        assert r.state == FINISHED, (i, r.state, r.error)
        np.testing.assert_array_equal(res[r.rid], ref[i])


def test_nan_fault_fails_only_affected_request():
    """With the retry budget at zero, a NaN-poisoned request FAILs (named,
    no raise) while its batchmate sails through bit-identical."""
    cfg = _dense_cfg()
    params = T.init_params(RNG, cfg)
    prompts = _prompts(cfg, [6, 9])
    ref = _reference(cfg, params, prompts, max_new=8)
    srv = _server(cfg, params, batch=2, pool_pages=12)
    plan = FaultPlan([Fault(step=3, kind=NAN_LOGITS, slots=(0,))])
    sched = RequestScheduler(srv, SchedulerConfig(max_preemptions=0),
                             faults=plan)
    r0 = sched.submit(prompts[0], max_new_tokens=8)
    r1 = sched.submit(prompts[1], max_new_tokens=8)
    sched.run()
    assert r0.state == FAILED and "evicted" in r0.error
    assert r1.state == FINISHED
    np.testing.assert_array_equal(np.asarray(r1.tokens_out), ref[1])
    # partial output before the fault is a clean prefix (no garbage token)
    np.testing.assert_array_equal(
        np.asarray(r0.tokens_out), ref[0][: len(r0.tokens_out)]
    )


# ---------------------------------------------------------------------------
# the acceptance test: chaos parity on the MoE serving stack
# ---------------------------------------------------------------------------

def _chaos_run(seed, n_requests=4, max_new=7, skew_router=False,
               prefill_chunk=None, ep_chunks=1, pool_pages=10):
    cfg = _moe_cfg()
    params = T.init_params(RNG, cfg)
    if skew_router:
        # Sustained skewed traffic: hot experts' router columns dominate,
        # so the balancer keeps a stream of stepped migrations in flight
        # concurrently with the chaos plan's faults.
        router = np.asarray(params["layers"]["moe"]["router"])  # (L, d, E)
        scale = np.ones(router.shape[-1], router.dtype)
        scale[[0, 1]] = 8.0
        params["layers"]["moe"]["router"] = jnp.asarray(router * scale)
    lens = [int(x) for x in
            np.random.default_rng(seed).integers(3, 14, size=n_requests)]
    prompts = _prompts(cfg, lens, seed=seed)
    moe_kw = dict(slots_per_device=3, virtual_ep=4)
    ref = _reference(cfg, params, prompts, max_new=max_new, **moe_kw)
    # one request retires early via EOS (truncate the reference to match)
    eos = int(ref[0][min(2, max_new - 1)])
    expected = list(ref)
    cut = int(np.argmax(ref[0] == eos)) + 1
    expected[0] = ref[0][:cut]

    srv = _server(cfg, params, batch=3, pool_pages=pool_pages, alpha=0.1,
                  prefill_chunk=prefill_chunk, ep_chunks=ep_chunks, **moe_kw)
    # poison slot 0: admission always picks the lowest free slot, so slot 0
    # is the one guaranteed to hold a live request mid-run
    plan = FaultPlan.chaos(seed, n_steps=12, n_devices=4, pressure_pages=5,
                           nan_slots=(0,))
    sched = RequestScheduler(srv, faults=plan)
    reqs = [sched.submit(p, max_new_tokens=max_new,
                         eos_id=eos if i == 0 else None, arrival=i)
            for i, p in enumerate(prompts)]
    res = sched.run()
    # the plan actually exercised the failure paths
    fired = {d[0] for s, k, d in sched.events if k == "fault"}
    assert {"device_death", "pool_pressure", "nan_logits"} <= fired
    for i, r in enumerate(reqs):
        assert r.state == FINISHED, (i, r.state, r.error)
        np.testing.assert_array_equal(res[r.rid], expected[i])
    return sched


def _pool_preemptions(sched) -> int:
    return sum(
        1 for _, kind, d in sched.events
        if kind == "preempt" and d[1] == "pool-exhausted"
    )


def test_chaos_parity_moe():
    """Ragged arrivals + undersized pool + device death + straggler + NaN
    step + mid-stream EOS: every admitted request completes and every
    output is bit-identical to the sequential fault-free decode — including
    requests that were preempted and recomputed. No decode step raises.

    The pool is sized so the chaos plan's pressure window (5 of 7 pages
    held) leaves less than the live requests' page growth: a pool-exhausted
    preemption is forced by page arithmetic, whatever tokens the model
    happens to emit."""
    sched = _chaos_run(seed=14, pool_pages=7)
    assert _pool_preemptions(sched) > 0     # the chaos actually bit


def test_chaos_parity_with_concurrent_migration_stream():
    """The chaos plan with a skewed router on top: live stepped migrations
    (slice copies + atomic table swaps) run concurrently with preemption,
    device death and NaN faults — and every surviving request still matches
    the sequential fault-free oracle bit-for-bit."""
    sched = _chaos_run(seed=14, skew_router=True)
    srv = sched.server
    assert srv.migrations > 0, "migration stream never ran"
    assert srv.driver is not None and srv.driver.history
    srv.table.check()


def test_chaos_parity_chunked_dispatch():
    """The chunked EP dispatch pipeline (ep_chunks=3 over the 12 virtual
    expert groups) under the full chaos plan — device death mid-stream,
    pool pressure, NaN faults, preemption and recompute: every stream must
    stay bit-identical to the *unchunked* sequential fault-free oracle,
    because chunking is a schedule, not a numerical change."""
    sched = _chaos_run(seed=14, ep_chunks=3, pool_pages=7)
    assert _pool_preemptions(sched) > 0
    assert sched.server.scfg.ep_chunks == 3


@pytest.mark.slow
@pytest.mark.parametrize("seed", [11, 23, 47])
def test_chaos_parity_moe_seeds(seed):
    _chaos_run(seed, n_requests=6, max_new=10)


@pytest.mark.slow
@pytest.mark.parametrize("ep_chunks", [2, 4])
def test_chaos_parity_chunked_dispatch_depths(ep_chunks):
    _chaos_run(seed=23, ep_chunks=ep_chunks)


# ---------------------------------------------------------------------------
# chunked admission: prefill as a lane in the decode step
# ---------------------------------------------------------------------------

def test_prefill_chunk_validation():
    """Bad prefill_chunk values fail at ServeConfig construction with a
    named error (validate_ep_token_split convention), not as an opaque
    scatter error inside the jitted step."""
    kw = dict(max_seq=64, paged=True, page_size=8)
    with pytest.raises(ValueError, match="positive"):
        ServeConfig(prefill_chunk=-8, **kw)
    with pytest.raises(ValueError, match="positive"):
        ServeConfig(prefill_chunk=0, **kw)
    with pytest.raises(ValueError, match="page-size-aligned"):
        ServeConfig(prefill_chunk=12, **kw)
    with pytest.raises(ValueError, match="max_seq"):
        ServeConfig(prefill_chunk=128, **kw)
    with pytest.raises(ValueError, match="paged=True"):
        ServeConfig(prefill_chunk=128, max_seq=256, paged=False)
    assert ServeConfig(prefill_chunk=16, **kw).prefill_chunk == 16


def test_chunked_admission_stream_parity_and_bounded_stall():
    """Chunked admission vs splice admission: bit-identical streams, O(1)
    inter-token gap for live requests while a long prompt admits, first
    token within ceil(len/chunk)+1 ticks of admission, and ONE compiled
    step program serving idle, decode-only and decode+chunk ticks."""
    cfg = _dense_cfg()
    params = T.init_params(RNG, cfg)
    prompts = _prompts(cfg, [30, 5, 9, 12])
    chunk = 8

    def collect(prefill_chunk):
        srv = _server(cfg, params, batch=3, pool_pages=32,
                      prefill_chunk=prefill_chunk)
        sched = RequestScheduler(srv)
        reqs = [sched.submit(p, max_new_tokens=6, arrival=i)
                for i, p in enumerate(prompts)]
        res = sched.run()
        return srv, sched, reqs, res

    _, sched_a, _, res_a = collect(None)
    srv_b, sched_b, reqs_b, res_b = collect(chunk)
    for rid in res_a:
        np.testing.assert_array_equal(res_b[rid], res_a[rid])
    assert srv_b._decode._cache_size() == 1
    stats = sched_b.stats()
    # no live request ever waited more than the one fused step per tick
    assert stats["max_stall_ticks"] == 0
    assert stats["queue_depth"] == 0 and stats["prefill_backlog"] == 0
    for r in reqs_b:
        assert r.state == FINISHED
        ticks_to_first = r.first_token_step - r.admitted_step + 1
        assert ticks_to_first <= -(-len(r.prompt) // chunk) + 1
        per = stats["per_request"][r.rid]
        assert per["ttft_ticks"] == r.ttft_ticks
        assert per["n_tokens"] == 6


def test_preempt_mid_prefill_requeues_without_tokens():
    """Preempting a half-prefilled request returns its chunk pages, resets
    its progress, counts no emitted tokens, and requeues it at the front;
    the eventual output still matches the sequential oracle."""
    cfg = _dense_cfg()
    params = T.init_params(RNG, cfg)
    prompts = _prompts(cfg, [40, 4])
    ref = _reference(cfg, params, prompts, max_new=5)
    srv = _server(cfg, params, batch=2, pool_pages=16, prefill_chunk=8)
    sched = RequestScheduler(srv)
    r0 = sched.submit(prompts[0], max_new_tokens=5)
    r1 = sched.submit(prompts[1], max_new_tokens=5)
    while not (r0.state == PREFILLING and r0.prefill_pos > 0):
        sched.step()
    free_before = srv.page_pool.n_free
    held = len(srv._prefill_pages[r0.slot])
    sched._preempt(r0, "test-evict")
    assert r0.tokens_out == [] and r0.prefill_pos == 0
    assert r0.preemptions == 1
    assert srv.page_pool.n_free == free_before + held
    assert sched.queue[0] is r0
    res = sched.run()
    assert r0.state == FINISHED and r1.state == FINISHED
    np.testing.assert_array_equal(res[r0.rid], ref[0])
    np.testing.assert_array_equal(res[r1.rid], ref[1])


def test_chaos_parity_chunked_prefill():
    """The full chaos plan (device death, pool pressure, NaN step, EOS)
    with chunked admission on: every stream still matches the sequential
    fault-free splice-admission oracle bit-for-bit, on one compiled step
    program. (Seed 11, not 14: chunked admission shifts the tick at which
    each request is live, and 14's pressure window happens to miss — 11's
    actually evicts someone.)"""
    sched = _chaos_run(seed=11, prefill_chunk=8)
    assert sched.n_preempted > 0
    assert sched.server._decode._cache_size() == 1


def test_crash_restart_mid_prefill(tmp_path):
    """crash_restart landing while a request is half-prefilled: the
    snapshot records PREFILLING progress, restore requeues the request
    (its chunk KV died with the process) and re-prefills from chunk zero,
    and the restored streams are bit-identical to an uninterrupted run."""
    from repro.runtime import snapshot as S

    cfg = _dense_cfg()
    params = T.init_params(RNG, cfg)
    prompts = _prompts(cfg, [20, 6])
    kw = dict(batch=2, pool_pages=16, prefill_chunk=8)

    ref_sched = RequestScheduler(_server(cfg, params, **kw))
    for i, p in enumerate(prompts):
        ref_sched.submit(p, max_new_tokens=5, arrival=i)
    ref = ref_sched.run()

    # rid 0's 20-token prompt takes 3 chunk ticks from its step-0
    # admission; the crash at step 1 lands mid-prefill (pos=8, no token).
    path = os.path.join(str(tmp_path), "snap.npz")
    plan = FaultPlan([Fault(step=1, kind=CRASH_RESTART, path=path)])
    sched = RequestScheduler(_server(cfg, params, **kw), faults=plan)
    reqs = [sched.submit(p, max_new_tokens=5, arrival=i)
            for i, p in enumerate(prompts)]
    with pytest.raises(SimulatedCrash):
        sched.run()
    assert reqs[0].state == PREFILLING
    assert 0 < reqs[0].prefill_pos < len(prompts[0])
    assert reqs[0].tokens_out == []

    restored = S.restore_scheduler(
        path, cfg, ParallelCtx(capacity_factor=8.0),
        jax.tree.map(jnp.copy, params), faults=plan,
    )
    rec = next(r for r in restored.requests if r.rid == reqs[0].rid)
    assert rec.prefill_pos == 0    # chunk KV died: restart from chunk zero
    res = restored.run()
    assert all(r.state == FINISHED for r in restored.requests)
    for rid, want in ref.items():
        np.testing.assert_array_equal(res[rid], want)
