"""Elastic recovery: device revival and crash-safe snapshot/restore.

Acceptance tests for the full death -> evacuate -> revive -> rebalance ->
crash -> restore lifecycle on one placement-table substrate:

(a) death->revive chaos parity — a seeded FaultPlan kills a device and
    revives it mid-run; every output stays bit-identical to the sequential
    fault-free decode, no token routes to the revived device before its
    first replica commits, and the rebalance moves load back onto it.
(b) crash_restart mid-stream — the scheduler snapshots at the crash tick,
    a *fresh* Server/scheduler is rebuilt from snapshot + params
    checkpoint, and the concatenated pre/post-crash token streams equal
    the uninterrupted run's — including requests QUEUED and just-admitted
    at crash time.

Plus unit coverage for revival_plan, drill_failure's revival reporting,
StepTimer edge cases and restore_elastic onto a different mesh shape.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke
from repro.core.ni_balancer import BalancerState, revival_plan
from repro.models import transformer as T
from repro.parallel.ctx import ParallelCtx
from repro.runtime import snapshot as S
from repro.runtime.elastic import StepTimer, drill_failure, restore_elastic
from repro.runtime.faults import (
    CRASH_RESTART,
    DEVICE_REVIVAL,
    Fault,
    FaultPlan,
    SimulatedCrash,
)
from repro.runtime.scheduler import FINISHED, RequestScheduler
from repro.runtime.serve import Server, ServeConfig

RNG = jax.random.PRNGKey(0)
MOE_KW = dict(slots_per_device=3, virtual_ep=4)


def _moe_cfg():
    return dataclasses.replace(
        smoke(get_config("dbrx-132b")), n_experts=4, experts_per_token=2
    )


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
# revival planning (balancer level)
# ---------------------------------------------------------------------------

def test_revival_plan_seeds_hot_experts_onto_blank_device():
    state = BalancerState.initial(n_experts=4, n_devices=4, slots_per_device=2)
    state.load_ema = np.array([0.5, 0.3, 0.15, 0.05])
    dist = lambda a, b: abs(a - b)  # noqa: E731
    state.mark_dead(2)
    state.table.drop_device(2)
    state.revive(2)
    assert 2 not in state.dead
    plan = revival_plan(state, 2, dist)
    assert plan, "a blank device under skewed load must get seeded"
    # every entry targets the revived device, from a live source
    for e, src, dst in plan:
        assert dst == 2 and src not in state.dead
    # hottest per-replica expert is seeded first
    assert plan[0][0] == 0
    # the plan is monotone on peak heat: applying it must not raise it
    before = state.heats().max()
    for mig in plan:
        state.apply(mig)
    assert state.heats().max() <= before + 1e-12


def test_revival_plan_refuses_dead_device():
    state = BalancerState.initial(4, 4, 2)
    state.mark_dead(1)
    with pytest.raises(Exception, match="dead"):
        revival_plan(state, 1, lambda a, b: abs(a - b))


def test_server_revive_guards():
    cfg = _moe_cfg()
    srv = _server(cfg, T.init_params(RNG, cfg), batch=2, pool_pages=10,
                  **MOE_KW)
    with pytest.raises(ValueError, match="not dead"):
        srv.revive(1)
    with pytest.raises(ValueError, match="EP axis"):
        srv.revive(99)


def test_drill_failure_reports_revival_recovery():
    """The ops drill runs death -> rebalance -> revival entirely through
    the public stepped-migration path and reports recovery time."""
    cfg = _moe_cfg()
    srv = _server(cfg, T.init_params(RNG, cfg), batch=2, pool_pages=10,
                  **MOE_KW)
    srv.state.load_ema = np.array([0.5, 0.3, 0.15, 0.05])
    rep = drill_failure(srv, device=2, revive=True)
    assert rep["supported"] and rep["evacuated"]
    assert rep["revival_migrations"] > 0
    # stepped copies take real ticks: commit strictly after submission
    assert rep["revival_recovery_ticks"] > 0
    assert rep["revival_replicas"] == rep["revival_migrations"]
    assert rep["peak_after_revival"] <= rep["peak_after"] + 1e-12
    assert srv.driver.pending == 0
    srv.table.check()
    assert 2 in srv.table.committed_devices()


# ---------------------------------------------------------------------------
# acceptance (a): death -> revive chaos parity + routing invariant
# ---------------------------------------------------------------------------

def test_death_revive_chaos_parity():
    """Seed 14's chaos plan (death of device 3 at step 2, revival at step
    7, plus pool pressure / NaN / straggler) — every output bit-identical
    to the sequential fault-free decode; the revived device is never in
    the committed routing view between death and its first re-committed
    replica; afterwards the rebalance moves load back onto it."""
    seed, max_new = 14, 7
    cfg = _moe_cfg()
    params = T.init_params(RNG, cfg)
    lens = [int(x) for x in
            np.random.default_rng(seed).integers(3, 14, size=4)]
    prompts = _prompts(cfg, lens, seed=seed)
    ref = _reference(cfg, params, prompts, max_new=max_new, **MOE_KW)
    eos = int(ref[0][min(2, max_new - 1)])
    expected = list(ref)
    cut = int(np.argmax(ref[0] == eos)) + 1
    expected[0] = ref[0][:cut]

    srv = _server(cfg, params, batch=3, pool_pages=10, alpha=0.1, **MOE_KW)
    plan = FaultPlan.chaos(seed, n_steps=12, n_devices=4, pressure_pages=5,
                           nan_slots=(0,), revive=True)
    dev = next(f.device for f in plan if f.kind == DEVICE_REVIVAL)

    # Instrument the routing truth: record, per decode tick, whether the
    # (to-be-)revived device appears in the committed routing view — the
    # placement the jitted step routes by.
    routed: list[tuple[int, bool]] = []
    marks: dict[str, int] = {}
    inner = srv._decode
    orig_dead, orig_revive = srv.mark_dead, srv.revive
    srv._decode = lambda *a, **k: (
        routed.append((srv.t, dev in srv.table.committed_devices())),
        inner(*a, **k),
    )[1]
    srv.mark_dead = lambda d: (marks.setdefault("death_t", srv.t),
                               orig_dead(d))[1]
    srv.revive = lambda d: (marks.setdefault("revive_t", srv.t),
                            orig_revive(d))[1]

    sched = RequestScheduler(srv, faults=plan)
    reqs = [sched.submit(p, max_new_tokens=max_new,
                         eos_id=eos if i == 0 else None, arrival=i)
            for i, p in enumerate(prompts)]
    res = sched.run()

    fired = {d[0] for s, k, d in sched.events if k == "fault"}
    assert {"device_death", "device_revival"} <= fired
    # parity: bit-identical to the sequential fault-free oracle
    for i, r in enumerate(reqs):
        assert r.state == FINISHED, (i, r.state, r.error)
        np.testing.assert_array_equal(res[r.rid], expected[i])

    # routing invariant: between death and the first committed replica on
    # the revived device, no decode tick ever saw it in the routing view
    commits = [rec["committed"] for rec in srv.driver.history
               if rec["mig"][2] == dev
               and rec["committed"] is not None
               and rec["committed"] > marks["revive_t"]]
    assert commits, "revival copies never committed"
    first = min(commits)
    window = [t for t, present in routed
              if marks["death_t"] <= t < first and present]
    assert not window, f"device {dev} routed during blackout ticks {window}"
    # ... and load moved back: committed replicas with finite positive heat
    assert any(present for t, present in routed if t >= first)
    assert dev in srv.table.committed_devices()
    heats = srv.state.heats()
    assert np.isfinite(heats[dev]) and heats[dev] > 0
    srv.table.check()


# ---------------------------------------------------------------------------
# acceptance (b): crash_restart mid-stream, bit-identical restore
# ---------------------------------------------------------------------------

def _crash_run(tmp_path, crash_step, seed=3, max_new=6, with_chaos=False):
    cfg = _moe_cfg()
    params = T.init_params(RNG, cfg)
    # arrivals straddle the crash: rid 3 admits the tick before it (at
    # most one decoded token — the "mid-prefill" case at a tick-boundary
    # snapshot), rid 4 is still QUEUED (arrival after the crash).
    lens = [5, 9, 4, 7, 6]
    arrivals = [0, 1, 2, crash_step - 1, crash_step + 2]
    prompts = _prompts(cfg, lens, seed=seed)
    scfg = dict(pool_pages=10, alpha=0.1, **MOE_KW)

    def submit_all(sched):
        return [sched.submit(p, max_new_tokens=max_new, arrival=a)
                for p, a in zip(prompts, arrivals)]

    # uninterrupted reference (same batch shape, no faults)
    ref_sched = RequestScheduler(_server(cfg, params, batch=2, **scfg))
    submit_all(ref_sched)
    ref = ref_sched.run()

    path = os.path.join(str(tmp_path), "snap.npz")
    faults = [Fault(step=crash_step, kind=CRASH_RESTART, path=path)]
    if with_chaos:
        # seed 14's full plan: pressure@1, death@2, nan@4, revival@7,
        # straggler@8, release@9 — crash_step=5 lands between death and
        # revival, so the snapshot carries a dead device mid-blackout.
        faults += list(FaultPlan.chaos(14, n_steps=12, n_devices=4,
                                       pressure_pages=3, nan_slots=(0,),
                                       revive=True))
    plan = FaultPlan(faults)
    sched = RequestScheduler(_server(cfg, params, batch=2, **scfg),
                             faults=plan)
    submit_all(sched)
    with pytest.raises(SimulatedCrash) as ei:
        sched.run()
    assert ei.value.step == crash_step
    assert os.path.exists(path) and os.path.exists(path + ".meta")
    states_at_crash = {r.rid: r.state for r in sched.requests}
    pre_crash = {r.rid: list(r.tokens_out) for r in sched.requests}

    # fresh process: new Server + scheduler from snapshot + params ckpt
    restored = S.restore_scheduler(
        path, cfg, ParallelCtx(capacity_factor=8.0),
        jax.tree.map(jnp.copy, params), faults=plan,
    )
    res = restored.run()
    return ref, res, pre_crash, states_at_crash, restored


def test_crash_restart_mid_stream(tmp_path):
    ref, res, pre, states, restored = _crash_run(tmp_path, crash_step=4)
    # the crash hit an interesting cross-section of lifecycles
    assert "DECODING" in states.values()
    assert "QUEUED" in states.values()
    for rid, want in ref.items():
        got = res[rid]
        # the post-restore stream extends the pre-crash prefix exactly
        np.testing.assert_array_equal(got[: len(pre[rid])], pre[rid])
        np.testing.assert_array_equal(got, want)
    assert all(r.state == FINISHED for r in restored.requests)
    # the crash is not charged against preemption budgets
    crash_victims = [r for r in restored.requests
                     if states[r.rid] == "DECODING"]
    assert crash_victims


def test_crash_restart_with_chaos_and_pending_migrations(tmp_path):
    """Crash landing in the middle of the seed-14 chaos plan (after the
    death, before the revival): the snapshot carries a non-trivial
    placement table and dead set, the remaining faults (revival included)
    re-fire after restore, and parity still holds."""
    ref, res, pre, states, restored = _crash_run(
        tmp_path, crash_step=5, with_chaos=True)
    for rid, want in ref.items():
        np.testing.assert_array_equal(res[rid], want)
    fired = {d[0] for s, k, d in restored.events if k == "fault"}
    assert "device_revival" in fired, "post-crash faults must re-fire"
    srv = restored.server
    assert not srv.state.dead
    srv.table.check()


def test_periodic_snapshot_cadence(tmp_path):
    """SchedulerConfig(snapshot_every=k) snapshots at tick boundaries;
    restoring from the *last periodic* snapshot (not a crash-tick one)
    also reproduces the uninterrupted streams."""
    from repro.runtime.scheduler import SchedulerConfig

    cfg = _moe_cfg()
    params = T.init_params(RNG, cfg)
    prompts = _prompts(cfg, [5, 8, 6], seed=7)
    scfg = dict(pool_pages=10, alpha=0.1, **MOE_KW)
    ref_sched = RequestScheduler(_server(cfg, params, batch=2, **scfg))
    for i, p in enumerate(prompts):
        ref_sched.submit(p, max_new_tokens=5, arrival=i)
    ref = ref_sched.run()

    path = os.path.join(str(tmp_path), "periodic.npz")
    sched = RequestScheduler(
        _server(cfg, params, batch=2, **scfg),
        SchedulerConfig(snapshot_every=3, snapshot_path=path),
    )
    for i, p in enumerate(prompts):
        sched.submit(p, max_new_tokens=5, arrival=i)
    sched.run()
    assert sched.last_snapshot is not None
    assert os.path.exists(path) and os.path.exists(path + ".meta")
    snap = S.load_snapshot(path)
    assert snap.step_no % 3 == 0
    restored = S.restore_scheduler(
        snap, cfg, ParallelCtx(capacity_factor=8.0),
        jax.tree.map(jnp.copy, params),
    )
    res = restored.run()
    for rid, want in ref.items():
        np.testing.assert_array_equal(res[rid], want)


# ---------------------------------------------------------------------------
# satellite: StepTimer + restore_elastic glue
# ---------------------------------------------------------------------------

def test_step_timer_ratio_before_first_step():
    t = StepTimer()
    assert t.ema is None
    assert t.ratio == 1.0
    assert not t.is_straggling


def test_step_timer_ema_and_straggler_threshold(monkeypatch):
    clock = iter([0.0, 1.0,    # step 1: dt = 1.0 (seeds the EMA)
                  1.0, 2.0,    # step 2: dt = 1.0 (healthy)
                  2.0, 4.0])   # step 3: dt = 2.0 (> 1.5x EMA)
    monkeypatch.setattr("repro.runtime.elastic.time.monotonic",
                        lambda: next(clock))
    t = StepTimer(alpha=0.9, threshold=1.5)
    with t:
        pass
    assert t.ema == pytest.approx(1.0)
    assert not t.is_straggling and t.ratio == pytest.approx(1.0)
    with t:
        pass
    assert t.ema == pytest.approx(1.0)
    with t:
        pass
    # EMA folds the outlier in at (1 - alpha) *before* the ratio is read
    assert t.ema == pytest.approx(0.9 * 1.0 + 0.1 * 2.0)
    assert t.is_straggling          # last 2.0 > 1.5 * 1.1
    assert t.ratio == pytest.approx(2.0 / 1.1, rel=1e-6)


def test_step_timer_zero_ema_ratio(monkeypatch):
    monkeypatch.setattr("repro.runtime.elastic.time.monotonic", lambda: 5.0)
    t = StepTimer()
    with t:
        pass
    assert t.ema == 0.0
    assert t.ratio == 1.0          # guarded: no division by zero
    assert not t.is_straggling


def test_restore_elastic_onto_different_mesh_shape(tmp_path):
    """Checkpoints written with no mesh restore onto a fresh (1, 1) mesh:
    arrays come back bitwise equal and placed under the new shardings."""
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.launch.mesh import make_mesh
    from repro.runtime.checkpoint import CheckpointManager

    state = {"w": np.arange(12, dtype=np.float32).reshape(3, 4),
             "b": np.ones(4, np.float32)}
    mgr = CheckpointManager(str(tmp_path), keep=2)
    mgr.save(7, state, extra={"data_step": 7})

    mesh = make_mesh((1, 1), ("data", "model"))

    def sharding_fn(mesh, template):
        return jax.tree.map(
            lambda _: NamedSharding(mesh, PartitionSpec()), template
        )

    restored, meta = restore_elastic(mgr, state, mesh, sharding_fn)
    assert meta["step"] == 7 and meta["data_step"] == 7
    for k in state:
        np.testing.assert_array_equal(np.asarray(restored[k]), state[k])
        assert restored[k].sharding.mesh.shape == {"data": 1, "model": 1}
