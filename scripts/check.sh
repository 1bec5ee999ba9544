#!/usr/bin/env bash
# One-command gate: tier-1 tests + interpret-mode kernel & bench smokes +
# the bench baseline regression check.
#
#   ./scripts/check.sh          # fast tier (-m "not slow") + smokes + baseline
#   ./scripts/check.sh --all    # full matrix incl. slow multidevice tests
#   ./scripts/check.sh --lint   # ruff only (what the CI lint job runs)
#   ./scripts/check.sh -k gmm   # extra args forwarded to the tier-1 pytest
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

if [[ "${1:-}" == "--lint" ]]; then
  echo "== lint: ruff check =="
  if ! python -m ruff --version >/dev/null 2>&1; then
    echo "check.sh --lint: ruff is not installed in this environment." >&2
    echo "Install it with:  pip install ruff  (see requirements-dev.txt)" >&2
    exit 1
  fi
  python -m ruff check .
  echo "LINT OK"
  exit 0
fi

# Fail early with a readable message when the runtime dependency is absent
# (a bare 'ModuleNotFoundError: jax' traceback from deep inside pytest
# collection is the alternative).
if ! python -c "import jax" >/dev/null 2>&1; then
  echo "check.sh: the 'jax' package is missing from this Python environment." >&2
  echo "This repo needs jax + jaxlib (CPU is fine; kernels run in interpret" >&2
  echo "mode off-TPU). Install with:  pip install jax jaxlib" >&2
  exit 1
fi

MARK=(-m "not slow")
TIER="fast tier (-m 'not slow'; --all for the full matrix)"
if [[ "${1:-}" == "--all" ]]; then
  MARK=()
  TIER="full matrix"
  shift
fi

echo "== tier-1: pytest [$TIER] =="
python -m pytest -x -q ${MARK[@]+"${MARK[@]}"} "$@"

echo "== kernel smoke (interpret mode) =="
python - <<'EOF'
import jax, jax.numpy as jnp, numpy as np
from repro.kernels.gmm.ops import expert_ffn_gather, expert_ffn_ragged
from repro.kernels.gmm.ref import expert_ffn_gather_ref, expert_ffn_ragged_ref
from repro.kernels.registry import attend, decode_attend
from repro.models.attention import causal_mask, gqa_attend

rng = jax.random.PRNGKey(0)
ks = jax.random.split(rng, 4)
x = jax.random.normal(ks[0], (4, 16, 8))
wg = jax.random.normal(ks[1], (4, 8, 12)) * 0.1
wu = jax.random.normal(ks[2], (4, 8, 12)) * 0.1
wd = jax.random.normal(ks[3], (4, 12, 8)) * 0.1
gs = jnp.asarray([0, 5, 16, 3], jnp.int32)
np.testing.assert_allclose(
    np.asarray(expert_ffn_ragged(x, wg, wu, wd, gs)),
    np.asarray(expert_ffn_ragged_ref(x, wg, wu, wd, gs)),
    rtol=1e-5, atol=1e-5)

# fused dispatch-gather: flat rows + per-bucket offsets, no padded buffer
rows = jax.random.normal(ks[0], (24, 8))
offs = jnp.asarray([0, 0, 5, 21], jnp.int32)
np.testing.assert_allclose(
    np.asarray(expert_ffn_gather(rows, wg, wu, wd, offs, gs, capacity=16)),
    np.asarray(expert_ffn_gather_ref(rows, wg, wu, wd, offs, gs, 16)),
    rtol=1e-5, atol=1e-5)

# compact combine leg: scatter epilogue + metadata combine — live rows of
# the flat output must match the compact oracle, and rows the combine
# drops may hold NaN garbage without poisoning any kept token
from repro.kernels.gmm.ops import expert_ffn_gather_compact
from repro.kernels.gmm.ref import expert_ffn_compact_ref
from repro.parallel.collectives import combine_from_rows
compact = np.asarray(
    expert_ffn_gather_compact(rows, wg, wu, wd, offs, gs, capacity=16))
oracle = np.asarray(expert_ffn_compact_ref(rows, wg, wu, wd, offs, gs, 16))
for off, cnt in zip(np.asarray(offs), np.asarray(gs)):
    np.testing.assert_allclose(
        compact[off:off+cnt], oracle[off:off+cnt], rtol=1e-5, atol=1e-5)
yf = jnp.asarray(oracle).at[23].set(jnp.nan)  # garbage in a dropped row
cmb = combine_from_rows(
    yf, jnp.asarray([[0], [5], [23]]), jnp.asarray([[True], [True], [False]]),
    jnp.ones((3, 1)))
assert np.isfinite(np.asarray(cmb)).all(), "dropped-row garbage leaked into combine"

# fully-fused single-kernel FFN: all three matmuls in one Pallas call, the
# SwiGLU hidden tile never leaves VMEM — live rows must match both the
# two-kernel gather+scatter composition and the oracle
from repro.kernels.gmm.ops import expert_ffn_fused
from repro.kernels.gmm.ref import expert_ffn_fused_ref
fused = np.asarray(
    expert_ffn_fused(rows, wg, wu, wd, offs, gs, capacity=16))
fref = np.asarray(expert_ffn_fused_ref(rows, wg, wu, wd, offs, gs, 16))
for off, cnt in zip(np.asarray(offs), np.asarray(gs)):
    np.testing.assert_allclose(
        fused[off:off+cnt], compact[off:off+cnt], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        fused[off:off+cnt], fref[off:off+cnt], rtol=1e-5, atol=1e-5)

q = jax.random.normal(ks[0], (1, 32, 4, 16))
k = jax.random.normal(ks[1], (1, 32, 2, 16))
v = jax.random.normal(ks[2], (1, 32, 2, 16))
np.testing.assert_allclose(
    np.asarray(attend(q, k, v, causal=True)),
    np.asarray(gqa_attend(q, k, v, causal_mask(32))),
    rtol=2e-5, atol=2e-5)

valid = (jnp.arange(32)[None, :] < 20).astype(jnp.int32)
out = decode_attend(q[:, 0], k, v, valid)
assert np.isfinite(np.asarray(out)).all()

# paged decode: block-table walk over a shared page pool must match the
# dense masked path (same lengths, identity table)
from repro.kernels.registry import decode_attend_paged
from repro.kernels.flash_decode.ref import decode_ref
bs = 8
pool_k = k.reshape(4, bs, 2, 16)
pool_v = v.reshape(4, bs, 2, 16)
tables = jnp.arange(4, dtype=jnp.int32).reshape(1, 4)
lengths = jnp.asarray([20], jnp.int32)
np.testing.assert_allclose(
    np.asarray(decode_attend_paged(q[:, 0], pool_k, pool_v, tables, lengths)),
    np.asarray(decode_ref(q[:, 0], k, v, valid)),
    rtol=2e-5, atol=2e-5)

# flash-decode partials over two disjoint halves LSE-merge to the full path
from repro.kernels.registry import decode_attend_partials
a1, m1, l1 = decode_attend_partials(q[:, 0], k[:, :16], v[:, :16], valid[:, :16])
a2, m2, l2 = decode_attend_partials(q[:, 0], k[:, 16:], v[:, 16:], valid[:, 16:])
mm = jnp.maximum(m1, m2)
num = a1 * jnp.exp(m1 - mm)[..., None] + a2 * jnp.exp(m2 - mm)[..., None]
den = l1 * jnp.exp(m1 - mm) + l2 * jnp.exp(m2 - mm)
np.testing.assert_allclose(
    np.asarray(num / jnp.maximum(den, 1e-30)[..., None]),
    np.asarray(decode_ref(q[:, 0], k, v, valid)),
    rtol=2e-5, atol=2e-5)
print("kernel smoke OK")
EOF

echo "== serving chaos smoke (seeded fault injection) =="
python - <<'EOF'
# Continuous-batching loop under a seeded fault plan: pool pressure forces
# preemption, a NaN step forces a requeue-and-recompute — every request
# must finish with tokens bit-identical to its sequential fault-free run.
import dataclasses
import numpy as np, jax, jax.numpy as jnp
from repro.configs import get_config, smoke
from repro.models import transformer as T
from repro.parallel.ctx import ParallelCtx
from repro.runtime.faults import Fault, FaultPlan, NAN_LOGITS, POOL_PRESSURE, POOL_RELEASE
from repro.runtime.scheduler import FINISHED, RequestScheduler
from repro.runtime.serve import Server, ServeConfig

cfg = smoke(get_config("llama3.2-1b"))
params = T.init_params(jax.random.PRNGKey(0), cfg)
ctx = ParallelCtx()
rng = np.random.default_rng(0)
prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
           for n in (5, 9, 6)]

def sched_for(batch, pool, faults=None):
    srv = Server(cfg, ctx, jax.tree.map(jnp.copy, params),
                 ServeConfig(max_seq=64, batch=batch, paged=True,
                             page_size=8, pool_pages=pool))
    return RequestScheduler(srv, faults=faults)

ref = []
for p in prompts:
    s = sched_for(1, 64)
    r = s.submit(p, max_new_tokens=6)
    s.run()
    assert r.state == FINISHED, (r.state, r.error)
    ref.append(list(r.tokens_out))

plan = FaultPlan([
    Fault(step=2, kind=POOL_PRESSURE, pages=4),
    Fault(step=3, kind=NAN_LOGITS, slots=(0,)),
    Fault(step=7, kind=POOL_RELEASE, pages=4),
])
s = sched_for(2, 8, faults=plan)
reqs = [s.submit(p, max_new_tokens=6, arrival=i) for i, p in enumerate(prompts)]
s.run()
assert s.n_preempted > 0, "fault plan should have forced a preemption"
for i, r in enumerate(reqs):
    assert r.state == FINISHED, (i, r.state, r.error)
    assert list(r.tokens_out) == ref[i], (i, r.tokens_out, ref[i])
print(f"chaos smoke OK ({s.n_preempted} preemptions, parity held)")
EOF

echo "== live stepped migration smoke (slice schedule + parity) =="
python - <<'EOF'
# Skewed router traffic trips the Eq. 2 trigger; the resulting migration
# must spread its weight copy over >= 3 decode ticks, commit only after the
# last slice, and leave the generated tokens bit-identical to both the
# instantaneous baseline (migration_slices=0) and the dense reference.
import dataclasses
import numpy as np, jax, jax.numpy as jnp
from repro.configs import get_config, smoke
from repro.models import transformer as T
from repro.parallel.ctx import ParallelCtx
from repro.runtime.serve import Server, ServeConfig

cfg = dataclasses.replace(
    smoke(get_config("dbrx-132b")), n_experts=4, experts_per_token=2)
params = T.init_params(jax.random.PRNGKey(0), cfg)
router = np.asarray(params["layers"]["moe"]["router"])
scale = np.ones(router.shape[-1], router.dtype)
scale[[0, 1]] = 8.0  # sustained hot experts
params["layers"]["moe"]["router"] = jnp.asarray(router * scale)

def serve(**kw):
    srv = Server(cfg, ParallelCtx(capacity_factor=8.0),
                 jax.tree.map(jnp.copy, params),
                 ServeConfig(max_seq=32, batch=2, **kw))
    out = srv.generate(jnp.ones((2, 6), jnp.int32), 12)
    return srv, np.asarray(out)

vep = dict(slots_per_device=3, virtual_ep=4, alpha=0.1)
_, dense = serve()
inst_srv, inst = serve(migration_slices=0, **vep)
step_srv, stepped = serve(migration_slices=4, **vep)
assert inst_srv.migrations > 0 and step_srv.migrations > 0
np.testing.assert_array_equal(dense, inst)
np.testing.assert_array_equal(dense, stepped)
for rec in step_srv.driver.history:
    assert len(set(rec["issue_ticks"])) >= 3, rec
    assert rec["committed"] > max(rec["issue_ticks"]), rec
print(f"migration smoke OK ({step_srv.migrations} stepped migrations, "
      "parity held)")
EOF

echo "== crash-and-restore smoke (snapshot at step k, bit-identical resume) =="
python - <<'EOF'
# Kill the serving loop after step k via a crash_restart fault, rebuild a
# *fresh* Server + scheduler from the on-disk snapshot plus the params
# checkpoint, and require the concatenated pre/post-crash token streams to
# equal the uninterrupted run's, for every request (including one still
# QUEUED at the crash).
import dataclasses, os, tempfile
import numpy as np, jax, jax.numpy as jnp
from repro.configs import get_config, smoke
from repro.models import transformer as T
from repro.parallel.ctx import ParallelCtx
from repro.runtime import snapshot as S
from repro.runtime.faults import CRASH_RESTART, Fault, FaultPlan, SimulatedCrash
from repro.runtime.scheduler import FINISHED, RequestScheduler
from repro.runtime.serve import Server, ServeConfig

cfg = dataclasses.replace(
    smoke(get_config("dbrx-132b")), n_experts=4, experts_per_token=2)
params = T.init_params(jax.random.PRNGKey(0), cfg)
rng = np.random.default_rng(3)
prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
           for n in (5, 9, 4, 7)]
arrivals = [0, 1, 2, 6]   # the last request is still QUEUED at the crash
scfg = dict(max_seq=64, paged=True, page_size=8, pool_pages=10, alpha=0.1,
            slots_per_device=3, virtual_ep=4, batch=2)

def sched_for(faults=None):
    srv = Server(cfg, ParallelCtx(capacity_factor=8.0),
                 jax.tree.map(jnp.copy, params), ServeConfig(**scfg))
    s = RequestScheduler(srv, faults=faults)
    for p, a in zip(prompts, arrivals):
        s.submit(p, max_new_tokens=6, arrival=a)
    return s

ref = sched_for().run()

k = 4
path = os.path.join(tempfile.mkdtemp(), "snap.npz")
plan = FaultPlan([Fault(step=k, kind=CRASH_RESTART, path=path)])
s = sched_for(faults=plan)
try:
    s.run()
    raise SystemExit("crash fault never fired")
except SimulatedCrash as e:
    assert e.step == k and os.path.exists(path)
pre = {r.rid: list(r.tokens_out) for r in s.requests}

restored = S.restore_scheduler(
    path, cfg, ParallelCtx(capacity_factor=8.0),
    jax.tree.map(jnp.copy, params), faults=plan)
res = restored.run()
for rid, want in ref.items():
    got = np.asarray(res[rid])
    assert np.array_equal(got[:len(pre[rid])], pre[rid]), (rid, "prefix torn")
    assert np.array_equal(got, want), (rid, got, want)
assert all(r.state == FINISHED for r in restored.requests)
print(f"crash-restore smoke OK (killed at step {k}, "
      f"{len(prompts)} streams bit-identical)")
EOF

echo "== chunked-admission smoke (prefill lane in the decode step) =="
python - <<'EOF'
# A long prompt admits through the decode step's prefill lane (one chunk
# per tick) while a live batch keeps emitting tokens. The live streams never
# stall more than one tick, the newcomer's first token must land within
# ceil(len/chunk)+1 ticks of admission, every stream must be bit-identical
# to splice admission, and ONE compiled step program must have served
# idle, decode-only and decode+chunk ticks alike.
import numpy as np, jax, jax.numpy as jnp
from repro.configs import get_config, smoke
from repro.models import transformer as T
from repro.parallel.ctx import ParallelCtx
from repro.runtime.scheduler import FINISHED, RequestScheduler
from repro.runtime.serve import Server, ServeConfig

cfg = smoke(get_config("llama3.2-1b"))
params = T.init_params(jax.random.PRNGKey(0), cfg)
rng = np.random.default_rng(0)
prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
           for n in (4, 6, 40)]   # two live decoders, then one long prompt
CHUNK = 8

def run(prefill_chunk):
    srv = Server(cfg, ParallelCtx(), jax.tree.map(jnp.copy, params),
                 ServeConfig(max_seq=64, batch=3, paged=True, page_size=8,
                             pool_pages=32, prefill_chunk=prefill_chunk))
    s = RequestScheduler(srv)
    reqs = [s.submit(p, max_new_tokens=10, arrival=[0, 0, 2][i])
            for i, p in enumerate(prompts)]
    s.run()
    assert all(r.state == FINISHED for r in reqs), [r.state for r in reqs]
    return srv, s, reqs

srv_a, s_a, _ = run(None)
srv_b, s_b, reqs = run(CHUNK)
for rid, want in s_a.results().items():
    assert np.array_equal(s_b.results()[rid], want), (rid, "stream diverged")
stats = s_b.stats()
assert stats["max_stall_ticks"] == 0, stats  # O(1) inter-token gap, always
long = reqs[2]
ticks = long.first_token_step - long.admitted_step + 1
bound = -(-len(prompts[2]) // CHUNK) + 1
assert ticks <= bound, (ticks, bound)
assert srv_b._decode._cache_size() == 1, srv_b._decode._cache_size()
print(f"chunked-admission smoke OK (ttft {ticks} <= {bound} ticks, "
      f"stall 0, parity held, 1 program)")
EOF

echo "== chunked-EP overlap smoke (pipelined dispatch parity + exposed-comm accounting) =="
XLA_FLAGS="--xla_force_host_platform_device_count=8${XLA_FLAGS:+ $XLA_FLAGS}" python - <<'EOF'
# ep_chunks must be a pure schedule knob: on a real 4-way all_to_all (8
# fake CPU devices, 2x4 mesh), skewed routing at capacity_factor=1.0 must
# produce bit-identical outputs for ep_chunks in {1, 2}, on prefill and
# decode shapes alike; the analytic exposed-comm schedule from
# bench_kernels must sit strictly below the single-shot baseline for
# every K > 1; and a chunk count that does not divide the expert-group
# count must fail loudly at ServeConfig construction.
import sys
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.parallel.collectives import ep_moe_shardmap, uniform_placement
from repro.parallel.ctx import ParallelCtx

mesh = make_mesh((2, 4), ("data", "model"))
ep, spd = 4, 2
e = ep * spd
d, f = 16, 32
slot_w = {
    "w_gate": jax.random.normal(jax.random.PRNGKey(1), (e, d, f)) * 0.1,
    "w_up": jax.random.normal(jax.random.PRNGKey(2), (e, d, f)) * 0.1,
    "w_down": jax.random.normal(jax.random.PRNGKey(3), (e, f, d)) * 0.1,
}
slot_of, n_rep = uniform_placement(e, e)
k = 2
hot = jnp.asarray([0] * 6 + [1] * 4 + list(range(e)))  # skewed routing pool
for shape in ((2, 8), (8, 1)):
    b, s = shape
    x = jax.random.normal(jax.random.PRNGKey(4), (b, s, d))
    ids = jax.random.choice(jax.random.PRNGKey(5), hot, (b, s, k))
    w = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(6), (b, s, k)), -1)
    with mesh:
        base = None
        for K in (1, 2):
            ctx = ParallelCtx(mesh=mesh, use_kernels=True, ep_chunks=K)
            out = np.asarray(ep_moe_shardmap(
                x, ids, w, slot_w, slot_of, n_rep, ctx, 1.0, spd,
                decode=(s == 1)))
            assert np.all(np.isfinite(out))
            if base is None:
                base = out
            else:
                np.testing.assert_array_equal(
                    out, base,
                    err_msg=f"shape={shape} ep_chunks={K}: chunked dispatch "
                    "diverged from the single-shot path")

sys.path.insert(0, "benchmarks")
from bench_kernels import ep_chunk_cell_accounting
_, _, per_k = ep_chunk_cell_accounting(
    "smoke_skewed", 4, 4, 64, 128, 256, (1, 2, 4), False)
exposed = {int(kk): acc["exposed_comm_ms"] for kk, acc in per_k.items()}
assert exposed[2] < exposed[1] and exposed[4] < exposed[1], exposed

from repro.runtime.serve import ServeConfig
try:
    ServeConfig(max_seq=32, batch=2, slots_per_device=3, ep_chunks=2)
    raise SystemExit("ep_chunks=2 with 3 expert groups should have raised")
except ValueError as err:
    assert "ep_chunks" in str(err), err
print(f"chunked-EP smoke OK (bit parity K=2 on a 2x4 mesh, "
      f"exposed_comm_ms {exposed})")
EOF

echo "== kernel-dispatch bench smoke (interpret mode) =="
python benchmarks/bench_kernels.py --smoke > /dev/null
echo "bench smoke OK"

echo "== bench baseline regression check (deterministic columns) =="
python benchmarks/bench_kernels.py --check BENCH_kernels.json
echo "bench baseline OK"

echo "ALL CHECKS PASSED"
