"""The benchmark's engine: one cell, one seed, one measured window.

Everything a cell is made of is data found by name: ``BENCHMARK.json``
names the cell, its configuration file (``configs/``), its traffic mix
(``traffic/<mix>.json``), its limits (``limits/<cell>.json``) and its
per-layer metrics (``metrics/<metric>.py``). This module only drives them:

1. set-up — weights from the seed (``weights.py``), the program's
   ``Server`` and ``RequestScheduler``, then the cell's own traffic until
   every shape the window uses has run and the batch has filled;
2. the window — ``RequestScheduler.step()`` as fast as the system goes
   (closed loop) or at the traffic's arrival times (open loop), for
   ``--seconds``, with every token stamped by the host clock as it reaches
   the host;
3. the check — the served tokens of a seeded sample of requests finished in
   the window, and the program's logits that emitted them, scored by the
   plain reference (``reference.py``) after the program's state is freed.

From the program it takes the system under test (``Server``,
``RequestScheduler``, its configuration registry and sharding rules) and
its host counters: request states and token lists, ``Server.migrations``,
the per-tick expert counts, read by wrapping ``server.state.observe``, and
each tick's logits at a seeded sample of vocabulary ids, read by wrapping
``Server.decode``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import time
from pathlib import Path

import numpy as np

import counts
import reference
import devtrace as tr
import traffic as tf
import weights

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
DRAIN_S = 60.0           # how long past the window an open-loop run waits
LOGIT_IDS = 256          # vocabulary ids at which the check reads the logits
EXPERT_FFN = r"^expert_ffn"   # the expert-FFN Pallas kernels, by pallas_call name
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


# ---------------------------------------------------------------------------
# the cell, as data
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Spec:
    cell: dict
    config: dict
    mix: dict
    limits: dict
    end_to_end: list
    per_layer: list

    @property
    def model(self) -> dict:
        return self.config["model"]


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_spec(workload: str, root: Path = ROOT, base: Path = HERE) -> Spec:
    """The cell ``workload`` of ``root/BENCHMARK.json``, with its files
    (traffic, limits) found by name under ``base``."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return Spec(
        cell=cell,
        config=json.loads((root / conf["file"]).read_text()),
        mix=json.loads((base / "traffic" / f"{cell['traffic']}.json").read_text()),
        limits=json.loads((base / "limits" / f"{workload}.json").read_text()),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, workload)],
    )


def load_metric(name: str, base: Path = HERE):
    """``metrics/<name>.py``'s ``read`` function."""
    path = base / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def program_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file, checked
    against the sizes the reference reads."""
    from repro.configs import get_config

    cfg = dataclasses.replace(get_config(conf["arch"]), **conf["overrides"])
    m = conf["model"]
    have = {
        "n_layers": cfg.n_layers, "d_model": cfg.d_model, "n_heads": cfg.n_heads,
        "n_kv_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim_,
        "n_experts": cfg.n_experts, "top_k": cfg.experts_per_token,
        "expert_d_ff": cfg.moe_d_ff_, "vocab": cfg.vocab_size,
        "rope_theta": cfg.rope_theta, "norm_eps": cfg.norm_eps,
        "sliding_window": cfg.sliding_window,
    }
    bad = {k: (v, m.get(k, 0)) for k, v in have.items() if v != m.get(k, 0)}
    if bad:
        raise ValueError(f"program config differs from {conf['name']}: {bad}")
    return cfg


# ---------------------------------------------------------------------------
# the served system
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Info:
    """Host-clock record of one request."""

    req: object
    due: float | None = None        # seconds, host clock (open loop)
    admitted: float | None = None
    first: float | None = None
    last: float | None = None
    n: int = 0
    in_window: bool = False
    logits: list | None = dataclasses.field(default_factory=list)  # per token, at the ids


@dataclasses.dataclass
class Tick:
    t0: float
    t1: float
    tokens: int = 0
    live: int = 0                   # rows PREFILLING or DECODING at decode
    decode_keys: list = dataclasses.field(default_factory=list)
    chunk: tuple | None = None      # (start, length, final)
    counts: np.ndarray | None = None
    rows: dict = dataclasses.field(default_factory=dict)   # rid -> batch row, DECODING
    lane_rid: int | None = None     # the request whose chunk rides the lane
    picked: object = None           # (B, LOGIT_IDS) decode logits at the ids
    lane_picked: object = None      # (1, LOGIT_IDS) final chunk's logits at the ids


def vocab_ids(seed: int, vocab: int) -> np.ndarray:
    """The seeded sample of vocabulary ids at which the check compares the
    program's logits with the reference's."""
    rng = np.random.default_rng([int(seed), 0x1D5])
    return np.sort(rng.choice(vocab, size=min(LOGIT_IDS, vocab), replace=False)).astype(np.int32)


class System:
    """The program's served path, built for one cell and one seed, with the
    benchmark's host counters attached."""

    def __init__(self, spec: Spec, seed: int, *, use_kernels="auto", devices=None, log=print):
        import jax

        from repro.parallel.ctx import ParallelCtx
        from repro.runtime.scheduler import RequestScheduler
        from repro.runtime.serve import Server, ServeConfig

        self.spec, self.log = spec, log
        conf, mix, m = spec.config, spec.mix, spec.model
        self.cfg = program_config(conf)
        devices = list(devices or jax.devices()[: spec.cell["chips"]])
        self.devices = devices
        mesh = None
        if conf["mesh"]:
            from jax.sharding import AxisType, Mesh

            mesh = Mesh(np.array(devices).reshape(conf["mesh"]), ("data", "model"),
                        axis_types=(AxisType.Auto, AxisType.Auto))
        self.mesh = mesh
        serve = dict(conf["serve"])
        n_model = mesh.shape["model"] if mesh is not None else 1
        n_slots = serve["slots_per_device"] * (serve.get("virtual_ep") or n_model)
        # Capacity = slots / top-k: a bucket holds every copy of a dispatch,
        # so no routed copy is ever dropped.
        ctx = ParallelCtx(mesh=mesh, capacity_factor=n_slots / m["top_k"],
                          use_kernels=use_kernels)
        shardings = None
        if mesh is not None:
            from repro.parallel.sharding import params_specs, to_shardings

            shardings = to_shardings(mesh, params_specs(self.cfg, weights.shapes(m), ctx))
        self.shardings = shardings
        t = time.perf_counter()
        params = weights.make(m, seed, out_shardings=shardings)
        jax.block_until_ready(params)
        log(f"weights: {weights.n_bytes(params) / 1e9:.3f} GB {m['dtype']} from seed "
            f"{seed} ({time.perf_counter() - t:.2f} s)")
        scfg = ServeConfig(max_seq=mix["max_seq"], batch=mix["batch"], paged=True,
                           page_size=mix["page_size"], prefill_chunk=mix["prefill_chunk"],
                           **serve)
        t = time.perf_counter()
        with self.in_mesh():
            self.server = Server(self.cfg, ctx, params, scfg)
            del params
            self.sched = RequestScheduler(self.server)
        log(f"server: {n_slots} expert slots over {self.server.ep} EP groups "
            f"({time.perf_counter() - t:.2f} s)")
        self.ids = vocab_ids(seed, m["vocab"])
        self._tick: Tick | None = None
        self._attach()

    def in_mesh(self):
        """The mesh as the ambient one (the program's sharding constraints
        name its axes), or nothing on one chip."""
        return self.mesh if self.mesh is not None else contextlib.nullcontext()

    def _attach(self):
        """Host counters: wrap the server's existing calls from here."""
        import jax
        import jax.numpy as jnp

        srv = self.server
        if srv.state is not None:
            observe = srv.state.observe

            def observed(c):
                if self._tick is not None:
                    self._tick.counts = np.asarray(c, np.float64).copy()
                return observe(c)

            srv.state.observe = observed
        chunk_operand = srv.chunk_operand

        def chunk_seen(slot, tokens, start, length):
            if self._tick is not None:
                req = self.sched.slots[slot]
                final = req is not None and start + length >= req.context_len
                self._tick.chunk = (int(start), int(length), bool(final))
                self._tick.lane_rid = req.rid if req is not None else None
            return chunk_operand(slot, tokens, start, length)

        srv.chunk_operand = chunk_seen
        decode = srv.decode
        ids = jnp.asarray(self.ids)
        pick = jax.jit(lambda logits, ids: logits[:, -1, :][:, ids].astype(jnp.float32))

        def decoded(token, cache, chunk=None):
            logits, cache = decode(token, cache, chunk=chunk)
            tick = self._tick
            if tick is not None:
                # copied to the host behind the scheduler's own logits, so
                # reading them after the tick waits for nothing
                tick.picked = pick(logits, ids)
                tick.picked.copy_to_host_async()
                if tick.chunk is not None and tick.chunk[2]:
                    tick.lane_picked = pick(srv.last_chunk_logits, ids)
                    tick.lane_picked.copy_to_host_async()
            return logits, cache

        srv.decode = decoded

    def n_replicas(self):
        return None if self.server.table is None else np.asarray(self.server.table.n_replicas)

    def step(self, tick: Tick) -> list:
        """One scheduler tick, recorded into ``tick``."""
        from repro.runtime.scheduler import DECODING

        sched = self.sched
        decoding = [(slot, r) for slot, r in enumerate(sched.slots)
                    if r is not None and r.state == DECODING]
        tick.decode_keys = [len(r.prompt) + len(r.tokens_out) for _, r in decoding]
        tick.rows = {r.rid: slot for slot, r in decoding}
        self._tick = tick
        try:
            return sched.step()
        finally:
            self._tick = None

    def warm_eager(self):
        """Run, once, the small host-side device ops whose shapes the
        traffic decides, so none compiles in the window: the idle prefill
        lane's operand, the release of ``n`` slots at once
        (``Server.decode`` pins their lengths with an index array of that
        length) for every ``n``, and one slice of an expert migration per
        weight (a copy of rows onto themselves)."""
        import jax
        import jax.numpy as jnp

        from repro.runtime import migration_driver as md

        jax.block_until_ready(self.server.noop_chunk())
        lengths = self.sched.cache["layers"]["lengths"]
        for n in range(1, self.spec.mix["batch"] + 1):
            idx = jnp.asarray(list(range(n)))
            jax.block_until_ready(lengths.at[:, idx].set(0))
        drv = self.server.driver
        if drv is not None:
            moe = self.server._moe()
            for name in md.MOE_WEIGHTS:
                w = moe[name]
                rows = min(w.shape[2], -(-w.shape[2] // drv.min_slices))
                moe[name] = jax.block_until_ready(md._copy_row_slice(
                    w, md._i32(0), md._i32(0), md._i32(0), rows=rows))

    def free(self):
        self.server = self.sched = None
        gc.collect()


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def pct(xs, q) -> float:
    xs = np.asarray(xs, np.float64)
    return float(np.percentile(xs, q)) if xs.size else float("nan")


class Run:
    """Set-up, window and drain of one cell at one seed."""

    def __init__(self, system: System, seed: int, clock=time.perf_counter, log=print):
        self.sys, self.log, self.clock = system, log, clock
        spec = system.spec
        self.traffic = tf.Traffic(spec.mix, seed, spec.model["vocab"])
        self.infos: dict[int, Info] = {}
        self.ticks: list[Tick] = []
        self.gaps: list[float] = []
        self.lateness: list[float] = []
        self.t_start = None          # open loop: host time of the phase's second 0
        self.stream: list = []       # open loop: the phase's requests not yet sent
        self.w0 = self.w1 = None
        self.compiles = 0

    # -- requests ----------------------------------------------------------

    def submit(self, gen: tf.Req, now: float, due: float | None = None) -> Info:
        sched = self.sys.sched
        req = sched.submit(gen.prompt, max_new_tokens=gen.out_len, arrival=sched.step_no)
        info = Info(req, due=due,
                    in_window=self.w0 is not None and (due if due is not None else now) >= self.w0)
        self.infos[req.rid] = info
        if due is not None:
            self.lateness.append(now - due)
        return info

    def _record(self, tick: Tick, finished: list):
        """Stamp the tokens this tick brought to the host."""
        from repro.runtime.scheduler import QUEUED

        sched = self.sys.sched
        seen = [r for r in sched.slots if r is not None] + finished
        if sched._prefilling is not None and sched._prefilling not in seen:
            seen.append(sched._prefilling)
        tick.live = sum(1 for r in sched.slots if r is not None)
        in_w = self.w0 is not None and self.w1 is None
        picked = None if tick.picked is None else np.asarray(tick.picked)
        lane = None if tick.lane_picked is None else np.asarray(tick.lane_picked)
        tick.picked = tick.lane_picked = None      # the device copies go
        for r in seen:
            info = self.infos[r.rid]
            if info.admitted is None and r.state != QUEUED:
                info.admitted = tick.t0
            n = len(r.tokens_out)
            if n == info.n:
                continue
            tick.tokens += n - info.n
            self._keep_logits(info, tick, picked, lane, n - info.n)
            if info.n == 0:
                info.first = tick.t1
            elif in_w:
                self.gaps.append(tick.t1 - info.last)
            info.n, info.last = n, tick.t1

    @staticmethod
    def _keep_logits(info: Info, tick: Tick, picked, lane, new: int):
        """Keep the program's logits (at the ids) that emitted this tick's
        token of ``info``: its decode row's, or the lane's on the request's
        final chunk. A token whose logits cannot be told apart leaves the
        request without logits (the check then leaves it out of that
        comparison and says so)."""
        rid = info.req.rid
        if info.logits is None:
            return
        if new == 1 and lane is not None and tick.lane_rid == rid:
            info.logits.append(lane[0])
        elif new == 1 and picked is not None and rid in tick.rows:
            info.logits.append(picked[tick.rows[rid]])
        else:
            info.logits = None

    def tick(self) -> list:
        tick = Tick(self.clock(), 0.0)
        finished = self.sys.step(tick)
        tick.t1 = self.clock()
        self._record(tick, finished)
        self.ticks.append(tick)
        return finished

    # -- loops ---------------------------------------------------------------

    def _closed_fill(self):
        for _ in range(self.sys.spec.mix["clients"]):
            self.submit(self.traffic.next(), self.clock())

    def _closed_tick(self):
        for _ in self.tick():
            self.submit(self.traffic.next(), self.clock())

    def _open_arrivals(self, until: float):
        """Submit every request of the current phase due by now; when the
        system has nothing to do, sleep until the next one is due (or
        ``until``)."""
        sched = self.sys.sched
        if not self.stream:
            return
        nxt = self.stream[0].due + self.t_start
        idle = not any(r is not None for r in sched.slots) and not sched.queue
        if idle:
            wait = min(nxt, until) - self.clock()
            if wait > 0:
                time.sleep(wait)
        now = self.clock()
        while self.stream and self.stream[0].due + self.t_start <= now:
            gen = self.stream.pop(0)
            self.submit(gen, now, due=gen.due + self.t_start)
            now = self.clock()

    def warm(self, closed: bool):
        """Set-up traffic: the cell's own requests until every shape of the
        step has run and, in a closed loop, every batch row has been filled;
        in an open loop, one short request through two lane chunks, decode
        and retirement, then ``warm_s`` seconds of arrivals."""
        mix = self.sys.spec.mix
        self.sys.warm_eager()
        if closed:
            self._closed_fill()
            while max((t.live for t in self.ticks), default=0) < mix["batch"]:
                self._closed_tick()
            return
        # two lane chunks, two tokens: every shape of an open-loop tick
        first = self.submit(self.traffic.probe(mix["prefill_chunk"] + 1, 2), self.clock())
        while not first.req.done:
            self.tick()
        self.stream = self.traffic.phase(max(1, round(mix["rate"] * mix["warm_s"])), 0)
        self.t_start = self.clock()
        end = self.t_start + mix["warm_s"]
        while self.clock() < end:
            self._open_arrivals(end)
            if any(r is not None for r in self.sys.sched.slots):
                self.tick()

    def window(self, seconds: float, closed: bool, traced: bool = False):
        """The measured window: ``seconds`` of ticks, none started after it
        closes."""
        import jax

        counter = [0]

        def on_compile(event, *_a, **_k):
            if event in COMPILE_EVENTS:
                counter[0] += 1

        jax.monitoring.register_event_duration_secs_listener(on_compile)
        self.ticks_before = len(self.ticks)
        self.mig0 = self.sys.server.migrations
        if not closed:
            mix = self.sys.spec.mix
            self.stream = self.traffic.phase(int(mix["rate"] * seconds), 1)
        self.w0 = self.clock()
        self.t_start = self.w0
        end = self.w0 + seconds
        ann = jax.profiler.TraceAnnotation if traced else None
        while self.clock() < end:
            if not closed:
                self._open_arrivals(end)
                if not any(r is not None for r in self.sys.sched.slots) and not self.sys.sched.queue:
                    continue
            if ann:
                with ann(tr.TICK_SPAN):
                    self._closed_tick() if closed else self.tick()
            else:
                self._closed_tick() if closed else self.tick()
        self.w1 = self.ticks[-1].t1 if len(self.ticks) > self.ticks_before else self.clock()
        self.w1 = max(self.w1, end)
        jax.monitoring.unregister_event_duration_listener(on_compile)
        self.compiles = counter[0]
        self.migrations = self.sys.server.migrations - self.mig0

    def drain(self):
        """Open loop: keep ticking, with no new arrivals, until every
        request due in the window has its first token (at most
        ``DRAIN_S`` past the close)."""
        stop = self.w1 + DRAIN_S
        while self.clock() < stop:
            if all(i.first is not None or i.req.done for i in self.window_requests()):
                return
            self.tick()

    def window_requests(self) -> list[Info]:
        return [i for i in self.infos.values() if i.in_window]

    def window_ticks(self) -> list[Tick]:
        return [t for t in self.ticks[self.ticks_before:] if t.t1 <= self.w1]


# ---------------------------------------------------------------------------
# end-to-end numbers and host counters
# ---------------------------------------------------------------------------

def end_to_end(run: Run, setup_s: float) -> dict:
    """Every end-to-end number a cell can report, by name."""
    ticks = run.window_ticks()
    span = run.w1 - run.w0
    out = {
        "setup_s": setup_s,
        "tokens_per_s": sum(t.tokens for t in ticks) / span,
        "itl_p95_ms": 1e3 * pct(run.gaps, 95),
    }
    if not run.traffic.closed:
        ttft = [(i.first - i.due) if i.first is not None else np.inf
                for i in run.window_requests()]
        out["ttft_p95_ms"] = 1e3 * pct(ttft, 95)
        out["ttft_p50_ms"] = 1e3 * pct(ttft, 50)
    return out


@dataclasses.dataclass
class Counters:
    """What a per-layer metric reads: host counters of the window, the
    reduced trace (``None`` in an untraced run), the cell and the peaks."""

    run: Run
    spec: Spec
    peak: dict
    chips: int
    trace: tr.Reduced | None
    n_replicas: np.ndarray | None

    @property
    def model(self) -> dict:
        return self.spec.model

    def ticks(self) -> list[Tick]:
        return self.run.window_ticks()

    def flops(self, ticks) -> float:
        m = self.model
        total = 0.0
        for t in ticks:
            s, n, fin = t.chunk or (0, 0, False)
            total += counts.tick_flops(m, t.decode_keys, s, n, fin)
        return total

    def mfu(self, busy_only: bool) -> float:
        """Useful operations of the window over chips x peak x time: the
        whole window, or (``busy_only``) only the time inside ticks."""
        ticks = self.ticks()
        span = (sum(t.t1 - t.t0 for t in ticks) if busy_only
                else self.run.w1 - self.run.w0)
        return 100.0 * self.flops(ticks) / (span * self.chips * self.peak["bf16_flops"])

    def expert_ffn_roofline(self) -> float | None:
        """Least time the useful expert-FFN work could take at the chips'
        peaks, over the summed device time of the expert-FFN kernels."""
        t_kernel = self.trace.kernel_s(EXPERT_FFN)
        fl, by = self.expert_work(self.ticks())
        if t_kernel <= 0 or fl <= 0:
            return None
        t_min, _ = counts.roofline_s(fl, by, self.peak, self.chips)
        # kernel_s averages over the chips; the work is the whole step's
        return 100.0 * t_min / t_kernel

    def expert_work(self, ticks) -> tuple[float, float]:
        m = self.model
        fl = by = 0.0
        for t in ticks:
            if t.counts is None:
                continue
            fl += counts.expert_ffn_flops(m, t.counts)
            by += counts.expert_ffn_bytes(m, t.counts, self.n_replicas)
        return fl, by


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Served:
    """What the check needs of one request, kept after the program is freed."""

    prompt: np.ndarray
    tokens: np.ndarray
    logits: np.ndarray | None      # (tokens, LOGIT_IDS): the program's, at the ids


def sample(run: Run, seed: int) -> list:
    """A seeded sample of requests that finished in the window, the longest
    of them in it, up to the mix's ``check`` budget of served tokens."""
    from repro.runtime.scheduler import FINISHED

    chk = run.sys.spec.mix["check"]
    done = sorted((i for i in run.infos.values()
                   if i.req.state == FINISHED and i.last is not None
                   and run.w0 <= i.last <= run.w1), key=lambda i: i.req.rid)
    if not done:
        return []
    longest = max(done, key=lambda i: (len(i.req.prompt) + i.n, -i.req.rid))
    rest = [i for i in done if i is not longest]
    order = np.random.default_rng([int(seed), 0xC4EC]).permutation(len(rest))
    picked, tokens = [longest], longest.n
    for j in order:
        if len(picked) >= chk["max_requests"] or tokens >= chk["tokens"]:
            break
        picked.append(rest[j])
        tokens += rest[j].n
    return [Served(np.array(i.req.prompt), np.array(i.req.tokens_out, np.int32),
                   None if i.logits is None or len(i.logits) != i.n else np.stack(i.logits))
            for i in picked]


def gap_stats(gaps: list) -> dict:
    """Numbers of a run's per-position gaps (all sampled requests): the
    widest, the 99th percentile, the mean, and the share of positions whose
    served token is not the reference's first."""
    g = np.concatenate(gaps) if gaps else np.full(1, np.inf)
    return {"served_logit_gap": float(np.max(g)),
            "served_gap_p99": float(np.percentile(g, 99)),
            "served_gap_mean": float(np.mean(g)),
            "served_disagree_share": float(np.mean(g > 0))}


def logit_err(got: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Per position: the RMS distance of ``got``'s logits from the
    reference's over the sampled ids, as a share of the spread (standard
    deviation) of the reference's logits there."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return np.sqrt(np.mean((got - ref) ** 2, axis=1)) / np.std(ref, axis=1)


def err_stats(errs: list) -> dict:
    """Numbers of a run's per-position logit errors: the mean, the median
    and the largest (``inf`` where no position was read)."""
    e = np.concatenate(errs) if errs else np.full(1, np.inf)
    return {"served_logit_err_mean": float(np.mean(e)),
            "served_logit_err_median": float(np.median(e)),
            "served_logit_err_max": float(np.max(e))}


def check(spec: Spec, seed: int, picked: list, controls=(), shardings=None,
          log=print) -> dict:
    """Score the sample against the reference, on weights made anew from
    the seed (the program's state must be freed first; on a mesh they are
    laid out as the program had them, so each chip holds its share)."""
    m = spec.model
    w = weights.make(m, seed, out_shardings=shardings)
    ids = vocab_ids(seed, m["vocab"])
    gaps, errs, worst, tokens = [], [], [], 0
    ctl = {k: [] for k in controls}
    ctl_errs = {k: [] for k in controls}
    t = time.perf_counter()
    for p in picked:
        r = reference.served_gaps(w, m, p.prompt, p.tokens, spec.mix["max_seq"], controls,
                                  ids=ids)
        gaps.append(r["gap"])
        if p.logits is not None:
            errs.append(logit_err(p.logits, r["logits"]))
        tokens += len(p.tokens)
        j = int(np.argmax(r["gap"]))
        worst.append((float(r["gap"][j]), float(r["router_margin"][j]),
                      float(np.median(r["router_margin"]))))
        for k, g in r["control_gap"].items():
            ctl[k].append(g)
            ctl_errs[k].append(logit_err(r["control_logits"][k], r["logits"]))
    del w
    out = {"stats": {**gap_stats(gaps), **err_stats(errs)}, "requests": len(picked),
           "tokens": tokens, "with_logits": len(errs),
           "control": {k: {**gap_stats(v), **err_stats(ctl_errs[k])} for k, v in ctl.items()},
           "seconds": time.perf_counter() - t}
    log(f"reference: {len(picked)} requests ({len(errs)} with the program's logits), "
        f"{tokens} served tokens scored ({out['seconds']:.1f} s); per request: widest "
        f"gap, its router margin, median router margin: "
        + "; ".join("%.4g %.3g %.3g" % x for x in worst))
    return out


def verdict(spec: Spec, scored: dict, failed: int) -> tuple[bool, list]:
    """``correct``, and each compared number beside its limit: the numbers
    of ``limits/<cell>.json``'s ``compare`` (each at most its limit), at
    least ``min_requests`` requests scored, no failed request, and, where a
    logit error is compared, the program's logits read for every request
    scored."""
    lim = spec.limits
    rows = [(name, scored["stats"][name], limit) for name, limit in lim["compare"].items()]
    ok = all(v <= limit for _, v, limit in rows)
    rows += [("min_requests_scored", scored["requests"], lim["min_requests"]),
             ("failed_requests", failed, 0)]
    ok = ok and scored["requests"] >= lim["min_requests"] and failed == 0
    if any(name.startswith("served_logit_err") for name in lim["compare"]):
        missing = scored["requests"] - scored["with_logits"]
        rows.append(("requests_without_logits", missing, 0))
        ok = ok and missing == 0
    return ok, rows


# ---------------------------------------------------------------------------
# one run of a cell
# ---------------------------------------------------------------------------

def peak_of(device_kind: str, path: Path = HERE / "peaks.json") -> dict:
    """The chip's peaks; a kind missing from the table is an error."""
    table = json.loads(path.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path.name}; "
                       f"known: {sorted(k for k in table if k != 'source')}")
    return table[device_kind]


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)


def run_cell(spec: Spec, seed: int, seconds: float, traced: bool, *, t_start: float,
             peak: dict, devices=None, use_kernels="auto", controls=(), log=print) -> dict:
    """Set-up, window, per-layer or end-to-end numbers, check. Returns the
    result object (the line the harness prints last) and the compared
    numbers (``result["checks"]``)."""
    system = System(spec, seed, use_kernels=use_kernels, devices=devices, log=log)
    run = Run(system, seed, log=log)
    closed = run.traffic.closed
    with system.in_mesh():
        run.warm(closed)
        with tr.capture(traced) as holder:
            run.window(seconds, closed, traced)
        setup_s = run.w0 - t_start
        if not closed:
            run.drain()
    wreq = run.window_requests()
    ticks = run.window_ticks()
    log(f"window: {run.w1 - run.w0:.3f} s, {len(ticks)} ticks, {len(wreq)} requests sent, "
        f"{sum(t.tokens for t in ticks)} tokens; compilations in the window: {run.compiles}")
    if run.lateness:
        late = np.asarray(run.lateness)
        log(f"generator lateness: mean {1e3 * late.mean():.3f} ms, p95 "
            f"{1e3 * np.percentile(late, 95):.3f} ms, max {1e3 * late.max():.3f} ms "
            f"over {late.size} arrivals")
    devs = system.devices
    mem = memory_peak(devs)
    e2e = end_to_end(run, setup_s)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    metrics, breakdown = {}, None
    if traced:
        red = tr.reduce(holder.path)
        tr.discard(holder)
        device["busy_s"] = red.busy_s()
        device["window_s"] = red.window_s
        breakdown = {"device_ops": red.top_ops(), "idle_gaps": red.idle_by_host()}
        ctr = Counters(run, spec, peak, len(devs), red, system.n_replicas())
        for m in spec.per_layer:
            v = load_metric(m["name"])(ctr)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        for m in spec.end_to_end:
            metrics[m["name"]] = {"value": float(e2e[m["name"]]), "unit": m["unit"]}
    log("end to end: " + ", ".join(f"{k} {v:.6g}" for k, v in e2e.items()))
    failed = sum(1 for i in wreq
                 if i.req.state == "FAILED" or (not closed and i.first is None))
    picked = sample(run, seed)
    shardings, mesh_ctx = system.shardings, system.in_mesh()
    system.free()
    del run, system
    gc.collect()
    with mesh_ctx:
        scored = check(spec, seed, picked, controls=controls, shardings=shardings, log=log)
    ok, rows = verdict(spec, scored, failed)
    result = {"correct": ok, "attempted": len(wreq), "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if controls:
        result["control"] = {"program": scored["stats"], "control": scored["control"],
                             "reference_s": scored["seconds"]}
    result["checks"] = {name: {"value": v, "limit": lim} for name, v, lim in rows}
    return result
