"""Continuous-batching request scheduler over the paged ``Server``.

The ``RequestScheduler`` owns the request lifecycle

    QUEUED -> PREFILLING -> DECODING -> FINISHED
                   ^            |
                   '-- PREEMPTED (requeued, recomputed on re-admission)
                            |
                         FAILED (capacity / retry exhaustion)

over the existing jit-stable step function: the batch shape never changes —
empty slots are masked inert (write-off pages, length pinned to 0, excluded
from MoE routing via ``slot_mask``) — so admission, retirement and
preemption are pure host-side bookkeeping between steps, with zero
recompilation.

Per tick (``step()``):

1. **faults** — drain the :class:`repro.runtime.faults.FaultPlan` for this
   step (device death, stragglers, pool pressure, NaN logits);
2. **admission** — FIFO over arrived requests, watermark-gated against
   ``PagePool`` occupancy (strict FIFO among arrived requests: the head
   blocks, so admission is starvation-free). With
   ``ServeConfig(prefill_chunk=C)`` admission only stakes out a slot and
   pre-allocates pages; the request then rides the decode step's prefill
   lane, one C-token chunk per tick, until the final chunk's logits emit
   its first token and the slot flips to DECODING — live decode slots
   never stall more than the one fused step they already share. Without
   ``prefill_chunk``, admission is a batch-1 prefill spliced into one
   empty slot (``Server.prefill_into_slot``), which stalls the batch for
   the full prompt length;
3. **headroom** — if the live requests' next writes need more fresh pages
   than the pool holds, preempt (victim: fewest decoded tokens, youngest
   first) until the step cannot exhaust the pool — instead of the
   ``RuntimeError`` mid-``decode`` that a pool miss used to raise;
4. **decode** — one jitted step over the whole batch; per-slot argmax,
   EOS / max-token retirement recycling pages and slots mid-flight.

Determinism contract (the chaos parity test): per-request outputs are a
pure function of (params, prompt, max_new_tokens, eos) — independent of
batch composition, arrival order, placement changes and preemptions —
because every per-token computation is row-independent, expert replicas
are exact weight copies, and preempted work is recomputed from the full
prompt + already-emitted tokens. The one caveat is capacity drops: keep
``ParallelCtx.capacity_factor`` high enough that no routed copy is ever
dropped, or whole-batch routing pressure leaks between requests.

See docs/serving.md for the full state machine and design notes.
"""

from __future__ import annotations

import collections
import dataclasses

import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.runtime import faults as F

QUEUED = "QUEUED"
PREFILLING = "PREFILLING"
DECODING = "DECODING"
FINISHED = "FINISHED"
PREEMPTED = "PREEMPTED"
FAILED = "FAILED"


@dataclasses.dataclass
class Request:
    """One generation request and its lifecycle state."""

    rid: int
    prompt: np.ndarray               # (P,) int32 prompt tokens
    max_new_tokens: int
    eos_id: int | None = None
    arrival: int = 0                 # earliest scheduler step for admission
    state: str = QUEUED
    slot: int | None = None          # batch row while PREFILLING/DECODING
    tokens_out: list = dataclasses.field(default_factory=list)
    preemptions: int = 0             # pool evictions + fault requeues
    error: str | None = None
    # Chunked-admission progress: context tokens already prefilled (the
    # chunk lane has written their KV). Meaningful only while PREFILLING;
    # reset to 0 on preemption/crash (the KV dies with the slot/process
    # and the standard recompute re-prefills from chunk zero).
    prefill_pos: int = 0
    # Serving stats (ticks are scheduler steps, not wall time).
    admitted_step: int | None = None     # first PREFILLING/DECODING tick
    first_token_step: int | None = None  # tick the first token was emitted
    last_token_step: int | None = None   # tick of the most recent token
    max_stall: int = 0                   # widest gap between tokens, -1 tick

    @property
    def ttft_ticks(self) -> int | None:
        """Ticks from arrival until the first token existed (1 = the very
        first eligible tick emitted it). None until it has."""
        if self.first_token_step is None:
            return None
        return self.first_token_step - self.arrival + 1

    @property
    def n_decoded(self) -> int:
        return len(self.tokens_out)

    @property
    def context_len(self) -> int:
        """Tokens a (re)admission prefill must write: the prompt plus every
        token already emitted (recompute-on-preemption semantics)."""
        return len(self.prompt) + len(self.tokens_out)

    @property
    def done(self) -> bool:
        return self.state in (FINISHED, FAILED)


@dataclasses.dataclass
class SchedulerConfig:
    # Admit only while (occupied + needed) / pool <= watermark — headroom
    # for lazy decode-time page growth. A request that can't pass the
    # watermark with the system otherwise empty is admitted anyway
    # (progress guarantee for pools smaller than the watermark slack).
    admit_watermark: float = 0.85
    # A request evicted (pool pressure or fault requeue) more than this
    # many times FAILs instead of looping forever.
    max_preemptions: int = 8
    # Prompts are right-padded to power-of-two buckets (>= this floor) so
    # admission prefills hit a bounded set of jit traces.
    prompt_bucket_floor: int = 8
    # run() safety valve.
    max_steps: int = 10_000
    # Crash safety: every `snapshot_every` ticks, snapshot the scheduler's
    # end-of-previous-tick state (kept on `last_snapshot`; also written
    # atomically to `snapshot_path` when set). 0 disables the cadence —
    # `crash_restart` faults still snapshot at the crash tick.
    snapshot_every: int = 0
    snapshot_path: str = ""


class RequestScheduler:
    """Host-side continuous-batching loop over a paged ``Server``."""

    def __init__(self, server, cfg: SchedulerConfig | None = None, faults=None):
        if not server.scfg.paged:
            raise ValueError(
                "RequestScheduler needs ServeConfig(paged=True): slot-level "
                "admission and retirement are page-table operations"
            )
        self.server = server
        self.cfg = cfg or SchedulerConfig()
        self.faults = faults or F.FaultPlan()
        self.batch = server.scfg.batch
        self.cap_tokens = server.n_blocks * server.page_size
        self.queue: collections.deque[Request] = collections.deque()
        self.slots: list[Request | None] = [None] * self.batch
        self.cache = server.empty_cache()
        self.next_tok = np.zeros((self.batch, 1), np.int32)
        self.step_no = 0
        self.requests: list[Request] = []
        self.events: list[tuple] = []        # (step, kind, detail)
        self.n_preempted = 0
        self._rid = 0
        self._hostage: list[int] = []        # pages stolen by pool_pressure
        self._poison: set[int] | None = None  # nan_logits slots this tick
        self.last_snapshot = None            # most recent ServerSnapshot
        # Chunked admission (ServeConfig.prefill_chunk): at most one request
        # is mid-prefill at a time — the head of admission, one chunk per
        # tick through the decode step's prefill lane.
        self.chunk: int | None = server.scfg.prefill_chunk
        self._prefilling: Request | None = None

    # -- submission ----------------------------------------------------------

    def submit(
        self,
        prompt,
        max_new_tokens: int,
        eos_id: int | None = None,
        arrival: int = 0,
    ) -> Request:
        """Enqueue a request. Requests whose full context can never fit the
        per-request KV capacity FAIL immediately (named, not a decode-time
        RuntimeError half way through)."""
        req = Request(
            rid=self._rid,
            prompt=np.asarray(prompt, np.int32).reshape(-1),
            max_new_tokens=int(max_new_tokens),
            eos_id=eos_id,
            arrival=int(arrival),
        )
        self._rid += 1
        self.requests.append(req)
        if req.max_new_tokens < 1 or len(req.prompt) < 1:
            req.state = FAILED
            req.error = "empty prompt or non-positive max_new_tokens"
            return req
        if len(req.prompt) + req.max_new_tokens - 1 > self.cap_tokens:
            req.state = FAILED
            req.error = (
                f"request needs {len(req.prompt) + req.max_new_tokens - 1} KV "
                f"rows > per-request capacity {self.cap_tokens}; raise "
                f"max_seq or trim the request"
            )
            return req
        self.queue.append(req)
        return req

    # -- pool accounting -----------------------------------------------------

    def _pages_for(self, n_tokens: int) -> int:
        ps = self.server.page_size
        nb = self.server.n_blocks
        return min(-(-min(n_tokens, self.cap_tokens) // ps), nb)

    def _live(self) -> list[Request]:
        return [r for r in self.slots if r is not None]

    def _free_slots(self) -> list[int]:
        return [i for i, r in enumerate(self.slots) if r is None]

    def _admissible(self, req: Request) -> bool:
        pool = self.server.page_pool
        need = self._pages_for(req.context_len)
        if need > pool.n_free:
            return False
        if not self._live():
            return True   # empty system: progress beats the watermark
        occupied = pool.n_pages - pool.n_free
        return occupied + need <= self.cfg.admit_watermark * pool.n_pages

    # -- lifecycle transitions ----------------------------------------------

    def _bucket(self, n: int) -> int:
        m = self.cfg.prompt_bucket_floor
        while m < n:
            m *= 2
        return min(m, self.cap_tokens)

    def _admit(self, req: Request, slot: int) -> None:
        req.state = PREFILLING
        req.admitted_step = self.step_no
        if self.chunk:
            # Chunked admission: no device work here — just stake out the
            # slot and pre-allocate the pages. The decode step's prefill
            # lane writes one chunk per tick (step() drives it) until the
            # final chunk's logits emit the first token and the slot flips
            # to DECODING.
            req.slot = slot
            req.prefill_pos = 0
            self.slots[slot] = req
            self._prefilling = req
            self.server.begin_chunk_prefill(slot, req.context_len)
            self.events.append((self.step_no, "admit", req.rid))
            return
        ctx_tokens = np.concatenate(
            [req.prompt, np.asarray(req.tokens_out, np.int32)]
        )
        true_len = len(ctx_tokens)
        padded = np.zeros(self._bucket(true_len), np.int32)
        padded[:true_len] = ctx_tokens
        logits, self.cache = self.server.prefill_into_slot(
            slot, padded[None, :], self.cache, length=true_len
        )
        req.slot = slot
        self.slots[slot] = req
        req.state = DECODING
        self.events.append((self.step_no, "admit", req.rid))
        # The prefill's last-position logits emit this request's next token
        # — for a recompute, bit-for-bit the token the preempted decode
        # would have produced next.
        self._push_token(req, int(np.argmax(np.asarray(logits[0, -1]))))

    def _push_token(self, req: Request, tok: int) -> bool:
        """Append an emitted token; retire on EOS / max-token. Returns
        whether the request finished."""
        req.tokens_out.append(tok)
        if req.first_token_step is None:
            req.first_token_step = self.step_no
        elif req.last_token_step is not None:
            req.max_stall = max(
                req.max_stall, self.step_no - req.last_token_step - 1
            )
        req.last_token_step = self.step_no
        hit_eos = req.eos_id is not None and tok == req.eos_id
        if hit_eos or len(req.tokens_out) >= req.max_new_tokens:
            self._retire(req, FINISHED)
            return True
        self.next_tok[req.slot, 0] = tok
        return False

    def _retire(self, req: Request, state: str) -> None:
        """Free the request's slot and pages (they are reusable by the very
        next admission, mid-flight)."""
        self.cache = self.server.release(req.slot, self.cache)
        self.slots[req.slot] = None
        req.slot = None
        req.state = state
        self.events.append((self.step_no, "retire", req.rid))

    def _preempt(self, req: Request, reason: str) -> None:
        """Evict a running request; requeue it at the front for recompute,
        or FAIL it past the retry budget. Only this request is affected —
        the step loop and its batchmates keep going. A request preempted
        mid-prefill never emitted a token, so nothing is ever un-counted:
        its chunk pages go back to the pool and re-admission restarts the
        chunk state machine from position 0."""
        if req.state == PREFILLING and self.chunk:
            self.server.abort_chunk_prefill(req.slot)
            if self._prefilling is req:
                self._prefilling = None
            req.prefill_pos = 0
        else:
            self.cache = self.server.release(req.slot, self.cache)
        self.slots[req.slot] = None
        req.slot = None
        req.preemptions += 1
        self.n_preempted += 1
        self.events.append((self.step_no, "preempt", (req.rid, reason)))
        if req.preemptions > self.cfg.max_preemptions:
            req.state = FAILED
            req.error = f"evicted {req.preemptions} times (last: {reason})"
        else:
            req.state = PREEMPTED
            self.queue.appendleft(req)

    # -- per-tick phases -----------------------------------------------------

    def _apply_faults(self) -> None:
        pool = self.server.page_pool
        for f in self.faults.at(self.step_no):
            self.events.append((self.step_no, "fault", (f.kind, f)))
            if f.kind == F.CRASH_RESTART:
                continue   # handled at the top of step(), pre-snapshot
            if f.kind == F.DEVICE_DEATH:
                plan = self.server.mark_dead(f.device)
                self.events.append(
                    (self.step_no, "evacuated", (f.device, len(plan)))
                )
            elif f.kind == F.DEVICE_REVIVAL:
                plan = self.server.revive(f.device)
                self.events.append(
                    (self.step_no, "revived", (f.device, len(plan)))
                )
            elif f.kind == F.STRAGGLER:
                self.server.report_step_time(f.device, f.ratio)
            elif f.kind == F.POOL_PRESSURE:
                stolen = pool.alloc(min(f.pages, pool.n_free))
                self._hostage.extend(stolen)
            elif f.kind == F.POOL_RELEASE:
                n = min(f.pages or len(self._hostage), len(self._hostage))
                back, self._hostage = self._hostage[:n], self._hostage[n:]
                pool.free(back)
            elif f.kind == F.NAN_LOGITS:
                self._poison = set(f.slots) if f.slots else None
                if self._poison is None:
                    self._poison = {i for i, r in enumerate(self.slots) if r}

    def _admit_ready(self) -> list[int]:
        """Admit arrived requests in FIFO order; returns their ids."""
        admitted: list[int] = []
        while self.queue:
            if self.chunk and self._prefilling is not None:
                # One admission in flight at a time: the prefill lane is a
                # single chunk per tick, and strict FIFO means nobody may
                # overtake the head mid-prefill anyway.
                break
            free = self._free_slots()
            if not free:
                break
            # Strict FIFO among arrived requests: the earliest-queued
            # arrived request either admits or blocks admission this tick.
            head = next(
                (r for r in self.queue if r.arrival <= self.step_no), None
            )
            if head is None or not self._admissible(head):
                break
            self.queue.remove(head)
            self._admit(head, free[0])
            admitted.append(head.rid)
        return admitted

    def _ensure_headroom(self) -> None:
        """Preempt until this step's lazy page growth cannot exhaust the
        pool (victim: fewest decoded tokens; ties broken youngest-first)."""
        srv = self.server
        while True:
            live = self._live()
            deficit = (
                sum(
                    srv.next_write_unbacked(r.slot)
                    for r in live
                    if r.state == DECODING
                )
                - srv.page_pool.n_free
            )
            if deficit <= 0 or not live:
                return
            # A mid-prefill request holds every page it will ever need (no
            # lazy growth), so it contributes nothing to the deficit — but
            # it is the cheapest victim (zero decoded tokens) and evicting
            # it returns the most pages at once.
            victim = min(live, key=lambda r: (r.n_decoded, -r.rid))
            self._preempt(victim, "pool-exhausted")

    def _drain_migrations(self) -> None:
        """Keep in-flight stepped expert migrations landing on idle ticks.
        When requests are live the decode step itself drives the
        MigrationDriver (one slice per decode tick, overlapped with the
        step's compute); on an idle tick there is no decode to ride, so
        the scheduler advances the slices here — a dead batch must not
        freeze a half-copied replica in limbo."""
        if not self._live():
            self.server.drain_migrations()

    # -- the tick ------------------------------------------------------------

    def save_snapshot(self, path: str | None = None):
        """Capture end-of-previous-tick state as a ServerSnapshot (kept on
        ``last_snapshot``); with ``path``, also persist it via the atomic
        checkpoint writer. Lazy import: snapshot.py layers on top of the
        scheduler, not under it."""
        from repro.runtime import snapshot as S

        snap = S.snapshot_scheduler(self)
        if path:
            S.save_snapshot(path, snap)
        self.last_snapshot = snap
        return snap

    def step(self) -> list[Request]:
        """One scheduler tick. Returns the requests that finished.

        Snapshot/crash handling comes first — before faults, admission or
        decode — so a snapshot always captures a clean tick boundary (the
        end of the previous tick) and the faults of the crash tick re-fire
        exactly once after a restore.

        Profiler spans: ``sched.step`` covers the tick; inside it
        ``sched.admit`` (faults, admission, headroom, idle-tick migrations;
        one ``rid`` argument per admitted request), ``sched.chunk`` (the
        lane operand), the server's ``server.decode`` and ``sched.sample``
        (logits to the host, then the tokens)."""
        with TraceAnnotation("sched.step"):
            if (
                self.cfg.snapshot_every
                and self.step_no
                and self.step_no % self.cfg.snapshot_every == 0
            ):
                self.save_snapshot(self.cfg.snapshot_path or None)
            crash = next(
                (
                    f
                    for f in self.faults.at(self.step_no)
                    if f.kind == F.CRASH_RESTART
                ),
                None,
            )
            if crash is not None:
                snap = self.save_snapshot(crash.path or None)
                raise F.SimulatedCrash(self.step_no, snap, crash.path)
            with TraceAnnotation("sched.admit") as span:
                self._apply_faults()
                for rid in self._admit_ready():
                    span.set_metadata(rid=rid)
                self._ensure_headroom()
                self._drain_migrations()
            finished = self._decode_tick() if self._live() else []
            self._poison = None
            self.step_no += 1
            return finished

    def _chunk_operand(self, pf: Request) -> tuple[dict, int]:
        """One fixed-size chunk of the head-of-admission request's context
        for this tick's step (right-padded — the shape is jit-stable;
        ``length`` marks the valid rows). Returns the operand and the
        number of valid tokens."""
        ctx_tokens = np.concatenate(
            [pf.prompt, np.asarray(pf.tokens_out, np.int32)]
        )
        chunk_n = min(self.chunk, len(ctx_tokens) - pf.prefill_pos)
        buf = np.zeros(self.chunk, np.int32)
        buf[:chunk_n] = ctx_tokens[pf.prefill_pos : pf.prefill_pos + chunk_n]
        chunk = self.server.chunk_operand(pf.slot, buf, pf.prefill_pos, chunk_n)
        return chunk, chunk_n

    def _decode_tick(self) -> list[Request]:
        """The decode step over the live batch (with the lane's chunk, if
        one is in flight), then one token per DECODING row and, on a final
        chunk, the admitted request's first token."""
        finished: list[Request] = []
        chunk = None
        chunk_n = 0
        pf = self._prefilling
        if pf is not None:
            with TraceAnnotation("sched.chunk", rid=pf.rid):
                chunk, chunk_n = self._chunk_operand(pf)
        logits, self.cache = self.server.decode(
            jnp.asarray(self.next_tok), self.cache, chunk=chunk
        )
        with TraceAnnotation("sched.sample"):
            with TraceAnnotation("sched.logits_to_host"):
                rows = np.asarray(logits[:, -1])             # (B, V)
            poison = self._poison or set()
            if poison:
                rows = rows.copy()
                rows[sorted(poison)] = np.nan
            for slot, req in enumerate(self.slots):
                if req is None or req.state != DECODING:
                    continue    # PREFILLING rows are masked garbage
                row = rows[slot]
                if not np.isfinite(row).all():
                    # Numerics blew up for this row only: requeue it for a
                    # clean recompute instead of emitting garbage; the
                    # step loop and the other requests never notice.
                    self._preempt(req, "non-finite-logits")
                    continue
                if self._push_token(req, int(np.argmax(row))):
                    finished.append(req)
            if pf is not None:
                pf.prefill_pos += chunk_n
                if pf.prefill_pos >= pf.context_len:
                    # Final chunk: its last-position logits emit the first
                    # token — for a recompute, bit-for-bit the token the
                    # preempted decode would have produced next — and the
                    # slot flips live atomically (table + length splice).
                    crow = np.asarray(self.server.last_chunk_logits[0, -1])
                    if pf.slot in poison or not np.isfinite(crow).all():
                        self._preempt(pf, "non-finite-logits")
                    else:
                        self.cache = self.server.finish_chunk_prefill(
                            pf.slot, self.cache, pf.context_len
                        )
                        pf.state = DECODING
                        self._prefilling = None
                        if self._push_token(pf, int(np.argmax(crow))):
                            finished.append(pf)
        return finished

    def run(self, max_steps: int | None = None) -> dict[int, np.ndarray]:
        """Drive ``step()`` until every submitted request is FINISHED or
        FAILED (idle ticks advance time toward future arrivals / faults).
        Returns ``results()``."""
        limit = max_steps or self.cfg.max_steps
        last_fault = max((f.step for f in self.faults), default=-1)
        for _ in range(limit):
            if all(r.done for r in self.requests) and not self.queue:
                return self.results()
            self.step()
            if (
                not self._live()
                and self.queue
                and self.step_no > last_fault
                and all(r.arrival <= self.step_no for r in self.queue)
            ):
                head = next(
                    (r for r in self.queue if self._admissible(r)), None
                )
                if head is None and not self._free_slots():
                    continue  # unreachable: no live => slots all free
                if head is None:
                    # Nothing live, nothing can ever admit (pool starved for
                    # good): fail the head instead of spinning forever.
                    stuck = self.queue.popleft()
                    stuck.state = FAILED
                    stuck.error = (
                        f"needs {self._pages_for(stuck.context_len)} pages; "
                        f"pool has {self.server.page_pool.n_free} free for "
                        f"good — undersized pool or leaked pressure"
                    )
                    self.events.append((self.step_no, "admit-failed", stuck.rid))
        if not all(r.done for r in self.requests):
            raise RuntimeError(
                f"scheduler made no full progress in {limit} steps: "
                f"{[r.state for r in self.requests]}"
            )
        return self.results()

    def results(self) -> dict[int, np.ndarray]:
        """rid -> emitted tokens (present for every submitted request;
        FAILED requests report what they produced before failing)."""
        return {
            r.rid: np.asarray(r.tokens_out, np.int32) for r in self.requests
        }

    def stats(self) -> dict:
        """Serving statistics in scheduler ticks (not wall time).

        ``prefill_backlog`` counts context tokens still to prefill: the
        in-flight request's remaining chunks plus the full context of every
        queued request. ``ttft_ticks`` is arrival-to-first-token (1 = the
        first eligible tick emitted it; ``ceil(len/chunk)`` for uncontended
        chunked admission); ``max_stall_ticks`` is the widest gap between a
        request's consecutive tokens minus one — 0 means every tick after
        the first token emitted one, i.e. O(1) inter-token latency even
        while long prompts were being admitted."""
        backlog = sum(r.context_len for r in self.queue)
        if self._prefilling is not None:
            pf = self._prefilling
            backlog += pf.context_len - pf.prefill_pos
        ttfts = [
            r.ttft_ticks for r in self.requests if r.ttft_ticks is not None
        ]
        return {
            "step": self.step_no,
            "queue_depth": len(self.queue),
            "prefill_backlog": backlog,
            "n_preempted": self.n_preempted,
            "max_ttft_ticks": max(ttfts, default=None),
            "max_stall_ticks": max(
                (r.max_stall for r in self.requests), default=0
            ),
            "per_request": {
                r.rid: {
                    "state": r.state,
                    "ttft_ticks": r.ttft_ticks,
                    "max_stall_ticks": r.max_stall,
                    "n_tokens": r.n_decoded,
                    "preemptions": r.preemptions,
                }
                for r in self.requests
            },
        }
