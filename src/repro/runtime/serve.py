"""Serving runtime: batched decode with the NI-Balancer in the loop.

The ``Server`` owns

* jitted prefill/decode closures (cache donated, placement traced),
* physical expert *slot* weights — ``(L, n_slots, d, f)`` rows, i.e. native
  experts + shadow-slot replicas, slot dim sharded over the model axis,
* the shared :class:`repro.parallel.placement.PlacementTable` — the single
  placement substrate read by the balancer (planning view) and the jitted
  decode step (committed routing view),
* a :class:`repro.core.ni_balancer.BalancerState` fed by the per-step
  expert counts the model emits,
* a :class:`repro.runtime.migration_driver.MigrationDriver` executing
  balancer plans as live stepped migrations,
* the ER-Mapping-derived hop distance used by Algorithm 1.

Every decode step: drain migrations (commit fully-copied replicas at the
step boundary — the atomic routing-table swap — then issue this tick's
weight-row slice copies, overlapped with the step's compute) -> route ->
dispatch -> observe counts -> (Eq. 2 trigger) -> plan with Algorithm 1 ->
submit the plan to the driver. ``ServeConfig(migration_slices=0)`` keeps
the old instantaneous path (synchronous whole-expert copy) as the parity
baseline.

Device failures: ``mark_dead`` aborts/fast-forwards in-flight migration
slices, evacuates orphaned experts (placement table *and* physical weight
rows) and drops the dead device's replicas from the routing table.
Stragglers: per-device step-time EMAs scale heats, draining load away.

Request-level serving (admission, preemption, retirement) lives one layer
up in :mod:`repro.runtime.scheduler`; this module provides the slot-level
substrate it drives (``empty_cache`` / ``prefill_into_slot`` / ``release``
/ ``next_write_unbacked`` / ``drain_migrations``). The full lifecycle is
documented in docs/serving.md.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.ni_balancer import (
    BalancerState,
    evacuate,
    revival_plan,
    should_trigger,
    topology_aware_balance,
)
from repro.models import attention as A
from repro.models import transformer as T
from repro.parallel.collectives import validate_ep_chunks
from repro.parallel.ctx import ParallelCtx
from repro.parallel.placement import PlacementTable
from repro.parallel.sharding import cache_specs, chunk_specs, to_shardings
from repro.runtime.migration_driver import MOE_WEIGHTS, MigrationDriver


@dataclasses.dataclass
class ServeConfig:
    max_seq: int = 1024
    batch: int = 8
    slots_per_device: int = 2      # native + shadow capacity per device
    alpha: float = 0.5             # Eq. 2 imbalance threshold
    beta: float = 0.0              # Eq. 2 refractory (0 = non-invasive)
    ema: float = 0.8
    # Live stepped migration: each balancer-planned migration copies its
    # expert's weight rows over this many decode ticks (one slice per tick,
    # floored by the migration's Local/Global hop count), and the routing
    # table swaps atomically only after the last slice lands. 0 = the
    # instantaneous baseline: synchronous whole-expert copy on the decode
    # path (the paper's invasive strawman; kept for parity testing).
    migration_slices: int = 4
    # Paged KV cache: requests share a physical page pool through per-
    # request block tables (attention.paged_cache_init); `pool_pages`
    # oversubscribes the pool vs the dense `batch * ceil(max_seq / page)`
    # worst case — ragged batches then fit where dense caches wouldn't.
    paged: bool = False
    page_size: int = A.PAGE_SIZE
    pool_pages: int | None = None  # None = fully backed (batch * NB)
    # Virtual EP (single process, no mesh): treat the expert slots as if
    # they were spread over this many logical devices, so the NI-Balancer —
    # replica routing, migration, evacuation, straggler draining — runs for
    # real (weight rows move between slot rows, routing tables update);
    # only the inter-device hop is notional (collectives.ep_moe_local).
    # Ignored under a real multi-device mesh (the model axis wins).
    virtual_ep: int | None = None
    # Chunked prefill: admission prefills run as a *lane inside the decode
    # step* — `prefill_chunk` context tokens per tick alongside the live
    # decode batch, so a long prompt never stalls running requests and
    # queued TTFT is bounded by ceil(len / prefill_chunk) ticks. None =
    # the splice-admission path (whole-prompt batch-1 prefill spliced into
    # the cache). Requires paged=True and full (non-windowed) attention;
    # must be a positive multiple of page_size no larger than max_seq.
    prefill_chunk: int | None = None
    # Chunked EP dispatch: split each device's expert groups into this many
    # chunks and pipeline the dispatch/combine all_to_all legs against the
    # fused expert FFN (collectives.ep_moe_shardmap; the virtual-EP local
    # path chunks the grouped FFN the same way). 1 = single-shot dispatch.
    # Must divide the expert-group count: slots_per_device on a mesh,
    # slots_per_device * virtual_ep on the single-process path. Static —
    # baked into the one compiled step program, never a traced switch.
    ep_chunks: int = 1

    def __post_init__(self):
        validate_prefill_chunk(
            self.prefill_chunk, self.page_size, self.max_seq, self.paged
        )
        validate_ep_chunks(self.ep_chunks, where="ServeConfig")
        if self.ep_chunks > 1:
            groups = self.slots_per_device * (self.virtual_ep or 1)
            validate_ep_chunks(
                self.ep_chunks,
                groups,
                where="ServeConfig slots_per_device"
                + (" * virtual_ep" if self.virtual_ep else ""),
            )


def validate_prefill_chunk(
    chunk: int | None, page_size: int, max_seq: int, paged: bool
) -> None:
    """Up-front validation for ``ServeConfig(prefill_chunk=...)``.

    Same convention as ``validate_ep_token_split``: a bad chunk size would
    otherwise surface as an opaque scatter/spec error deep inside the jitted
    step (or silently mis-page the chunk's KV). Fail at construction,
    naming the offending numbers."""
    if chunk is None:
        return
    chunk = int(chunk)
    if chunk <= 0:
        raise ValueError(
            f"ServeConfig: prefill_chunk={chunk} must be a positive number "
            f"of tokens (use prefill_chunk=None for splice admission)"
        )
    if chunk % page_size:
        raise ValueError(
            f"ServeConfig: prefill_chunk={chunk} is not page-size-aligned "
            f"(page_size={page_size}) — each chunk must fill whole KV "
            f"pages so the chunk scatter never straddles an unallocated "
            f"block"
        )
    if chunk > max_seq:
        raise ValueError(
            f"ServeConfig: prefill_chunk={chunk} exceeds max_seq={max_seq} "
            f"— a chunk can never hold more context than one request's KV "
            f"capacity"
        )
    if not paged:
        raise ValueError(
            "ServeConfig: prefill_chunk requires paged=True — the chunk "
            "lane writes KV through a page table (dense caches have no "
            "per-request block mapping to write through)"
        )


# A revived device's HBM is blank (no on-wafer disk); its free slot rows are
# scrubbed with this loud finite sentinel until migration slices overwrite
# them. Finite so inert paths stay exactly zero (an empty expert bucket
# computes FFN(0 @ W) = 0 regardless of W), loud so any routing leak to an
# uncommitted replica explodes the logits instead of silently decoding.
BLANK_WEIGHT = 1e30


class SlotReleaseError(RuntimeError):
    """``Server.release`` of a slot that holds no pages — a double release,
    or a slot that was never admitted. Silently no-opping here (the old
    behaviour) let lifecycle bugs surface much later as stale-table
    corruption; failing at the call site names the culprit."""


class PagePool:
    """Host-side physical-page allocator for the paged KV cache.

    Pages are plain int ids into the pool's leading dim; ``alloc``/``free``
    are O(1) list ops off the jit path (the device-side scatter/gather goes
    through the block *tables*, which reference these ids). Exhaustion
    raises — admission control belongs to the caller.
    """

    def __init__(self, n_pages: int):
        self.n_pages = n_pages
        self._free = list(range(n_pages - 1, -1, -1))
        self._live: set[int] = set()

    @property
    def n_free(self) -> int:
        return len(self._free)

    def alloc(self, n: int) -> list[int]:
        if n > len(self._free):
            raise RuntimeError(
                f"page pool exhausted: want {n}, have {len(self._free)} "
                f"of {self.n_pages}"
            )
        pages = [self._free.pop() for _ in range(n)]
        self._live.update(pages)
        return pages

    def free(self, pages) -> None:
        for p in pages:
            if p not in self._live:
                raise ValueError(f"double free of page {p}")
            self._live.discard(p)
            self._free.append(p)


def _paged_cache_shardings(cfg: ModelConfig, cache: dict, ctx: ParallelCtx):
    """NamedShardings of a paged serving cache under ``ctx.mesh`` (the
    ``sharding.cache_specs`` rule table); None off-mesh or for dense
    caches. ``cache`` may hold arrays, shapes or tracers."""
    if ctx.mesh is None or not A.is_paged(cache["layers"]):
        return None
    batch = cache["layers"]["lengths"].shape[-1]
    return to_shardings(ctx.mesh, cache_specs(cfg, cache, ctx, batch))


def _decode_step(params, token, cache, *, cfg, ctx, **kw):
    """``transformer.decode_step`` with the paged cache pinned to its mesh
    layout on the way out — the same layout ``Server.decode`` places it in
    on the way in — so host-side table/length edits between ticks never
    present the step with a new input sharding (one compiled program)."""
    logits, cache, stats = T.decode_step(params, token, cache, cfg=cfg, ctx=ctx, **kw)
    shardings = _paged_cache_shardings(cfg, cache, ctx)
    if shardings is not None:
        cache = jax.lax.with_sharding_constraint(cache, shardings)
    return logits, cache, stats


def _expand_slots(w: jax.Array, rows: np.ndarray, ctx: ParallelCtx) -> jax.Array:
    """Logical expert rows -> physical slot rows (dim 1). Under a mesh the
    slot dim comes out sharded over the model axis, so each device builds
    and keeps only its own slots and the step never reshards them."""
    if ctx.mesh is None:
        return jnp.take(w, rows, axis=1)
    spec = P(None, ctx.model_axis, *(None,) * (w.ndim - 2))
    return jax.jit(
        lambda a: jnp.take(a, rows, axis=1),
        out_shardings=NamedSharding(ctx.mesh, spec),
    )(w)


class Server:
    def __init__(
        self,
        cfg: ModelConfig,
        ctx: ParallelCtx,
        params,
        serve_cfg: ServeConfig = ServeConfig(),
        distance=None,
        table: PlacementTable | None = None,
    ):
        self.cfg = cfg
        if serve_cfg.ep_chunks != getattr(ctx, "ep_chunks", 1):
            # Static pipeline depth: the chunk count is baked into the
            # jitted step closures built below (one compiled program, no
            # traced switch), so it must land on the ctx first.
            ctx = dataclasses.replace(ctx, ep_chunks=serve_cfg.ep_chunks)
        self.ctx = ctx
        self.scfg = serve_cfg
        self.params = params
        self.ep = ctx.n_model
        if self.ep == 1 and serve_cfg.virtual_ep:
            self.ep = serve_cfg.virtual_ep
        self.use_balancer = cfg.is_moe and self.ep > 1
        self.distance = distance or (lambda a, b: abs(a - b))
        self.t = 0
        self.last_mig = -(10**9)
        self.migrations = 0

        if self.use_balancer:
            # Slot-expanded weights require EP dispatch everywhere; the
            # "auto" impl would pick ESP when n_experts % ep != 0, and ESP
            # indexes weights by logical expert, not physical slot.
            if ctx.moe_impl == "auto":
                self.ctx = ctx = dataclasses.replace(ctx, moe_impl="ep")
            spd = serve_cfg.slots_per_device
            n_slots = self.ep * spd
            if n_slots < cfg.n_experts:
                raise ValueError("not enough slots for native experts")
            # Expand per-layer expert rows to physical slots. Fresh start:
            # slot s holds expert s % E. Snapshot restore: a saved table
            # dictates the owner of every committed slot, so the restored
            # weights land exactly where the crashed process routed them;
            # free slots fall back to s % E (never routed to).
            if table is not None:
                if (
                    table.n_experts != cfg.n_experts
                    or table.n_slots != n_slots
                    or table.slots_per_device != spd
                ):
                    raise ValueError(
                        f"restored table shape ({table.n_experts} experts, "
                        f"{table.n_slots} slots, {table.slots_per_device} "
                        f"per device) does not match serve config "
                        f"({cfg.n_experts}, {n_slots}, {spd})"
                    )
                owner = table.owner_of_slots()
                rows = np.where(
                    owner >= 0, owner, np.arange(n_slots) % cfg.n_experts
                )
            else:
                rows = np.arange(n_slots) % cfg.n_experts
            # One weight at a time, each finished (and its logical array
            # freed) before the next starts: peak HBM is the parameters
            # plus one expanded weight, not all three in flight at once.
            # The caller's ``params`` dict is updated in place, so it holds
            # no second reference to the logical rows.
            moe = self.params["layers"]["moe"]
            for w in MOE_WEIGHTS:
                moe[w] = jax.block_until_ready(_expand_slots(moe[w], rows, ctx))
            # The one placement substrate: expert e natively lives in slot
            # e, i.e. on device e // spd. The balancer plans against it
            # (committed + in-flight view) and the jitted decode routes by
            # its committed device_view — no mirrored tables to diverge.
            self.table = table or PlacementTable.uniform(
                cfg.n_experts, n_slots, spd
            )
            self.state = BalancerState(
                n_experts=cfg.n_experts,
                n_devices=self.ep,
                slots_per_device=spd,
                table=self.table,
                load_ema=np.ones(cfg.n_experts) / cfg.n_experts,
                ema_decay=serve_cfg.ema,
            )
            self.driver = (
                MigrationDriver(
                    self.table, min_slices=serve_cfg.migration_slices
                )
                if serve_cfg.migration_slices > 0
                else None
            )
        else:
            self.table = None
            self.state = None
            self.driver = None

        # KV caches hold activations at the parameters' dtype (bf16 serving
        # keeps bf16 pages, so the decode kernels see one input dtype).
        self.cache_dtype = params["embed"].dtype
        prefill_kw: dict = {"dtype": self.cache_dtype}
        if serve_cfg.paged:
            self.page_size, self.n_blocks = A.paged_layout(
                cfg, serve_cfg.max_seq, serve_cfg.page_size
            )
            backed = serve_cfg.batch * self.n_blocks
            self.n_pool_pages = serve_cfg.pool_pages or backed
            self.page_pool = PagePool(self.n_pool_pages)
            self.trash_page = self.n_pool_pages  # write-off page index
            self._tables = np.full(
                (serve_cfg.batch, self.n_blocks), self.trash_page, np.int32
            )
            self._pages: dict[int, list[int]] = {}
            self._released: set[int] = set()
            self._tables_dirty = False
            # host-side mirror of per-request written counts (lengths): the
            # block-boundary check must not force a device sync per token.
            self._written: np.ndarray | None = None
            # Chunked-prefill ledger: pages/table-row of the (at most one)
            # request mid-prefill, kept OUT of `_pages`/`_tables` until the
            # final chunk lands — `_ensure_pages` and the decode lane must
            # treat the slot as empty (trash table, length 0) while the
            # chunk lane writes its KV through the side row.
            self._prefill_pages: dict[int, list[int]] = {}
            self._prefill_row: dict[int, np.ndarray] = {}
            self.last_chunk_logits = None
            if serve_cfg.prefill_chunk:
                if cfg.sliding_window:
                    raise ValueError(
                        f"prefill_chunk requires full attention: sliding_"
                        f"window={cfg.sliding_window} breaks the chunk "
                        f"lane's slot-j-holds-position-j invariant (the "
                        f"ring remaps logical slots as context wraps)"
                    )
                if serve_cfg.prefill_chunk % self.page_size:
                    raise ValueError(
                        f"prefill_chunk={serve_cfg.prefill_chunk} is not a "
                        f"multiple of the effective page size "
                        f"{self.page_size} (paged_layout shrank it from "
                        f"{serve_cfg.page_size})"
                    )
            prefill_kw.update(
                paged=True,
                page_size=serve_cfg.page_size,
                n_pages=self.n_pool_pages,
            )
        # host-side mirror of cache["pos"] — the overflow guard must not
        # block on the previous step's device computation every token.
        self._pos: int | None = None
        # donate the *cache* (argnum 2: params, token, cache). Donating the
        # token (the old argnums=(1,)) was an off-by-one: harmless off-mesh
        # (XLA refused it — the recurring "donated buffers were not usable"
        # warning), but under a mesh the donation can be accepted and
        # generate() then concatenates a deleted token array.
        self._decode = jax.jit(
            functools.partial(_decode_step, cfg=cfg, ctx=ctx),
            donate_argnums=(2,),
        )
        # Chunk operands are tiny host-built metadata; under a mesh they
        # are placed explicitly (replicated — see sharding.chunk_specs) so
        # the fused step never re-triggers layout inference per tick.
        self._chunk_shardings = None
        if serve_cfg.paged and serve_cfg.prefill_chunk and ctx.mesh is not None:
            self._chunk_shardings = to_shardings(ctx.mesh, chunk_specs())
        self._prefill = jax.jit(
            functools.partial(
                T.prefill, cfg=cfg, ctx=ctx, max_seq=serve_cfg.max_seq,
                **prefill_kw,
            ),
            static_argnames=(),
        )
        # Slot admission: splice one request's prefilled pool pages into the
        # live batch cache (donates the big pool — no second copy resident).
        self._splice_pages = jax.jit(
            lambda bk, bv, sk, sv, idx: (
                bk.at[:, idx].set(sk[:, idx]),
                bv.at[:, idx].set(sv[:, idx]),
            ),
            donate_argnums=(0, 1),
        )

    # -- placement views -----------------------------------------------------

    @property
    def slot_of(self):
        """Committed routing table (device mirror) — reads through the
        shared PlacementTable; kept as a property for callers that predate
        the unification."""
        return None if self.table is None else self.table.device_view()[0]

    @property
    def n_replicas(self):
        return None if self.table is None else self.table.device_view()[1]

    def _moe(self) -> dict:
        return self.params["layers"]["moe"]

    # -- request lifecycle ---------------------------------------------------

    def _prompt_rows(self, tokens, embeds) -> int:
        """KV rows a prefill writes per request: prompt tokens plus any
        prepended frontend-stub embeddings (see T.prefill)."""
        s = tokens.shape[1]
        if (
            embeds is not None
            and self.cfg.frontend_stub
            and self.cfg.block_pattern != "encdec"
        ):
            s += embeds.shape[1]
        return s

    def prefill(self, tokens, embeds=None, lengths=None):
        """Prime a cache for a batch of prompts.

        Paged mode: allocates each request's blocks from the shared pool
        (``lengths`` marks true per-request prompt lengths for right-padded
        ragged batches — shorter requests hold fewer pages; prepended
        frontend embeds count toward every request). Pages of a previously
        prefilled batch are auto-released."""
        s = self._prompt_rows(tokens, embeds)
        if not self.scfg.paged:
            logits, cache = self._prefill(self.params, tokens, embeds=embeds)
            self._pos = s
            return logits, cache
        b = tokens.shape[0]
        n_embed = s - tokens.shape[1]
        lens = (
            np.full(b, s, np.int32)
            if lengths is None
            else np.asarray(lengths, np.int32) + n_embed
        )
        for slot in list(self._pages):
            self.release(slot)
        for slot in list(self._prefill_pages):
            self.abort_chunk_prefill(slot)
        self._released = set()
        self._tables = np.full((b, self.n_blocks), self.trash_page, np.int32)
        self._tables_dirty = False
        cap = self.n_blocks * self.page_size
        for slot in range(b):
            need = min(-(-int(min(lens[slot], cap)) // self.page_size), self.n_blocks)
            pages = self.page_pool.alloc(need)
            self._pages[slot] = pages
            self._tables[slot, :need] = pages
        logits, cache = self._prefill(
            self.params,
            tokens,
            embeds=embeds,
            tables=jnp.asarray(self._tables),
            lengths=jnp.asarray(lens),
        )
        self._written = lens.copy()
        self._pos = s
        return logits, cache

    def release(self, slot: int, cache: dict | None = None):
        """Free request ``slot``'s pages back to the pool. With ``cache``,
        also clears its table row and length immediately; without it, the
        device tables are refreshed on the next ``decode`` (before any
        write), so the freed pages are never scattered into once they're
        re-allocated. The batch row keeps stepping (its writes land on the
        write-off page and its output is meaningless until re-admitted) —
        ``decode`` pins its length back to 0 each step so it never grows a
        live prefix or new pages.

        Raises :class:`SlotReleaseError` if the slot holds no pages
        (double release / never admitted)."""
        if slot not in self._pages:
            raise SlotReleaseError(
                f"release of slot {slot}, which holds no pages (already "
                f"released, or never admitted)"
            )
        self.page_pool.free(self._pages.pop(slot))
        self._released.add(slot)
        self._tables[slot, :] = self.trash_page
        if self._written is not None:
            self._written[slot] = 0
        if cache is None:
            self._tables_dirty = True
            return None
        layers = dict(cache["layers"])
        layers["tables"] = self._stacked_tables(layers["tables"].shape[0])
        layers["lengths"] = layers["lengths"].at[:, slot].set(0)
        return {**cache, "layers": layers}

    def _stacked_tables(self, n_layers: int):
        return jnp.broadcast_to(
            jnp.asarray(self._tables), (n_layers, *self._tables.shape)
        ).copy()

    # -- slot-level admission (continuous batching substrate) ----------------

    def empty_cache(self) -> dict:
        """A paged cache with every batch slot empty — the starting state
        for slot-level admission (``prefill_into_slot``). All table rows
        point at the write-off page, all lengths are 0, and any previously
        admitted requests' pages go back to the pool."""
        if not self.scfg.paged:
            raise ValueError("empty_cache requires ServeConfig(paged=True)")
        b = self.scfg.batch
        for slot in list(self._pages):
            self.release(slot)
        for slot in list(self._prefill_pages):
            self.abort_chunk_prefill(slot)
        self._released = set(range(b))
        self._tables = np.full((b, self.n_blocks), self.trash_page, np.int32)
        self._tables_dirty = False
        self._written = np.zeros(b, np.int32)
        self._pos = 0
        return T.init_cache(
            self.cfg,
            b,
            self.scfg.max_seq,
            self.cache_dtype,
            paged=True,
            page_size=self.scfg.page_size,
            n_pages=self.n_pool_pages,
        )

    def prefill_into_slot(self, slot: int, tokens, cache: dict, length=None):
        """Admit one request into batch row ``slot`` of a *live* cache.

        Runs the jitted prefill at batch 1 over this request alone (its
        block table indexes the same shared pool id space), then splices
        the request's pool pages, table row and length into ``cache`` —
        the other batch rows are untouched, so admission happens mid-flight
        without pausing or recomputing live requests.

        ``length`` marks the true prompt length when ``tokens`` is
        right-padded (jit-stable prompt buckets). Returns ``(logits,
        cache)`` with logits at the request's true last prompt position
        (``(1, 1, vocab)``).
        """
        if not self.scfg.paged:
            raise ValueError("prefill_into_slot requires ServeConfig(paged=True)")
        if slot in self._pages:
            raise RuntimeError(
                f"slot {slot} is still admitted; release it before reuse"
            )
        if self._written is None:
            self._written = np.zeros(self.scfg.batch, np.int32)
        tokens = jnp.asarray(tokens, jnp.int32).reshape(1, -1)
        true_len = int(length if length is not None else tokens.shape[1])
        cap = self.n_blocks * self.page_size
        need = min(-(-min(true_len, cap) // self.page_size), self.n_blocks)
        pages = self.page_pool.alloc(need)
        row = np.full((1, self.n_blocks), self.trash_page, np.int32)
        row[0, :need] = pages
        logits, small = self._prefill(
            self.params,
            tokens,
            embeds=None,
            tables=jnp.asarray(row),
            lengths=jnp.asarray([true_len], np.int32),
        )
        self._pages[slot] = pages
        self._tables[slot] = row[0]
        self._released.discard(slot)
        self._written[slot] = true_len
        self._tables_dirty = False
        layers = dict(cache["layers"])
        if need:
            idx = jnp.asarray(pages)
            layers["pool_k"], layers["pool_v"] = self._splice_pages(
                layers["pool_k"],
                layers["pool_v"],
                small["layers"]["pool_k"],
                small["layers"]["pool_v"],
                idx,
            )
        layers["tables"] = self._stacked_tables(layers["tables"].shape[0])
        layers["lengths"] = layers["lengths"].at[:, slot].set(true_len)
        return logits, {**cache, "layers": layers}

    # -- chunked prefill (the admission lane inside the decode step) ---------

    def begin_chunk_prefill(self, slot: int, length: int) -> None:
        """Start a chunked admission into batch row ``slot``: allocate every
        page ``length`` context rows will need, into a *side* ledger. The
        live cache is untouched — the slot's device table row stays at the
        write-off page and its length stays 0 for the whole prefill, so the
        decode lane's masked write for this row keeps landing on the trash
        page instead of corrupting the chunk's real position-0 KV. The
        chunk lane writes through the side row (``chunk_operand``);
        ``finish_chunk_prefill`` splices the mapping in atomically when the
        last chunk lands."""
        if not self.scfg.prefill_chunk:
            raise ValueError(
                "begin_chunk_prefill requires ServeConfig(prefill_chunk=N)"
            )
        if slot in self._pages or slot in self._prefill_pages:
            raise RuntimeError(
                f"slot {slot} is still admitted or mid-prefill; release or "
                f"abort it before reuse"
            )
        cap = self.n_blocks * self.page_size
        need = min(-(-min(int(length), cap) // self.page_size), self.n_blocks)
        pages = self.page_pool.alloc(need)
        row = np.full(self.n_blocks, self.trash_page, np.int32)
        row[:need] = pages
        self._prefill_pages[slot] = pages
        self._prefill_row[slot] = row

    def chunk_operand(self, slot: int, tokens, start: int, length: int) -> dict:
        """Build the decode step's prefill-lane operand for one chunk of the
        request mid-prefill in ``slot``. ``tokens`` is the fixed-size
        ``(prefill_chunk,)`` buffer (right-padded past ``length``);
        ``start`` is the request's prefill progress (absolute position of
        ``tokens[0]``)."""
        if slot not in self._prefill_row:
            raise RuntimeError(
                f"slot {slot} has no chunked prefill in flight "
                f"(begin_chunk_prefill first)"
            )
        tokens = np.asarray(tokens, np.int32).reshape(1, -1)
        if tokens.shape[1] != self.scfg.prefill_chunk:
            raise ValueError(
                f"chunk_operand: got {tokens.shape[1]} tokens, want exactly "
                f"prefill_chunk={self.scfg.prefill_chunk} (right-pad past "
                f"`length` — the shape is jit-stable)"
            )
        return {
            "tokens": jnp.asarray(tokens),
            "table": jnp.asarray(self._prefill_row[slot]),
            "start": jnp.asarray(int(start), jnp.int32),
            "length": jnp.asarray(int(length), jnp.int32),
        }

    def noop_chunk(self) -> dict:
        """The idle prefill-lane operand (length 0, all-trash table): padded
        rows write to the write-off page and route nowhere, so ticks with no
        admission in flight reuse the exact same compiled program."""
        return {
            "tokens": jnp.zeros((1, self.scfg.prefill_chunk), jnp.int32),
            "table": jnp.full((self.n_blocks,), self.trash_page, jnp.int32),
            "start": jnp.zeros((), jnp.int32),
            "length": jnp.zeros((), jnp.int32),
        }

    def finish_chunk_prefill(self, slot: int, cache: dict, length: int) -> dict:
        """The final chunk landed: atomically flip ``slot`` live. The
        chunk lane already wrote every KV row into the pool through the
        side table, so this is pure mapping surgery — move the pages into
        the live ledger and splice the table row + true length into the
        device cache (the exact splice ``prefill_into_slot`` does, minus
        the pool copy it needed for its separate batch-1 cache)."""
        if slot not in self._prefill_pages:
            raise RuntimeError(
                f"slot {slot} has no chunked prefill in flight"
            )
        if self._written is None:
            self._written = np.zeros(self.scfg.batch, np.int32)
        self._pages[slot] = self._prefill_pages.pop(slot)
        self._tables[slot] = self._prefill_row.pop(slot)
        self._released.discard(slot)
        self._written[slot] = int(length)
        self._tables_dirty = False
        layers = dict(cache["layers"])
        layers["tables"] = self._stacked_tables(layers["tables"].shape[0])
        layers["lengths"] = layers["lengths"].at[:, slot].set(int(length))
        return {**cache, "layers": layers}

    def abort_chunk_prefill(self, slot: int) -> None:
        """Tear down a mid-prefill admission (preemption, device pressure,
        crash recovery): free the side pages back to the pool. Nothing was
        ever spliced into the live cache, so there is no device state to
        undo — the half-written pool pages are unreachable once freed and
        get overwritten by their next owner."""
        if slot not in self._prefill_pages:
            raise SlotReleaseError(
                f"abort_chunk_prefill of slot {slot}, which has no chunked "
                f"prefill in flight"
            )
        self.page_pool.free(self._prefill_pages.pop(slot))
        del self._prefill_row[slot]

    def next_write_unbacked(self, slot: int) -> bool:
        """Would this request's next decode write need a fresh pool page
        (its block table doesn't back the target block yet)? The scheduler
        sums this over live slots to preempt *before* ``_ensure_pages``
        would hit pool exhaustion mid-step."""
        cap = self.n_blocks * self.page_size
        written = int(self._written[slot])
        if self.cfg.sliding_window:
            nxt = written % cap
        else:
            nxt = min(written, cap - 1)
        return bool(self._tables[slot, nxt // self.page_size] == self.trash_page)

    def _ensure_pages(self, cache: dict) -> dict:
        """Allocate the page a request's next write lands on, if its block
        table doesn't back it yet (lazy per-request growth at block
        boundaries). Both the boundary check (host mirror ``_written``) and
        the alloc are host-side — no per-token device sync on the hot path."""
        layers = cache["layers"]
        if self._written is None:
            # cache primed outside this Server (e.g. T.prefill directly):
            # sync the mirror once, then track host-side. No pages to grow
            # (this Server's allocator doesn't own that cache's mapping).
            self._written = np.asarray(layers["lengths"][0]).copy()
        cap = self.n_blocks * self.page_size
        w = self.cfg.sliding_window or 0
        changed = self._tables_dirty   # release(slot) without a cache handle
        self._tables_dirty = False
        for slot in self._pages:
            if self.next_write_unbacked(slot):
                written = int(self._written[slot])
                nxt = written % cap if w else min(written, cap - 1)
                (page,) = self.page_pool.alloc(1)
                self._pages[slot].append(page)
                self._tables[slot, nxt // self.page_size] = page
                changed = True
        if not changed:
            return cache
        layers = dict(layers)
        layers["tables"] = self._stacked_tables(layers["tables"].shape[0])
        return {**cache, "layers": layers}

    def decode(self, token, cache, chunk: dict | None = None):
        """One fused step. With ``ServeConfig(prefill_chunk=N)`` a chunk
        operand is ALWAYS passed to the jitted step — ``chunk=None`` here
        substitutes the no-op chunk — so idle, decode-only and decode+chunk
        ticks compile to one program per shape.

        Profiler spans: ``server.decode`` covers the call; inside it
        ``server.prepare`` (operands, pages, capacity, layout, row mask),
        ``server.migrate`` (commits and this tick's slice copies),
        ``server.dispatch`` (the step's launch and the host mirrors after
        it), ``server.counts`` (the expert counts to the host: the first
        wait on the step) and ``server.balance`` (balancer update and
        plan)."""
        with TraceAnnotation("server.decode"):
            with TraceAnnotation("server.prepare"):
                chunk, cache, slot_mask = self._prepare_step(token, cache, chunk)
            placement = None
            if self.use_balancer:
                with TraceAnnotation("server.migrate"):
                    # Step boundary: commit migrations whose last slice
                    # landed (the atomic routing-table swap), then issue
                    # this tick's weight slices — dispatched before the
                    # step so the copy overlaps the decode compute below.
                    self.drain_migrations()
                    placement = self.table.device_view()
            with TraceAnnotation("server.dispatch"):
                logits, cache, stats = self._decode(
                    self.params, token, cache, placement=placement,
                    slot_mask=slot_mask, chunk=chunk,
                )
                cache = self._advance(cache, stats)
            if self.use_balancer:
                with TraceAnnotation("server.counts"):
                    counts = np.asarray(stats["expert_counts"])
                with TraceAnnotation("server.balance"):
                    self.state.observe(counts)
                    self._maybe_balance(counts)
            return logits, cache

    def _prepare_step(self, token, cache, chunk):
        """The step's operands: the lane's chunk (the no-op chunk when none
        rides), the next write's pages, the capacity check, the cache in
        the step's layout and the live-row mask."""
        if self.scfg.prefill_chunk:
            if chunk is None:
                chunk = self.noop_chunk()
            if self._chunk_shardings is not None:
                chunk = jax.device_put(chunk, self._chunk_shardings)
        elif chunk is not None:
            raise ValueError(
                "decode(chunk=...) requires ServeConfig(prefill_chunk=N)"
            )
        if self._pos is None:   # cache primed outside this Server
            self._pos = int(cache["pos"])
        windowed = bool(self.cfg.sliding_window or 0)
        if self.scfg.paged:
            cache = self._ensure_pages(cache)   # also syncs _written
            if not windowed:
                # Per-request occupancy: a ragged batch keeps serving as
                # long as every *live* request has headroom (releasing a
                # finished request really does restore capacity).
                cap = self.n_blocks * self.page_size
                live = self._pages or range(len(self._written))
                full = [s for s in live if self._written[s] >= cap]
                if full:
                    raise RuntimeError(
                        f"decode past capacity={cap} for request(s) {full} "
                        f"(cache full): release them or raise max_seq"
                    )
        elif not windowed and self._pos >= self.scfg.max_seq:
            # Dense caches used to clobber the last slot silently here;
            # both layouts now freeze at capacity and serving refuses.
            raise RuntimeError(
                f"decode past max_seq={self.scfg.max_seq} (cache full, "
                f"pos={self._pos}): release the request or raise max_seq"
            )
        shardings = _paged_cache_shardings(self.cfg, cache, self.ctx)
        if shardings is not None:
            # Host-built leaves (fresh cache, rebuilt tables) join the
            # step's mesh layout; leaves already there are left in place.
            cache = jax.device_put(cache, shardings)
        slot_mask = None
        if self.scfg.paged:
            # Continuous batching: released/empty rows still step (fixed
            # shapes) but are masked out of MoE routing so they never spend
            # expert bucket capacity or skew the balancer's counts. Always
            # an array (all-live when nothing is released): were it None on
            # full batches, the mask's appearance after the first retire
            # would change the step's pytree structure and force a second
            # compile — one program must serve idle, decode-only and
            # decode+chunk ticks alike.
            live = np.ones(token.shape[0], bool)
            live[sorted(self._released)] = False
            slot_mask = jnp.asarray(live)
        return chunk, cache, slot_mask

    def _advance(self, cache, stats):
        """Host mirrors after the step: the lane's logits, the written
        lengths, the position, the tick; released rows' lengths pinned."""
        # Chunk-lane logits (last valid chunk position): on the final chunk
        # of an admission these emit the request's first token. Host mirror
        # — the scheduler reads it right after the step it drove.
        self.last_chunk_logits = stats.get("chunk_logits")
        if self.scfg.paged and self._written is not None:
            for slot in range(len(self._written)):
                if slot not in self._released:
                    self._written[slot] += 1
            if self._released:
                # keep released rows inert: the model step incremented their
                # length past 0, which would grow a live prefix over the
                # write-off page — pin it back down.
                lengths = cache["layers"]["lengths"]
                idx = jnp.asarray(sorted(self._released))
                cache = {
                    **cache,
                    "layers": {
                        **cache["layers"],
                        "lengths": lengths.at[:, idx].set(0),
                    },
                }
        self._pos += 1
        self.t += 1
        return cache

    def generate(self, prompt, n_tokens: int, embeds=None):
        logits, cache = self.prefill(prompt, embeds=embeds)
        out = []
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        for _ in range(n_tokens):
            out.append(tok)
            logits, cache = self.decode(tok, cache)
            tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        return jnp.concatenate(out, axis=1)

    # -- balancing -----------------------------------------------------------

    def _maybe_balance(self, counts):
        if not should_trigger(
            [counts], self.scfg.alpha, self.t - self.last_mig, self.scfg.beta
        ):
            return
        plan = topology_aware_balance(self.state, self.distance)
        if not plan:
            return
        self.last_mig = self.t
        self.apply_plan(plan)

    def apply_plan(self, plan) -> int:
        """Execute a balancer plan ``[(expert, src, dst), ...]`` through
        the configured migration path — the one public entry point for
        placement changes (balancing, ops drills, revival seeding).

        With a driver (``migration_slices > 0``): reserve destination
        slots now; slices are issued one per decode tick by
        ``drain_migrations`` and ``self.migrations`` counts commits (the
        atomic table swaps). Returns the number of migrations accepted.
        Without a driver: synchronous whole-expert copies, applied (and
        counted) immediately."""
        if not plan:
            return 0
        if self.driver is None:
            applied = sum(self._apply_migration(mig) for mig in plan)
            self.migrations += applied
            return applied
        return len(self.driver.submit(plan, self._moe(), self.t))

    def drain_migrations(self) -> int:
        """Advance in-flight stepped migrations by one tick: commit the
        fully-copied ones (routing-table swap at a step boundary), then
        issue one weight-row slice for the rest. ``decode`` calls this at
        the top of every step; the scheduler calls it on idle ticks so
        migrations keep landing while no request is decodable. Returns the
        number of migrations committed this tick."""
        if self.driver is None:
            return 0
        committed = self.driver.tick(self._moe(), self.t)
        self.migrations += len(committed)
        return len(committed)

    def _copy_expert_rows(self, src_slot: int, dst_slot: int) -> None:
        """Whole-expert row copy (the instantaneous/fast-forward path; the
        stepped hot path copies per-tick slices in the driver instead)."""
        moe = self._moe()
        for w in MOE_WEIGHTS:
            moe[w] = moe[w].at[:, dst_slot].set(moe[w][:, src_slot])

    def _apply_migration(self, mig) -> bool:
        """Replicate expert ``e`` onto a free slot of device ``dst``,
        instantaneously. Returns True iff the migration was physically
        applied; a no-op (no free slot, or the expert is at its replica
        cap) leaves the table untouched — the reserve/commit pair cannot
        apply the routing half without the data movement or vice versa
        (the old split-table behaviour at the cap overwrote
        ``slot_of[e, -1]`` and leaked the previous replica's slot from the
        free-slot accounting forever)."""
        e, _src, dst = mig
        slot = self.table.try_reserve(e, dst)
        if slot is None:
            return False
        # Data movement: copy the expert's weight rows into the shadow slot
        # (a device-to-device transfer under the slot sharding).
        self._copy_expert_rows(int(self.table.slot_of[e, 0]), slot)
        self.table.commit(e, slot)
        return True

    # -- fault tolerance ------------------------------------------------------

    def _retarget(self, dead: int, mig):
        """Replacement for a migration aborted by ``dead``'s death: same
        expert, re-sourced from a live committed replica, aimed at the
        nearest live device with a free slot that doesn't already host (or
        expect) the expert. None when no such device exists."""
        e, _src, _dst = mig
        src = next(
            (
                d
                for d in self.table.replica_devices(e, include_pending=False)
                if d != dead and d not in self.state.dead
            ),
            None,
        )
        if src is None:
            return None            # evacuation will recreate the expert
        cand = [
            d
            for d in range(self.ep)
            if d != dead
            and d not in self.state.dead
            and d not in self.table.replica_devices(e)
            and self.table.free_slot(d) is not None
        ]
        if not cand:
            return None
        return (e, src, min(cand, key=lambda d: self.distance(src, d)))

    def mark_dead(self, device: int) -> list:
        """Node failure — the full evacuation path:

        1. in-flight stepped migrations touching the device are resolved
           first: slices headed *to* it abort (reservation released, then
           requeued toward a live destination from slice zero), slices
           sourced *from* it fast-forward to completion — either way no
           torn replica is ever committed;
        2. ``evacuate`` pins the device's heat to infinity and commits
           (table-side) a replica for every expert whose only live copy
           sat on the dead device;
        3. each evacuation entry's weight rows are copied whole
           (fast-forward — availability beats overlap here). The rows are
           read from the dead device's slot — valid in this logical
           simulation, where "death" means the scheduler stops routing to
           the device but its HBM is still addressable; a real wafer die
           failure would restore the rows from checkpoint shards instead;
        4. the dead device's replicas drop out of the shared table's
           routing view, so no token copy is dispatched to it again.

        Returns the evacuation plan (list of ``(expert, src, dst)``).
        """
        if self.state is None:
            return []
        if self.driver is not None:
            self.driver.handle_device_death(
                device,
                self._moe(),
                self.t,
                retarget=functools.partial(self._retarget, device),
            )
        plan = evacuate(self.state, device, self.distance)
        for e, _src, dst in plan:
            # Orphan source: usually the dying device's slot; under repeated
            # failures the sole copy may sit on an earlier-dead device, so
            # fall back to the native column (0 — commit appends after it).
            src_slot = self.table.slot_on_device(e, device)
            if src_slot is None:
                src_slot = int(self.table.slot_of[e, 0])
            dst_slot = self.table.slot_on_device(e, dst)
            self._copy_expert_rows(src_slot, dst_slot)
        self.table.drop_device(device)
        return plan

    def revive(self, device: int) -> list:
        """Device revival — re-admit a repaired device with *blank* HBM
        (wafer-scale chips have no on-wafer disk; everything it held died
        with it):

        1. the balancer forgets the death (finite heat, straggler penalty
           reset) so planning may target the device again;
        2. the device's free slot rows are scrubbed with ``BLANK_WEIGHT``
           — any premature route to an uncommitted replica now explodes
           instead of silently decoding stale weights. Slots still
           committed there (sole-copy orphans left by a failed evacuation)
           are spared: they are all the routing view has for that expert;
        3. :func:`~repro.core.ni_balancer.revival_plan` seeds the blank
           slots with the hottest per-replica experts from their nearest
           live hosts, and the plan goes through ``apply_plan`` — i.e. the
           stepped MigrationDriver when configured, so copies overlap
           decode ticks and routing only references the device once each
           replica's last slice commits. A second death mid-revival rides
           the driver's existing abort/fast-forward handling.

        After the seeded replicas commit, ``_maybe_balance`` sees the
        device's (low) heat and rebalances onto it naturally. Returns the
        revival plan."""
        if self.state is None:
            raise ValueError("revive requires the balancer serving path")
        device = int(device)
        if not 0 <= device < self.ep:
            raise ValueError(
                f"revive: device {device} is outside the EP axis "
                f"(want 0 <= device < {self.ep})"
            )
        if device not in self.state.dead:
            raise ValueError(f"revive: device {device} is not dead")
        self.state.revive(device)
        spd = self.table.slots_per_device
        used = self.table.used_slots()
        blank = [
            s
            for s in range(device * spd, (device + 1) * spd)
            if not used[s]
        ]
        if blank:
            moe = self._moe()
            idx = jnp.asarray(blank)
            for w in MOE_WEIGHTS:
                moe[w] = moe[w].at[:, idx].set(BLANK_WEIGHT)
        plan = revival_plan(self.state, device, self.distance)
        self.apply_plan(plan)
        return plan

    # -- crash-safe snapshot/restore ------------------------------------------

    @classmethod
    def restore_snapshot(
        cls, snap, cfg: ModelConfig, ctx: ParallelCtx, params, distance=None
    ):
        """Rebuild a live ``Server`` on a fresh process from a
        :class:`~repro.runtime.snapshot.ServerSnapshot` plus the params
        checkpoint (``params`` holds *logical* expert rows, exactly as a
        fresh ``__init__`` expects — the snapshot deliberately excludes
        weights). Expert rows are re-placed per the saved committed table,
        balancer truth (load EMA, dead set, slowdowns) is restored, and the
        pending-migration ledger is re-submitted from slice zero — partial
        slices died with the old process's HBM, and re-copying is
        idempotent because nothing routes to a reservation until commit."""
        scfg = ServeConfig(**snap.serve_cfg)
        table = None
        if snap.table is not None:
            table = PlacementTable(
                n_experts=cfg.n_experts,
                n_slots=int(snap.table["n_slots"]),
                slots_per_device=int(snap.table["slots_per_device"]),
                slot_of=snap.table["slot_of"],
                n_replicas=snap.table["n_replicas"],
            )
        srv = cls(cfg, ctx, params, scfg, distance=distance, table=table)
        srv.t = int(snap.t)
        srv.last_mig = int(snap.last_mig)
        srv.migrations = int(snap.migrations)
        if srv.state is not None:
            srv.state.load_ema = np.asarray(snap.load_ema, float).copy()
            srv.state.dead = set(int(d) for d in snap.dead)
            srv.state.slowdown = (
                None
                if snap.slowdown is None
                else np.asarray(snap.slowdown, float).copy()
            )
            if srv.driver is not None and snap.pending_migrations:
                srv.driver.submit(
                    [tuple(m["mig"]) for m in snap.pending_migrations],
                    srv._moe(),
                    srv.t,
                )
        return srv

    def report_step_time(self, device: int, ratio: float):
        """Straggler mitigation: fold measured step-time ratio into heats.

        Validates its inputs the way ``validate_ep_token_split`` does —
        the old silent acceptance let an out-of-range device id grow the
        slowdown array past the EP axis and a negative ratio drive a
        device's heat below zero, both corrupting Algorithm 1's ordering
        long after the bad report."""
        if self.state is None:
            return
        device = int(device)
        if not 0 <= device < self.ep:
            raise ValueError(
                f"report_step_time: device {device} is outside the EP axis "
                f"(want 0 <= device < {self.ep})"
            )
        ratio = float(ratio)
        if not np.isfinite(ratio) or ratio <= 0:
            raise ValueError(
                f"report_step_time: ratio {ratio} must be a finite positive "
                f"step-time ratio (measured / median); a non-positive EMA "
                f"would corrupt the balancer's heat ordering"
            )
        if self.state.slowdown is None:
            self.state.slowdown = np.ones(self.ep)
        self.state.slowdown[device] = (
            0.8 * self.state.slowdown[device] + 0.2 * ratio
        )
