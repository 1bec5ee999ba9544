"""Live stepped expert migration, driven through the decode loop.

The analytical :class:`~repro.core.migration.MigrationEngine` showed that a
migration decomposed into Local/Global hops can ride the cold links the
attention/MoE collectives leave idle. This module is the *executable*
counterpart: it moves real expert weight rows, one slice per decode tick,
and swaps the routing table atomically only when the last slice has landed.

Lifecycle of one migration ``(expert, src_device, dst_device)``:

1. **submit** — reserve a destination slot in the shared
   :class:`~repro.parallel.placement.PlacementTable` (pending: visible to
   the balancer's planning view, invisible to routing) and decompose the
   move via :func:`repro.core.migration.decompose` into its Local/Global
   hop schedule. The hop count floors the slice count: a 3-hop migration
   never lands in fewer than 3 ticks.
2. **tick** (one per decode step) — issue one weight-row slice per tensor:
   a donated jit'd ``dynamic_slice``/``dynamic_update_slice`` pair copies
   rows ``[lo, lo+chunk)`` of the source slot into the reserved slot,
   in-place in the live parameter buffers. The copy is dispatched before
   the decode step and the arrays only meet again at the *next* step, so
   the transfer overlaps the step's compute — there is no whole-expert
   copy on the hot path. Tokens cannot observe the half-copied slot: it
   is not in the committed table.
3. **commit** — at the first tick boundary after the final slice was
   issued (i.e. after the XLA data dependency guarantees it landed before
   anything that consumes the new buffers), the table commit publishes the
   replica to the routing view. That single host-side table swap is the
   atomic commit point.

Device death mid-migration (``Server.mark_dead``) must never publish a
torn replica: in-flight migrations *to* the dead device are aborted (the
reservation is released) and requeued toward a live destination from slice
zero; migrations *from* the dead device are fast-forwarded — the remaining
slices are issued immediately and committed, which is safe under the
repo's logical death model (the scheduler stops routing to the device but
its memory stays addressable; see ``Server.mark_dead``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.er_mapping import Mapping, baseline_mapping
from repro.core.migration import MigStep, decompose
from repro.core.ni_balancer import Migration
from repro.core.topology import MeshTopology
from repro.parallel.placement import PlacementTable

MOE_WEIGHTS = ("w_gate", "w_up", "w_down")


@functools.partial(jax.jit, static_argnames=("rows",), donate_argnums=(0,))
def _copy_row_slice(w, src_slot, dst_slot, lo, *, rows: int):
    """Copy rows ``[lo, lo+rows)`` of slot ``src_slot`` onto ``dst_slot``.

    ``w`` is ``(L, n_slots, rows_total, cols)`` and is donated: the copy
    updates the live buffer instead of round-tripping every expert weight
    (the old full-tensor ``.at[:, slot].set(...)`` functional update).
    Slot ids and ``lo`` are traced scalars, so every slice of every
    migration reuses one compiled program per (shape, chunk). Its ops carry
    the named scope ``migration``."""
    with jax.named_scope("migration"):
        blk = jax.lax.dynamic_slice(
            w, (0, src_slot, lo, 0), (w.shape[0], 1, rows, w.shape[3])
        )
        return jax.lax.dynamic_update_slice(w, blk, (0, dst_slot, lo, 0))


def _i32(x: int):
    return jnp.asarray(x, jnp.int32)


@dataclasses.dataclass
class InFlightMigration:
    mig: Migration
    src_slot: int
    dst_slot: int
    n_slices: int
    hops: list[MigStep]
    submitted: int                 # server tick at submission
    next_slice: int = 0
    issue_ticks: list[int] = dataclasses.field(default_factory=list)

    @property
    def expert(self) -> int:
        return self.mig[0]

    @property
    def copied(self) -> bool:
        return self.next_slice >= self.n_slices

    def record(self, committed: int | None) -> dict:
        return {
            "mig": tuple(self.mig),
            "expert": self.expert,
            "src_slot": self.src_slot,
            "dst_slot": self.dst_slot,
            "n_slices": self.n_slices,
            "hops": [(h.kind, h.src, h.dst) for h in self.hops],
            "submitted": self.submitted,
            "issue_ticks": list(self.issue_ticks),
            "committed": committed,
        }


class MigrationDriver:
    """Owns the in-flight migrations; the Server ticks it once per decode
    step (and the scheduler on idle ticks, via ``drain_migrations``)."""

    def __init__(
        self,
        table: PlacementTable,
        min_slices: int = 4,
        mapping: Mapping | None = None,
        expert_bytes: float | None = None,
    ):
        self.table = table
        self.min_slices = max(1, int(min_slices))
        # Hop decomposition needs a topology; virtual EP has no physical
        # mesh, so default to a 1-D mesh where every device shares one FTD
        # (decompose then yields the single-Local-hop schedule).
        self.mapping = mapping or baseline_mapping(
            MeshTopology(1, table.n_devices), table.n_devices, 1
        )
        self.expert_bytes = expert_bytes
        self.in_flight: list[InFlightMigration] = []
        self.history: list[dict] = []
        self.aborted: list[dict] = []

    # -- submission ----------------------------------------------------------

    def _slot_bytes(self, moe: dict) -> float:
        if self.expert_bytes is None:
            self.expert_bytes = float(
                sum(
                    moe[w].dtype.itemsize * moe[w].size / moe[w].shape[1]
                    for w in MOE_WEIGHTS
                )
            )
        return self.expert_bytes

    def submit(
        self, plan: list[Migration], moe: dict, t: int
    ) -> list[Migration]:
        """Reserve destination slots for a balancer plan and build each
        migration's slice schedule. Unplaceable entries (no free slot /
        replica cap / already hosted or in flight) are skipped, mirroring
        the instantaneous path's no-op contract. Returns the accepted
        migrations."""
        accepted: list[Migration] = []
        nbytes = self._slot_bytes(moe)
        for mig in plan:
            e, src, dst = mig
            src_slot = self.table.slot_on_device(e, src)
            if src_slot is None:
                continue
            dst_slot = self.table.try_reserve(e, dst)
            if dst_slot is None:
                continue
            hops = decompose(mig, self.mapping, nbytes)
            self.in_flight.append(
                InFlightMigration(
                    mig=mig,
                    src_slot=src_slot,
                    dst_slot=dst_slot,
                    n_slices=max(self.min_slices, len(hops)),
                    hops=hops,
                    submitted=t,
                )
            )
            accepted.append(mig)
        return accepted

    # -- per-tick drive ------------------------------------------------------

    def _issue_slice(self, moe: dict, fl: InFlightMigration, t: int) -> None:
        i = fl.next_slice
        for name in MOE_WEIGHTS:
            w = moe[name]
            total = w.shape[2]
            chunk = min(total, -(-total // fl.n_slices))
            lo = max(0, min(i * chunk, total - chunk))
            moe[name] = _copy_row_slice(
                w, _i32(fl.src_slot), _i32(fl.dst_slot), _i32(lo), rows=chunk
            )
        fl.next_slice += 1
        fl.issue_ticks.append(t)

    def tick(self, moe: dict, t: int) -> list[dict]:
        """One decode-tick worth of progress: first commit migrations whose
        last slice was issued on a *previous* tick (the atomic table swap,
        at the step boundary), then issue this tick's slice for the rest.
        Returns the committed records."""
        committed: list[dict] = []
        remaining: list[InFlightMigration] = []
        for fl in self.in_flight:
            if fl.copied:
                self.table.commit(fl.expert, fl.dst_slot)
                rec = fl.record(committed=t)
                self.history.append(rec)
                committed.append(rec)
            else:
                self._issue_slice(moe, fl, t)
                remaining.append(fl)
        self.in_flight = remaining
        return committed

    # -- device death --------------------------------------------------------

    def handle_device_death(
        self,
        device: int,
        moe: dict,
        t: int,
        retarget: Callable[[Migration], Migration | None] | None = None,
    ) -> dict:
        """Resolve in-flight migrations touching a dead device *before*
        evacuation plans against the table. Migrations **to** the device
        abort (reservation released — the routing view never saw the slot)
        and requeue as ``retarget(mig)`` — a replacement migration with a
        live source and destination — from slice zero; migrations **from**
        it fast-forward (remaining slices issued now, then committed) so
        the expert keeps a fully-copied live replica."""
        survivors: list[InFlightMigration] = []
        out = {"aborted": [], "requeued": [], "fast_forwarded": []}
        requeue: list[Migration] = []
        for fl in self.in_flight:
            e, src, dst = fl.mig
            if self.table.device_of(fl.dst_slot) == device:
                self.table.release_pending(e, fl.dst_slot)
                rec = fl.record(committed=None)
                self.aborted.append(rec)
                out["aborted"].append(rec)
                new_mig = retarget(fl.mig) if retarget else None
                if new_mig is not None:
                    requeue.append(new_mig)
            elif self.table.device_of(fl.src_slot) == device:
                while not fl.copied:
                    self._issue_slice(moe, fl, t)
                self.table.commit(e, fl.dst_slot)
                rec = fl.record(committed=t)
                self.history.append(rec)
                out["fast_forwarded"].append(rec)
            else:
                survivors.append(fl)
        self.in_flight = survivors
        if requeue:
            out["requeued"] = self.submit(requeue, moe, t)
        return out

    @property
    def pending(self) -> int:
        return len(self.in_flight)

    def export_in_flight(self) -> list[dict]:
        """JSON-able ledger of in-flight migrations, for crash snapshots.
        Only the plan entry and its progress are exported: a restore
        re-submits from slice zero (partially copied slices died with the
        crashed process's HBM), so slot ids and hop schedules are
        recomputed against the restored table."""
        return [
            {
                "mig": list(fl.mig),
                "next_slice": fl.next_slice,
                "n_slices": fl.n_slices,
                "submitted": fl.submitted,
            }
            for fl in self.in_flight
        ]
