"""The program's own spans and named scopes, read from a reduced trace.

Host spans: the scheduler and the server write ``jax.profiler``
``TraceAnnotation``s (``sched.step`` > ``sched.admit``, ``sched.chunk``,
``server.decode`` > ``server.prepare`` / ``server.migrate`` /
``server.dispatch`` / ``server.counts`` / ``server.balance``, and
``sched.sample`` > ``sched.logits_to_host``) into the profiler's trace, on
the device trace's clock; ``devtrace.reduce`` keeps them in
``Reduced.host``. :func:`span_s` sums them over the window.

Device scopes: the step marks its parts with ``jax.named_scope``
(``decode`` and ``lane`` around the decode rows' and the prefill lane's
layers, ``lm_head`` around each LM head; ``router``, ``dispatch``,
``expert_ffn`` and ``combine`` inside the MoE layer), and the migration
slice copy with ``migration``. A scope is op metadata only: it reaches the
compiled program as each HLO instruction's ``op_name`` (the name stack,
``.../lane/expert_ffn/jit(expert_ffn_ragged)/pallas_call``). The reduced
trace keeps each executed op by its instruction name and each executed
program's interval, not the op's metadata (nor does the trace hold it: on
a TPU v5e an ``XLA Ops`` event's stats, as ``jax.profiler.ProfileData``
reads them, are ``device_offset_ps``, ``device_duration_ps`` and ``Time
Scale Multiplier`` only), so :class:`Scopes` maps an op to the
``op_name`` of its instruction in the compiled HLO of the program that ran
it: the step (lowered again from its live operands; the executable is
the one in memory) and the slice copy (compiled anew; see
:func:`copy_hlo`). Ops of other programs have no scope.
"""

from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np

import devtrace as tr

INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+) = ")
OP_NAME = re.compile(r'\bop_name="([^"]*)"')
MODULE = re.compile(r"^HloModule\s+([^\s,]+)")


def op_names(hlo: str) -> dict:
    """Instruction name -> ``op_name`` metadata, from a compiled program's
    HLO text."""
    out = {}
    for line in hlo.splitlines():
        m = INSTR.match(line)
        if m:
            n = OP_NAME.search(line)
            if n:
                out[m.group(1)] = n.group(1)
    return out


def module_name(hlo: str) -> str:
    m = MODULE.match(hlo)
    return m.group(1) if m else ""


def in_scope(stack: str, component: str) -> bool:
    """Whether ``component`` is a whole part of the name stack ``stack``."""
    return component in stack.split("/")


class Scopes:
    """Op name stacks of a reduced trace: ``step`` maps the step program's
    instructions (the trace's ``step_program()``), ``others`` maps, by HLO
    module name, the instructions of further programs."""

    def __init__(self, trace: tr.Reduced, step: dict, others: dict | None = None):
        self.trace, self.step, self.others = trace, step, dict(others or {})
        self._step_module = trace.step_program()

    def _table(self, module: str) -> dict:
        if module == self._step_module:
            return self.step
        return self.others.get(module.split("(")[0], {})

    def stacks(self, dev: tr.Device) -> list:
        """Each op's name stack ("" where its program is not mapped): the
        program is the one whose execution interval holds the op's start."""
        mods = sorted(dev.modules, key=lambda m: m[1])
        starts = np.array([m[1] for m in mods], np.float64)
        ks = np.searchsorted(starts, dev.iv[:, 0], side="right") - 1
        out = []
        for name, s, k in zip(dev.names, dev.iv[:, 0], ks):
            table = self._table(mods[k][0]) if k >= 0 and s <= mods[k][2] else {}
            out.append(table.get(name, ""))
        return out

    @functools.cached_property
    def _stacks(self) -> list:
        return [self.stacks(d) for d in self.trace.devices]

    def scope_s(self, component: str) -> float:
        """Summed device time of the ops under the scope ``component``,
        averaged over the chips (seconds)."""
        per = []
        for d, st in zip(self.trace.devices, self._stacks):
            hit = np.array([in_scope(s, component) for s in st], bool)
            per.append(float(np.sum(np.diff(d.iv[hit], axis=1))) if hit.any() else 0.0)
        return float(np.mean(per)) * 1e-9


def span_s(trace: tr.Reduced, *names: str) -> float | None:
    """Host seconds inside the spans named ``names``, each clipped to the
    window, summed; ``None`` where the trace holds no such span."""
    iv = np.array([(s, e) for n, s, e in trace.host if n in names], np.float64)
    if not len(iv):
        return None
    return float(np.sum(np.diff(tr.clip(iv.reshape(-1, 2), trace.lo, trace.hi), axis=1))) * 1e-9


def per_tick_ms(trace: tr.Reduced, *names: str) -> float | None:
    """:func:`span_s` in milliseconds per tick of the window."""
    s = span_s(trace, *names)
    return None if s is None or trace.n_ticks == 0 else 1e3 * s / trace.n_ticks


def step_hlo(system) -> str:
    """The compiled HLO of the served step, lowered from the live operands
    of the system's last tick: JAX's in-memory cache hands back the very
    executable the window ran."""
    srv, sched = system.server, system.sched
    chunk = None
    if srv.scfg.prefill_chunk:
        chunk = srv.noop_chunk()
        if srv._chunk_shardings is not None:
            chunk = jax.device_put(chunk, srv._chunk_shardings)
    placement = srv.table.device_view() if srv.use_balancer else None
    low = srv._decode.lower(
        srv.params, jnp.asarray(sched.next_tok), sched.cache, placement=placement,
        slot_mask=jnp.ones(sched.batch, bool), chunk=chunk,
    )
    return low.compile().as_text()


def copy_hlo(system) -> list:
    """The compiled HLO of the migration slice copy, one per weight shape,
    at the slice size of a migration's fewest slices (``min_slices``),
    compiled anew with the metadata in the persistent cache's key. That
    key otherwise leaves the scopes out, so the executable the window
    loaded may be one that a build with other scopes left in a shared
    cache, with that build's ``op_name``s (its instructions' names are
    the same). JAX's in-memory caches, which hold that executable, are
    cleared first: call this after :func:`step_hlo`, which wants the very
    executable that ran."""
    from repro.runtime import migration_driver as md

    srv = system.server
    if srv.driver is None:
        return []
    out = []
    jax.clear_caches()
    key = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(jax.config, key)
    jax.config.update(key, True)
    try:
        for name in md.MOE_WEIGHTS:
            w = srv._moe()[name]
            rows = min(w.shape[2], -(-w.shape[2] // srv.driver.min_slices))
            out.append(md._copy_row_slice.lower(
                w, md._i32(0), md._i32(0), md._i32(0), rows=rows).compile().as_text())
    finally:
        jax.config.update(key, was)
    return out


def of(c) -> Scopes:
    """The :class:`Scopes` of a run's counters (built once per run)."""
    if getattr(c, "_scopes", None) is None:
        step = op_names(step_hlo(c.run.sys))
        others: dict = {}
        for hlo in copy_hlo(c.run.sys):
            others.setdefault(module_name(hlo), {}).update(op_names(hlo))
        c._scopes = Scopes(c.trace, step, others)
    return c._scopes
