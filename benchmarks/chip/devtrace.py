"""Profiler trace of the measured window, and its reduction to numbers.

``capture`` wraps the window in ``jax.profiler`` (host spans of the
benchmark's own code land in the same trace, on the same clock);
``reduce`` turns the ``.xplane.pb`` into a :class:`Reduced` that every
per-layer metric reads. Nothing here knows a cell or a metric.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per executed HLO operation, named by the instruction's text
(``%expert_ffn_ragged.8 = bf16[...] custom-call(...)``; Pallas kernels
carry the name of their ``pallas_call``), cut here to the instruction's
name (``expert_ffn_ragged.8``); ``XLA Modules`` holds one event per
executed program (``jit_<name>(<fingerprint>)``; the serving step is the
one that takes most device time). Host spans written by the benchmark
(``jax.profiler.TraceAnnotation``) sit on the host plane's thread lines.
"""

from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import re
import shutil
import tempfile

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_NAME = re.compile(r"^%?([^\s=]+)")
TICK_SPAN = "bench.tick"


@contextlib.contextmanager
def capture(enabled: bool):
    """Trace the body when ``enabled``; yields a holder whose ``path`` is
    the ``.xplane.pb`` after the body (``None`` when not tracing). The
    trace goes to a temporary directory (under ``TMPDIR``), removed by
    :func:`discard`."""
    holder = type("Trace", (), {"path": None, "dir": None})()
    if not enabled:
        yield holder
        return
    import jax

    holder.dir = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0     # the Python tracer would slow the host loop
    opts.host_tracer_level = 2
    jax.profiler.start_trace(holder.dir, profiler_options=opts)
    try:
        yield holder
    finally:
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(holder.dir, "**", "*.xplane.pb"), recursive=True)
        holder.path = found[0] if found else None


def discard(holder) -> None:
    if holder.dir:
        shutil.rmtree(holder.dir, ignore_errors=True)


def union(iv: np.ndarray) -> float:
    """Length covered by intervals ``iv`` (``(n, 2)`` start, end)."""
    if len(iv) == 0:
        return 0.0
    iv = iv[np.argsort(iv[:, 0])]
    total, cs, ce = 0.0, iv[0, 0], iv[0, 1]
    for s, e in iv[1:]:
        if s > ce:
            total += ce - cs
            cs, ce = s, e
        else:
            ce = max(ce, e)
    return total + ce - cs


def merged(iv: np.ndarray) -> np.ndarray:
    """``iv`` merged into disjoint sorted intervals."""
    if len(iv) == 0:
        return np.zeros((0, 2))
    iv = iv[np.argsort(iv[:, 0])]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s > out[-1][1]:
            out.append([s, e])
        else:
            out[-1][1] = max(out[-1][1], e)
    return np.asarray(out, np.float64)


def gaps(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """Idle intervals of ``[lo, hi]`` not covered by ``iv``."""
    m = merged(clip(iv, lo, hi))
    edges = np.concatenate([[lo], m.reshape(-1), [hi]]).reshape(-1, 2)
    keep = edges[:, 1] > edges[:, 0]
    return edges[keep]


def clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    if len(iv) == 0:
        return np.zeros((0, 2))
    c = np.stack([np.maximum(iv[:, 0], lo), np.minimum(iv[:, 1], hi)], axis=1)
    return c[c[:, 1] > c[:, 0]]


def minus(a: np.ndarray, b: np.ndarray) -> float:
    """Length of the union of ``a`` not covered by ``b``."""
    if len(a) == 0:
        return 0.0
    a = merged(a)
    b = merged(b)
    total = 0.0
    for s, e in a:
        total += e - s
        if len(b):
            total -= union(clip(b, s, e))
    return total


@dataclasses.dataclass
class Device:
    """One chip's operations inside the window (times in ns)."""

    names: list
    iv: np.ndarray            # (n, 2) op start, end
    modules: list             # [(name, start, end)] executed programs

    def select(self, pattern: str) -> np.ndarray:
        rx = re.compile(pattern)
        keep = [i for i, n in enumerate(self.names) if rx.search(n)]
        return self.iv[keep] if keep else np.zeros((0, 2))

    def busy(self) -> float:
        return union(self.iv)


@dataclasses.dataclass
class Reduced:
    """The window of a traced run, reduced."""

    lo: float                 # window start, ns (first tick span)
    hi: float                 # window end, ns (last tick span)
    devices: list             # [Device]
    host: list                # [(name, start, end)] benchmark host spans
    n_ticks: int

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over the chips."""
        return float(np.mean([d.busy() for d in self.devices])) * 1e-9

    def kernel_s(self, pattern: str) -> float:
        """Summed device time of ops matching ``pattern``, averaged over the
        chips."""
        return float(np.mean([
            float(np.sum(d.select(pattern)[:, 1] - d.select(pattern)[:, 0]))
            for d in self.devices
        ])) * 1e-9

    def exposed_s(self, pattern: str) -> float:
        """Time of ops matching ``pattern`` during which no other op runs on
        that chip, averaged over the chips."""
        out = []
        for d in self.devices:
            rx = re.compile(pattern)
            hit = np.array([bool(rx.search(n)) for n in d.names], bool)
            out.append(minus(d.iv[hit], d.iv[~hit]) if hit.any() else 0.0)
        return float(np.mean(out)) * 1e-9

    def step_program(self) -> str | None:
        """The program that took most device time on the first chip."""
        tot: dict = {}
        for n, s, e in self.devices[0].modules:
            tot[n] = tot.get(n, 0.0) + (e - s)
        return max(tot, key=tot.get) if tot else None

    def step_gaps_s(self) -> np.ndarray:
        """Idle gaps between consecutive executions of the step program on
        the first chip (seconds)."""
        step = self.step_program()
        mods = sorted((s, e) for n, s, e in self.devices[0].modules if n == step)
        if len(mods) < 2:
            return np.zeros(0)
        m = np.asarray(mods, np.float64)
        return np.maximum(m[1:, 0] - m[:-1, 1], 0.0) * 1e-9

    def top_ops(self, k: int = 10) -> list:
        """The ``k`` operations that took most device time (first chip)."""
        d = self.devices[0]
        tot: dict = {}
        for n, (s, e) in zip(d.names, d.iv):
            tot[n] = tot.get(n, 0.0) + (e - s)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[n, v * 1e-9] for n, v in top]

    def idle_by_host(self, k: int = 10) -> list:
        """Device-idle time on the first chip, by the innermost benchmark
        host span covering each idle gap's middle."""
        spans = sorted(self.host, key=lambda x: x[2] - x[1])   # innermost first
        tot: dict = {}
        for s, e in gaps(self.devices[0].iv, self.lo, self.hi):
            mid = 0.5 * (s + e)
            name = next((n for n, hs, he in spans if hs <= mid <= he), "outside benchmark spans")
            tot[name] = tot.get(name, 0.0) + (e - s)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[n, v * 1e-9] for n, v in top]


def reduce(path: str, host_prefixes=("bench.", "server.", "sched.")) -> Reduced:
    """Read an ``.xplane.pb`` and cut it to the window spanned by the
    benchmark's tick spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host, ticks, devs = [], [], {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            names, iv, mods = [], [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for ev in line.events:
                        names.append(OP_NAME.match(ev.name).group(1))
                        iv.append((ev.start_ns, ev.end_ns))
                elif line.name == MODULES_LINE:
                    mods.extend((ev.name, ev.start_ns, ev.end_ns) for ev in line.events)
            devs[int(m.group(1))] = (names, np.asarray(iv, np.float64).reshape(-1, 2), mods)
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(host_prefixes):
                        host.append((ev.name, ev.start_ns, ev.end_ns))
                        if ev.name == TICK_SPAN:
                            ticks.append((ev.start_ns, ev.end_ns))
    if not ticks:
        raise ValueError(f"no {TICK_SPAN!r} spans in {path}")
    if not devs:
        raise ValueError(f"no device plane in {path}")
    lo, hi = min(t[0] for t in ticks), max(t[1] for t in ticks)
    devices = []
    for k in sorted(devs):
        names, iv, mods = devs[k]
        keep = (iv[:, 1] > lo) & (iv[:, 0] < hi) if len(iv) else np.zeros(0, bool)
        c = np.clip(iv[keep], lo, hi)
        devices.append(Device(
            [n for n, kk in zip(names, keep) if kk], c,
            [x for x in mods if x[2] > lo and x[1] < hi],
        ))
    return Reduced(lo, hi, devices, host, len(ticks))
