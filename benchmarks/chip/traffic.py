"""One generator for every traffic mix: ``traffic/<mix>.json`` holds only data.

A mix file names its loop and its distributions; this module turns it and
``--seed`` into requests. Every seed gets the same *set* of sizes and gaps,
in another order: the sizes are the distribution's quantiles at
``(i + 0.5) / n`` for ``i < n`` (stratified, so the set follows the stated
distribution exactly), and the seed only permutes them and draws the prompt
token ids. So two seeds ask for the same work, and a difference between
their runs is the system's, not the traffic's.

Distributions (``{"dist": ..., ...}``):

* ``fixed``      — ``value``;
* ``uniform``    — ``min`` .. ``max``;
* ``loguniform`` — ``min`` .. ``max``, uniform in the logarithm;
* ``lognormal``  — ``median`` and ``sigma`` (of the logarithm), clipped to
  ``min`` .. ``max``;
* ``exponential``— ``mean`` (used for Poisson inter-arrival gaps).

Loops:

* ``closed`` — ``clients`` callers, each sending its next request as soon as
  its last one finished;
* ``open``   — Poisson arrivals at ``rate`` requests per second, whatever
  the system does (a request is timed from when it was due). The set-up's
  arrivals and the window's are each a phase of their own
  (:meth:`Traffic.phase`): the window holds exactly ``floor(rate x
  seconds)`` arrivals whose sizes and gaps are stratified over that number,
  so every seed's window has the same requests and the same gaps, in
  another order.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

_PHI = NormalDist()


def quantile(dist: dict, u: np.ndarray) -> np.ndarray:
    """Values of ``dist`` at probabilities ``u`` (floats, not rounded)."""
    kind = dist["dist"]
    if kind == "fixed":
        return np.full(u.shape, float(dist["value"]))
    if kind == "uniform":
        return dist["min"] + u * (dist["max"] - dist["min"])
    if kind == "loguniform":
        lo, hi = math.log(dist["min"]), math.log(dist["max"])
        return np.exp(lo + u * (hi - lo))
    if kind == "lognormal":
        z = np.array([_PHI.inv_cdf(float(p)) for p in u])
        x = dist["median"] * np.exp(dist["sigma"] * z)
        return np.clip(x, dist.get("min", 0), dist.get("max", np.inf))
    if kind == "exponential":
        return -dist["mean"] * np.log1p(-u)
    raise ValueError(f"unknown distribution {kind!r}")


def stratified(dist: dict, n: int) -> np.ndarray:
    """The ``n`` stratified quantiles of ``dist``, in increasing order."""
    return quantile(dist, (np.arange(n) + 0.5) / n)


def lengths(dist: dict, n: int) -> np.ndarray:
    return np.maximum(np.rint(stratified(dist, n)), 1).astype(np.int64)


@dataclasses.dataclass
class Req:
    """One generated request: its prompt, its output length and (open loop)
    the second, counted from the window's start, at which it is due."""

    index: int
    prompt: np.ndarray
    out_len: int
    due: float | None = None


class Traffic:
    """Requests of one mix for one seed, in the order they are sent."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix = mix
        self.seed = int(seed)
        self.vocab = int(vocab)
        self.n = int(mix["pool"])
        if mix["loop"] not in ("open", "closed"):
            raise ValueError(f"unknown loop {mix['loop']!r}")
        rng = np.random.default_rng([self.seed, 0x7EAF])
        self._prompts = rng.permutation(lengths(mix["prompt"], self.n))
        self._outs = rng.permutation(lengths(mix["output"], self.n))
        self._tokens = np.random.default_rng([self.seed, 0x70C5])
        self.sent = 0

    @property
    def closed(self) -> bool:
        return self.mix["loop"] == "closed"

    def _req(self, prompt_len: int, out_len: int, due=None) -> Req:
        prompt = self._tokens.integers(0, self.vocab, size=int(prompt_len), dtype=np.int32)
        self.sent += 1
        return Req(self.sent - 1, prompt, int(out_len), due)

    def probe(self, prompt_len: int, out_len: int) -> Req:
        """A request of the given sizes outside the mix (set-up's own)."""
        return self._req(prompt_len, out_len)

    def next(self) -> Req:
        """The closed loop's next request (the pool repeats, in the same
        order, if a run outlasts it)."""
        i = self.sent % self.n
        return self._req(self._prompts[i], self._outs[i])

    def phase(self, n: int, tag: int) -> list[Req]:
        """``n`` open-loop arrivals, due at the returned ``due`` seconds
        after the phase starts (the first at 0): prompt and output lengths
        and the Poisson gaps each stratified over ``n`` and permuted by the
        seed (and ``tag``, one per phase)."""
        rng = np.random.default_rng([self.seed, 0x0DE7, int(tag)])
        prompts = rng.permutation(lengths(self.mix["prompt"], n))
        outs = rng.permutation(lengths(self.mix["output"], n))
        gaps = rng.permutation(stratified(
            {"dist": "exponential", "mean": 1.0 / self.mix["rate"]}, n))
        due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
        return [self._req(p, o, float(t)) for p, o, t in zip(prompts, outs, due)]
