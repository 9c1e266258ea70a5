"""The one traffic generator: every mix is a data file it reads.

A mix (``bench/traffic/<name>.json``) gives the loop (``open`` at
``rate_per_s``, or ``closed`` with ``clients``), the length distributions of
prompts and outputs, and ``max_total``, the serving path's bound on prompt
plus output.  Sizes and gaps are stratified so that every seed gets the same
work in another order: each cycle of ``cycle`` requests takes each
distribution's quantiles at ``(j + 0.5) / cycle`` once, prompts and outputs
paired by one fixed permutation, and the seed only permutes the pairs and
the gaps (and draws the prompt tokens).  Runs with different seeds then
differ by arrival order, not by how much work the window holds.
"""

from __future__ import annotations

import bisect
import json
import math
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"

_NORMAL = NormalDist()


def load_mix(name: str, directory: Path = TRAFFIC_DIR) -> dict:
    path = directory / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} ({path})")
    mix = json.loads(path.read_text())
    if mix.get("loop") not in ("open", "closed"):
        raise ValueError(f"mix {name!r}: loop must be 'open' or 'closed'")
    if mix["loop"] == "open" and not mix.get("rate_per_s"):
        raise ValueError(f"mix {name!r}: an open loop needs rate_per_s")
    if mix["loop"] == "closed" and not mix.get("clients"):
        raise ValueError(f"mix {name!r}: a closed loop needs clients")
    return mix


def quantile(dist: dict, q: float) -> int:
    """The ``q`` quantile of a length distribution, as a whole number."""
    kind = dist["dist"]
    if kind == "lognormal":
        x = dist["median"] * math.exp(dist["sigma"] * _NORMAL.inv_cdf(q))
        steps = dist.get("round_up_to")
        if steps:
            return steps[min(bisect.bisect_left(steps, x), len(steps) - 1)]
        return max(1, math.ceil(x))
    if kind == "choice":
        vals = dist["values"]
        return vals[min(int(q * len(vals)), len(vals) - 1)]
    if kind == "uniform_int":
        lo, hi = dist["min"], dist["max"]
        return lo + min(int(q * (hi - lo + 1)), hi - lo)
    raise ValueError(f"unknown length distribution {kind!r}")


def prompt_lengths(mix: dict) -> list[int]:
    """Every prompt length the mix can send: the shapes set-up warms."""
    k = mix["cycle"]
    return sorted({quantile(mix["prompt"], (j + 0.5) / k) for j in range(k)})


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([seed & (2**64 - 1), *stream])


@dataclass(frozen=True)
class Spec:
    """One request: its tokens, its output budget, and (open loop) the
    seconds after the start of traffic at which it is due."""

    index: int
    prompt: np.ndarray
    max_new: int
    due: float


class Traffic:
    """The request stream of one mix under one seed; ``spec(i)`` is the
    ``i``-th request.  Deterministic in ``(mix, seed)``."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix = mix
        self.seed = seed
        self.vocab = vocab
        self.cycle = int(mix["cycle"])
        k = self.cycle
        qs = [(j + 0.5) / k for j in range(k)]
        prompts = [quantile(mix["prompt"], q) for q in qs]
        outputs = [quantile(mix["output"], q) for q in qs]
        pairing = _rng(0, 0).permutation(k)
        lo = mix["output"].get("min", 1)
        self._pairs = [(p, max(lo, min(outputs[pairing[j]],
                                       mix["max_total"] - p)))
                       for j, p in enumerate(prompts)]
        rate = mix.get("rate_per_s")
        self._gap_q = ([-math.log(1.0 - q) / rate for q in qs]
                       if rate else None)
        self._due: list[float] = []
        self._cycles: dict[int, tuple] = {}

    def _cycle(self, c: int):
        got = self._cycles.get(c)
        if got is None:
            rng = _rng(self.seed, 1, c)
            got = (rng.permutation(self.cycle), rng.permutation(self.cycle))
            self._cycles[c] = got
        return got

    def due(self, i: int) -> float:
        """Seconds after traffic starts at which request ``i`` is due (open
        loop only): the sum of the first ``i + 1`` gaps."""
        while len(self._due) <= i:
            n = len(self._due)
            c, j = divmod(n, self.cycle)
            gap = self._gap_q[self._cycle(c)[1][j]]
            self._due.append((self._due[-1] if self._due else 0.0) + gap)
        return self._due[i]

    def spec(self, i: int) -> Spec:
        c, j = divmod(i, self.cycle)
        plen, out = self._pairs[self._cycle(c)[0][j]]
        prompt = _rng(self.seed, 2, i).integers(0, self.vocab, plen,
                                                dtype=np.int32)
        due = self.due(i) if self._gap_q is not None else 0.0
        return Spec(index=i, prompt=prompt, max_new=int(out), due=due)
