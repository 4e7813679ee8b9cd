"""The one traffic generator: reads a mix's data file and draws requests
from the seed.

A mix file (``traffic/<mix>.json``) names the cache policy every request
asks for and the arrival process:

* ``"arrivals": "poisson"``: open loop at ``rate_per_s``, in blocks of
  ``block`` arrivals that each span ``block / rate_per_s`` seconds, until
  the window ends (a window of whole blocks offers the same count to
  every seed).  A block's gaps are the midpoint quantiles of the
  exponential law (a Poisson process's gaps) in an order drawn from the
  seed, so every seed offers the same requests at the same gaps, only
  ordered differently, and the load never drifts from the rate by more
  than a block.
* ``"arrivals": "backlog"``: ``depth`` requests are ready at the start of
  the window and the queue is topped up to ``depth`` whenever it falls
  below, until the window ends.

Each request's conditioning record (a class label, a prompt, ...) is
drawn from the same stream, right after its seed, by the configuration's
reference (``condition``), which reads the mix's own keys for it; this
module passes records through without reading them.  The same seed gives
the same requests; the program sees only the generated requests.
"""
from __future__ import annotations

import math
from typing import Any, Callable, List, Optional, Tuple

import numpy as np

#: salts that keep the streams of one seed apart
_SALT = {"arrivals": 1, "requests": 2, "warmup": 3, "sample": 4,
         "calibration": 5}


def rng_for(seed: int, stream: str) -> np.random.Generator:
    seed = int(seed)
    return np.random.default_rng([seed & 0xFFFFFFFF, (seed >> 32)
                                  & 0xFFFFFFFF, _SALT[stream]])


def exponential_gaps(rate: float, k: int) -> np.ndarray:
    """``k`` gaps at the midpoint quantiles of the exponential law of mean
    ``1 / rate``, scaled so that they span exactly ``k / rate``."""
    g = -np.log1p(-(np.arange(k) + 0.5) / k) / rate
    return g * (k / rate / g.sum())


def blocked_arrivals(rate: float, k: int, blocks: int, rng) -> List[float]:
    """``blocks × k`` arrival offsets: each block of ``k`` arrivals spans
    ``k / rate`` seconds with the gaps of :func:`exponential_gaps`, in an
    order drawn from ``rng``; the first comes at 0.  Every seed gets the
    same gaps and the same count; only their order within each block
    differs."""
    g = exponential_gaps(rate, k)
    gaps = np.concatenate([rng.permutation(g) for _ in range(blocks)]
                          or [np.zeros(0)])
    return (np.cumsum(gaps) - gaps).tolist()


def buckets(max_batch: int) -> Tuple[int, ...]:
    out, b = [], 1
    while b <= max_batch:
        out.append(b)
        b *= 2
    return tuple(out)


class Traffic:
    """The requests of one run: ``(offset or None, request seed, record)``
    triples, offsets in seconds from the start of the window.
    ``condition(rng)`` draws one request's conditioning record."""

    def __init__(self, mix: dict, seed: int, seconds: float,
                 condition: Callable[[np.random.Generator], Any]):
        self.mix = mix
        self.condition = condition
        self.seconds = float(seconds)
        self._req = rng_for(seed, "requests")
        kind = mix["arrivals"]
        if kind == "poisson":
            rate, k = float(mix["rate_per_s"]), int(mix["block"])
            blocks = math.ceil(self.seconds * rate / k - 1e-9)
            self.offsets = [t for t in blocked_arrivals(
                rate, k, blocks, rng_for(seed, "arrivals"))
                if t < self.seconds]
        elif kind == "backlog":
            self.offsets = None
            self.depth = int(mix["depth"])
        else:
            raise ValueError(f"unknown arrival process {kind!r}")

    @property
    def open_loop(self) -> bool:
        return self.offsets is not None

    def draw(self) -> Tuple[int, Any]:
        """The next request's (seed, conditioning record)."""
        seed = int(self._req.integers(0, 1 << 31))
        return seed, self.condition(self._req)

    def initial(self) -> List[Tuple[Optional[float], int, Any]]:
        """Every request due in the window (open loop), or the backlog
        ready at its start."""
        if self.open_loop:
            return [(t,) + self.draw() for t in self.offsets]
        return [(0.0,) + self.draw() for _ in range(self.depth)]

    def buckets(self, max_batch: int) -> Tuple[int, ...]:
        """The batch shapes this traffic forms: every bucket for open-loop
        arrivals, only the full one behind a backlog of at least
        ``max_batch``."""
        if self.open_loop:
            return buckets(max_batch)
        if self.depth < 2 * max_batch:
            raise ValueError(f"a backlog of {self.depth} cannot keep "
                             f"batches of {max_batch} full")
        return (max_batch,)
