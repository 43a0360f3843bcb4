"""Closed loop: a fixed number of clients, each sending its next request
the moment its previous one finishes.

Each client's sequence of (prompt length, output length) is drawn once
from the mix's ``schedule_seed``; ``--seed`` draws the tokens and the
weights.  The clients start together at time 0 (the start of the warm-up
traffic), so their first requests are cut short in proportion to the
client's index, ``out * (c + 0.5) / clients``: their completions, and so
the admissions that follow, are spread as in a loop that has run for a
while, instead of arriving all at once.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from bench.traffic.common import draw_lengths, tokens, warm_requests

PROMPT_STREAM = 3
#: what a cell running this traffic states in its own file (nothing)
CELL_KEYS = ()
#: requests per client drawn ahead (a client needs a few per window)
JOBS = 64


class Source:
    """Requests of a closed loop of clients, from a mix's parameters."""

    def __init__(self, mix: Dict, seed: int, m: Dict,
                 load: Optional[Dict] = None):
        self.mix = mix
        self.seed = int(seed)
        self.vocab = int(m["vocab_size"])
        self.tenants = [(t["name"], int(t["priority"]))
                        for t in mix["tenants"]]
        self.clients = int(mix["clients"])
        rng = np.random.default_rng(int(mix["schedule_seed"]))
        n = self.clients * JOBS
        self._plen = draw_lengths(rng, mix["prompt_tokens"], n) \
            .reshape(self.clients, JOBS)
        out = draw_lengths(rng, mix["output_tokens"], n) \
            .reshape(self.clients, JOBS)
        first = np.maximum(1, np.rint(out[:, 0] * (np.arange(self.clients)
                                                   + 0.5) / self.clients))
        out[:, 0] = first.astype(int)
        self._out = out
        self._job = [0] * self.clients
        self._ready: List[tuple] = [(0.0, c) for c in range(self.clients)]

    @property
    def prompt_lengths(self) -> List[int]:
        return sorted(set(int(v) for v in self.mix["prompt_tokens"]["values"]))

    def warm_requests(self) -> List[Dict]:
        return warm_requests(self.prompt_lengths, self.tenants[0][0],
                             self.seed, self.vocab)

    def due(self, t: float) -> List[Dict]:
        """The next request of every client that is free by ``t``."""
        out = []
        keep = []
        for when, c in self._ready:
            if when > t:
                keep.append((when, c))
                continue
            j = self._job[c]
            if j >= JOBS:
                raise RuntimeError(f"client {c} ran out of requests")
            self._job[c] = j + 1
            key = c * JOBS + j
            out.append({"key": key, "tenant": self.tenants[0][0],
                        "prompt": tokens(self.seed, PROMPT_STREAM, key,
                                         int(self._plen[c, j]), self.vocab),
                        "max_new": int(self._out[c, j]), "due": float(when)})
        self._ready = keep
        return out

    def next_due(self) -> Optional[float]:
        return min((w for w, _ in self._ready), default=None)

    def finished(self, key: int, t: float) -> None:
        """The client of request ``key`` sends its next one at ``t``."""
        self._ready.append((float(t), key // JOBS))


def make(mix: Dict, seed: int, m: Dict, load: Optional[Dict] = None
         ) -> Source:
    """The mix's requests for configuration ``m`` (its vocabulary),
    tokens drawn from ``seed``; a closed loop reads nothing of the
    cell's load."""
    return Source(mix, seed, m, load)
