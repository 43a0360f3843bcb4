"""Open-loop arrivals: requests are due on a clock, whether or not the
server keeps up.

A mix gives tenants (priority, share of requests, system-prompt length),
user-turn and output length distributions and the coefficient of
variation of the gamma inter-arrival times; the mean rate is the cell's
(``rate_per_s`` in ``bench/cells/<cell>.json``: four fifths of the knee
found for that cell on the chip).  The arrival
schedule (gaps, tenants, lengths) is drawn once from the mix's own
``schedule_seed``, so every ``--seed`` offers the same sequence of sizes
at the same times; ``--seed`` draws the tokens (each tenant's system
prompt, every user turn) and the weights.  Time 0 is the start of the
warm-up traffic; the window opens ``warmup_s`` later.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from bench.traffic.common import draw_lengths, tokens, warm_requests

SYSTEM_STREAM = 1
USER_STREAM = 2
#: what a cell running this traffic states in its own file
CELL_KEYS = ("rate_per_s",)


class Source:
    """Requests due by a clock, from a mix's parameters."""

    def __init__(self, mix: Dict, seed: int, m: Dict, load: Dict):
        self.mix = mix
        self.seed = int(seed)
        self.vocab = int(m["vocab_size"])
        self.rate = float(load["rate_per_s"])
        self.tenants = [(t["name"], int(t["priority"]))
                        for t in mix["tenants"]]
        rng = np.random.default_rng(int(mix["schedule_seed"]))
        n = int(mix["schedule_requests"])
        shape = 1.0 / mix["cv"] ** 2
        gaps = rng.gamma(shape, 1.0 / shape, size=n)        # mean 1
        share = np.asarray([t["share"] for t in mix["tenants"]], float)
        self._tenant = rng.choice(len(self.tenants), size=n,
                                  p=share / share.sum())
        self._user = draw_lengths(rng, mix["user_tokens"], n)
        self._out = draw_lengths(rng, mix["output_tokens"], n)
        self._due = np.cumsum(gaps) / self.rate
        self._system = [tokens(self.seed, SYSTEM_STREAM, i,
                               t["system_tokens"], self.vocab)
                        for i, t in enumerate(mix["tenants"])]
        self._next = 0

    @property
    def prompt_lengths(self) -> List[int]:
        sys_lens = {t["system_tokens"] for t in self.mix["tenants"]}
        return sorted({s + u for s in sys_lens
                       for u in self.mix["user_tokens"]["values"]})

    def warm_requests(self) -> List[Dict]:
        return warm_requests(self.prompt_lengths, self.tenants[0][0],
                             self.seed, self.vocab)

    def _request(self, i: int) -> Dict:
        t = int(self._tenant[i])
        user = tokens(self.seed, USER_STREAM, i, int(self._user[i]),
                      self.vocab)
        return {"key": i, "tenant": self.tenants[t][0],
                "prompt": np.concatenate([self._system[t], user]),
                "max_new": int(self._out[i]), "due": float(self._due[i])}

    def due(self, t: float) -> List[Dict]:
        """Every request due at or before ``t`` not handed out yet."""
        out = []
        while self._next < len(self._due) and self._due[self._next] <= t:
            out.append(self._request(self._next))
            self._next += 1
        if self._next >= len(self._due):
            raise RuntimeError("schedule exhausted: raise schedule_requests")
        return out

    def next_due(self) -> Optional[float]:
        return float(self._due[self._next])

    def finished(self, key: int, t: float) -> None:
        """Completions do not steer an open loop."""


def make(mix: Dict, seed: int, m: Dict, load: Dict) -> Source:
    """The mix's requests for configuration ``m`` (its vocabulary) at the
    cell's rate ``load["rate_per_s"]``, tokens drawn from ``seed``."""
    return Source(mix, seed, m, load)
