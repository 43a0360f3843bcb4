"""Pieces every traffic generator shares: length draws, token draws and
the requests that warm every prompt shape before the window."""
from __future__ import annotations

from typing import Dict, List

import numpy as np


def draw_lengths(rng: np.random.Generator, spec: Dict, n: int) -> np.ndarray:
    """``n`` lengths from a spec: ``{"values", "weights"}`` (a discrete
    mix) or ``{"dist": "lognormal", "median", "sigma", "min", "max"}``."""
    if "values" in spec:
        p = np.asarray(spec["weights"], float)
        return rng.choice(np.asarray(spec["values"], int), size=n,
                          p=p / p.sum())
    if spec.get("dist") == "lognormal":
        x = rng.lognormal(np.log(spec["median"]), spec["sigma"], size=n)
        return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)
    raise ValueError(f"unknown length spec {spec}")


def tokens(seed: int, stream: int, index: int, n: int, vocab: int
           ) -> np.ndarray:
    """``n`` token ids in ``[2, vocab)`` from ``--seed``, one independent
    stream per (``stream``, ``index``) so every request's tokens are the
    same whatever order they are drawn in."""
    rng = np.random.default_rng([int(seed), int(stream), int(index)])
    return rng.integers(2, vocab, size=n).astype(np.int32)


#: token stream of the shape warm-up (apart from every window stream)
WARM_STREAM = 9


def warm_requests(lengths: List[int], tenant: str, seed: int, vocab: int,
                  max_new: int = 2) -> List[Dict]:
    """One short request per prompt length the window will send."""
    return [{"tenant": tenant, "max_new": max_new, "due": 0.0,
             "prompt": tokens(seed, WARM_STREAM, i, n, vocab)}
            for i, n in enumerate(lengths)]
