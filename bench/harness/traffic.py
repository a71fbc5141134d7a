"""The one traffic generator: a mix's parameters and a seed -> requests.

A traffic file holds only numbers. The generator reads:

- ``prompt_len``: feature vectors in each request's prompt window;
- ``served_steps``: ``[min, max]`` classes served per request after its
  prompt, one per further feature vector;
- ``distinct``: how many distinct requests the mix holds. Their served
  lengths are spread evenly over ``[min, max]``; the seed draws their
  order and every feature value. Request ``i`` of a run is distinct
  request ``i % distinct``, so every seed offers the same set of sizes,
  and the reference needs only the distinct requests.

Features are standard normal, like standardized jet features.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


def rng(seed: int, stream: int) -> np.random.Generator:
    """Independent generator ``stream`` of a run's ``seed`` (any size)."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


@dataclass
class Pool:
    """The distinct requests of a run."""
    prompt_len: int
    served: np.ndarray       # (P,) classes served by each distinct request
    feats: np.ndarray        # (P, prompt_len + max(served), X) float32

    def __post_init__(self):
        S = self.prompt_len
        self._items = [(j, self.feats[j, :S], self.feats[j, S:S + int(n)],
                        int(n)) for j, n in enumerate(self.served)]

    def __len__(self) -> int:
        return len(self.served)

    def item(self, i: int):
        """(pool index, prompt (S, X), stream (n, X), n) of request ``i``."""
        return self._items[i % len(self._items)]


def pool(traffic: dict, input_dim: int, seed: int) -> Pool:
    lo, hi = (int(v) for v in traffic["served_steps"])
    P = int(traffic["distinct"])
    if not 1 <= lo <= hi or P < 1:
        raise ValueError(f"bad traffic sizes: served_steps={lo, hi}, "
                         f"distinct={P}")
    g = rng(seed, 1)
    served = g.permutation(np.rint(np.linspace(lo, hi, P)).astype(np.int64))
    S = int(traffic["prompt_len"])
    feats = g.standard_normal((P, S + hi, input_dim), dtype=np.float32)
    return Pool(prompt_len=S, served=served, feats=feats)
