"""Count matrices of the exchange cells, from the parameters of a traffic file.

`hugetrace_like_counts` and `banded_counts` are the signatures of the
paper's irregular patterns (arXiv:2604.05099, Fig. 3/4): banded locality
plus heavily loaded receivers, as SuiteSparse hugetrace-00020 partitioned
over ranks shows, and strictly banded neighbourhood traffic.
"""

from __future__ import annotations

import numpy as np


def hugetrace_like_counts(p: int, base_rows: int, seed: int,
                          hot_ranks=(), hot_factor: float = 6.0) -> np.ndarray:
    """Banded structure plus receiver hot spots."""
    rng = np.random.default_rng(seed)
    c = np.zeros((p, p), np.int64)
    for i in range(p):
        for j in range(p):
            band = max(0.0, 1.0 - abs(i - j) / 2.5)     # near-diagonal locality
            c[i, j] = rng.poisson(base_rows * (0.15 + band))
    for j in hot_ranks:                                  # skewed receivers
        c[:, j] = (c[:, j] * hot_factor).astype(np.int64)
    return c


def banded_counts(p: int, base_rows: int, seed: int, width: int = 1) -> np.ndarray:
    """Traffic only within `width` ring hops."""
    rng = np.random.default_rng(seed)
    c = np.zeros((p, p), np.int64)
    for i in range(p):
        for d in range(-width, width + 1):
            c[i, (i + d) % p] = rng.integers(base_rows // 2, base_rows + 1)
    return c


GENERATORS = {"hugetrace": hugetrace_like_counts, "banded": banded_counts}


def counts(traffic: dict, ranks: int, row_bytes: int) -> np.ndarray:
    """The traffic's count matrix, scaled so that the mean over all P*P
    pairs is `mean_pair_bytes` (to the nearest row per pair)."""
    kw = dict(traffic.get("pattern_args", {}))
    c = GENERATORS[traffic["pattern"]](ranks, int(traffic["base_rows"]),
                                       int(traffic["pattern_seed"]), **kw)
    want = traffic["mean_pair_bytes"] / row_bytes * ranks * ranks
    return np.rint(c * (want / c.sum())).astype(np.int64)
