"""Plain alltoallv in numpy, and the comparison that decides `correct`.

MPI semantics: rank i's send buffer holds its rows for rank 0, 1, ... in
order, contiguous (displacements are exclusive prefix sums of the counts);
rank j receives the blocks of rank 0, 1, ... in sender order, contiguous.
"""

from __future__ import annotations

import numpy as np


def alltoallv(sendbufs: np.ndarray, counts) -> list[np.ndarray]:
    """`sendbufs` [P, rows, F...] -> list of P arrays [recv_count_j, F...]."""
    c = np.asarray(counts, np.int64)
    p = c.shape[0]
    sdisp = np.concatenate([np.zeros((p, 1), np.int64),
                            np.cumsum(c, axis=1)[:, :-1]], axis=1)
    out = []
    for j in range(p):
        out.append(np.concatenate(
            [sendbufs[i, sdisp[i, j]:sdisp[i, j] + c[i, j]] for i in range(p)],
            axis=0))
    return out


def mismatches(recv: np.ndarray, expected: list[np.ndarray]) -> int:
    """Elements of the valid received rows that differ bit for bit.

    `recv` is [P, recv_rows, F...]; rows past a rank's receive count are
    padding and are not compared.  A rank whose buffer is too short for its
    count counts every missing element as wrong."""
    bad = 0
    for j, want in enumerate(expected):
        n = want.shape[0]
        got = recv[j, :n]
        bad += (n - got.shape[0]) * int(np.prod(want.shape[1:], dtype=np.int64))
        a = np.ascontiguousarray(got).view(np.uint8).reshape(got.shape[0], -1)
        b = np.ascontiguousarray(want[:got.shape[0]]).view(np.uint8).reshape(
            got.shape[0], -1)
        item = want.dtype.itemsize
        diff = (a != b).reshape(got.shape[0], -1, item).any(axis=-1)
        bad += int(diff.sum())
    return bad
