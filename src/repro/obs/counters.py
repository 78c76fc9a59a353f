"""Process-wide named counters for what the serving and exchange hot paths
count.

A counter is a name and a running integer total, bumped under a lock (the
metrics server's scrape thread reads while the serving loop adds).  Names:

  ``serve.decode_steps``      decode steps run by ``ServeEngine.generate``
  ``serve.experts_touched``   experts with at least one kept assignment,
                              summed over the MoE layers of each decode
                              step (``serve.experts_touched`` /
                              (``serve.decode_steps`` x MoE layers) is the
                              mean experts one layer-step reads)
  ``a2a.epochs``              standalone alltoallv epochs launched
                              (``ExchangePlan.start`` and
                              ``start_pipelined``)
  ``a2a.wire_bytes``          bytes those epochs' collectives carried
                              between distinct chips, padding included,
                              summed over the mesh (``plan.wire_bytes`` per
                              epoch; plans without a closed form for it,
                              such as hierarchy schedules, add nothing)

Embedded epochs (``ExchangePlan.embed()`` bodies inside a larger jitted
program, such as the MoE dispatch) run on the device with no host call per
epoch, so they bump neither ``a2a.*`` counter.

``obs.metrics`` renders each as ``repro_<name with _ for .>_total``.
"""

from __future__ import annotations

import threading


class Counters:
    """Named integer totals, cumulative per process; ``reset()`` zeroes
    them."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._totals: dict[str, int] = {}

    def add(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._totals[name] = self._totals.get(name, 0) + int(n)

    def get(self, name: str) -> int:
        with self._lock:
            return self._totals.get(name, 0)

    def snapshot(self) -> dict[str, int]:
        with self._lock:
            return dict(self._totals)

    def reset(self) -> None:
        with self._lock:
            self._totals.clear()


COUNTERS = Counters()
