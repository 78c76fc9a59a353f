"""Share of the bytes an epoch's collective puts between chips that is
padding (%): 100 x (1 - useful off-chip bytes / wire bytes) per epoch.

Useful bytes are the rows of every pair i != j, summed over the mesh, at the
configuration's row size; padding never counts.  Wire bytes per epoch are
the program's `a2a.wire_bytes` over `a2a.epochs`, both as deltas over the
window (the driver takes them).  A program without those counters reads
nothing."""

import sys

import numpy as np


def useful_offchip_bytes(counts, row_bytes: int) -> int:
    c = np.asarray(counts, np.int64)
    return int((c.sum() - np.trace(c)) * int(row_bytes))


def read(ctx):
    lay = ctx["layer"]
    epochs, wire = lay.get("a2a_epochs"), lay.get("a2a_wire_bytes")
    if not epochs or not wire:
        return None
    per_epoch = wire / epochs
    useful = useful_offchip_bytes(lay["counts"], lay["row_bytes"])
    print(f"a2a.wire_pad_share: variant {lay.get('variant')}, "
          f"{per_epoch / 1e6:.6f} MB on the wire per epoch, "
          f"{useful / 1e6:.6f} MB useful", file=sys.stderr)
    return 100.0 * (1.0 - useful / per_epoch)
