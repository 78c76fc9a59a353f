"""Least time of one epoch over the fullest chip's busy time per epoch (%).

The least time is the larger of the useful off-chip bytes over the ICI
peak and the useful bytes read and written once over the HBM peak
(`work.exchange_least_seconds`); padding is never counted."""

import sys

from chipbench import work


def read(ctx):
    lay, peaks = ctx["layer"], ctx["peaks"]
    if peaks is None or not lay.get("epochs"):
        return None
    least, bound = work.exchange_least_seconds(lay["counts"], lay["row_bytes"],
                                               peaks)
    busy = ctx["trace"].fullest().busy_ns * 1e-9 / lay["epochs"]
    print(f"a2a.exchange_roofline: {bound} bound, least {least * 1e6:.3f} us, "
          f"busy {busy * 1e6:.3f} us per epoch", file=sys.stderr)
    return 100.0 * least / busy
