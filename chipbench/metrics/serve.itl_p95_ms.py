"""Inter-token latency, p95 (ms): the gaps between the ends of successive
token fetches (`serve.first_token`, then each `serve.token_fetch`) inside
one `serve.generate` span, pooled over the calls that lie wholly inside the
window.  A token is on the host when its fetch ends, so a gap is what a
streaming client would wait between two tokens."""

import numpy as np

FETCHES = ("serve.first_token", "serve.token_fetch")


def read(ctx):
    s = ctx["trace"]
    t0, t1 = s.window
    calls = [(a, e) for n, a, e in s.host_spans
             if n == "serve.generate" and a >= t0 and e <= t1]
    fetches = [(a, e) for n, a, e in s.host_spans if n in FETCHES]
    gaps = []
    for a, e in calls:
        ends = sorted(fe for fa, fe in fetches if fa >= a and fe <= e)
        gaps += [y - x for x, y in zip(ends, ends[1:])]
    if not gaps:
        return None
    return float(np.percentile(gaps, 95)) * 1e-6
