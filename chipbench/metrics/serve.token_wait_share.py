"""Share of the window (%) in which the fullest chip is idle while the
host waits in a `serve.token_fetch` span (the per-token host round trip).
It is a part of `serve.idle_share`, never more."""

import bisect


def read(ctx):
    s = ctx["trace"]
    t0, t1 = s.window
    busy = s.fullest().busy
    starts = [b for b, _ in busy]
    idle_ns = fetches = 0
    for name, a, e in s.host_spans:
        if name != "serve.token_fetch":
            continue
        a, e = max(a, t0), min(e, t1)
        if e <= a:
            continue
        fetches += 1
        covered = 0
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(busy) and busy[i][0] < e:
            covered += max(0, min(busy[i][1], e) - max(busy[i][0], a))
            i += 1
        idle_ns += (e - a) - covered
    if not fetches:
        return None
    return 100.0 * idle_ns / s.window_ns
