"""Time to first token (ms): the mean over the `serve.generate` spans that
lie wholly inside the window of the time from the span's start to the end
of its `serve.first_token` span.  Both are the program's spans on the
benchmark thread's host line, on the profiler's clock; a program without
them reads nothing."""


def read(ctx):
    s = ctx["trace"]
    t0, t1 = s.window
    calls = [(a, e) for n, a, e in s.host_spans
             if n == "serve.generate" and a >= t0 and e <= t1]
    firsts = [(a, e) for n, a, e in s.host_spans if n == "serve.first_token"]
    ttft = []
    for a, e in calls:
        ends = [fe for fa, fe in firsts if fa >= a and fe <= e]
        if ends:
            ttft.append(min(ends) - a)
    if not ttft:
        return None
    return sum(ttft) / len(ttft) * 1e-6
