"""Idle share of the chip over the traced window of generate calls (%)."""


def read(ctx):
    s = ctx["trace"]
    return 100.0 * (1.0 - s.fullest().busy_ns / s.window_ns)
