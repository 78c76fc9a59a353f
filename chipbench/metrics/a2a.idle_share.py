"""Idle share of the fullest chip over the traced window of epochs (%)."""


def read(ctx):
    s = ctx["trace"]
    return 100.0 * (1.0 - s.fullest().busy_ns / s.window_ns)
