"""Seconds of `alltoallv_init`, on the benchmark's clock."""


def read(ctx):
    return ctx["layer"].get("init_s")
