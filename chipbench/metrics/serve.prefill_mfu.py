"""Useful prefill FLOPs over the prefill executions' device time at the
bf16 peak (%).  Prefill executions are the XLA modules named for it."""


def read(ctx):
    s, peaks = ctx["trace"], ctx["peaks"]
    n, ns = s.module_ns(s.fullest(), r"prefill")
    if peaks is None or n == 0 or ns == 0:
        return None
    flops = ctx["layer"]["prefill_flops"] * n
    return 100.0 * flops / (ns * 1e-9 * peaks.flops_bf16)
