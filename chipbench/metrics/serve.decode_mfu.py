"""Useful decode FLOPs over the decode steps' device time at the bf16
peak (%).  Decode executions are the XLA modules named for it; the traced
window holds whole calls, so the mean FLOPs of a step is exact."""


def read(ctx):
    s, peaks = ctx["trace"], ctx["peaks"]
    n, ns = s.module_ns(s.fullest(), r"decode")
    if peaks is None or n == 0 or ns == 0:
        return None
    flops = ctx["layer"]["decode_flops_per_step"] * n
    return 100.0 * flops / (ns * 1e-9 * peaks.flops_bf16)
