"""Decode steps' share of their HBM roofline (%): the least time of the
steps, useful bytes at the HBM peak, over the device time of the XLA
modules named for decode (as `serve.decode_mfu` finds them).

Useful bytes of one step are what a step must read once: the non-expert
weights (attention projections and norms, the layer norms, the router, the
final norm and the LM head over the valid vocabulary; the embedding only as
the batch's rows), the experts the step touched (the program's
`serve.experts_touched` / (`serve.decode_steps` x layers) experts per
layer-step, each three matrices), and the K and V cache of the filled
positions.  Padding, capacity buckets and experts no token chose never
count.  A program without the counters reads nothing."""

import sys

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def decode_step_bytes(m: dict, batch: int, prompt_len: int, new_tokens: int,
                      experts_per_layer: float) -> float:
    """Mean useful bytes of one decode step of a `new_tokens` request."""
    w = DTYPE_BYTES[m["dtype"]]
    d, f = m["hidden_size"], m["intermediate_size"]
    h, kv, dh = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    layers = m["num_hidden_layers"]
    attention = 2 * d * h * dh + 2 * d * kv * dh + 2 * dh
    per_layer = attention + 2 * d + d * m["num_experts"]
    experts = experts_per_layer * 3 * d * f
    weights = layers * (per_layer + experts) + d + d * m["vocab_size"]
    embed_rows = batch * d
    # the step at position p reads p + 1 keys; over the steps
    # p = prompt_len .. prompt_len + new_tokens - 2 that is prompt_len +
    # new_tokens / 2 on average
    keys = prompt_len + new_tokens / 2
    cache = layers * 2 * batch * keys * kv * dh
    return w * (weights + embed_rows + cache)


def read(ctx):
    s, peaks = ctx["trace"], ctx["peaks"]
    n, ns = s.module_ns(s.fullest(), r"decode")
    if peaks is None or n == 0 or ns == 0:
        return None
    try:
        from repro.obs.counters import COUNTERS
    except ImportError:
        return None
    m, tr = ctx["config"]["model"], ctx["traffic"]
    steps = COUNTERS.get("serve.decode_steps")
    if steps == 0:
        return None
    per_layer = (COUNTERS.get("serve.experts_touched")
                 / (steps * m["num_hidden_layers"]))
    useful = decode_step_bytes(m, int(tr["batch"]), int(tr["prompt_len"]),
                               int(tr["new_tokens"]), per_layer)
    print(f"serve.decode_hbm_roofline: {per_layer:.3f} experts touched per "
          f"layer-step, {useful / 1e9:.4f} GB useful, least "
          f"{useful / peaks.hbm_bw * 1e3:.3f} ms, device "
          f"{ns * 1e-6 / n:.3f} ms per step", file=sys.stderr)
    return 100.0 * n * useful / peaks.hbm_bw / (ns * 1e-9)
