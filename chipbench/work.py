"""Useful work as a function of shapes: the same work whatever implements it.

Counts never include padding (capacity buckets, padded vocabulary rows,
masked attention), so a change that removes padding shows as a higher
share of the roofline, not as less work.
"""

from __future__ import annotations

import numpy as np


def exchange_bytes(counts, row_bytes: int) -> dict:
    """Useful bytes of one alltoallv epoch on its fullest chip.

    `ici`: rows that leave or enter a chip (pairs i != j), the larger of
    what the fullest chip sends and what it receives.  `hbm`: every valid
    row read once from the send buffer and written once into the receive
    buffer, on the chip that moves the most.
    """
    c = np.asarray(counts, np.int64)
    off = c.copy()
    np.fill_diagonal(off, 0)
    ici = max(int(off.sum(axis=1).max()), int(off.sum(axis=0).max()))
    hbm = int((c.sum(axis=1) + c.sum(axis=0)).max())
    return {"ici": ici * int(row_bytes), "hbm": hbm * int(row_bytes)}


def exchange_least_seconds(counts, row_bytes: int, peaks) -> tuple[float, str]:
    """The larger of the ICI and HBM bounds of one epoch, and which it is."""
    b = exchange_bytes(counts, row_bytes)
    t_ici = b["ici"] / peaks.ici_bw
    t_hbm = b["hbm"] / peaks.hbm_bw
    return (t_ici, "ici") if t_ici >= t_hbm else (t_hbm, "hbm")


def lm_token_flops(m: dict) -> int:
    """FLOPs of one token through one layer, outside attention's scores.

    Q/K/V/O projections, the router, and `top_k` experts (gate, up, down):
    a routed token counts `top_k` experts, never the capacity slots.
    """
    d, h, kv, dh = m["hidden_size"], m["num_attention_heads"], \
        m["num_key_value_heads"], m["head_dim"]
    proj = 2 * d * h * dh + 2 * 2 * d * kv * dh + 2 * h * dh * d
    router = 2 * d * m["num_experts"]
    experts = m["num_experts_per_tok"] * 3 * 2 * d * m["intermediate_size"]
    return proj + router + experts


def _attn_flops(m: dict, contexts: int) -> int:
    """Scores and weighted values, summed over queries of a causal context:
    `contexts` is the sum over queries of the keys each one sees."""
    return 4 * m["num_attention_heads"] * m["head_dim"] * int(contexts)


def _head_flops(m: dict) -> int:
    return 2 * m["hidden_size"] * m["vocab_size"]


def prefill_flops(m: dict, batch: int, prompt_len: int) -> int:
    """One prefill of `batch` prompts: every layer over every prompt token,
    causal attention, and the LM head at the last position only (the one
    whose logits pick the first token)."""
    s = int(prompt_len)
    per_row = (m["num_hidden_layers"]
               * (s * lm_token_flops(m) + _attn_flops(m, s * (s + 1) // 2))
               + _head_flops(m))
    return int(batch) * per_row


def decode_flops(m: dict, batch: int, prompt_len: int, new_tokens: int) -> int:
    """All decode steps of one request: `new_tokens - 1` steps, the step at
    position p attending to p + 1 keys, each ending in the LM head."""
    total = 0
    for i in range(int(new_tokens) - 1):
        p = int(prompt_len) + i
        total += (m["num_hidden_layers"]
                  * (lm_token_flops(m) + _attn_flops(m, p + 1))
                  + _head_flops(m))
    return int(batch) * total
