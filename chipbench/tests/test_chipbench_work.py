"""Useful-work counts and the peaks table, checked by hand."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import peaks, work  # noqa: E402

TINY = {"hidden_size": 4, "num_attention_heads": 2, "num_key_value_heads": 1,
        "head_dim": 2, "num_experts": 3, "num_experts_per_tok": 2,
        "intermediate_size": 5, "num_hidden_layers": 2, "vocab_size": 7}


def test_exchange_bytes_by_hand():
    # off-chip: rank 0 sends 2, rank 1 sends 3; rank 0 receives 3, rank 1 2.
    # read+write: rank 0 sends 3 receives 4 (7), rank 1 sends 7 receives 6.
    b = work.exchange_bytes([[1, 2], [3, 4]], 10)
    assert b == {"ici": 30, "hbm": 130}


def test_exchange_least_time_picks_the_larger_bound():
    class P:
        ici_bw, hbm_bw = 10.0, 100.0
    assert work.exchange_least_seconds([[1, 2], [3, 4]], 10, P) == (3.0, "ici")
    P.ici_bw = 1000.0
    assert work.exchange_least_seconds([[1, 2], [3, 4]], 10, P) == (1.3, "hbm")


def test_token_flops_by_hand():
    # q 2*4*2*2=32, k and v 2*(2*4*1*2)=32, o 2*2*2*4=32; router 2*4*3=24;
    # two experts of gate, up, down: 2*3*2*4*5=240
    assert work.lm_token_flops(TINY) == 32 + 32 + 32 + 24 + 240


def test_prefill_flops_by_hand():
    # per row: 2 layers * (3 tokens * 360 + attention 4*2*2*(1+2+3)=96)
    # plus the head at the last position 2*4*7=56; two rows
    assert work.prefill_flops(TINY, 2, 3) == 2 * (2 * (3 * 360 + 96) + 56)


def test_decode_flops_by_hand():
    # 3 new tokens = 2 decode steps, at positions 3 and 4 (4 and 5 keys)
    step3 = 2 * (360 + 4 * 2 * 2 * 4) + 56
    step4 = 2 * (360 + 4 * 2 * 2 * 5) + 56
    assert work.decode_flops(TINY, 2, 3, 3) == 2 * (step3 + step4)
    assert work.decode_flops(TINY, 2, 3, 1) == 0


def test_peaks_keyed_by_device_kind():
    p = peaks.peaks("TPU v5 lite")
    assert (p.flops_bf16, p.hbm_bw, p.ici_bw) == (197e12, 819e9, 200e9)
    assert "TPU v5e" in p.source
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")
