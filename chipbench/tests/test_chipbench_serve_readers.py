"""The serve readers that take the program's spans and counters from a
trace: time to first token, the inter-token tail, the token-wait share and
the decode HBM roofline, each on a synthetic trace with a known answer; and
the step executables' names, which the MFU readers match."""

import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402
from chipbench import trace as tr  # noqa: E402

WINDOW = (0, 1000)

# Two calls inside the window and one that the window's end cuts.  Call 1:
# first token ends at 80, fetches end at 120, 200, 290; call 2: 400, 450,
# 600; the cut call: 950, 1100.
SPANS = [
    ("generate", 10, 300), ("serve.generate", 10, 300),
    ("serve.prefill", 10, 40), ("serve.grow_caches", 40, 50),
    ("serve.first_token", 50, 80),
    ("serve.decode_dispatch", 80, 90), ("serve.token_fetch", 90, 120),
    ("serve.decode_dispatch", 120, 150), ("serve.token_fetch", 150, 200),
    ("serve.decode_dispatch", 200, 210), ("serve.token_fetch", 210, 290),
    ("serve.generate", 300, 700), ("serve.prefill", 300, 310),
    ("serve.first_token", 320, 400),
    ("serve.decode_dispatch", 400, 410), ("serve.token_fetch", 410, 450),
    ("serve.decode_dispatch", 450, 460), ("serve.token_fetch", 460, 600),
    ("serve.generate", 900, 1200), ("serve.first_token", 920, 950),
    ("serve.decode_dispatch", 950, 960), ("serve.token_fetch", 990, 1100),
]
BUSY = [(0, 100), (110, 150), (180, 260), (300, 420), (470, 480)]


def _summary(spans=SPANS, busy=BUSY, modules=None):
    dev = tr.Device("/device:TPU:0", sum(e - s for s, e in busy), {}, {},
                    modules or {}, busy)
    return tr.Summary(WINDOW, [dev], spans)


def _read(metric, summary, **ctx):
    return harness.load_module("metrics", metric).read(
        {"trace": summary, **ctx})


def test_ttft_is_call_start_to_first_token_of_whole_calls():
    # (80 - 10) and (400 - 300): the cut call is left out
    assert _read("serve.ttft_ms", _summary()) == pytest.approx(85e-6)


def test_inter_token_p95_pools_the_gaps_of_whole_calls():
    # gaps 40, 80, 90 and 50, 150: numpy's p95 of five lies 0.8 of the way
    # from 90 to 150
    assert _read("serve.itl_p95_ms", _summary()) == pytest.approx(138e-6)


def test_token_wait_is_idle_time_inside_fetches_over_the_window():
    # idle inside fetches: [100,110) 10; [150,180) 30; [260,290) 30;
    # [420,450) 30; [460,470) + [480,600) 130; the cut fetch [990,1000) 10
    s = _summary()
    share = _read("serve.token_wait_share", s)
    assert share == pytest.approx(100.0 * 240 / 1000)
    assert share <= _read("serve.idle_share", s)


@pytest.mark.parametrize("metric", ["serve.ttft_ms", "serve.itl_p95_ms",
                                    "serve.token_wait_share"])
def test_a_program_without_serve_spans_reads_nothing(metric):
    spans = [("generate", 10, 300), ("np.asarray(jax.Array)", 90, 120)]
    assert _read(metric, _summary(spans=spans)) is None


# A tiny model whose decode bytes are counted by hand below.
MODEL = {"dtype": "bfloat16", "hidden_size": 4, "intermediate_size": 8,
         "num_attention_heads": 2, "num_key_value_heads": 2, "head_dim": 2,
         "num_hidden_layers": 1, "num_experts": 4, "vocab_size": 10}
TRAFFIC = {"batch": 1, "prompt_len": 3, "new_tokens": 3}


@pytest.fixture
def counters():
    from repro.obs.counters import COUNTERS
    saved = COUNTERS.snapshot()
    COUNTERS.reset()
    yield COUNTERS
    COUNTERS.reset()
    for name, n in saved.items():
        COUNTERS.add(name, n)


def test_decode_step_bytes_by_hand():
    mod = harness.load_module("metrics", "serve.decode_hbm_roofline")
    weights = (4 * 2 * 2) * 4 + 2 * 2   # wq, wk, wv, wo; q and k norms
    weights += 2 * 4 + 4 * 4            # ln1, ln2; router
    weights += 2 * 3 * (4 * 8)          # two experts of three matrices
    weights += 4 + 4 * 10               # ln_f; LM head
    embed = 1 * 4                       # one row
    cache = 2 * 1 * 2 * 2 * (4 + 5) / 2     # K and V of 4, then 5 keys
    assert mod.decode_step_bytes(MODEL, 1, 3, 3, 2.0) == \
        pytest.approx(2 * (weights + embed + cache))


def test_decode_hbm_roofline_reads_the_program_counters(counters):
    counters.add("serve.decode_steps", 4)
    counters.add("serve.experts_touched", 8)       # 2 per layer-step
    peaks = types.SimpleNamespace(hbm_bw=1e9)
    # 2 decode executions; 736 useful bytes each at 1 GB/s = 1472 ns
    s = _summary(modules={"jit_decode_step(7)": [2, 2944],
                          "jit_prefill(3)": [1, 5000]})
    ctx = {"config": {"model": MODEL}, "traffic": TRAFFIC, "peaks": peaks}
    assert _read("serve.decode_hbm_roofline", s, **ctx) == pytest.approx(50.0)
    counters.reset()
    assert _read("serve.decode_hbm_roofline", s, **ctx) is None


@pytest.mark.parametrize("kind,metric,other", [
    ("prefill", "serve.prefill_mfu", "serve.decode_mfu"),
    ("decode", "serve.decode_mfu", "serve.prefill_mfu")])
def test_step_module_names_match_the_mfu_readers(kind, metric, other):
    """The MFU readers find the steps by the name of their XLA module: keep
    the prefill and decode executables named so that each reader finds its
    own step and not the other's."""
    from repro.configs import get_reduced
    from repro.configs.base import ShapeConfig
    from repro.launch import steps as steps_mod
    from repro.launch.mesh import make_mesh

    cfg = get_reduced("olmoe-1b-7b")
    mesh = make_mesh((1, 1), ("data", "model"))
    bundle = steps_mod.make_bundle(cfg, ShapeConfig(kind, kind, 16, 2), mesh)
    module = bundle.lower().compile().as_text().split()[1].rstrip(",")
    s = _summary(modules={f"{module}(1234)": [3, 3000]})
    ctx = {"peaks": types.SimpleNamespace(flops_bf16=1e12),
           "layer": {"prefill_flops": 1e6, "decode_flops_per_step": 1e6}}
    assert _read(metric, s, **ctx) == pytest.approx(100.0)
    assert _read(other, s, **ctx) is None
