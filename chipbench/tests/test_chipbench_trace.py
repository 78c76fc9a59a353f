"""The trace reduction: busy union, op classes, idle gaps by host span."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import trace as tr  # noqa: E402


def test_op_names_and_classes():
    text = ("%fusion.132 = s32[1,16,2,128]{3,2,1,0:T(2,128)S(1)} "
            "fusion(s32[2,2048]{1,0:T(2,128)} %p), kind=kLoop")
    assert tr.parse_op(text) == ("fusion.132", "fusion")
    loop = ("%while.3 = (s32[]{:T(128)}, bf16[2,8]{1,0:T(8,128)(2,1)S(1)}) "
            "while((s32[]{:T(128)}, bf16[2,8]) %tuple), condition=%c")
    assert tr.parse_op(loop) == ("while.3", "while")
    assert tr.parse_op("%custom-call.10 = bf16[16]{0} custom-call(), "
                       "custom_call_target=\"tpu_custom_call\"")[1] == "custom-call"
    assert tr.op_class("all-to-all") == "collective"
    assert tr.op_class("collective-permute-start") == "collective"
    assert tr.op_class("ragged-all-to-all") == "collective"
    assert tr.op_class("fusion") == "other"
    assert tr.op_class("custom-call") == "mosaic"
    assert tr.op_class("while") == "control"


def test_merged_busy_intervals():
    assert tr.merged([(5, 7), (0, 2), (1, 3), (6, 9)]) == [(0, 3), (5, 9)]


def test_idle_gaps_go_to_the_innermost_host_span():
    dev = tr.Device("/device:TPU:0", 40, {"a": [2, 30, "other"],
                                           "b": [1, 10, "collective"]},
                    {"other": 30, "collective": 10}, {}, [(10, 30), (50, 70)])
    spans = [("epoch", 0, 45), ("wait", 30, 45), ("epoch", 45, 100)]
    s = tr.Summary((0, 100), [dev], spans)
    assert s.window_s == pytest.approx(100e-9)
    # gaps: [0,10) mid 5 in epoch; [30,50) mid 40 in wait; [70,100) mid 85
    assert dict(s.idle_gaps()) == pytest.approx({"epoch": 40e-9, "wait": 20e-9})
    assert s.top_ops(1)[0][0] == "a" and s.top_ops(1)[0][1] == pytest.approx(30e-9)
    assert s.busy_s_mean() == pytest.approx(40e-9)


# A trace recorded on one TPU v5e: one `generate` call of the
# olmoe.prefill.p2048 cell (batch 2, prompt 2048, 4 new tokens).
PREFILL_TRACE = os.path.join(os.path.dirname(__file__), "data",
                             "prefill-p2048.xplane.pb.gz")


@pytest.fixture(scope="module")
def prefill_summary(tmp_path_factory):
    import gzip
    import shutil
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with gzip.open(PREFILL_TRACE) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return tr.summarize(str(path))


def test_recorded_trace_reduces_to_its_steps(prefill_summary):
    s = prefill_summary
    assert len(s.devices) == 1 and s.devices[0].name == "/device:TPU:0"
    d = s.fullest()
    assert s.window_ns == 301407662
    assert d.busy_ns == 290962509
    # ops do not overlap once control flow is left out: classes sum to busy
    assert sum(d.class_ns.values()) == pytest.approx(d.busy_ns, rel=1e-4)
    calls = sum(1 for name, *_ in s.host_spans if name == "generate")
    assert calls == 1
    assert s.module_ns(d, "prefill")[0] == calls
    assert s.module_ns(d, "decode")[0] == calls * 3
    assert s.idle_gaps(1)[0][0] == "np.asarray(jax.Array)"


def test_recorded_trace_feeds_the_serving_readers(prefill_summary):
    import json
    from chipbench import harness, work
    from chipbench.peaks import peaks
    m = json.load(open(os.path.join(ROOT, "chipbench/configs/olmoe-1b-7b.json")))["model"]
    ctx = {"trace": prefill_summary, "peaks": peaks("TPU v5 lite"),
           "layer": {"prefill_flops": work.prefill_flops(m, 2, 2048),
                     "decode_flops_per_step": work.decode_flops(m, 2, 2048, 4) / 3}}
    read = {n: harness.load_module("metrics", n).read(ctx)
            for n in ("serve.idle_share", "serve.prefill_mfu", "serve.decode_mfu")}
    assert read["serve.idle_share"] == pytest.approx(100 * (1 - 290962509 / 301407662))
    # 9.4 TFLOP of useful prefill in 210.9 ms at 197 TFLOP/s
    assert read["serve.prefill_mfu"] == pytest.approx(
        100 * work.prefill_flops(m, 2, 2048) / (210927556e-9 * 197e12))
    assert 10 < read["serve.prefill_mfu"] < 100
    assert 0 < read["serve.decode_mfu"] < 5
