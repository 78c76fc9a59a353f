"""A trace recorded on a v5e 2x2 host: 8 epochs of the a2a.hugetrace.1mib
cell (fence, 16,383 rows of 1 KiB), window 10 ms.  It reduces to four chips,
the driver counts as many `plan.start` spans in it as the host counted
epochs, and every `a2a.*` reader reads a value from it."""

import gzip
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import harness, patterns, work  # noqa: E402
from chipbench import trace as tr  # noqa: E402
from chipbench.drivers import exchange_request  # noqa: E402
from chipbench.peaks import peaks  # noqa: E402

TRACE = os.path.join(os.path.dirname(__file__), "data",
                     "a2a-hugetrace.xplane.pb.gz")
HOST_EPOCHS = 8
ROW_BYTES = 1024
A2A = ("a2a.idle_share", "a2a.exchange_roofline", "a2a.local_copy_us",
       "a2a.init_s", "a2a.wire_pad_share")


@pytest.fixture(scope="module")
def trace_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("a2a-trace")
    with gzip.open(TRACE) as src, open(d / "t.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(d)


@pytest.fixture(scope="module")
def summary(trace_dir):
    return tr.summarize(tr.find_xplane(trace_dir))


def test_recorded_exchange_trace_reduces_to_four_chips(summary):
    names = sorted(d.name for d in summary.devices)
    assert names == [f"/device:TPU:{n}" for n in range(4)]
    d = summary.fullest()
    assert 0 < d.busy_ns < summary.window_ns
    assert d.class_ns.get("collective", 0) > 0


def test_traced_epochs_match_the_hosts(trace_dir):
    assert exchange_request.traced_epochs(trace_dir) == HOST_EPOCHS


def test_recorded_exchange_trace_feeds_the_a2a_readers(summary):
    traffic = json.load(open(os.path.join(ROOT, "chipbench/traffic/hugetrace-1mib.json")))
    counts = patterns.counts(traffic, 4, ROW_BYTES)
    cap = -(-int(counts.max()) // 8) * 8
    wire = 4 * 3 * cap * ROW_BYTES
    ctx = {"trace": summary, "peaks": peaks("TPU v5 lite"),
           "layer": {"epochs": HOST_EPOCHS, "counts": counts,
                     "row_bytes": ROW_BYTES, "init_s": 0.106,
                     "variant": "fence", "a2a_epochs": HOST_EPOCHS,
                     "a2a_wire_bytes": HOST_EPOCHS * wire}}
    read = {n: harness.load_module("metrics", n).read(ctx) for n in A2A}
    assert all(v is not None for v in read.values()), read
    assert 0 < read["a2a.exchange_roofline"] <= 100
    least, bound = work.exchange_least_seconds(counts, ROW_BYTES, ctx["peaks"])
    busy = summary.fullest().busy_ns * 1e-9 / HOST_EPOCHS
    assert bound == "ici"
    assert read["a2a.exchange_roofline"] == pytest.approx(100 * least / busy)
    assert 0 < read["a2a.idle_share"] < 100
    assert 0 < read["a2a.local_copy_us"] < busy * 1e6
    useful = int(counts.sum() - counts.trace()) * ROW_BYTES
    assert read["a2a.wire_pad_share"] == pytest.approx(100 * (1 - useful / wire))
