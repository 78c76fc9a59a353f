"""The exchange cell as BENCHMARK.json holds it: its entries, the driver
that reports an epoch as `request_ms`, and `a2a.wire_pad_share` against the
padding reckoned from the counts, driven on 4 virtual CPU devices at a
small size; the bf16-codec control is not correct."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT]

from chipbench import harness  # noqa: E402

CELL = "a2a.hugetrace.1mib"
TILE_ROWS = 8


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_exchange_cell_is_in_the_benchmark(bench):
    cells = [w["name"] for w in bench["workloads"]]
    assert CELL in cells
    for m in bench["per_layer"]:
        if m["name"].startswith("a2a."):
            assert m["workloads"] == [CELL], m["name"]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert sorted(e2e["request_ms"]["workloads"]) == sorted(cells)
    mine = {m["name"] for m in harness.cell_metrics(bench, "end_to_end", CELL)}
    assert mine == {"request_ms", "setup_s"}
    for m in harness.cell_metrics(bench, "per_layer", CELL):
        assert m["moves"] in mine, m["name"]


def test_the_exchange_cell_finds_its_files():
    bench, w, config, traffic = harness.load_cell(CELL)
    assert w["chips"] == 4 and config["name"] == w["config"] == "alltoallv-p4"
    assert config["driver"] == "exchange_request"
    assert harness.load_module("drivers", config["driver"]).run
    for m in harness.cell_metrics(bench, "per_layer", CELL):
        assert harness.load_module("metrics", m["name"]).read
    assert traffic["limits"] == {"mismatched_elements": 0}


@pytest.fixture(scope="module")
def cases():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable,
                        os.path.join(HERE, "_cpu_request_case.py")],
                       capture_output=True, text=True, timeout=600, env=env,
                       cwd=ROOT)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1]), r.stderr


def test_an_epoch_is_reported_as_a_request(cases):
    out, err = cases
    clean = out["clean"]
    assert clean["correct"]
    assert clean["checks"]["mismatched_elements"]["value"] == 0
    assert clean["metrics"] == ["request_ms", "setup_s"]
    assert "exchange: variant fence" in err
    lay = clean["layer"]
    assert lay["a2a_epochs"] == lay["epochs"] > 0


def test_wire_pad_share_is_the_padding_reckoned_from_the_counts(cases):
    lay = cases[0]["clean"]["layer"]
    c = np.asarray(lay["counts"])
    p = c.shape[0]
    cap = max(-(-int(c.max()) // TILE_ROWS) * TILE_ROWS, TILE_ROWS)
    wire = p * (p - 1) * cap * lay["row_bytes"]
    assert lay["a2a_wire_bytes"] == lay["a2a_epochs"] * wire
    useful = int(c.sum() - np.trace(c)) * lay["row_bytes"]
    want = 100.0 * (1.0 - useful / wire)
    assert cases[0]["clean"]["wire_pad_share"] == pytest.approx(want, abs=1e-9)
    assert 50.0 < want < 100.0


def test_bf16_control_is_not_correct(cases):
    ctl = cases[0]["control_bf16"]
    assert not ctl["correct"]
    assert ctl["checks"]["mismatched_elements"]["value"] > 0
