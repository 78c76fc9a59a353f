"""The benchmark's files: BENCHMARK.json keeps to its contract, every cell
finds its configuration, traffic and metric files by name, a cell whose
files are missing is refused by name, and a run without a TPU exits
non-zero with no result."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.dirname(os.path.abspath(__file__))]

from chipbench import harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "chipbench/run.py"]
    assert bench["paths"] == ["chipbench"]
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_entries_keep_their_shapes(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("chipbench/")
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert all(NAME.match(k) for k in c["reduced"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in bench[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, len(bench["workloads"]) // 2)


def test_every_cell_reports_what_it_must(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    used = set()
    for w in bench["workloads"]:
        mine = {m["name"] for m in harness.cell_metrics(bench, "end_to_end", w["name"])}
        layer = harness.cell_metrics(bench, "per_layer", w["name"])
        assert "setup_s" in mine and len(mine) >= 2 and layer
        for m in layer:
            assert m["moves"] in mine, (w["name"], m["name"])
        used.add(w["config"])
    assert used == {c["name"] for c in bench["configs"]}


def test_check_fits_the_day(bench):
    # 2 + 14 runs per cell at run_seconds + 60, 2 x 90 s of compile per
    # cell, 1200 s spare, with the full 24 cells
    cells = 24
    total = (2 + 14 * cells) * (bench["run_seconds"] + 60) + cells * 180 + 1200
    assert total <= 43200


@pytest.mark.parametrize("cell", ["olmoe.decode.b8", "olmoe.prefill.p2048",
                                  "a2a.hugetrace.1mib"])
def test_each_cell_finds_its_files(tmp_path, cell):
    from _cpu_cases import pending_root
    root = harness.Path(pending_root(str(tmp_path / "checkout")))
    bench, w, config, traffic = harness.load_cell(cell, root)
    assert harness.load_module("drivers", config["driver"], root).run
    for m in harness.cell_metrics(bench, "per_layer", cell):
        assert harness.load_module("metrics", m["name"], root).read
    assert traffic["limits"]


def test_pending_cells_keep_their_shapes(bench):
    pending = os.path.join(ROOT, "chipbench", "pending")
    for name in os.listdir(pending):
        with open(os.path.join(pending, name)) as f:
            extra = json.load(f)
        assert [w["name"] for w in extra["workloads"]] == [name[:-len(".json")]]
        for m in extra["end_to_end"] + extra["per_layer"]:
            assert m["workloads"] == [name[:-len(".json")]]
        names = {x["name"] for k in ("configs", "workloads", "end_to_end",
                                     "per_layer") for x in bench[k]}
        assert not names & {x["name"] for k in ("configs", "workloads",
                                                "end_to_end", "per_layer")
                            for x in extra[k]}


def _copy(tmp_path):
    dst = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "chipbench"), dst / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", ".jax_cache"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst / "BENCHMARK.json")
    return dst


@pytest.mark.parametrize("missing", ["traffic", "configs", "metrics"])
def test_a_cell_whose_files_are_missing_is_refused_by_name(tmp_path, missing):
    dst = _copy(tmp_path)
    bench = json.loads((dst / "BENCHMARK.json").read_text())
    if missing == "traffic":
        bench["workloads"][0]["traffic"] = "no-such-mix"
        want = "no-such-mix"
    elif missing == "configs":
        os.remove(dst / bench["configs"][0]["file"])
        want = bench["configs"][0]["file"]
    else:
        bench["per_layer"][0]["name"] = "a2a.no_such_metric"
        want = "a2a.no_such_metric"
    (dst / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(harness.Refused, match=re.escape(want)):
        _, _, config, _ = harness.load_cell(bench["workloads"][0]["name"], dst)
        if missing == "metrics":
            harness.load_module("metrics", want, dst)


def _run_cli(cwd, *extra):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "olmoe.decode.b8",
         "--seed", str(2 ** 31 + 5), "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, capture_output=True, text=True, timeout=300, env=env)


def test_without_a_tpu_the_run_exits_nonzero_and_prints_no_result():
    r = _run_cli(ROOT)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "needs a TPU" in r.stderr


def test_a_checkout_of_only_the_benchmark_prints_no_result(tmp_path):
    r = _run_cli(_copy(tmp_path))
    assert r.returncode != 0
    assert r.stdout.strip() == ""
