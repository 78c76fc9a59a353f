"""Unit tests for the unified observability layer (repro.obs) and the
telemetry extensions under it: span buffer/tracer semantics, trace export
+ structural validation, Prometheus rendering, break-even residuals,
thread-safe counters, per-rank rings, and the ``core.init_stats()``
snapshot/diff contract across PlanCache reuse and ``reset()``."""

import json
import math
import threading
import urllib.request

import numpy as np
import pytest

from repro.core import EXEC_TELEMETRY, INIT_STATS, EpochRing
from repro.obs import (TRACER, SpanBuffer, TraceValidationError,
                       breakeven_residual, check_breakeven, chrome_trace,
                       render_metrics, validate_trace, write_jsonl,
                       write_trace)
from repro.obs.spans import COMPLETE, INSTANT


@pytest.fixture(autouse=True)
def _clean_tracer():
    TRACER.reset()
    yield
    TRACER.reset()


# --- spans -------------------------------------------------------------------

def test_tracer_disabled_is_noop():
    assert not TRACER.enabled
    ctx = TRACER.span("x", "init", a=1)
    with ctx:
        pass
    TRACER.instant("y", "runtime")
    TRACER.emit_span("z", "execute", 0.0, 1.0)
    assert TRACER.snapshot()["records"] == []
    # with the ring off a span is the profiler annotation alone
    import jax
    assert isinstance(TRACER.span("a", "init"), jax.profiler.TraceAnnotation)


def test_span_records_args_and_outcome_mutation():
    TRACER.enable()
    with TRACER.span("store_get", "store", backend="/s") as sp:
        sp.args["result"] = "hit"
    (rec,) = TRACER.snapshot()["records"]
    name, cat, ph, ts, dur, tid, args = rec
    assert (name, cat, ph) == ("store_get", "store", COMPLETE)
    assert args == {"backend": "/s", "result": "hit"}
    assert dur >= 0 and tid == threading.get_ident()


def test_span_records_exception_as_error_arg():
    TRACER.enable()
    with pytest.raises(ValueError):
        with TRACER.span("bake", "init.bake"):
            raise ValueError("boom")
    (rec,) = TRACER.snapshot()["records"]
    assert "boom" in rec[6]["error"]


def test_instant_and_emit_span():
    TRACER.enable()
    TRACER.instant("swap", "runtime", old="a", new="b")
    TRACER.emit_span("epoch", "execute", 1.0, 1.5, {"digest": "d"})
    recs = TRACER.snapshot()["records"]
    phases = {r[0]: r[2] for r in recs}
    assert phases == {"swap": INSTANT, "epoch": COMPLETE}
    epoch = next(r for r in recs if r[0] == "epoch")
    assert epoch[4] == pytest.approx(0.5)


def test_span_buffer_ring_overwrites_oldest():
    buf = SpanBuffer(capacity=8)
    for i in range(20):
        buf.emit(("s", "execute", COMPLETE, float(i), 0.0, 1, None))
    kept = [r[3] for r in buf.snapshot()]
    assert kept == [float(i) for i in range(12, 20)]


def test_span_buffer_concurrent_writers_never_tear():
    buf = SpanBuffer(capacity=64)
    n_threads, per = 8, 500

    def w(k):
        for i in range(per):
            buf.emit(("s", "execute", COMPLETE, float(i), 0.0, k, None))

    ts = [threading.Thread(target=w, args=(k,)) for k in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    recs = buf.snapshot()
    assert len(recs) == 64
    assert all(len(r) == 7 for r in recs)       # no torn records


def test_tracer_thread_names_registered():
    TRACER.enable()

    def w():
        TRACER.instant("bg", "runtime")

    t = threading.Thread(target=w, name="repro-replan")
    t.start()
    t.join()
    TRACER.instant("fg", "runtime")
    names = TRACER.snapshot()["thread_names"]
    assert "repro-replan" in names.values()
    assert len(names) >= 2


# --- trace export + validation ----------------------------------------------

def _span(name, cat, ts_us, dur_us, tid=1, args=None):
    return {"name": name, "cat": cat, "ph": "X", "pid": 1, "tid": tid,
            "ts": ts_us, "dur": dur_us, "args": args or {}}


def test_chrome_trace_structure_and_units():
    TRACER.enable()
    TRACER.emit_span("epoch", "execute", 0.001, 0.003, {"digest": "d"})
    TRACER.instant("swap", "runtime")
    trace = chrome_trace()
    evs = trace["traceEvents"]
    assert evs[0] == {"name": "process_name", "ph": "M", "pid": evs[0]["pid"],
                      "tid": 0, "args": {"name": "repro-driver"}}
    x = next(e for e in evs if e["ph"] == "X")
    assert x["dur"] == pytest.approx(2000.0)     # seconds -> microseconds
    i = next(e for e in evs if e["ph"] == "i")
    assert i["s"] == "t"
    assert trace["displayTimeUnit"] == "ms"


def test_validate_trace_accepts_nested_spans():
    trace = {"traceEvents": [
        _span("plan_init", "init", 0, 100, args={"warm": False}),
        _span("index_table_bake", "init.bake", 10, 20),
        _span("measure_bursts", "init.autotune", 40, 50),
        _span("epoch", "execute", 200, 10),
    ]}
    s = validate_trace(trace, expect_cats=("init", "execute"))
    assert s["events"] == 4 and s["cold_inits"] == 1 and s["warm_inits"] == 0


def test_validate_trace_rejects_partial_overlap():
    trace = {"traceEvents": [
        _span("a", "execute", 0, 100),
        _span("b", "execute", 50, 100),        # spills past a's end
    ]}
    with pytest.raises(TraceValidationError, match="overlaps"):
        validate_trace(trace)


def test_validate_trace_store_spans_exempt_from_nesting():
    # CAS-merge retries legitimately produce overlapping store timings.
    trace = {"traceEvents": [
        _span("store_merge", "store", 0, 100),
        _span("store_put", "store", 50, 100),
    ]}
    validate_trace(trace)


def test_validate_trace_warm_init_with_bake_child_fails():
    trace = {"traceEvents": [
        _span("plan_init", "init", 0, 100, args={"warm": True}),
        _span("index_table_bake", "init.bake", 10, 20),
    ]}
    with pytest.raises(TraceValidationError, match="warm-start contract"):
        validate_trace(trace)


def test_validate_trace_missing_expected_category_fails():
    trace = {"traceEvents": [_span("epoch", "execute", 0, 10)]}
    with pytest.raises(TraceValidationError, match="expected category"):
        validate_trace(trace, expect_cats=("runtime",))


def test_validate_trace_malformed_inputs(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    with pytest.raises(TraceValidationError, match="not valid JSON"):
        validate_trace(str(bad))
    with pytest.raises(TraceValidationError, match="traceEvents"):
        validate_trace({"other": []})
    with pytest.raises(TraceValidationError, match="missing/negative dur"):
        validate_trace({"traceEvents": [
            {"name": "a", "cat": "x", "ph": "X", "pid": 1, "tid": 1,
             "ts": 0, "dur": -1}]})
    with pytest.raises(TraceValidationError, match="unknown phase"):
        validate_trace({"traceEvents": [
            {"name": "a", "ph": "B", "pid": 1, "tid": 1, "ts": 0}]})


def test_write_trace_and_jsonl_roundtrip(tmp_path):
    import time

    TRACER.enable()
    t1 = time.perf_counter()
    TRACER.emit_span("epoch", "execute", t1 - 0.5, t1, {"digest": "d"})
    p = tmp_path / "t.json"
    trace = write_trace(str(p))
    assert validate_trace(str(p))["events"] == 1
    assert json.loads(p.read_text()) == json.loads(json.dumps(trace))
    lp = tmp_path / "t.jsonl"
    assert write_jsonl(str(lp)) == 1
    rec = json.loads(lp.read_text().splitlines()[0])
    assert rec["name"] == "epoch" and rec["dur_s"] == pytest.approx(0.5)
    # time_unix maps the span back to wall time via origin_unix
    assert abs(rec["time_unix"] - time.time()) < 60.0


# --- epoch rings + exec telemetry -------------------------------------------

def test_epoch_ring_summary_has_tail_quantiles():
    ring = EpochRing(capacity=128)
    for v in np.linspace(0.001, 0.1, 100):
        ring.record(float(v))
    s = ring.summary()
    assert s["p50_s"] <= s["p95_s"] <= s["p99_s"] <= s["max_s"]
    assert s["p95_s"] == pytest.approx(
        float(np.percentile(np.linspace(0.001, 0.1, 100), 95)))


def test_exec_telemetry_rank_rings_and_summary():
    tel = type(EXEC_TELEMETRY)()        # fresh instance, not the singleton
    for e in range(6):
        for r in range(4):
            tel.record_rank("d1", r, 0.001 * (r + 1))
    rs = tel.rank_summary("d1")
    assert sorted(rs) == [0, 1, 2, 3]
    assert rs[3]["p50_s"] == pytest.approx(0.004)
    assert rs[0]["count"] == 6
    assert tel.rank_summary("other") == {}
    snap = tel.snapshot()
    assert ("d1", 3) in snap["ranks"]
    tel.reset()
    assert tel.rank_summary("d1") == {} and tel.snapshot()["ranks"] == {}


def test_exec_telemetry_snapshot_safe_under_concurrent_mutation():
    tel = type(EXEC_TELEMETRY)()
    stop = threading.Event()
    errors = []

    def mutate():
        i = 0
        while not stop.is_set():
            tel.record(f"d{i % 50}", 0.001)
            tel.record_rank(f"d{i % 50}", i % 8, 0.001)
            tel.record_swap(old="a", new="b", reason={"kind": "t"})
            i += 1

    def read():
        try:
            for _ in range(200):
                snap = tel.snapshot()
                for s in snap["plans"].values():
                    assert s["count"] >= 0
        except Exception as e:      # noqa: BLE001 — the assertion IS the test
            errors.append(e)

    w = threading.Thread(target=mutate)
    r = threading.Thread(target=read)
    w.start(); r.start()
    r.join(); stop.set(); w.join()
    assert errors == []


# --- init stats (satellite: snapshot/diff across PlanCache reuse) -----------

def test_init_stats_bump_is_thread_safe():
    INIT_STATS.reset()
    n_threads, per = 8, 1000

    def w():
        for _ in range(per):
            INIT_STATS.bump("table_bakes")

    ts = [threading.Thread(target=w) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert INIT_STATS.table_bakes == n_threads * per
    INIT_STATS.reset()


def test_init_stats_snapshot_diff_across_plancache_reuse():
    """init_stats() snapshots diff cleanly around INIT work: a first build
    pays bakes, an in-cache rebuild of the same spec pays nothing, and
    reset() rebaselines to all-zero."""
    import jax.numpy as jnp

    from repro.core import PlanCache, alltoallv_init, init_stats, \
        reset_init_stats
    from repro.launch.mesh import make_host_mesh

    reset_init_stats()
    base = init_stats()
    assert set(base) >= {"cold_inits", "warm_inits", "table_bakes",
                         "store_hits"}
    assert all(v == 0 for v in base.values())

    mesh = make_host_mesh(1)
    cache = PlanCache()
    counts = np.full((1, 1), 8)
    alltoallv_init(counts, (4,), jnp.float32, mesh, axis="x",
                   variant="fence", cache=cache)
    after_cold = init_stats()
    diff = {k: after_cold[k] - base[k] for k in base}
    assert diff["cold_inits"] == 1 and diff["table_bakes"] >= 1
    assert diff["warm_inits"] == 0

    # Same spec through the same cache: a pure cache hit does no INIT work.
    alltoallv_init(counts, (4,), jnp.float32, mesh, axis="x",
                   variant="fence", cache=cache)
    after_reuse = init_stats()
    assert after_reuse == after_cold, (after_cold, after_reuse)

    # A fresh cache re-pays the bake (no store configured to warm from).
    alltoallv_init(counts, (4,), jnp.float32, mesh, axis="x",
                   variant="fence", cache=PlanCache())
    assert init_stats()["cold_inits"] == after_reuse["cold_inits"] + 1

    reset_init_stats()
    assert all(v == 0 for v in init_stats().values())


# --- break-even --------------------------------------------------------------

def test_breakeven_residual_math():
    fit = {"t_best": 0.010, "t_second": 0.012, "sweep_seconds": 0.1}
    assert breakeven_residual(fit, 0.010) == pytest.approx(0.0)
    assert breakeven_residual(fit, 0.011) == pytest.approx(0.1)
    assert breakeven_residual({"t_best": 0.0}, 0.01) == math.inf


def test_check_breakeven_gates_on_warmup_and_reports_n_observed():
    snap = {"fits": {"d1": {"t_best": 0.010, "t_second": 0.012,
                            "sweep_seconds": 0.1, "n_amortize": 50},
                     "d2": {"t_best": 0.010, "t_second": 0.012,
                            "sweep_seconds": 0.1}},
            "plans": {"d1": {"count": 20, "p50_s": 0.011},
                      "d2": {"count": 2, "p50_s": 0.011}},    # <= warmup
            "swaps": [], "ranks": {}}
    out = check_breakeven(snap)
    assert [r["digest"] for r in out] == ["d1"]
    r = out[0]
    assert r["residual"] == pytest.approx(0.1)
    assert r["n_observed"] == math.ceil(0.1 / (0.012 - 0.011))
    assert r["n_amortize"] == 50


def test_check_breakeven_no_positive_margin_no_n_observed():
    snap = {"fits": {"d": {"t_best": 0.010, "t_second": 0.012,
                           "sweep_seconds": 0.1}},
            "plans": {"d": {"count": 9, "p50_s": 0.013}}}    # worse than 2nd
    (r,) = check_breakeven(snap)
    assert r["n_observed"] is None and r["residual"] == pytest.approx(0.3)


# --- metrics -----------------------------------------------------------------

def _fake_snapshots():
    init = {"cold_inits": 2, "warm_inits": 3, "table_bakes": 4,
            "autotune_sweeps": 1, "autotune_bursts": 18, "store_hits": 3,
            "store_misses": 1, "store_puts": 2, "store_invalid": 0}
    ex = {"plans": {"abc": {"count": 10, "mean_s": 0.01, "p50_s": 0.01,
                            "p95_s": 0.02, "p99_s": 0.03, "max_s": 0.03,
                            "last_s": 0.01}},
          "ranks": {("abc", 0): {"count": 10, "p50_s": 0.009},
                    ("abc", 1): {"count": 10, "p50_s": 0.013}},
          "swaps": [{"old": "x", "new": "abc"}],
          "fits": {"abc": {"t_best": 0.01, "t_second": 0.012,
                           "sweep_seconds": 0.5, "n_amortize": 250}}}
    return ex, init


def test_render_metrics_exposition():
    ex, init = _fake_snapshots()
    text = render_metrics(exec_snapshot=ex, init_snapshot=init)
    assert 'repro_init_total{kind="warm"} 3' in text
    assert 'repro_init_total{kind="cold"} 2' in text
    assert "repro_table_bakes_total 4" in text
    assert 'repro_store_requests_total{result="hit"} 3' in text
    assert "repro_store_hit_ratio 0.750000" in text
    assert "repro_plan_swaps_total 1" in text
    assert 'repro_epoch_seconds{digest="abc",quantile="0.99"}' in text
    assert 'repro_epoch_seconds_count{digest="abc"} 10' in text
    assert 'repro_epoch_rank_seconds{digest="abc",rank="1"} 0.013' in text
    assert 'repro_breakeven_residual{digest="abc"} 0.000000' in text
    assert 'repro_breakeven_n_amortize{digest="abc"} 250' in text
    # every non-comment line is "name{labels} value" — scrapable
    for line in text.splitlines():
        if line and not line.startswith("#"):
            assert len(line.rsplit(" ", 1)) == 2, line


def test_metrics_server_serves_and_404s():
    from repro.obs import MetricsServer
    srv = MetricsServer(0).start()
    try:
        url = f"http://127.0.0.1:{srv.port}"
        body = urllib.request.urlopen(f"{url}/metrics").read().decode()
        assert "repro_init_total" in body
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"{url}/other")
    finally:
        srv.stop()


# --- CLI ---------------------------------------------------------------------

def test_obs_cli_trace_validate(tmp_path, capsys):
    from repro.obs.__main__ import main
    TRACER.enable()
    with TRACER.span("plan_init", "init", warm=False):
        with TRACER.span("index_table_bake", "init.bake"):
            pass
    TRACER.emit_span("epoch", "execute", 0.1, 0.2, {"digest": "d"})
    p = tmp_path / "trace.json"
    write_trace(str(p))

    assert main(["trace", str(p), "--validate", "--expect", "init",
                 "--expect", "execute"]) == 0
    assert "TRACE OK" in capsys.readouterr().out

    assert main(["trace", str(p), "--validate",
                 "--expect", "runtime"]) == 1
    assert "TRACE INVALID" in capsys.readouterr().err

    assert main(["report", "--trace", str(p)]) == 0
    out = capsys.readouterr().out
    assert "init.bake" in out and "execute" in out


def test_obs_cli_metrics_out(tmp_path, capsys):
    from repro.obs.__main__ import main
    p = tmp_path / "m.prom"
    assert main(["metrics", "--out", str(p)]) == 0
    assert "repro_init_total" in p.read_text()


# --- plan-level wiring --------------------------------------------------------

def test_plan_epoch_spans_and_record_epoch_anchor():
    """A plan's start() and wait() emit plan.start / plan.wait spans when
    tracing is on, and record_epoch(t_end=...) anchors the backdated epoch
    span exactly."""
    import jax
    import jax.numpy as jnp

    from repro.core import PlanCache, alltoallv_init
    from repro.launch.mesh import make_host_mesh

    EXEC_TELEMETRY.reset()
    mesh = make_host_mesh(1)
    plan = alltoallv_init(np.full((1, 1), 8), (4,), jnp.float32, mesh,
                          axis="x", variant="fence", cache=PlanCache())
    x = jax.device_put(jnp.zeros(plan.global_send_shape, jnp.float32),
                       plan._x_sharding)
    TRACER.enable()
    import time as _time

    jax.block_until_ready(plan.wait(plan.start(x)))
    t_end = _time.perf_counter()
    plan.record_epoch(0.25, t_end=t_end)
    recs = TRACER.snapshot()["records"]
    steps = [r for r in recs if r[0].startswith("plan.")]
    assert [(r[0], r[1]) for r in steps] == [("plan.start", "execute"),
                                             ("plan.wait", "execute")]
    assert steps[0][6]["digest"] == plan.signature.digest
    recs = [r for r in recs if r[0] == "epoch"]
    assert len(recs) == 1
    anchored = recs[0]
    assert anchored[3] + anchored[4] == pytest.approx(t_end - TRACER._t0)
    assert anchored[4] == pytest.approx(0.25)
    assert anchored[6]["digest"] == plan.signature.digest
    ring = plan.epoch_ring
    assert ring.count == 2
