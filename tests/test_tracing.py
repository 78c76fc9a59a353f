"""The program's spans, scopes and counters, as a profiler and an operator
see them: the serve host loop's spans in a ``jax.profiler`` trace and in the
ring, the experts-touched counter against a numpy recount, the layer scopes
in the step executables' ``op_name`` metadata, and the alltoallv epoch's
stage scopes."""

import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.configs.base import MoEConfig, ShapeConfig
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_mesh
from repro.models import moe as moe_mod
from repro.obs import COUNTERS, TRACER, render_metrics
from repro.serve import ServeEngine

BATCH, PROMPT, TOKENS = 2, 8, 4
SERVE_SPANS = ("serve.generate", "serve.prefill", "serve.grow_caches",
               "serve.first_token", "serve.decode_dispatch",
               "serve.token_fetch")


@pytest.fixture(scope="module")
def engine():
    cfg = get_reduced("olmoe-1b-7b")
    mesh = make_mesh((1, 1), ("data", "model"))
    eng = ServeEngine(cfg, mesh, batch=BATCH, prompt_len=PROMPT,
                      max_seq=PROMPT + TOKENS, seed=0)
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (BATCH, PROMPT)).astype(np.int32)
    eng.generate(prompts, TOKENS)            # compile outside the traces
    return eng, prompts


@pytest.fixture
def ring():
    TRACER.reset()
    TRACER.enable()
    yield TRACER
    TRACER.reset()


def _host_serve_spans(trace_dir):
    """(name, start_ns, end_ns) of the serve.* events on the host line that
    holds them, in start order."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(ev.name, int(ev.start_ns), int(ev.end_ns))
                   for ev in line.events if ev.name.startswith("serve.")]
            if evs:
                return sorted(evs, key=lambda x: (x[1], -x[2]))
    return []


def test_generate_spans_reach_the_profiler_and_the_ring(engine, ring,
                                                         tmp_path):
    eng, prompts = engine
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        eng.generate(prompts, TOKENS)
    spans = _host_serve_spans(tmp_path)
    names = [n for n, _, _ in spans]
    steps = ["serve.decode_dispatch", "serve.token_fetch"] * (TOKENS - 1)
    assert names == ["serve.generate", "serve.prefill", "serve.grow_caches",
                     "serve.first_token"] + steps
    # every step nests in serve.generate, and the steps follow one another
    (_, g0, g1), children = spans[0], spans[1:]
    assert all(g0 <= s <= e <= g1 for _, s, e in children)
    assert all(a[2] <= b[1] for a, b in zip(children, children[1:]))
    assert names.count("serve.token_fetch") == TOKENS - 1

    recs = [r for r in ring.snapshot()["records"] if r[0] in SERVE_SPANS]
    assert [r[0] for r in sorted(recs, key=lambda r: r[3])] == names
    assert {r[1] for r in recs} == {"execute"}


def test_generate_counts_decode_steps_and_experts(engine):
    eng, prompts = engine
    cfg = eng.cfg
    before = COUNTERS.snapshot()
    eng.generate(prompts, TOKENS)
    after = COUNTERS.snapshot()
    steps = after["serve.decode_steps"] - before.get("serve.decode_steps", 0)
    touched = (after["serve.experts_touched"]
               - before.get("serve.experts_touched", 0))
    assert steps == TOKENS - 1
    # each MoE layer-step touches between top_k and min(E, B * top_k) experts
    k, e = cfg.moe.top_k, cfg.moe.n_experts
    layer_steps = steps * cfg.n_layers
    assert k * layer_steps <= touched <= min(e, BATCH * k) * layer_steps
    text = render_metrics()
    assert (f"repro_serve_decode_steps_total {after['serve.decode_steps']}"
            in text)
    assert (f"repro_serve_experts_touched_total "
            f"{after['serve.experts_touched']}" in text)


def test_generate_bumps_no_exchange_counter(engine):
    eng, prompts = engine
    before = COUNTERS.snapshot()
    eng.generate(prompts, TOKENS)
    after = COUNTERS.snapshot()
    for name in ("a2a.epochs", "a2a.wire_bytes"):
        assert after.get(name, 0) == before.get(name, 0), name


def test_exchange_counters_render_in_prometheus():
    COUNTERS.add("a2a.epochs", 2)
    COUNTERS.add("a2a.wire_bytes", 96)
    snap = COUNTERS.snapshot()
    text = render_metrics()
    assert f"repro_a2a_epochs_total {snap['a2a.epochs']}" in text
    assert f"repro_a2a_wire_bytes_total {snap['a2a.wire_bytes']}" in text
    assert "# TYPE repro_a2a_wire_bytes_total counter" in text


@pytest.mark.parametrize("capacity_factor", [0.25, 4.0])
def test_experts_touched_matches_a_numpy_recount(capacity_factor):
    """The third aux entry counts the experts with at least one kept
    assignment; recount them from the same routing in numpy."""
    from repro.parallel.sharding import ParamFactory

    t, d, e, k = 24, 16, 8, 2
    mcfg = MoEConfig(n_experts=e, top_k=k, d_expert=8,
                     capacity_factor=capacity_factor, dispatch="gspmd")
    f = ParamFactory(jax.random.key(3), jnp.float32)
    moe_mod.init_moe(f.scope("moe"), d, mcfg)
    params = f.params["moe"]
    rng = np.random.default_rng(3)
    x = np.abs(rng.standard_normal((1, t, d))).astype(np.float32)
    # positive inputs, and a router that scores experts 0-2 up and the rest
    # down: every token picks two of the first three, the others stay idle
    router = np.abs(rng.standard_normal((d, e))).astype(np.float32)
    router[:, 3:] *= -1
    params = dict(params, router=jnp.asarray(router))
    plan = moe_mod.MoEDispatchPlan.build(mcfg, t, None)
    _, aux = moe_mod.apply_moe(params, jnp.asarray(x), mcfg, plan)

    logits = x[0] @ router
    top = np.argsort(-logits, axis=-1, kind="stable")[:, :k]
    cap = plan.capacity * plan.ep_size
    seen = np.zeros(e, int)
    kept = np.zeros(e, int)
    for ex in top.reshape(-1):                # earlier tokens win the slots
        if seen[ex] < cap:
            kept[ex] += 1
        seen[ex] += 1
    assert aux.shape == (moe_mod.N_AUX,)
    assert float(aux[2]) == (kept > 0).sum() == 3


@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_step_executables_name_their_layers(kind):
    cfg = get_reduced("olmoe-1b-7b")
    mesh = make_mesh((1, 1), ("data", "model"))
    shape = ShapeConfig(kind, kind, PROMPT if kind == "prefill" else 16, BATCH)
    text = steps_mod.make_bundle(cfg, shape, mesh).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("attention/", "attention/kv_cache/", "moe/router/",
                  "moe/dispatch/", "moe/expert_ffn/", "moe/combine/",
                  "lm_head/"):
        assert any(scope in n for n in names), (kind, scope)


def test_alltoallv_epoch_names_its_stages(dist):
    dist("a2a_epoch_scopes", devices=4)


def test_alltoallv_epochs_count_their_wire_bytes(dist):
    dist("a2a_wire_counters", devices=4)

