"""The program's profiler spans and plan-phase counters (core/spans.py):
two requests served through a SMOKE SpectralServer under the profiler on
the CPU, read back from the trace; and the plan build's phase seconds in
``PlanCache.stats()``."""

import glob

import jax
import pytest

from repro.configs import resnet18_spectral, vgg16_spectral
from repro.core import spans
from repro.core.plan import PlanCache
from repro.launch import spectral_serve as ss
from repro.models import cnn

CONFIGS = {"vgg16": vgg16_spectral.SMOKE,
           "resnet18": resnet18_spectral.SMOKE}
TICK_CHILDREN = [spans.SERVE_TAKE, spans.SERVE_UPLOAD, spans.SERVE_PLAN,
                 spans.SERVE_FORWARD, spans.SERVE_STAGE_NEXT,
                 spans.SERVE_READBACK, spans.SERVE_FINISH]
EXECUTE = "PjRtCpuExecutable::ExecuteHelper"


def host_events(log_dir):
    """``(name, start_ns, end_ns, stats)`` of the host thread that ran the
    program's spans, in start order."""
    from jax.profiler import ProfileData
    [path] = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    pd = ProfileData.from_file(path)
    [host] = [p for p in pd.planes if p.name == "/host:CPU"]
    for line in host.lines:
        evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                dict(e.stats)) for e in line.events]
        if any(n == spans.SERVE_TICK for n, *_ in evs):
            return sorted(evs, key=lambda ev: (ev[1], -ev[2]))
    raise AssertionError("no thread holds a serve.tick span")


def inside(ev, outer):
    return outer[1] <= ev[1] and ev[2] <= outer[2]


@pytest.fixture(scope="module", params=list(CONFIGS))
def served(request, tmp_path_factory):
    cfg = CONFIGS[request.param]
    srv = ss.SpectralServer(cfg, buckets=(1,), warm=True,
                            warm_forward=True)
    reqs = ss.synthetic_requests(2, cfg, seed=3, rid0=40)
    log_dir = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(log_dir):
        for r in reqs:
            srv.submit(r)
            srv.tick()
    assert all(r.ok for r in reqs)
    return srv, reqs, host_events(log_dir)


def test_each_tick_holds_its_phases_in_order(served):
    srv, reqs, evs = served
    ticks = [ev for ev in evs if ev[0] == spans.SERVE_TICK]
    submits = [ev for ev in evs if ev[0] == spans.SERVE_SUBMIT]
    assert [t[3]["rid"] for t in ticks] == [r.rid for r in reqs]
    assert [s[3]["rid"] for s in submits] == [r.rid for r in reqs]
    for tick in ticks:
        assert tick[3]["n"] == 1 and tick[3]["bucket"] == 1
        assert tick[3]["rung"] == "fused"
        children = [ev for ev in evs if ev[0].startswith("serve.")
                    and ev is not tick and inside(ev, tick)]
        assert [c[0] for c in children] == TICK_CHILDREN
        assert all(a[2] <= b[1] for a, b in zip(children, children[1:]))
    uploads = [ev for ev in evs if ev[0] == spans.SERVE_UPLOAD]
    # each request arrived after the previous tick: nothing was staged
    assert [u[3]["staged_hit"] for u in uploads] == [0, 0]


def test_the_forward_span_holds_one_node_span_per_graph_node(served):
    srv, _, evs = served
    plan = srv.plans.get(srv.params, srv.cfg, 1, **srv.plan_kwargs)
    graph = plan.execution_graph
    for fwd in [ev for ev in evs if ev[0] == spans.SERVE_FORWARD]:
        nodes = [ev for ev in evs if ev[0] == spans.FORWARD_NODE
                 and inside(ev, fwd)]
        assert [n[3]["node"] for n in nodes] == [g.id for g in graph]
        for n, g in zip(nodes, graph):
            assert n[3]["kind"] == g.kind
            if g.kind == "conv":
                lp = plan.node_plan(g)
                assert n[3]["hadamard"] == lp.hadamard
                assert n[3]["flow"] == lp.tuning.flow
                assert n[3]["input_mode"] == lp.input_mode
                assert n[3]["backend"] == lp.backend
                assert n[3]["predicted_us"] == pytest.approx(
                    lp.tuning.predicted_s * 1e6)
        heads = [ev for ev in evs if ev[0] == spans.FORWARD_FC_HEAD
                 and inside(ev, fwd)]
        assert len(heads) == 1 and heads[0][1] >= nodes[-1][2]


def test_every_execution_of_the_walk_falls_in_a_node_span(served):
    """The run id of each execution the walk dispatches is on an event
    inside a ``forward.node`` (or the head's) span, so a trace ties each
    execution to its node; every conv node dispatches at least one."""
    _, _, evs = served
    owners = [ev for ev in evs if ev[0] in (spans.FORWARD_NODE,
                                             spans.FORWARD_FC_HEAD)]
    for fwd in [ev for ev in evs if ev[0] == spans.SERVE_FORWARD]:
        runs = [ev for ev in evs if ev[0] == EXECUTE and inside(ev, fwd)]
        assert runs and all("run_id" in r[3] for r in runs)
        for r in runs:
            assert any(inside(r, o) for o in owners), r
        for node in owners:
            if inside(node, fwd) and node[3].get("kind") == "conv":
                assert any(inside(r, node) for r in runs), node[3]["node"]


def test_the_plan_build_counts_its_phases(served):
    srv, _, _ = served
    st = srv.plans.stats()
    assert set(st["phase_s"]) <= set(spans.PLAN_PHASES)
    assert all(v >= 0 for v in st["phase_s"].values())
    assert sum(st["phase_s"].values()) == pytest.approx(st["build_s"],
                                                        rel=0.1)
    assert srv.stats()["plan_phase_s"] == st["phase_s"]
    assert srv.health_report()["plan_cache"]["phase_s"] == st["phase_s"]


def test_phase_seconds_sum_over_builds_and_name_every_phase():
    cfg = vgg16_spectral.SMOKE
    params = cnn.init(jax.random.PRNGKey(0), cfg)
    cache = PlanCache()
    plans = [cache.get(params, cfg, b, hadamard="scheduled")
             for b in (1, 2)]
    st = cache.stats()
    assert set(st["phase_s"]) == set(spans.PLAN_PHASES)
    assert st["phase_s"]["tables"] > 0
    for phase, sec in st["phase_s"].items():
        assert sec == pytest.approx(sum(p.phase_s[phase] for p in plans))
    assert sum(st["phase_s"].values()) == pytest.approx(st["build_s"],
                                                        rel=0.1)
    cache.get(params, cfg, 1, hadamard="scheduled")      # a hit
    assert cache.stats()["phase_s"] == st["phase_s"]


def test_counted_adds_seconds_even_when_the_phase_raises():
    phase_s = {}
    with spans.counted(phase_s, "prune", layer="conv1"):
        pass
    with pytest.raises(ValueError):
        with spans.counted(phase_s, "prune"):
            raise ValueError("boom")
    assert set(phase_s) == {"prune"} and phase_s["prune"] >= 0
