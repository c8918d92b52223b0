"""The reduction of the program's own spans (bench/program_trace.py) on a
hand-made trace, and the readers of its numbers on known contexts."""

import random
from pathlib import Path

import pytest

from bench import harness, program_trace as pt, trace_reduce as tr, work
from bench.configs import spectral_cnn

DATA = Path(__file__).resolve().parent / "data" / "trace_program.pbtxt"
US = 1e-6


@pytest.fixture(scope="module")
def parsed():
    from jax.profiler import ProfileData
    return pt.read_profile(ProfileData.from_text_proto(DATA.read_text()))


@pytest.fixture(scope="module")
def reduced(parsed):
    return pt.reduce(*parsed)


def test_reads_the_program_spans_beside_the_benchmarks(parsed):
    devices, spans, enqueues, program, direct = parsed
    assert sorted({s[0] for s in spans}) == [
        "bench.submit", "bench.tick", "bench.window"]
    assert [p[0] for p in program].count("forward.node") == 4
    assert len(program) == 14 and len(enqueues) == 9
    # run 1 was enqueued by a task thread, every other on the walker's
    assert direct == set(enqueues) - {1}
    tick = next(p for p in program if p[0] == "serve.tick")
    assert tick[3] == {"rid": 7, "n": 1, "bucket": 1, "rung": "fused"}
    conv2 = next(p for p in program if p[3].get("node") == "conv2")
    assert conv2[3]["hadamard"] == "scheduled"
    assert conv2[3]["predicted_us"] == 37.0


def test_idle_goes_to_the_innermost_program_span(reduced):
    assert reduced["idle_by_span"] == pytest.approx({
        "forward.node": 26 * US, "serve.readback": 34 * US,
        pt.OUTSIDE: 25 * US})


def test_device_time_goes_to_the_node_that_dispatched_it(reduced):
    nodes = reduced["nodes"]
    assert list(nodes) == ["conv1", "conv2", "conv3", "pool"]
    # conv1 and conv3 run one executable (hash 111): the run ids tell
    # them apart.  conv1's kernel was enqueued late, inside conv2's span,
    # by a task thread: it is still conv1's
    assert nodes["conv1"]["module_s"] == pytest.approx(
        {"jit__fused_conv": 22 * US})
    assert nodes["conv2"]["module_s"] == pytest.approx(
        {"jit__fused_conv": 37 * US, "jit_add": 2 * US})
    assert nodes["conv2"]["module_n"] == {"jit__fused_conv": 1,
                                          "jit_add": 1}
    assert nodes["conv3"]["device_s"] == pytest.approx(20 * US)
    assert nodes["pool"]["module_s"] == pytest.approx(
        {"jit__reduce_max": 2 * US})
    assert all(n["calls"] == 1 for n in nodes.values())
    assert nodes["conv1"]["args"]["predicted_us"] == 11.0
    # the upload's, the head's and the finish's executions belong to no
    # node, and run 8 (after the window) is clipped away
    assert reduced["unowned_s"] == pytest.approx(
        {"jit_convert_element_type": 2 * US, "jit_matmul": 20 * US,
         "jit_copy": 10 * US})


def test_the_nodes_account_for_every_kernel_second_and_idle_adds_up(
        parsed, reduced):
    bench = tr.reduce(*parsed[:3])
    owned = sum(pt.node_kernel_s(n) for n in reduced["nodes"].values())
    assert owned == pytest.approx(bench["module_s"]["jit__fused_conv"])
    idle = bench["window_s"] - bench["busy_s"]
    assert sum(reduced["idle_by_span"].values()) == pytest.approx(idle)


def test_a_trace_without_a_window_is_refused(parsed):
    devices, spans, enqueues, program, direct = parsed
    with pytest.raises(ValueError, match="bench.window"):
        pt.reduce(devices, [s for s in spans if s[0] != "bench.window"],
                  enqueues, program, direct)


def test_the_sweep_charges_as_the_benchmarks_reduction_does():
    """On nested spans, ``charge_innermost`` gives what
    ``trace_reduce.charge_gaps`` gives (with its own name for no
    span)."""
    rng = random.Random(5)

    def nest(lo, hi, depth, out):
        t = lo
        while depth and t < hi - 4:
            s = rng.uniform(t, hi - 4)
            e = rng.uniform(s + 1, min(hi, s + 40))
            out.append((f"s{depth}.{len(out)}", s, e, {}))
            nest(s, e, depth - 1, out)
            t = e + rng.uniform(0, 5)
        return out

    spans = nest(0, 500, 3, [])
    cuts = sorted(rng.uniform(0, 500) for _ in range(80))
    gap_list = list(zip(cuts[::2], cuts[1::2]))
    want = tr.charge_gaps(gap_list, [s[:3] for s in spans])
    want[pt.OUTSIDE] = want.pop("(no span)", 0.0)
    got = pt.charge_innermost(gap_list, spans)
    assert {k: v for k, v in got.items() if v} == pytest.approx(
        {k: v for k, v in want.items() if v})


def test_deferred_enqueues_go_to_the_node_that_owns_nothing_yet():
    node = lambda s, e: ("forward.node", s, e, {})
    head = lambda s, e: ("forward.fc_head", s, e, {})
    walk = [node(10, 20), node(30, 40), head(42, 45),
            node(50, 60), node(70, 80), head(80, 85)]
    # two forward passes.  Direct: 2 in node 1, 3 in the head, 6 in node
    # 4.  Deferred: 1 (node 0's, late, inside node 1), 4 (node 1's
    # second run, after node 1 closed), 5 (node 3's, before node 4),
    # 7 (in the readback after the second head), 8 before any span
    enqueues = {1: 32, 2: 35, 3: 43, 4: 41, 5: 65, 6: 75, 7: 90, 8: 5}
    direct = {2, 3, 6, 7, 8}
    assert pt.owners(enqueues, walk, direct) == {
        1: 0, 2: 1, 3: 2, 4: 1, 5: 3, 6: 4, 7: 5}


# ---------------------------------------------------------------------------
# Readers
# ---------------------------------------------------------------------------

LAYER = {"ksize": 3, "pad": 1, "stride": 1, "h_in": 16, "w_in": 16,
         "c_in": 8, "c_out": 8}
CFG = {"reference": "spectral_cnn", "image_size": 16, "n_classes": 10,
       "fc_dim": 16, "fft_size": 8, "alpha": 4.0,
       "layers": [{**LAYER, "name": f"conv{i}"} for i in (1, 2, 3)]}


def context(parsed, reduced, **kw):
    trace = {**tr.reduce(*parsed[:3]), "program": reduced}
    return {"cfg": CFG, "images": 1, "batches": {1: 1},
            "peaks": work.peaks_for("TPU v5 lite"), "trace": trace,
            "stats": {"plan_phase_s": {"prune": 9.0, "schedule_stats": 2.5,
                                       "tables": 40.0, "autotune": 0.5}},
            **kw}


def test_readers_on_a_known_context(parsed, reduced):
    ctx = context(parsed, reduced)
    # 26 us of 200 in forward.node; readback 34 in serve.*
    assert pt.idle_in_forward_share(ctx) == pytest.approx(13.0)
    assert pt.idle_in_serve_share(ctx) == pytest.approx(17.0)
    # measured / predicted: conv1 22/11, conv2 37/37, conv3 20/80
    assert pt.fused_conv_model_x(ctx) == pytest.approx(2.0)
    assert harness.metric_reader("plan_schedule_s")(ctx) == 42.5
    share = harness.metric_reader("device_idle_share")(ctx)
    outside = 100 * 25 * US / ctx["trace"]["window_s"]
    assert (pt.idle_in_forward_share(ctx) + pt.idle_in_serve_share(ctx)
            + outside) == pytest.approx(share)


def test_the_breakdown_ranks_conv_nodes_by_device_time(parsed, reduced):
    ctx = context(parsed, reduced)
    out = pt.breakdown(ctx, top=2)
    assert [k for k, _ in out["program_idle"]] == ["serve.readback",
                                                    "forward.node"]
    assert [v for _, v in out["program_idle"]] == pytest.approx(
        [34 * US, 26 * US])
    assert [n for n, _ in out["nodes"]] == ["conv2", "conv1"]
    conv2 = out["nodes"][0][1]
    assert conv2["ms_per_image"] == pytest.approx(39e-3)
    assert conv2["kernel_us_per_call"] == pytest.approx(37.0)
    assert conv2["predicted_us"] == 37.0
    assert conv2["hadamard"] == "scheduled"
    w = spectral_cnn.conv_work(CFG["layers"][1], 8, 4.0)
    least = work.least_time_s(w["flops"], w["bytes"], ctx["peaks"])[0]
    assert conv2["least_ms_per_image"] == pytest.approx(1e3 * least)
    assert conv2["roofline_pct"] == pytest.approx(100 * least / (39 * US))


def test_the_walk_between_node_spans_counts_as_forward():
    prog = {"idle_by_span": {"serve.forward": 1.0, "forward.fc_head": 2.0,
                             "serve.tick": 3.0, "serve.upload": 4.0,
                             pt.OUTSIDE: 5.0},
            "nodes": {}, "unowned_s": {}}
    ctx = {"trace": {"window_s": 100.0, "program": prog}}
    assert pt.idle_in_forward_share(ctx) == pytest.approx(3.0)
    assert pt.idle_in_serve_share(ctx) == pytest.approx(7.0)


def test_nothing_to_read_gives_none(parsed, reduced):
    empty = {"idle_by_span": {pt.OUTSIDE: 1.0}, "nodes": {},
             "unowned_s": {}}
    for trace in (None, {"window_s": 1.0},
                  {"window_s": 1.0, "program": empty}):
        ctx = context(parsed, reduced, trace=trace)
        for read in (pt.idle_in_forward_share, pt.idle_in_serve_share,
                     pt.fused_conv_model_x):
            assert read(ctx) is None
        assert pt.breakdown(ctx) == {}
    # a program that does not count its plan phases
    for stats in ({}, {"plan_phase_s": {}}):
        ctx = context(parsed, reduced, stats=stats)
        assert harness.metric_reader("plan_schedule_s")(ctx) is None


def test_the_program_run_refuses_without_a_tpu(capsys):
    from bench import program_run, run
    rc = program_run.main(["--workload", "resnet18-b1-stream", "--seed",
                           "3", "--seconds", "1"])
    out = capsys.readouterr()
    assert rc == run.NO_CHIP
    assert out.out == "" and "not falling back" in out.err
