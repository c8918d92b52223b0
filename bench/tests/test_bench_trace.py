"""The trace reduction (bench/trace_reduce.py) on hand-made traces."""

from pathlib import Path

import pytest

from bench import trace_reduce as tr

SMALL = Path(__file__).resolve().parent / "data" / "trace_small.pbtxt"
US = 1e-6


def test_union_and_gaps():
    iv = [(5, 10), (0, 3), (8, 12), (20, 30)]
    assert tr.union_s(iv, 0, 100) == 3 + 7 + 10
    assert tr.union_s(iv, 9, 25) == 3 + 5
    assert tr.gaps(iv, 0, 40) == [(3, 5), (12, 20), (30, 40)]
    assert tr.gaps(iv, 1, 25) == [(3, 5), (12, 20)]
    assert tr.gaps([], 0, 4) == [(0, 4)]


def test_gaps_go_to_the_innermost_span():
    spans = [("bench.tick", 0, 50), ("bench.submit", 10, 20)]
    got = tr.charge_gaps([(12, 18), (30, 40), (60, 70)], spans)
    assert got == {"bench.submit": 6, "bench.tick": 10, "(no span)": 10}


def test_module_names_drop_the_hash():
    assert tr.module_name("jit__fused_conv(11566793028207636826)") == \
        "jit__fused_conv"
    assert tr.module_name("jit_matmul") == "jit_matmul"


@pytest.fixture(scope="module")
def small():
    from jax.profiler import ProfileData
    return tr.read_profile(ProfileData.from_text_proto(SMALL.read_text()))


def test_reads_device_ops_modules_spans_and_enqueues(small):
    devices, spans, enqueues = small
    assert list(devices) == ["/device:TPU:0"]
    assert len(devices["/device:TPU:0"]["ops"]) == 4
    assert [m[0] for m in devices["/device:TPU:0"]["modules"]] == [
        "jit__fused_conv", "jit_matmul", "jit__fused_conv"]
    assert sorted(s[0] for s in spans) == [
        "bench.submit", "bench.tick", "bench.window"]
    assert enqueues == {1: 20_000, 2: 50_000}


def test_small_trace_reduces_to_the_hand_count(small):
    r = tr.reduce(*small)
    assert r["clock_shift_s"] == pytest.approx(5 * US)
    assert r["window_s"] == pytest.approx(100 * US)
    # ops on the host clock: 20-30, 32-40, 52-62; 115-125 is outside
    assert r["busy_s"] == pytest.approx(28 * US)
    assert 1 - r["busy_s"] / r["window_s"] == pytest.approx(0.72)
    assert r["module_s"] == pytest.approx(
        {"jit__fused_conv": 20 * US, "jit_matmul": 10 * US})
    assert r["device_ops"][0][0] == "jit__fused_conv"
    # gaps 0-20 (mid 10, in submit 2-14), 30-32, 40-52, 62-100 (tick)
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"bench.submit": 20 * US, "bench.tick": 52 * US})


def test_a_trace_without_a_window_is_refused(small):
    devices, spans, enqueues = small
    with pytest.raises(ValueError, match="bench.window"):
        tr.reduce(devices, [s for s in spans if s[0] != "bench.window"],
                  enqueues)
