"""The harness on the CPU: files found by name, the chip guard, the
traffic generator on a stub server, the metric readers, and whole runs of
small configurations through the program with the chip check skipped:
correct when the program is sound, not correct when an answer is
altered where it is produced, and the control (the reference at one step
less precision) reads far above the program.  A stand-in configuration,
made here, enters by its own files alone."""

import dataclasses
import importlib
import json
import re
import sys
import types

import numpy as np
import pytest

from bench import graph, harness, run, traffic, work

SPEC = harness.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def preset(name: str):
    """The program's preset module of configuration ``name``:
    ``repro.configs.<name with '-' as '_'>``, with ``CONFIG`` and
    ``SMOKE``."""
    module = "repro.configs." + name.replace("-", "_")
    try:
        return importlib.import_module(module)
    except ModuleNotFoundError as e:
        if e.name != module:
            raise
        raise ModuleNotFoundError(
            f"configuration {name!r} has no preset module {module} "
            f"(with CONFIG and SMOKE)", name=module) from e


# ---------------------------------------------------------------------------
# BENCHMARK.json and the files it names
# ---------------------------------------------------------------------------

def test_benchmark_json_keeps_its_contract():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"] == ["python3", "bench/run.py"]
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for m in SPEC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = {}
    for m in SPEC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        layers.setdefault(m["layer"], m["layer"])
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    names = [c["name"] for c in SPEC["configs"]]
    for w in SPEC["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in names
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    assert [w["name"] for w in SPEC["workloads"]][:2] == [
        "vgg16-b1-stream", "resnet18-b1-stream"]


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda c: c["name"])
def test_each_config_loads_by_name_and_is_the_programs_preset(entry):
    cfg = harness.config(SPEC, entry["name"])
    assert cfg["name"] == entry["name"]
    assert entry["file"].startswith("bench/configs/")
    assert cfg["reduced"] == entry["reduced"]
    ref = harness.reference_module(cfg)
    assert callable(ref.make_params) and callable(ref.Reference)
    assert callable(ref.network_work)
    assert harness.program_config(cfg) == preset(entry["name"]).CONFIG


def test_a_key_that_names_no_field_is_refused():
    cfg = {**harness.config(SPEC, "vgg16-spectral"), "fc_dimm": 512}
    with pytest.raises(ValueError, match=r"\['fc_dimm'\]"):
        harness.program_config(cfg)


def test_a_config_without_a_preset_names_the_module_it_needs():
    with pytest.raises(ModuleNotFoundError,
                       match="repro.configs.no_such_cnn"):
        preset("no-such-cnn")


@pytest.mark.parametrize("w", SPEC["workloads"], ids=lambda w: w["name"])
def test_each_cell_finds_its_traffic_and_metrics(w):
    mx = harness.mix(w["traffic"])
    assert mx["arrivals"]["kind"] in ("closed", "poisson")
    assert mx["queue_limit"] >= max(mx["buckets"])
    for m in harness.per_layer_metrics(SPEC, w["name"]):
        assert callable(harness.metric_reader(m["name"]))
    assert harness.end_to_end_metrics(SPEC, w["name"])


# ---------------------------------------------------------------------------
# The chip guard
# ---------------------------------------------------------------------------

def test_the_run_refuses_without_a_tpu(capsys):
    rc = run.main(["--workload", "vgg16-b1-stream", "--seed", "7",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc == run.NO_CHIP
    assert out.out == ""
    assert "not falling back" in out.err


def test_find_chips_needs_a_tpu():
    assert run.find_chips(1) is None


# ---------------------------------------------------------------------------
# The traffic generator on a stub server
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class StubRequest:
    rid: int
    image: np.ndarray
    deadline_s: float | None = None
    code: str | None = None
    logits: np.ndarray | None = None

    @property
    def terminal(self):
        return self.code is not None


class StubServer:
    """Answers up to ``max(buckets)`` queued requests per tick with the
    image's mean as its one logit."""

    def __init__(self, buckets=(1,), queue_limit=16):
        self.buckets, self.queue_limit = tuple(buckets), queue_limit
        self.queue, self.sizes = [], []

    def submit(self, req):
        if len(self.queue) >= self.queue_limit:
            req.code = "overloaded"
        else:
            self.queue.append(req)

    def tick(self):
        batch = self.queue[:max(self.buckets)]
        del self.queue[:len(batch)]
        for r in batch:
            r.code, r.logits = "ok", np.array([r.image.mean()])
        self.sizes.append(len(batch))
        return len(batch)


def stub_request(rid, image, deadline_s):
    return StubRequest(rid, image, deadline_s)


def test_closed_loop_keeps_one_request_in_flight_per_client():
    mx = harness.mix("stream-b1")
    pool = traffic.image_pool((3, 4, 4), mx["pool"], seed=2 ** 40 + 3)
    srv = harness._CountingServer(StubServer(mx["buckets"]))
    recs, t0, t_end = traffic.drive(srv, stub_request, mx, pool, 0.05,
                                    seed=2 ** 40 + 3)
    assert len(recs) > 10 and all(r.code == "ok" for r in recs)
    assert [r.rid for r in recs] == list(range(len(recs)))
    assert all(r.image == r.rid % mx["pool"] for r in recs)
    assert all(np.allclose(r.logits, pool[r.image].mean()) for r in recs)
    # one client: each request arrives after the previous one is done
    assert all(b.arrived >= a.done for a, b in zip(recs, recs[1:]))
    assert t_end == recs[-1].done and t_end - t0 >= 0.05
    assert srv.batches == {1: len(recs)}


def test_closed_loop_clients_fill_the_bucket():
    mx = {"arrivals": {"kind": "closed", "clients": 8}, "buckets": [8],
          "queue_limit": 8, "deadline_ms": None, "pool": 16}
    pool = traffic.image_pool((3, 4, 4), mx["pool"], seed=5)
    stub = StubServer(mx["buckets"], mx["queue_limit"])
    recs, _, _ = traffic.drive(stub, stub_request, mx, pool, 0.02, seed=5)
    assert len(recs) % 8 == 0 and set(stub.sizes) == {8}


def test_open_loop_arrivals_follow_the_seed():
    arr = {"kind": "poisson", "rate_per_s": 200.0}
    a = traffic.arrival_times(arr, 10.0, seed=-12345)
    assert a.size == pytest.approx(2000, rel=0.1)
    assert np.array_equal(a, traffic.arrival_times(arr, 10.0, seed=-12345))
    assert not np.array_equal(a, traffic.arrival_times(arr, 10.0, seed=1))
    burst = {**arr, "burst": {"period_s": 5.0, "on_s": 1.0, "mult": 4}}
    b = traffic.arrival_times(burst, 10.0, seed=3)
    on = (b % 5.0) < 1.0
    # 4x the rate in the on-second: 800 a second against 200
    assert on.sum() / 2 == pytest.approx(800, rel=0.15)
    assert (~on).sum() / 8 == pytest.approx(200, rel=0.15)


def test_open_loop_drives_every_arrival():
    mx = {"arrivals": {"kind": "poisson", "rate_per_s": 500.0},
          "buckets": [1, 2, 4], "queue_limit": 64, "deadline_ms": None,
          "pool": 8}
    pool = traffic.image_pool((2, 2), 8, seed=9)
    stub = StubServer(mx["buckets"], mx["queue_limit"])
    recs, t0, _ = traffic.drive(stub, stub_request, mx, pool, 0.1, seed=9)
    want = traffic.arrival_times(mx["arrivals"], 0.1, seed=9)
    assert len(recs) == want.size
    assert np.allclose([r.arrived - t0 for r in recs], want)
    assert all(r.code == "ok" and r.latency_s >= 0 for r in recs)


def test_images_follow_the_seed():
    a = traffic.image_pool((3, 8, 8), 4, seed=2 ** 33 + 1)
    assert a.dtype == np.float32 and a.shape == (4, 3, 8, 8)
    assert np.array_equal(a, traffic.image_pool((3, 8, 8), 4, 2 ** 33 + 1))
    assert not np.array_equal(a, traffic.image_pool((3, 8, 8), 4, 2))


# ---------------------------------------------------------------------------
# Metric readers
# ---------------------------------------------------------------------------

def test_metric_readers_on_a_known_context():
    cfg = harness.config(SPEC, "vgg16-spectral")
    peaks = work.peaks_for("TPU v5 lite")
    least = work.conv_least_time_s(cfg, peaks)[0]
    ctx = {"cfg": cfg, "plan_build_s": 170.0, "warmup_s": 3.0,
           "stats": {"served_by_rung": {"fused": 99, "staged": 1,
                                        "einsum": 0}},
           "images": 400, "window_s": 10.0, "batches": {1: 400},
           "peaks": peaks,
           "trace": {"window_s": 10.0, "busy_s": 8.0,
                     "module_s": {"jit__fused_conv": 100 * least * 400,
                                  "jit_matmul": 1.0}}}
    read = lambda name: harness.metric_reader(name)(ctx)
    assert read("plan_build_s") == 170.0 and read("warmup_s") == 3.0
    assert read("fused_rung_share") == pytest.approx(99.0)
    assert read("device_idle_share") == pytest.approx(20.0)
    assert read("fused_conv_ms_per_image") == pytest.approx(1e5 * least)
    assert read("fused_conv_roofline") == pytest.approx(1.0)
    flops = work.network_work(cfg)["flops"]
    assert read("forward_mfu") == pytest.approx(
        100 * flops * 40 / peaks["flops_per_s"])
    # the readings the work count gave before it moved to the reference
    assert read("forward_mfu") == 0.17432381595939087
    assert read("fused_conv_roofline") == 1.0
    ctx["batches"] = {1: 300, 8: 20}
    assert read("fused_conv_roofline") == 0.9056978285526872
    # nothing to read: no value, never a 0
    ctx["trace"] = None
    for name in ("device_idle_share", "fused_conv_ms_per_image",
                 "fused_conv_roofline"):
        assert read(name) is None


# ---------------------------------------------------------------------------
# Whole runs at a small size, chip check skipped
# ---------------------------------------------------------------------------

def file_value(value):
    """A program field's value as a configuration file holds it."""
    if dataclasses.is_dataclass(value):
        return {f.name: file_value(getattr(value, f.name))
                for f in dataclasses.fields(value)}
    if isinstance(value, frozenset):
        return sorted(value)
    if isinstance(value, (tuple, list)):
        return [file_value(v) for v in value]
    return value


def small_config(name: str, spec: dict = SPEC) -> dict:
    """The configuration file with every field of the program's SMOKE
    preset in place of its own, and the full-size cell's limits."""
    pc = preset(name).SMOKE
    cfg = harness.config(spec, name)
    cfg.update(file_value(pc))
    assert harness.program_config(cfg) == pc
    return cfg


def small_run(name, seed, **kw):
    cfg = small_config(name)
    cell = next(w for w in SPEC["workloads"] if w["config"] == name)
    return harness.run(cell["name"], seed, 1.0, False, t_start=0.0,
                       device={"platform": "cpu", "kind": "cpu",
                               "count": 1},
                       cfg=cfg, mx=harness.mix(cell["traffic"]),
                       log=lambda msg: None, **kw)


@pytest.mark.parametrize("name", [c["name"] for c in SPEC["configs"]])
def test_a_small_run_is_correct(name):
    out = small_run(name, 2 ** 35 + 17)
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"latency_ms_p50", "latency_ms_p95",
                                   "images_per_s", "setup_s"}
    assert out["checks"]["logit_err"]["value"] < 1e-5


def test_the_window_runs_with_the_set_up_frozen(monkeypatch):
    """Set-up's objects are out of the collector's reach in the window,
    and back in it before the reference runs."""
    import gc
    drive, checks, frozen = traffic.drive, harness.checks, []

    def watched(fn):
        def call(*args, **kw):
            frozen.append(gc.get_freeze_count())
            return fn(*args, **kw)
        return call

    monkeypatch.setattr(traffic, "drive", watched(drive))
    monkeypatch.setattr(harness, "checks", watched(checks))
    assert small_run("vgg16-spectral", 5)["correct"]
    # a full collection on CPython 3.12 itself leaves a few hundred
    # objects in the permanent generation
    assert len(frozen) == 2 and frozen[1] < frozen[0] // 100


def test_an_answer_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from repro.models import cnn
    head = cnn.fc_head

    def altered(params, x):
        y = head(params, x)
        return y.at[:, 0].add(1e-3 * abs(y).max())

    monkeypatch.setattr(cnn, "fc_head", altered)
    out = small_run("vgg16-spectral", 11)
    assert not out["correct"]
    assert out["checks"]["logit_err"]["value"] >= 1e-3


@pytest.mark.parametrize("name", [c["name"] for c in SPEC["configs"]])
def test_the_control_reads_far_above_the_program(name):
    """The reference at "high" (three bf16 passes) in the program's
    place fails the cell's limit, where the program passes it."""
    cfg = small_config(name)
    params = harness.reference_module(cfg).make_params(cfg, 0)
    ref = harness.reference_module(cfg).Reference(cfg, params)
    shape = graph.shapes(cfg)["input"]
    pool = traffic.image_pool(shape, 8, seed=4)
    recs = [traffic.Record(i, i, 0.0, 0.0, "ok") for i in range(8)]
    win = harness.Window(recs, 0.0, 1.0, pool, {1: 8}, 0)
    control = harness.compare(ref, win, recs, control=True)
    limit = cfg["limits"]["logit_err"]
    assert control > limit


# ---------------------------------------------------------------------------
# A configuration that enters by its own files alone
# ---------------------------------------------------------------------------

STANDIN = "standin-cnn"


def standin_work(cfg: dict, *, batch: int = 1) -> dict:
    """The stand-in reference's count: its ``"dense"`` nodes as 1x1
    GEMMs, the others as spectral convs, and a one-layer head."""
    from bench.configs import spectral_cnn
    ops = {n["id"]: n["op"] for n in cfg["graph"] if n["kind"] == "conv"}
    convs = []
    for l in cfg["layers"]:
        if ops[l["name"]] == "dense":
            px, mn = l["h_in"] * l["w_in"], l["c_in"] * l["c_out"]
            convs.append({"name": l["name"], "kind": "dense",
                          "flops": 2.0 * batch * px * mn,
                          "bytes": 4 * (batch * px * (l["c_in"] + l["c_out"])
                                        + mn)})
        else:
            convs.append(spectral_cnn.conv_work(
                l, cfg["fft_size"], cfg["alpha"], batch=batch))
    head = graph.feature_dim(cfg) * cfg["n_classes"]
    conv_flops = sum(c["flops"] for c in convs)
    return {"convs": convs, "conv_flops": conv_flops,
            "conv_bytes": sum(c["bytes"] for c in convs),
            "fc_flops": 2.0 * batch * head, "fc_bytes": 4 * head,
            "flops": conv_flops + 2.0 * batch * head}


@pytest.fixture
def standin(monkeypatch, tmp_path):
    """A third configuration whose program fields include two that the
    program lacks (a node's ``op``, the config's ``fc_layers``), with its
    file, preset module and reference module: the spec that lists it."""
    from repro.core import dataflow
    from repro.models import cnn

    @dataclasses.dataclass(frozen=True)
    class Node(dataflow.NodeSpec):
        op: str = "spectral"

    @dataclasses.dataclass(frozen=True)
    class Config(cnn.SpectralCNNConfig):
        fc_layers: int = 3

    def make(name, size, width):
        return Config(
            name=name, n_classes=10, image_size=size, fc_dim=width,
            pool_after=frozenset(), fc_layers=1,
            layers=(dataflow.ConvLayer("a", 3, width, size, size),
                    dataflow.ConvLayer("b", width, 2 * width, size, size,
                                       ksize=1, pad=0)),
            graph=(Node("a"), Node("b", inputs=("a",), op="dense"),
                   Node("head:pool", kind="pool", pool="avg",
                        inputs=("b",))))

    monkeypatch.setattr(dataflow, "NodeSpec", Node)
    monkeypatch.setattr(cnn, "SpectralCNNConfig", Config)
    monkeypatch.setitem(sys.modules, "repro.configs.standin_cnn",
                        types.SimpleNamespace(CONFIG=make(STANDIN, 32, 16),
                                              SMOKE=make("standin-s", 8, 4)))
    monkeypatch.setitem(sys.modules, "bench.configs.standin_ref",
                        types.SimpleNamespace(network_work=standin_work))
    layer = {"h_in": 32, "w_in": 32, "stride": 1}
    file = {
        "name": STANDIN, "source": "https://example.org/standin",
        "reference": "standin_ref", "weights_seed": 0,
        "image_size": 32, "fft_size": 8, "alpha": 4.0, "n_classes": 10,
        "fc_dim": 16, "fc_layers": 1, "pool_after": [],
        "layers": [{**layer, "name": "a", "c_in": 3, "c_out": 16,
                    "ksize": 3, "pad": 1},
                   {**layer, "name": "b", "c_in": 16, "c_out": 32,
                    "ksize": 1, "pad": 0}],
        "graph": [{"id": "a", "kind": "conv", "inputs": ["input"],
                   "op": "spectral"},
                  {"id": "b", "kind": "conv", "inputs": ["a"],
                   "op": "dense"},
                  {"id": "head:pool", "kind": "pool", "inputs": ["b"],
                   "pool": "avg"}],
        "reduced": [], "limits": {"logit_err": 5e-06}}
    path = tmp_path / f"{STANDIN}.json"
    path.write_text(json.dumps(file))
    return {**SPEC,
            "configs": SPEC["configs"] + [
                {"name": STANDIN, "source": file["source"],
                 "file": str(path), "reduced": [], "why": "a stand-in"}],
            "workloads": SPEC["workloads"] + [
                {"name": "standin-b1-stream", "config": STANDIN,
                 "traffic": "stream-b1", "chips": 1, "why": "a stand-in"}]}


def test_a_config_with_fields_of_its_own_reaches_the_program(standin):
    cfg = harness.config(standin, STANDIN)
    pc = harness.program_config(cfg)
    assert pc == preset(STANDIN).CONFIG
    assert pc.fc_layers == 1 and [n.op for n in pc.graph[:2]] == [
        "spectral", "dense"]
    small = small_config(STANDIN, standin)
    assert small["name"] == "standin-s" and small["fc_layers"] == 1
    assert [n.get("op") for n in small["graph"]] == [
        "spectral", "dense", "spectral"]
    assert small["limits"] == cfg["limits"]


def test_the_roofline_counts_only_spectral_nodes_and_mfu_all(standin):
    cfg = harness.config(standin, STANDIN)
    w = work.network_work(cfg)
    assert [(c["name"], c["kind"]) for c in w["convs"]] == [
        ("a", "spectral"), ("b", "dense")]
    peaks = work.peaks_for("TPU v5 lite")
    spectral = work.least_time_s(w["convs"][0]["flops"],
                                 w["convs"][0]["bytes"], peaks)[0]
    assert work.conv_least_time_s(cfg, peaks) == (
        spectral, {"flops": 0, "bytes": 1})
    ctx = {"cfg": cfg, "images": 400, "window_s": 10.0, "batches": {1: 400},
           "peaks": peaks, "trace": {"window_s": 10.0, "busy_s": 8.0,
                                     "module_s": {"jit__fused_conv":
                                                  100 * spectral * 400}}}
    assert harness.metric_reader("fused_conv_roofline")(ctx) == (
        pytest.approx(1.0))
    flops = (w["convs"][0]["flops"] + w["convs"][1]["flops"]
             + 2.0 * 16 * 16 * 32 * 10)
    assert w["flops"] == flops
    assert harness.metric_reader("forward_mfu")(ctx) == pytest.approx(
        100 * flops * 40 / peaks["flops_per_s"])
