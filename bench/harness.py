"""One run of one cell: set-up, the measured window, the check, the result.

Everything here is general.  What belongs to one configuration, traffic
mix or per-layer metric lives in a file of its own, found by the name
that ``BENCHMARK.json`` gives it:

- ``bench/configs/<config>.json`` (the path in the config's ``file``),
  whose ``reference`` names the plain reference module beside it;
- ``bench/traffic/<traffic>.json``, read by ``bench/traffic.py``;
- ``bench/metrics/<metric>.py``, a ``read(ctx)`` that returns the
  metric's value, or None where the run holds nothing to read.

A configuration enters the benchmark with files of its own and entries
in ``BENCHMARK.json``, and no edit to a file that is here:

- its JSON file.  The harness reads ``name``, ``reference``,
  ``weights_seed`` and ``limits`` (``logit_err``); ``source``, ``about``,
  ``reduced`` and ``assumed`` are records for the reader.  Every other
  key names a field of the program's ``SpectralCNNConfig`` and is passed
  to it (``program_config``); a key that is neither is an error;
- the program's preset module ``repro.configs.<name, '-' as '_'>``,
  with ``CONFIG`` (what ``program_config`` of the file must equal) and
  ``SMOKE`` (the size the CPU tests run);
- its reference module ``bench/configs/<reference>.py``, with
  ``make_params(cfg, seed)``, ``Reference(cfg, params).logits(images,
  precision)`` and ``network_work(cfg, *, batch)`` (``bench/work.py``).

The program under test is used through its serving front end
(``repro.launch.spectral_serve.SpectralServer``) and its configuration
classes; nothing else of it is read.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import importlib
import importlib.util
import json
import math
import shutil
import tempfile
import time
from pathlib import Path

import numpy as np

from bench import traffic, work

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
CACHE_DIR = ROOT / ".jax_cache"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def workload(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                   f"(known: {[w['name'] for w in spec['workloads']]})")


def config(spec: dict, name: str) -> dict:
    for c in spec["configs"]:
        if c["name"] == name:
            return load_json(ROOT / c["file"])
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def mix(name: str) -> dict:
    return load_json(BENCH / "traffic" / f"{name}.json")


def metric_reader(name: str):
    """``read(ctx)`` of ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def reference_module(cfg: dict):
    return importlib.import_module(f"bench.configs.{cfg['reference']}")


def per_layer_metrics(spec: dict, cell: str) -> list[dict]:
    return [m for m in spec["per_layer"]
            if cell in m.get("workloads", [cell])]


def end_to_end_metrics(spec: dict, cell: str) -> list[dict]:
    return [m for m in spec["end_to_end"]
            if cell in m.get("workloads", [cell])]


# ---------------------------------------------------------------------------
# The program under test
# ---------------------------------------------------------------------------

# keys of a configuration file that are the benchmark's, not the program's
BENCH_KEYS = frozenset({"source", "about", "reference", "weights_seed",
                        "limits", "reduced", "assumed"})


def _field_value(key: str, value):
    from repro.core.dataflow import ConvLayer, NodeSpec
    if key == "layers":
        return tuple(ConvLayer(**l) for l in value)
    if key == "graph":
        return None if value is None else tuple(
            NodeSpec(**{**n, "inputs": tuple(n["inputs"])}) for n in value)
    if key == "pool_after":
        return frozenset(value)
    return tuple(value) if isinstance(value, list) else value


def program_config(cfg: dict):
    """The program's configuration object for a configuration file: every
    key that names a field of ``SpectralCNNConfig`` is passed to it.
    ``layers`` become ``ConvLayer``s, ``graph`` nodes ``NodeSpec``s,
    ``pool_after`` a frozenset, any other list a tuple.  A file without
    ``pool_after`` has no 2x2 pools after its layers (``bench/graph.py``
    reads it so), whatever the program's default."""
    from repro.models.cnn import SpectralCNNConfig
    fields = {f.name for f in dataclasses.fields(SpectralCNNConfig)}
    unknown = sorted(set(cfg) - fields - BENCH_KEYS)
    if unknown:
        raise ValueError(
            f"configuration {cfg.get('name')!r}: keys {unknown} name no "
            f"field of SpectralCNNConfig and no key of the benchmark's")
    return SpectralCNNConfig(**{"pool_after": frozenset(), **{
        k: _field_value(k, v) for k, v in cfg.items() if k in fields}})


@dataclasses.dataclass
class Setup:
    server: object
    params: dict
    plan_build_s: float
    warmup_s: float
    image_shape: tuple


def build(cfg: dict, mx: dict, *, interpret=None) -> Setup:
    """The server with the configuration's plan built for every bucket of
    the mix, weights from the configuration's seed, every shape warmed."""
    from repro.launch import spectral_serve as ss
    pcfg = program_config(cfg)
    srv = ss.SpectralServer(pcfg, buckets=tuple(mx["buckets"]),
                            queue_limit=mx["queue_limit"],
                            interpret=interpret, warm=False)
    # the benchmark's weights replace the ones the server drew itself
    srv.params = None
    gc.collect()
    params = reference_module(cfg).make_params(cfg, cfg["weights_seed"])
    srv.params = params
    srv.plans.warm(params, pcfg, srv.buckets, mesh_shape=srv.mesh_shape,
                   **srv.plan_kwargs)
    plan_build_s = srv.plans.stats()["build_s"]
    t = time.perf_counter()
    srv.warm_forward()
    shape = srv.image_shape
    for b in srv.buckets:          # each bucket once through submit/tick
        reqs = [ss.InferenceRequest(-1 - i, np.zeros(shape, np.float32))
                for i in range(b)]
        for r in reqs:
            srv.submit(r)
        while not all(r.terminal for r in reqs):
            srv.tick()
    warmup_s = time.perf_counter() - t
    # the plan and weights live as long as the server: one full collection
    # now (~50 ms over ~200 k objects on a v5e host), then out of the
    # collector's reach, so that none of its passes over them lands in the
    # window
    gc.collect()
    gc.freeze()
    return Setup(srv, params, plan_build_s, warmup_s, shape)


def new_request(rid: int, image, deadline_s):
    from repro.launch.spectral_serve import InferenceRequest
    return InferenceRequest(rid, image, deadline_s=deadline_s)


# ---------------------------------------------------------------------------
# The measured window
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Window:
    records: list
    t0: float
    t_end: float
    pool: np.ndarray
    batches: dict          # bucket size -> batches run
    compiles: int
    trace: dict | None = None

    @property
    def ok(self) -> list:
        return [r for r in self.records if r.code == "ok"]

    @property
    def seconds(self) -> float:
        return self.t_end - self.t0


class _Counter:
    """Compiles seen while ``on``: none may happen inside the window."""

    def __init__(self):
        import jax
        self.on, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event: str, duration: float, **_) -> None:
        if self.on and event == COMPILE_EVENT:
            self.n += 1


class _CountingServer:
    """The server, with each tick's batch counted by its bucket."""

    def __init__(self, server):
        self.server, self.batches = server, collections.Counter()

    @property
    def queue(self):
        return self.server.queue

    def submit(self, req):
        return self.server.submit(req)

    def tick(self) -> int:
        n = self.server.tick()
        if n:
            self.batches[min(b for b in self.server.buckets if b >= n)] += 1
        return n


def serve(setup: Setup, mx: dict, seed: int, seconds: float, *,
          trace: bool = False, counter: _Counter | None = None) -> Window:
    """Offer the mix for ``seconds``; with ``trace`` the profiler records
    the window and its reduction comes back in ``Window.trace``."""
    import jax
    pool = traffic.image_pool(setup.image_shape, mx["pool"], seed)
    srv = _CountingServer(setup.server)
    span = jax.profiler.TraceAnnotation if trace else None
    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(log_dir, profiler_options=opts)
        if counter:
            counter.on = True
        with (span("bench.window") if trace else contextlib.nullcontext()):
            records, t0, t_end = traffic.drive(
                srv, new_request, mx, pool, seconds, seed, span=span)
        if counter:
            counter.on = False
        summary = None
        if trace:
            jax.profiler.stop_trace()
            from bench import trace_reduce
            summary = trace_reduce.reduce_dir(log_dir)
    finally:
        if log_dir:
            shutil.rmtree(log_dir, ignore_errors=True)
    return Window(records, t0, t_end, pool, dict(srv.batches),
                  counter.n if counter else 0, summary)


# ---------------------------------------------------------------------------
# The check
# ---------------------------------------------------------------------------

ANSWERED = ("ok", "overloaded", "deadline_exceeded")


def rel_err(y, ref) -> float:
    y, ref = np.asarray(y, np.float64), np.asarray(ref, np.float64)
    return float(np.abs(y - ref).max() / max(np.abs(ref).max(), 1e-30))


def sample(window: Window, n: int, seed: int) -> list:
    """Up to ``n`` answered requests, drawn from the seed."""
    ok = window.ok
    idx = traffic.seed_rng(seed, 2).choice(len(ok), min(n, len(ok)),
                                           replace=False)
    return [ok[i] for i in sorted(idx)]


def compare(ref, window: Window, picked: list, *,
            control: bool = False) -> float:
    """Widest ``rel_err`` of the picked answers against the reference.
    With ``control`` the reference at one step less precision stands in
    for the program's answers."""
    if not picked:
        return math.inf
    images = sorted({r.image for r in picked})
    want = dict(zip(images, ref.logits(window.pool[images], "highest")))
    if control:
        got = ref.logits(window.pool[images], "high")
        return max(rel_err(g, want[i]) for i, g in zip(images, got))
    return max(rel_err(r.logits, want[r.image]) for r in picked)


def checks(cfg: dict, mx: dict, ref, window: Window, seed: int) -> dict:
    """Each number compared, with its limit."""
    picked = sample(window, mx["check_sample"], seed)
    lost = sum(r.code not in ANSWERED for r in window.records)
    return {"logit_err": {"value": compare(ref, window, picked),
                          "limit": cfg["limits"]["logit_err"]},
            "unanswered": {"value": lost, "limit": 0}}


def passed(numbers: dict) -> bool:
    return all(n["limit"] is not None and math.isfinite(n["value"])
               and n["value"] <= n["limit"] for n in numbers.values())


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

def enable_compile_cache() -> None:
    """JAX's persistent cache at a fixed path inside the checkout, every
    program in it (small eager ones too), with no size bound: eviction
    would read an access-time file that entries written without a bound
    lack, and every later write would fail."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run(cell: str, seed: int, seconds: float, trace: bool, *, t_start: float,
        device: dict, spec: dict | None = None, cfg: dict | None = None,
        mx: dict | None = None, interpret=None, log=print) -> dict:
    """One run of ``cell``; returns the result object.  ``cfg`` and ``mx``
    stand in for the cell's own files (tests run small ones)."""
    import jax
    spec = spec or benchmark()
    wl = workload(spec, cell)
    cfg = cfg or config(spec, wl["config"])
    mx = mx or mix(wl["traffic"])
    counter = _Counter()
    setup = build(cfg, mx, interpret=interpret)
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s (plan build {setup.plan_build_s:.3f} s, "
        f"warm-up {setup.warmup_s:.3f} s)")
    win = serve(setup, mx, seed, seconds, trace=trace, counter=counter)
    log(f"window {win.seconds:.3f} s: {len(win.records)} requests, "
        f"{len(win.ok)} ok, {win.compiles} compiles inside it")
    stats = setup.server.stats()
    dev = jax.local_devices()[0]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    device = {**device, "memory_peak_bytes": peak}

    units = {m["name"]: m["unit"] for m in spec["end_to_end"]
             + spec["per_layer"]}
    if trace:
        ctx = {"cfg": cfg, "mix": mx, "stats": stats,
               "plan_build_s": setup.plan_build_s,
               "warmup_s": setup.warmup_s, "images": len(win.ok),
               "window_s": win.seconds, "batches": win.batches,
               "trace": win.trace, "peaks": work.peaks_for(device["kind"])}
        values = {m["name"]: metric_reader(m["name"])(ctx)
                  for m in per_layer_metrics(spec, cell)}
        device.update(busy_s=win.trace["busy_s"],
                      window_s=win.trace["window_s"])
    else:
        lat = np.array([r.latency_s for r in win.ok]) * 1e3
        values = {"setup_s": setup_s,
                  "latency_ms_p50": float(np.percentile(lat, 50)),
                  "latency_ms_p95": float(np.percentile(lat, 95)),
                  "images_per_s": len(win.ok) / win.seconds}
        values = {m["name"]: values[m["name"]]
                  for m in end_to_end_metrics(spec, cell)}
    metrics = {k: {"value": v, "unit": units[k]}
               for k, v in values.items() if v is not None}

    # the reference runs once the program's state is gone
    params = setup.params
    del setup, stats
    gc.unfreeze()
    gc.collect()
    t = time.perf_counter()
    ref = reference_module(cfg).Reference(cfg, params)
    numbers = checks(cfg, mx, ref, win, seed)
    log(f"check took {time.perf_counter() - t:.3f} s")
    out = {"correct": passed(numbers), "attempted": len(win.records),
           "failed": len(win.records) - len(win.ok), "metrics": metrics,
           "device": device}
    if trace:
        out["breakdown"] = {k: win.trace[k]
                            for k in ("device_ops", "idle_gaps")}
    out["checks"] = numbers
    return out
