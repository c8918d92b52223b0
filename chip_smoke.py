#!/usr/bin/env python3
"""Smoke run of spectral VGG16 on a TPU: the main path, once, at full size.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the sharded path on a 2x2 v5e host

One chip: ``SpectralServer(vgg16_spectral.CONFIG, buckets=(1,),
interpret=False, warm_forward=True)`` — 224x224 input, the published
VGG16 widths, alpha=4, K=8, random weights from seed 0 — serves
``N_REQUESTS`` images from ``synthetic_requests(seed=0)``.  The run fails
unless every request is served on the fused rung with no kernel fault,
demotion or loop death; every layer's probe passes (hardening the plan
returns the same layer objects); the jitted forward compiles to at least
one Mosaic kernel (``tpu_custom_call``) per conv node; and each
response's logits agree with the einsum oracle on the same plan within
``PARITY_TOL`` of max |logit|.  The oracle runs at HIGHEST matmul
precision (a TPU's default rounds f32 matmul inputs to bf16); the
served path runs as users run it.

Four chips: the VGG16 plan at batch 1 is partitioned over a 4-device
mesh three ways (spatial forced, Alg 1's choice, channel forced) and
each sharded forward is compared with the one-chip fused forward within
``SHARD_TOL``.  Each channel-sharded kernel must keep one slice on each
device, and every device must hold data.  The four forwards are jitted
with the plans' arrays as arguments and compiled concurrently.  The
plans are built without Alg-2 schedules (plane Hadamard), which keeps
host plan building under a minute.  At batch 1 Alg 1 streams planes on
the one chip too (the in-kernel table expansion costs more than the
planes' bytes); ``tests/test_tpu_compile.py`` compiles the scheduled
datapath for the chip.

Everything runs in this one process.  Without a TPU the script exits 1
and prints no result: it never falls back to the CPU.  The seconds it
prints are set-up figures of a smoke run, not a benchmark.  The last
line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

N_REQUESTS = 8
PARITY_TOL = 1e-4     # fused vs einsum oracle, relative to max |logit|
SHARD_TOL = 1e-5      # sharded vs one-chip fused forward, same measure
N_SHARDS = 4


class SmokeFailure(Exception):
    pass


def log(msg: str) -> None:
    print(f"[chip-smoke] {msg}", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    log(f"ok    {what}")


def plan_arrays(obj):
    """(arrays, rebuild): the device arrays held anywhere in a plan
    (dataclass, NamedTuple and tuple fields), and ``rebuild(arrays)``,
    the same plan around the arrays given.  A jitted forward that takes
    the arrays as arguments, with the plan's structure closed over,
    keeps ~0.6 GB of kernel operands out of the compiled program (and
    so within the compilation cache's entry size)."""
    import dataclasses

    import jax
    arrays = []

    def split(o):
        if isinstance(o, jax.Array):
            arrays.append(o)
            i = len(arrays) - 1
            return lambda a: a[i]
        if dataclasses.is_dataclass(o) and not isinstance(o, type):
            parts = {f.name: split(getattr(o, f.name))
                     for f in dataclasses.fields(o)}
            parts = {k: p for k, p in parts.items() if p}
            if not parts:
                return None
            return lambda a: dataclasses.replace(
                o, **{k: p(a) for k, p in parts.items()})
        if isinstance(o, tuple):
            parts = [split(v) for v in o]
            if not any(parts):
                return None
            make = type(o)._make if hasattr(o, "_fields") else type(o)
            return lambda a: make(p(a) if p else v
                                  for p, v in zip(parts, o))
        return None

    rebuild = split(obj) or (lambda a: obj)
    return arrays, rebuild


def rel_err(y, ref) -> float:
    import numpy as np
    y, ref = np.asarray(y, np.float64), np.asarray(ref, np.float64)
    return float(np.abs(y - ref).max() / max(np.abs(ref).max(), 1e-30))


def one_chip(cfg, *, interpret: bool = False) -> None:
    """Serve ``N_REQUESTS`` through the fused rung and check them."""
    import jax
    import numpy as np

    from repro.core import resilience as res
    from repro.launch import spectral_serve as ss
    from repro.models import cnn

    t0 = time.perf_counter()
    srv = ss.SpectralServer(cfg, buckets=(1,), interpret=interpret,
                            warm_forward=False)
    log(f"smoke set-up: plan build {time.perf_counter() - t0:.1f} s "
        f"({cfg.name}, batch 1)")
    plan = srv.plans.get(srv.params, cfg, 1, mesh_shape=srv.mesh_shape,
                         **srv.plan_kwargs)
    for lp in plan.layers:
        tn = lp.tuning
        log(f"  {lp.layer.name:10s} {lp.hadamard:9s} {lp.input_mode:8s} "
            f"{tn.flow:17s} n{tn.block_n} m{tn.block_m} p{tn.block_p} "
            f"vmem {tn.vmem_bytes / 2**20:.1f} MiB")

    x0 = jax.numpy.zeros((1,) + srv.image_shape, jax.numpy.float32)
    arrays, rebuild = plan_arrays(plan)
    fwd = jax.jit(lambda p, a, x: cnn.forward_spectral(
        p, rebuild(a), x, backend="pallas_fused", interpret=interpret))
    t0 = time.perf_counter()
    compiled = fwd.lower(srv.params, arrays, x0).compile()
    log(f"smoke set-up: compile {time.perf_counter() - t0:.1f} s "
        f"(jitted forward)")
    n_kernels = compiled.as_text().count("tpu_custom_call")
    n_conv = len(plan.layers)
    check(n_kernels >= n_conv,
          f"{n_kernels} tpu_custom_call in the compiled forward "
          f"(>= {n_conv} conv nodes)")

    t0 = time.perf_counter()
    srv.warm_forward()
    log(f"smoke set-up: first call {time.perf_counter() - t0:.1f} s "
        f"(server warm-up forward)")

    hardened = res.harden_network_plan(plan, interpret=interpret)
    check(all(a is b for a, b in zip(hardened.layers, plan.layers)),
          "harden_network_plan returns the same layer objects "
          "(every probe passed)")

    reqs = ss.synthetic_requests(N_REQUESTS, cfg, seed=0)
    for r in reqs:
        srv.submit(r)
    stats = srv.run_until_drained()
    health = srv.health_report()
    log(f"served_by_rung {stats['served_by_rung']}")
    check(stats["served_by_rung"]
          == {"fused": N_REQUESTS, "staged": 0, "einsum": 0},
          f"all {N_REQUESTS} requests served on the fused rung")
    check(stats["counters"]["ok"] == N_REQUESTS, "every request ok")
    check(stats["counters"]["kernel_faults"] == 0, "0 kernel faults")
    check(stats["demotions"] == 0, "0 load-ladder demotions")
    check(stats["loop_deaths"] == 0, "0 loop deaths")
    demoted = [name for b in health["plans"].values()
               for name in b["demoted_layers"]]
    check(not demoted, "no layer with demotion provenance")

    ref_fn = jax.jit(lambda p, a, x: cnn.forward_spectral(
        p, rebuild(a), x, backend="einsum"))
    # the oracle's einsums at full f32 (a TPU's default rounds matmul
    # inputs to bf16); the served path sets its own precision
    with jax.default_matmul_precision("highest"):
        errs = [rel_err(r.logits,
                        ref_fn(srv.params, arrays, r.image[None])[0])
                for r in reqs]
    log(f"parity vs einsum oracle: max |err| / max |logit| = "
        f"{max(errs):.3e} (per request {', '.join(f'{e:.2e}' for e in errs)})")
    check(max(errs) <= PARITY_TOL and np.isfinite(errs).all(),
          f"logits within {PARITY_TOL} of the einsum oracle")


def four_chips(cfg, *, interpret: bool = False) -> None:
    """Sharded forward on ``N_SHARDS`` devices vs the one-chip forward."""
    from concurrent.futures import ThreadPoolExecutor

    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.core.plan import build_network_plan, build_sharded_network_plan
    from repro.distributed.executor import forward_spectral_sharded
    from repro.launch import spectral_serve as ss
    from repro.launch.mesh import make_spectral_mesh
    from repro.models import cnn

    devs = jax.devices()
    check(len(devs) >= N_SHARDS, f"{len(devs)} devices >= {N_SHARDS}")
    mesh = make_spectral_mesh(N_SHARDS)
    on_mesh = frozenset(devs[:N_SHARDS])
    check(set(mesh.devices.flat) == on_mesh,
          f"the spectral mesh spans {N_SHARDS} distinct devices")
    kw = dict(batch=1, schedule=False)
    params = cnn.init(jax.random.PRNGKey(0), cfg)
    x = jax.numpy.asarray(ss.synthetic_requests(1, cfg, seed=0)[0].image[None])
    t0 = time.perf_counter()
    base = build_network_plan(params, cfg, **kw)
    splans = {name: build_sharded_network_plan(
                  params, cfg, n_shards=N_SHARDS, strategies=strategies,
                  base=base, mesh=mesh, **kw)
              for name, strategies in (("spatial", ("spatial",)),
                                       ("auto", None),
                                       ("channel", ("channel",)))}
    log(f"smoke set-up: plan build {time.perf_counter() - t0:.1f} s "
        f"(base + 3 partitionings)")

    # Every forward is one jitted program with the plan's arrays as
    # arguments.  A sharded program's arguments all live on the mesh:
    # the channel kernels where the plan placed them, the rest
    # replicated (one copy per array, however many plans share it).
    rep = NamedSharding(mesh, PartitionSpec())
    copies = {}

    def replicated(a):
        if a.sharding.device_set == on_mesh:
            return a
        if id(a) not in copies:           # holding a keeps its id unique
            copies[id(a)] = (a, jax.device_put(a, rep))
        return copies[id(a)][1]

    arrays, rebuild = plan_arrays(base)
    jobs = {"one-chip": (jax.jit(
        lambda p, a, x, rebuild=rebuild: cnn.forward_spectral(
            p, rebuild(a), x, backend="pallas_fused", interpret=interpret)),
        (params, arrays, x))}
    for name, splan in splans.items():
        arrays, rebuild = plan_arrays(splan)
        jobs[name] = (
            jax.jit(lambda p, a, x, rebuild=rebuild:
                    forward_spectral_sharded(p, rebuild(a), x, mesh=mesh,
                                             interpret=interpret)),
            jax.tree.map(replicated, (params, arrays, x)))
    # compiles release the GIL: the four programs compile side by side
    t0 = time.perf_counter()
    with ThreadPoolExecutor(len(jobs)) as pool:
        futures = {name: pool.submit(lambda f, a: f.lower(*a).compile(),
                                     *job)
                   for name, job in jobs.items()}
        compiled = {name: f.result() for name, f in futures.items()}
    log(f"smoke set-up: compile {time.perf_counter() - t0:.1f} s "
        f"({len(jobs)} jitted forwards, concurrently)")

    t0 = time.perf_counter()
    ref = jax.block_until_ready(compiled["one-chip"](*jobs["one-chip"][1]))
    log(f"one-chip: first call {time.perf_counter() - t0:.1f} s")
    for name, splan in splans.items():
        picked = {}
        for s in splan.strategies.values():
            picked[s] = picked.get(s, 0) + 1
        if not interpret:
            n_kernels = compiled[name].as_text().count("tpu_custom_call")
            check(n_kernels >= len(splan.layers),
                  f"{name}: {n_kernels} tpu_custom_call in the compiled "
                  f"sharded forward (>= {len(splan.layers)} conv nodes)")
        t0 = time.perf_counter()
        y = jax.block_until_ready(compiled[name](*jobs[name][1]))
        log(f"{name}: strategies {picked}; first call "
            f"{time.perf_counter() - t0:.3f} s")
        # a channel-sharded layer keeps one slice of its kernel on each
        # device: shard d of the stacked operands on the mesh's device d
        slices = [a for slp in splan.layers for a in slp.operands]
        n_channel = sum(slp.strategy == "channel" for slp in splan.layers)
        check(all([s.device for s in a.addressable_shards]
                  == list(mesh.devices.flat)
                  and all(s.data.shape[0] == 1 for s in a.addressable_shards)
                  for a in slices) and (n_channel > 0 or name != "channel"),
              f"{name}: {n_channel} channel layers, each with one kernel "
              f"slice on each of {N_SHARDS} devices")
        in_use = [(d.memory_stats() or {}).get("bytes_in_use")
                  for d in devs[:N_SHARDS]]
        log(f"{name}: bytes_in_use per device {in_use}; output sharding "
            f"{y.sharding}")
        if not interpret:
            check(all(in_use), f"{name}: all {N_SHARDS} devices hold data")
        check(len(y.sharding.device_set) == N_SHARDS,
              f"{name}: output spans {N_SHARDS} devices")
        err = rel_err(y, ref)
        log(f"{name}: parity vs one-chip fused forward = {err:.3e}")
        check(err <= SHARD_TOL, f"{name}: sharded logits within {SHARD_TOL}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded path on a 4-chip host")
    args = ap.parse_args(argv)
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"[chip-smoke] FAILED: the repository's sources are not "
              f"next to this script ({e})", file=sys.stderr)
        return 1
    cache = enable_compile_cache()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"[chip-smoke] FAILED: needs a TPU, but JAX's first device "
              f"is on platform {dev.platform!r}; not falling back",
              file=sys.stderr)
        return 1
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log(f"device_kind {dev.device_kind!r}, {device['count']} device(s); "
        f"compile cache {cache}")

    from repro.configs import vgg16_spectral
    try:
        (four_chips if args.four_chips else one_chip)(vgg16_spectral.CONFIG)
    except SmokeFailure as e:
        print(f"[chip-smoke] FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
