"""Serving benchmark for ``launch.spectral_serve`` — throughput, tail
latency and resilience counters under a 4x-capacity burst, plus the
chaos soak as a CI gate.

  PYTHONPATH=src python -m benchmarks.serve_bench [--quick] [--chaos]
      [--json BENCH_serve.json] [--merge-into BENCH_e2e.json]

Two sections:

  load   real-clock load benchmark: warm the plan cache + jit, run two
         steady waves, then slam a 4x-``queue_limit`` burst into the
         bounded queue and drain it.  Reports throughput (img/s) and
         p50/p95/p99 latency alongside the shed/demotion counters —
         the tail numbers the paper's single-stream latency claim has
         to survive.
  chaos  (``--chaos``) ``testing.faults.chaos_soak``: the deterministic
         fault-injected burst on a virtual clock (kernel faults,
         plan-cache corruption, slow-service windows, tight deadlines).
         Its gates — zero loop deaths, zero silent wrong answers,
         demotion AND promotion observed — fail this process nonzero.

BENCH_serve.json schema
-----------------------
  bench / backend / interpret_mode / model / quick     run metadata.
  load.requests / load.queue_limit / load.buckets      offered load.
  load.warm_s
      startup cost: plan builds for every bucket + one jit warm
      forward per bucket.  Paid once, BEFORE serving — the
      ``plan_cache_warm_only`` gate asserts no request ever triggered
      a plan build.
  load.stats
      the server's drained-run stats: terminal-outcome counters,
      throughput_img_s, latency_ms {mean, p50, p95, p99}, demotions /
      promotions, served_by_rung, loop_deaths.
  load.health
      final ``health_report()`` — ladder transitions with the pressure
      that drove them, breaker snapshots, plan-cache counters.
  chaos
      the full ``chaos_soak`` report (present with ``--chaos``).
  batch_sweep
      the per-bucket serving table: one plan per bucket, tuned AT that
      batch (``dataflow.INTERPRET_STEP_S`` priced in — calibrated to
      zero, see its comment), fused vs einsum wall clock at
      every bucket, and the GATING acceptance boolean
      ``fused_le_einsum_all_buckets``.  This graduated from the old
      ``known_gaps`` batch-8 entry (fused 92.9 ms vs einsum 81.3 ms
      when batch-8 buckets inherited batch-1 block choices — ROADMAP
      items 1+2, fixed by the batch-aware autotune + manual-DMA
      accumulators).
  gates / failed_gates
      pass/fail booleans; any False exits nonzero AFTER the report is
      written (CI blocks, artifact stays inspectable).

``--merge-into BENCH_e2e.json`` additionally folds a summary (load
stats + gate status + batch_sweep) into the e2e report under a
``serve`` key, atomically, so the serving columns live next to the
latency/traffic ones.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import jax


def load_bench(*, queue_limit: int = 16, seed: int = 0,
               quick: bool = False) -> dict:
    """Real-clock serving benchmark: steady waves, then a 4x-capacity
    burst into the bounded queue."""
    from repro.configs import vgg16_spectral
    from repro.launch import spectral_serve as ss

    cfg = vgg16_spectral.SMOKE
    t0 = time.perf_counter()
    srv = ss.SpectralServer(cfg, queue_limit=queue_limit, seed=seed,
                            warm_forward=True)
    warm_s = time.perf_counter() - t0
    print(f"      warm: {len(srv.buckets)} bucket plans + jit in "
          f"{warm_s:.1f}s")

    reqs: list = []

    def burst(n: int) -> None:
        wave = ss.synthetic_requests(n, cfg, seed=seed + len(reqs),
                                     rid0=len(reqs))
        for r in wave:
            srv.submit(r)
        reqs.extend(wave)

    steady = max(2, queue_limit // (4 if quick else 2))
    for _ in range(1 if quick else 2):
        burst(steady)
        srv.run_until_drained()
    burst(4 * queue_limit)
    srv.run_until_drained()

    stats = srv.stats()
    health = srv.health_report()
    cache = srv.plans.stats()
    gates = {
        "all_terminal": all(r.terminal for r in reqs),
        "zero_loop_deaths": stats["loop_deaths"] == 0,
        "shed_nonzero": stats["counters"]["overloaded"] > 0,
        "demotion_and_promotion": (stats["demotions"] >= 1
                                   and stats["promotions"] >= 1),
        "latency_reported": ("latency_ms" in stats
                             and "throughput_img_s" in stats),
        # every plan build happened during warm(), never on a request
        "plan_cache_warm_only": cache["builds"] == len(srv.buckets),
    }
    return {
        "requests": len(reqs),
        "queue_limit": queue_limit,
        "buckets": list(srv.buckets),
        "warm_s": warm_s,
        "stats": stats,
        "health": health,
        "gates": gates,
        "failed_gates": sorted(k for k, v in gates.items() if not v),
    }


def batch_sweep(*, buckets=(1, 2, 4, 8), seed: int = 0, iters: int = 3,
                quick: bool = False) -> dict:
    """GATING per-bucket sweep: one plan per serving bucket, tuned AT
    that batch (step overhead priced for the interpret backend), fused
    vs einsum wall clock.  The fused rung must beat or match the
    oracle it degrades to at EVERY bucket — otherwise the serving
    ladder's best rung would be slower than its own fallback."""
    from repro.configs import vgg16_spectral
    from repro.core import dataflow as df
    from repro.core.plan import build_network_plan
    from repro.models import cnn
    import jax.numpy as jnp

    cfg = vgg16_spectral.SMOKE
    key = jax.random.PRNGKey(seed)
    params = cnn.init(key, cfg)
    step_s = (df.INTERPRET_STEP_S if jax.default_backend() != "tpu"
              else 0.0)
    iters = 1 if quick else iters
    per_bucket = {}
    for b in buckets:
        plan = build_network_plan(params, cfg, batch=b,
                                  step_overhead_s=step_s)
        x = jax.random.normal(key, (b, 3, cfg.image_size,
                                    cfg.image_size), jnp.float32)

        def timed(backend):
            fn = lambda: cnn.forward_spectral(params, plan, x,
                                              backend=backend)
            jax.block_until_ready(fn())          # compile
            t0 = time.perf_counter()
            for _ in range(iters):
                out = fn()
            jax.block_until_ready(out)
            return 1e3 * (time.perf_counter() - t0) / iters

        fused_ms = timed("pallas_fused")
        einsum_ms = timed("einsum")
        per_bucket[f"batch{b}"] = {
            "fused_ms": fused_ms,
            "einsum_ms": einsum_ms,
            "fused_le_einsum": bool(fused_ms <= einsum_ms),
            "tuned_flows": sorted({lp.tuning.flow
                                   for lp in plan.layers}),
            "tuned_input_modes": sorted({lp.input_mode
                                         for lp in plan.layers}),
        }
    return {
        "buckets": list(buckets),
        "iters": iters,
        "step_overhead_s": step_s,
        "per_bucket": per_bucket,
        "fused_le_einsum_all_buckets": all(
            r["fused_le_einsum"] for r in per_bucket.values()),
    }


def _write_report_atomic(report: dict, path: str) -> None:
    """tmp + os.replace, same contract as benchmarks.e2e_latency."""
    dirname = os.path.dirname(os.path.abspath(path))
    fd, tmp = tempfile.mkstemp(dir=dirname, prefix=".bench_serve_",
                               suffix=".json.tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(report, f, indent=2)
            f.write("\n")
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def _merge_into_e2e(report: dict, path: str) -> None:
    """Fold the serve summary into BENCH_e2e.json under ``serve``."""
    with open(path) as f:
        e2e = json.load(f)
    load = report["load"]
    e2e["serve"] = {
        "requests": load["requests"],
        "queue_limit": load["queue_limit"],
        "buckets": load["buckets"],
        "warm_s": load["warm_s"],
        "throughput_img_s": load["stats"].get("throughput_img_s"),
        "latency_ms": load["stats"].get("latency_ms"),
        "counters": load["stats"]["counters"],
        "demotions": load["stats"]["demotions"],
        "promotions": load["stats"]["promotions"],
        "loop_deaths": load["stats"]["loop_deaths"],
        "chaos_failed_gates": report.get("chaos", {}).get(
            "failed_gates"),
        "failed_gates": report["failed_gates"],
        "batch_sweep": report["batch_sweep"],
    }
    _write_report_atomic(e2e, path)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default="BENCH_serve.json",
                    help="output path for the JSON report")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke path: smaller steady phase")
    ap.add_argument("--chaos", action="store_true",
                    help="also run the fault-injected chaos soak "
                    "(virtual clock, deterministic) and gate on it")
    ap.add_argument("--queue-limit", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--merge-into", default=None, metavar="E2E_JSON",
                    help="also fold a serve summary into this "
                    "BENCH_e2e.json (atomic rewrite)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    n_steps = 2 + bool(args.chaos)
    report: dict = {
        "bench": "serve_bench",
        "backend": jax.default_backend(),
        "interpret_mode": jax.default_backend() != "tpu",
        "model": "vgg16-spectral-smoke",
        "quick": bool(args.quick),
        "seed": args.seed,
    }

    print(f"[1/{n_steps}] load bench: steady waves + 4x-capacity burst "
          f"(queue_limit={args.queue_limit})")
    report["load"] = load_bench(queue_limit=args.queue_limit,
                                seed=args.seed, quick=args.quick)
    st = report["load"]["stats"]
    lm = st.get("latency_ms", {})
    print(f"      {report['load']['requests']} requests: "
          f"{st['counters']['ok']} ok / {st['counters']['overloaded']} "
          f"shed / {st['counters']['failed']} failed; "
          f"{st.get('throughput_img_s', float('nan')):.1f} img/s; "
          f"latency ms p50 {lm.get('p50', float('nan')):.1f} / p95 "
          f"{lm.get('p95', float('nan')):.1f} / p99 "
          f"{lm.get('p99', float('nan')):.1f}; "
          f"{st['demotions']} demotions, {st['promotions']} promotions,"
          f" {st['loop_deaths']} loop deaths")

    if args.chaos:
        print(f"[2/{n_steps}] chaos soak: fault-injected burst on a "
              "virtual clock")
        from repro.testing import faults
        report["chaos"] = faults.chaos_soak(
            queue_limit=args.queue_limit, seed=args.seed,
            log=lambda m: print(f"      {m}"))

    print(f"[{n_steps}/{n_steps}] batch sweep: per-bucket fused vs "
          f"einsum, batch-tuned plans (GATING)")
    report["batch_sweep"] = batch_sweep(seed=args.seed, quick=args.quick)
    for name, row in sorted(report["batch_sweep"]["per_bucket"].items()):
        mark = "<=" if row["fused_le_einsum"] else "> !!"
        print(f"      {name}: fused {row['fused_ms']:.1f} ms {mark} "
              f"einsum {row['einsum_ms']:.1f} ms "
              f"(flows {','.join(row['tuned_flows'])}; input "
              f"{','.join(row['tuned_input_modes'])})")

    failed = [f"load.{g}" for g in report["load"]["failed_gates"]]
    if "chaos" in report:
        failed += [f"chaos.{g}" for g in report["chaos"]["failed_gates"]]
    if not report["batch_sweep"]["fused_le_einsum_all_buckets"]:
        failed.append("batch_sweep.fused_le_einsum_all_buckets")
    report["gates"] = {
        "load": report["load"]["gates"],
        **({"chaos": report["chaos"]["gates"]} if "chaos" in report
           else {}),
        "batch_sweep": {
            "fused_le_einsum_all_buckets":
                report["batch_sweep"]["fused_le_einsum_all_buckets"]},
    }
    report["failed_gates"] = failed

    _write_report_atomic(report, args.json)
    print(f"wrote {args.json}")
    if args.merge_into:
        _merge_into_e2e(report, args.merge_into)
        print(f"merged serve summary into {args.merge_into}")

    if failed:
        print("[gates] FAILED:", file=sys.stderr)
        for name in failed:
            print(f"  - {name}", file=sys.stderr)
        sys.exit(1)
    print("[gates] all serving gates pass")


if __name__ == "__main__":
    main()
