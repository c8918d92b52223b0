#!/usr/bin/env python3
"""One cell, with the program's own spans reduced: where its idle time,
its conv time and its plan build go.

    python3 bench/program_run.py --workload <name> --seed <n> --seconds <s>

Builds the cell once as ``bench/run.py`` does, then offers its traffic
for two windows of ``--seconds`` with the same seed: the first untraced,
the second under the profiler.  The traced window's trace is reduced by
``bench/trace_reduce.py`` (the benchmark's own numbers) and by
``bench/program_trace.py`` (idle by program span, device time by graph
node), the latter under ``trace["program"]``: ``harness.serve`` removes
its trace directory once ``trace_reduce.reduce_dir`` has read it, so the
script wraps that function for the traced window.  The last line of
standard output is one JSON object: latency with tracing off and on, every
per-layer metric of the cell, the program's own numbers and the
breakdown.  Like ``bench/run.py`` it needs the chip and exits with code 3
without one.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def latency_ms(win) -> dict:
    lat = np.array([r.latency_s for r in win.ok]) * 1e3
    return {"p50": float(np.percentile(lat, 50)),
            "p95": float(np.percentile(lat, 95)),
            "images": len(win.ok), "window_s": win.seconds}


def with_program(reduce_dir):
    """``trace_reduce.reduce_dir`` that also holds the program's
    reduction of the same trace under ``"program"``."""
    from bench import program_trace

    def both(log_dir):
        out = reduce_dir(log_dir)
        out["program"] = program_trace.reduce_dir(log_dir)
        return out
    return both


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness, program_trace, run, trace_reduce, work
    spec = harness.benchmark()
    cell = harness.workload(spec, args.workload)
    device = run.find_chips(cell["chips"])
    if device is None:
        print(f"[program-run] {args.workload} needs {cell['chips']} TPU "
              f"chip(s); not falling back", file=sys.stderr)
        return run.NO_CHIP
    harness.enable_compile_cache()
    log = run.log_to_stderr(device)
    cfg = harness.config(spec, cell["config"])
    mx = harness.mix(cell["traffic"])
    counter = harness._Counter()
    setup = harness.build(cfg, mx)
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s:.3f} s (plan build {setup.plan_build_s:.3f} s)")
    off = harness.serve(setup, mx, args.seed, args.seconds, counter=counter)
    trace_reduce.reduce_dir = with_program(trace_reduce.reduce_dir)
    on = harness.serve(setup, mx, args.seed, args.seconds, trace=True,
                       counter=counter)
    stats = setup.server.stats()
    ctx = {"cfg": cfg, "mix": mx, "stats": stats,
           "plan_build_s": setup.plan_build_s, "warmup_s": setup.warmup_s,
           "images": len(on.ok), "window_s": on.seconds,
           "batches": on.batches, "trace": on.trace,
           "peaks": work.peaks_for(device["kind"])}
    metrics = {m["name"]: harness.metric_reader(m["name"])(ctx)
               for m in harness.per_layer_metrics(spec, args.workload)}
    for name in ("idle_in_forward_share", "idle_in_serve_share",
                 "fused_conv_model_x"):
        metrics[name] = getattr(program_trace, name)(ctx)
    prog = on.trace["program"]
    kernel = on.trace["module_s"].get(f"jit_{program_trace.KERNEL}", 0.0)
    owned = sum(program_trace.node_kernel_s(n)
                for n in prog["nodes"].values())
    out = {"workload": args.workload, "seed": args.seed, "device": device,
           "setup_s": setup_s, "compiles": counter.n,
           "latency_ms": {"off": latency_ms(off), "on": latency_ms(on)},
           "metrics": metrics,
           "plan_phase_s": stats["plan_phase_s"],
           "kernel_s": {"module": kernel, "owned_by_nodes": owned},
           "idle_by_span": prog["idle_by_span"],
           "unowned_s": prog["unowned_s"],
           "breakdown": program_trace.breakdown(ctx, top=32),
           "device_ops": on.trace["device_ops"],
           "idle_gaps": on.trace["idle_gaps"]}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
