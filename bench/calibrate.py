#!/usr/bin/env python3
"""Readings that a cell's correctness limits are set from, in one process.

    python3 bench/calibrate.py --workload <name> [--seeds 12] [--control 4]
        [--seconds 4] [--out FILE]

Builds the cell's server once, then for each seed offers the cell's
traffic for ``--seconds`` (the cell's own load and sizes) and keeps the
answers.  Once every window has closed and the server is freed, it reads
each seed's ``logit_err`` as a run would (the lower reading is the
largest over the seeds), and for the first ``--control`` seeds the
control's: the reference at one step less precision ("high", three
bfloat16 passes) in the program's place (the upper reading is the
smallest).  The benchmark's own runs never run the control.  Prints one
JSON object per seed and a summary; ``--out`` also writes them there.
Needs the chips the cell asks for, like ``bench/run.py``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
SEED0 = 3_000_000_000       # seeds beyond 32 signed bits


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=4)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--control-only", action="store_true",
                    help="no server: the control's readings alone, on "
                         "images drawn from each seed")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    from bench.run import NO_CHIP, find_chips
    spec = harness.benchmark()
    cell = harness.workload(spec, args.workload)
    device = find_chips(cell["chips"])
    if device is None:
        print("[calibrate] no TPU with enough chips; not falling back",
              file=sys.stderr)
        return NO_CHIP
    harness.enable_compile_cache()
    cfg = harness.config(spec, cell["config"])
    mx = harness.mix(cell["traffic"])
    seeds = [SEED0 + 7919 * i for i in range(args.seeds)]
    if args.control_only:
        from bench import traffic
        shape = (cfg["layers"][0]["c_in"], cfg["image_size"],
                 cfg["image_size"])
        windows = []
        for seed in seeds:
            recs = [traffic.Record(i, i, 0.0, 0.0, "ok")
                    for i in range(mx["pool"])]
            windows.append(harness.Window(
                recs, 0.0, 1.0, traffic.image_pool(shape, mx["pool"], seed),
                {}, 0))
        served = None
        params = harness.reference_module(cfg).make_params(
            cfg, cfg["weights_seed"])
    else:
        setup = harness.build(cfg, mx)
        print(f"[calibrate] set-up {time.perf_counter() - T_START:.1f} s",
              flush=True)
        windows = [harness.serve(setup, mx, s, args.seconds) for s in seeds]
        served = setup.server.stats()["served_by_rung"]
        params = setup.params
        del setup
        gc.unfreeze()
        gc.collect()
    t = time.perf_counter()
    ref = harness.reference_module(cfg).Reference(cfg, params)
    ref_build_s = time.perf_counter() - t
    rows = []
    for i, (seed, win) in enumerate(zip(seeds, windows)):
        picked = harness.sample(win, mx["check_sample"], seed)
        t1 = time.perf_counter()
        row = {"seed": seed, "requests": len(win.records),
               "ok": len(win.ok), "sampled": len(picked)}
        if not args.control_only:
            row["logit_err"] = harness.compare(ref, win, picked)
            row["check_s"] = time.perf_counter() - t1
        if i < args.control:
            row["control_logit_err"] = harness.compare(ref, win, picked,
                                                       control=True)
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {
        "workload": args.workload, "device": device,
        "served_by_rung": served,
        "reference_build_s": ref_build_s,
        "lower": max((r["logit_err"] for r in rows if "logit_err" in r),
                     default=None),
        "upper": min(r["control_logit_err"] for r in rows
                     if "control_logit_err" in r),
        "limit_now": cfg["limits"]["logit_err"]}
    print(json.dumps(summary), flush=True)
    if args.out:
        Path(args.out).write_text(
            "\n".join(json.dumps(r) for r in rows + [summary]) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
