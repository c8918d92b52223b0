#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and metrics are found by name
from ``BENCHMARK.json`` (see ``bench/harness.py``).  The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1`` a
``breakdown``, and last ``checks``: each number compared with its limit,
which are also the last lines of standard error.

The run needs the chips the cell asks for: without a TPU, or with fewer
chips, it exits with code 3 and prints no result; it never falls back to
the CPU.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
NO_CHIP = 3


def log_to_stderr(device: dict):
    tag = f"[bench {device['platform']} '{device['kind']}' x{device['count']}]"
    return lambda msg: print(f"{tag} {msg}", file=sys.stderr, flush=True)


def find_chips(chips: int) -> dict | None:
    """The device record when JAX sees at least ``chips`` TPU chips, else
    None."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        return None
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    spec = harness.benchmark()
    cell = harness.workload(spec, args.workload)
    device = find_chips(cell["chips"])
    if device is None:
        import jax
        dev = jax.devices()[0]
        print(f"[bench] {args.workload} needs {cell['chips']} TPU chip(s); "
              f"JAX sees {len(jax.devices())} device(s), platform "
              f"{dev.platform!r}, kind {dev.device_kind!r}; not falling "
              f"back", file=sys.stderr)
        return NO_CHIP
    harness.enable_compile_cache()
    log = log_to_stderr(device)
    out = harness.run(args.workload, args.seed, args.seconds,
                      bool(args.trace), t_start=T_START, device=device,
                      spec=spec, log=log)
    for name, n in out["checks"].items():
        log(f"check {name} {n['value']!r} limit {n['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
