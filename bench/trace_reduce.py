"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

What a TPU trace holds, as read by ``jax.profiler.ProfileData``:

- one plane per chip, ``/device:TPU:<n>``, with a line ``XLA Modules``
  (one event per executable run, named ``jit_<function>(<hash>)``, with a
  ``run_id`` stat) and a line ``XLA Ops`` (one event per HLO operation);
- the plane ``/host:CPU``, one line per host thread, where the
  benchmark's own ``jax.profiler.TraceAnnotation`` spans appear by name
  and the runtime's ``DoEnqueueProgram`` events carry the ``run_id`` of
  the executable they hand to the chip.

The device clock is offset from the host's by up to a few milliseconds.
The offset is taken from the run ids: no executable starts on the device
before the host enqueued it, so device times are shifted by the least
(device start - host enqueue) over all runs.

Busy time is the union of the ``XLA Ops`` intervals inside the window,
which is the benchmark's span ``bench.window``; an idle gap is a stretch
of the window that no op covers, and it is charged to the innermost
benchmark span (``bench.*``) around its midpoint.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
_HASH = re.compile(r"\(\d+\)$")


def module_name(event_name: str) -> str:
    """``jit__fused_conv(1234)`` -> ``jit__fused_conv``."""
    return _HASH.sub("", event_name)


def union_s(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``(start, end)`` intervals clipped to
    ``[lo, hi]``."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The stretches of ``[lo, hi]`` that no interval covers."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def charge_gaps(gap_list, spans) -> dict[str, float]:
    """Seconds of idle gap per host span name: each gap goes to the
    shortest span ``(name, start, end)`` that holds its midpoint, or to
    ``(no span)``."""
    out: dict[str, float] = defaultdict(float)
    for s, e in gap_list:
        mid = (s + e) / 2
        holders = [(se - ss, name) for name, ss, se in spans
                   if ss <= mid <= se]
        out[min(holders)[1] if holders else "(no span)"] += e - s
    return dict(out)


def read_xplane(path: str):
    """Plain lists from one trace file: ``devices`` maps a device plane's
    name to ``{"ops": [(s, e)], "modules": [(name, s, e, run_id)]}``;
    ``spans`` lists the benchmark's host spans ``(name, s, e)``;
    ``enqueues`` maps a run id to its host enqueue time.  Times in ns."""
    from jax.profiler import ProfileData
    return read_profile(ProfileData.from_file(path))


def read_profile(pd):
    devices, spans, enqueues = {}, [], {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev["ops"] = [(e.start_ns, e.start_ns + e.duration_ns)
                                  for e in line.events]
                elif line.name == "XLA Modules":
                    for e in line.events:
                        run = dict(e.stats).get("run_id")
                        dev["modules"].append(
                            (module_name(e.name), e.start_ns,
                             e.start_ns + e.duration_ns, run))
            devices[plane.name] = dev
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
                    elif e.name == "DoEnqueueProgram":
                        run = dict(e.stats).get("run_id")
                        if run is not None:
                            enqueues.setdefault(run, e.start_ns)
    return devices, spans, enqueues


def clock_shift_ns(devices, enqueues) -> float:
    """What to add to device times so that no run starts before its
    enqueue (0 when no run id matches or none would)."""
    lead = [s - enqueues[r] for d in devices.values()
            for _, s, _, r in d["modules"] if r in enqueues]
    return max(0.0, -min(lead)) if lead else 0.0


def reduce(devices, spans, enqueues, *, top: int = 10) -> dict:
    """The traced window's numbers.  Seconds throughout:

    - ``window_s``: length of the ``bench.window`` span;
    - ``busy_s``: op-union time inside it, averaged over the chips;
    - ``module_s``: device time per executable name, summed over chips;
    - ``device_ops``: the ``top`` executables by device time;
    - ``idle_gaps``: idle time of chip 0 per host span, largest first.
    """
    windows = [(s, e) for name, s, e in spans if name == WINDOW_SPAN]
    if not windows or not devices:
        raise ValueError("trace holds no bench.window span or no TPU plane")
    lo, hi = windows[0]
    shift = clock_shift_ns(devices, enqueues)
    busy, module_s = [], defaultdict(float)
    for dev in devices.values():
        ops = [(s + shift, e + shift) for s, e in dev["ops"]]
        busy.append(union_s(ops, lo, hi))
        for name, s, e, _ in dev["modules"]:
            s, e = max(s + shift, lo), min(e + shift, hi)
            if e > s:
                module_s[name] += (e - s) * 1e-9
    first = devices[min(devices)]
    idle = charge_gaps(
        gaps([(s + shift, e + shift) for s, e in first["ops"]], lo, hi),
        [sp for sp in spans if sp[0] != WINDOW_SPAN])
    by_time = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return {"window_s": (hi - lo) * 1e-9,
            "busy_s": sum(busy) / len(busy) * 1e-9,
            "n_devices": len(devices),
            "clock_shift_s": shift * 1e-9,
            "module_s": dict(module_s),
            "device_ops": [[k, v] for k, v in by_time(module_s)],
            "idle_gaps": [[k, v * 1e-9] for k, v in by_time(idle)]}


def reduce_dir(log_dir: str) -> dict:
    """Reduce the one ``.xplane.pb`` that a ``jax.profiler`` session wrote
    under ``log_dir``."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {log_dir}, "
                         f"found {len(paths)}")
    return reduce(*read_xplane(paths[0]))
