"""Reduce the program's own spans in a profiler trace: idle time by program
span, device time by graph node, and the numbers read from them.

The program writes ``serve.*`` and ``forward.*`` spans
(``repro.core.spans``) into the same trace as the benchmark's ``bench.*``
spans; ``bench/trace_reduce.py`` reads only the latter.  This module
reads the same ``.xplane.pb`` on the same clock
(``trace_reduce.clock_shift_ns``) and returns:

- ``idle_by_span``: chip 0's idle seconds inside ``bench.window``, each
  gap charged to the innermost program span that holds its midpoint, or
  to ``(outside program)``;
- ``nodes``: per graph node (the ``node`` arg of ``forward.node``): the
  device seconds and the count, by executable name, of the executions the
  node owns, clipped to the window as ``module_s`` is and summed over
  chips; the node's spans in the window (``calls``); and its span args.
  Nodes that share one compiled executable are told apart by run id.
- ``unowned_s``: device seconds, by executable name, of the executions
  no node owns (the FC head's, and any outside the walk).

An execution belongs to the walk span (``forward.node`` or
``forward.fc_head``) that dispatched it, found from the host's enqueue of
its run id (``DoEnqueueProgram``); see ``owners``.

The readers at the end take the harness's metric context with this
reduction under ``ctx["trace"]["program"]``, and return None where it is
absent or holds no program span.
"""

from __future__ import annotations

import bisect
import glob
import math
import os
from collections import defaultdict

from bench import trace_reduce as tr
from bench import work

PROGRAM_PREFIXES = ("serve.", "forward.")
NODE_SPAN = "forward.node"
WALK_SPANS = (NODE_SPAN, "forward.fc_head")
OUTSIDE = "(outside program)"
KERNEL = "_fused_conv"


def read_profile(pd):
    """``trace_reduce.read_profile``'s ``(devices, spans, enqueues)``, the
    program's host spans ``(name, start_ns, end_ns, args)``, and the set
    of run ids enqueued on the thread that walked the graph."""
    devices, spans, enqueues = tr.read_profile(pd)
    program, direct = [], set()
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            runs, walks = [], False
            for e in line.events:
                if e.name.startswith(PROGRAM_PREFIXES):
                    program.append((e.name, e.start_ns,
                                    e.start_ns + e.duration_ns,
                                    dict(e.stats)))
                    walks = walks or e.name == NODE_SPAN
                elif e.name == "DoEnqueueProgram":
                    runs.append(dict(e.stats).get("run_id"))
            if walks:
                direct.update(runs)
    return devices, spans, enqueues, program, direct


def charge_innermost(gap_list, spans) -> dict[str, float]:
    """Seconds of idle gap per span name: each gap goes to the innermost
    span ``(name, start, end, args)`` that holds its midpoint, or to
    ``OUTSIDE``.  The spans nest (one host thread opens them), so one
    sweep with a stack of the open spans finds it: what
    ``trace_reduce.charge_gaps`` does, for tens of thousands of spans."""
    spans = sorted(spans, key=lambda sp: (sp[1], -sp[2]))
    out: dict[str, float] = defaultdict(float)
    stack, i = [], 0
    for s, e in sorted(gap_list, key=lambda g: g[0] + g[1]):
        mid = (s + e) / 2
        while i < len(spans) and spans[i][1] <= mid:
            while stack and stack[-1][2] < spans[i][1]:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1][2] < mid:
            stack.pop()
        out[stack[-1][0] if stack else OUTSIDE] += e - s
    return dict(out)


def owners(enqueues: dict, walk: list, direct: set) -> dict:
    """Run id -> index into ``walk`` (the walk spans, sorted by start;
    one host thread opens them in turn, a ``forward.fc_head`` last in each
    forward pass) of the span that dispatched the run.

    A run enqueued on the walking thread (``direct``) was enqueued inside
    the call that dispatched it: the span holding the enqueue owns it.
    The runtime defers a run whose input is still being copied to the
    chip and enqueues it later from a task thread, often after its span
    closed: in 20 s traces on a TPU v5e the first conv of every image
    waited for the image's upload, and was enqueued after its own span
    in 59% of VGG16's images (49% inside the next node's span) and 30%
    of ResNet-18's.  A deferred run goes to
    the first node span of its forward pass, opened before the enqueue,
    that owns no run yet; if every one owns some, to the last span opened
    before the enqueue.  Runs enqueued before the first span are left
    out."""
    starts = [s for _, s, _, _ in walk]
    first, k = [], 0          # index of the first span of each pass
    for i, sp in enumerate(walk):
        first.append(k)
        if sp[0] != NODE_SPAN:
            k = i + 1
    out, deferred = {}, []
    for run, t in enqueues.items():
        i = bisect.bisect_right(starts, t) - 1
        if i < 0:
            continue
        if run in direct and t <= walk[i][2]:
            out[run] = i
        else:
            deferred.append((t, run, i))
    owned = set(out.values())
    for _, run, i in sorted(deferred):
        j = next((j for j in range(first[i], i + 1)
                  if walk[j][0] == NODE_SPAN and j not in owned), i)
        out[run] = j
        owned.add(j)
    return out


def reduce(devices, spans, enqueues, program, direct) -> dict:
    """The program's numbers in the traced window (seconds)."""
    windows = [(s, e) for name, s, e in spans if name == tr.WINDOW_SPAN]
    if not windows or not devices:
        raise ValueError("trace holds no bench.window span or no TPU plane")
    lo, hi = windows[0]
    shift = tr.clock_shift_ns(devices, enqueues)
    first = devices[min(devices)]
    idle = charge_innermost(
        tr.gaps([(s + shift, e + shift) for s, e in first["ops"]], lo, hi),
        program)

    walk = sorted((sp for sp in program if sp[0] in WALK_SPANS),
                  key=lambda sp: sp[1])
    owner = owners(enqueues, walk, direct)
    nodes: dict[str, dict] = {}
    for name, s, _, args in walk:
        if name != NODE_SPAN:
            continue
        node = nodes.setdefault(args["node"], {
            "args": args, "calls": 0, "module_s": defaultdict(float),
            "module_n": defaultdict(int)})
        if lo <= s < hi:
            node["calls"] += 1
    unowned = defaultdict(float)
    for dev in devices.values():
        for name, s, e, run in dev["modules"]:
            s, e = max(s + shift, lo), min(e + shift, hi)
            if e <= s:
                continue
            span = walk[owner[run]] if run in owner else None
            if span is not None and span[0] == NODE_SPAN:
                node = nodes[span[3]["node"]]
                node["module_s"][name] += (e - s) * 1e-9
                node["module_n"][name] += 1
            else:
                unowned[name] += (e - s) * 1e-9
    for node in nodes.values():
        node["module_s"] = dict(node["module_s"])
        node["module_n"] = dict(node["module_n"])
        node["device_s"] = sum(node["module_s"].values())
    return {"idle_by_span": {k: v * 1e-9 for k, v in idle.items()},
            "nodes": nodes,
            "unowned_s": dict(unowned)}


def read_xplane(path: str):
    from jax.profiler import ProfileData
    return read_profile(ProfileData.from_file(path))


def reduce_dir(log_dir: str) -> dict:
    """Reduce the one ``.xplane.pb`` under ``log_dir``."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {log_dir}, "
                         f"found {len(paths)}")
    return reduce(*read_xplane(paths[0]))


# ---------------------------------------------------------------------------
# Readers: a metric context in, one number (or None) out
# ---------------------------------------------------------------------------

def _program(ctx) -> dict | None:
    prog = (ctx.get("trace") or {}).get("program")
    if not prog or not (prog["nodes"] or set(prog["idle_by_span"])
                        - {OUTSIDE}):
        return None
    return prog


def _idle_share(ctx, charged) -> float | None:
    prog = _program(ctx)
    if prog is None or ctx["trace"]["window_s"] <= 0:
        return None
    idle = sum(v for k, v in prog["idle_by_span"].items() if charged(k))
    return 100.0 * idle / ctx["trace"]["window_s"]


def idle_in_forward_share(ctx) -> float | None:
    """Percent of the window in which the chip is idle while the host is
    in the forward walk: the innermost program span is ``forward.*`` or
    ``serve.forward`` itself (the walk between node spans)."""
    return _idle_share(ctx, lambda k: k.startswith("forward.")
                       or k == "serve.forward")


def idle_in_serve_share(ctx) -> float | None:
    """Percent of the window in which the chip is idle while the host is
    in the server around the walk: the innermost program span is a
    ``serve.*`` span other than ``serve.forward`` (take, upload, plan,
    stage_next, readback, finish, or ``serve.tick`` between them)."""
    return _idle_share(ctx, lambda k: k.startswith("serve.")
                       and k != "serve.forward")


def node_kernel_s(node: dict) -> float:
    """Device seconds of a node's fused conv executable."""
    return sum(v for k, v in node["module_s"].items() if KERNEL in k)


def kernel_us_per_call(node: dict) -> float | None:
    return (1e6 * node_kernel_s(node) / node["calls"] if node["calls"]
            else None)


def fused_conv_model_x(ctx) -> float | None:
    """Geometric mean over conv nodes of max(measured, predicted) /
    min(measured, predicted): measured is the node's fused-conv device
    time per call, predicted Alg 1's ``predicted_us``.  1.0 is a model
    that predicts every node; 2.0 misses by a factor of two on (the
    geometric) average, either way."""
    prog = _program(ctx)
    if prog is None:
        return None
    logs = []
    for node in prog["nodes"].values():
        pred = node["args"].get("predicted_us")
        meas = kernel_us_per_call(node)
        if pred and meas:
            logs.append(abs(math.log(meas / pred)))
    return math.exp(sum(logs) / len(logs)) if logs else None


def breakdown(ctx, *, top: int = 10) -> dict:
    """``program_idle``: the ``top`` program spans by idle seconds;
    ``nodes``: the ``top`` conv nodes by device ms per image, each with
    its least time per image (its entry of ``work.network_work``),
    roofline share and Alg 1's prediction against its measured kernel
    time per call."""
    prog = _program(ctx)
    if prog is None:
        return {}
    idle = sorted(prog["idle_by_span"].items(), key=lambda kv: -kv[1])
    images = ctx["images"] or 1
    least = {}
    for b, n in ctx["batches"].items():
        for w in work.network_work(ctx["cfg"], batch=b)["convs"]:
            least[w["name"]] = least.get(w["name"], 0.0) + n * (
                work.least_time_s(w["flops"], w["bytes"], ctx["peaks"])[0])
    rows = []
    for nid, node in prog["nodes"].items():
        if node["args"].get("kind") != "conv":
            continue
        rows.append([nid, {
            "ms_per_image": 1e3 * node["device_s"] / images,
            "least_ms_per_image": 1e3 * least[nid] / images,
            "roofline_pct": (100.0 * least[nid] / node["device_s"]
                             if node["device_s"] else None),
            "kernel_us_per_call": kernel_us_per_call(node),
            "predicted_us": node["args"].get("predicted_us"),
            "calls": node["calls"],
            **{k: node["args"].get(k) for k in (
                "hadamard", "flow", "input_mode", "residual", "backend")},
            "module_s": node["module_s"]}])
    rows.sort(key=lambda r: -r[1]["ms_per_image"])
    return {"program_idle": [[k, v] for k, v in idle[:top]],
            "nodes": rows[:top]}

