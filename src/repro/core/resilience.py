"""Resilient execution layer: validated plans, tiered degradation, guards.

Everything aggressive in the fused path — element-offset halo blocks,
scheduled one-hot Hadamard, the in-kernel epilogue — is validated in
interpret mode but flagged as unverified on real Mosaic (ROADMAP item 6).
Until this module existed, any lowering failure, VMEM overflow or
corrupted Alg-2 table surfaced as a raw Pallas traceback or, worse, a
silently wrong output.  The paper's own framing (and SPEC2's fixed-point
parity gates) is that a compressed/scheduled datapath earns its speedups
only if it is *provably equivalent* to the reference — so the execution
layer needs a principled failure model:

  1. **Plan validation** (build time).  ``validate_plan`` runs structured
     invariant checks over a ``core.plan.NetworkPlan`` — VMEM budget vs
     the chosen blocks, Alg-2 INDEX/VALUE table bounds and dtypes, halo
     block starts within the raw image, manual-DMA accumulator
     geometry (tile bounds / revisit order / slot budget) —
     and raises ``PlanValidationError`` with per-layer diagnostics
     instead of a bare ``ValueError`` or a kernel-launch-time assert.

  2. **Tiered graceful degradation** (plan hardening).
     ``harden_network_plan`` probes each layer's chosen kernel variant
     (compile + one forward on zeros) and, on failure, demotes the layer
     one rung at a time along the explicit ladder

         input_mode   halo      -> windowed
         hadamard     scheduled -> dense (plane datapath)
         backend      fused     -> staged -> einsum

     re-pricing the tuning via ``dataflow.tpu_fused_flow_cost`` /
     ``tpu_flow_cost`` so the recorded cost stays honest.  Every
     demotion is recorded in ``LayerPlan.provenance`` and surfaced via
     ``NetworkPlan.health_report()``.  Every rung lands on a datapath
     that is numerically equivalent to the one it replaces (windowed ==
     halo bit-for-bit; plane == scheduled to float tolerance; staged /
     einsum are the standing oracles), so a demoted plan stays inside
     the existing parity gates.

     The backend axis is also exposed in isolation
     (``BACKEND_RUNGS`` / ``demote_layer_backend`` /
     ``plan_at_backend_rung``) together with a per-backend
     ``CircuitBreaker`` — the rungs the serving front end
     (``launch.spectral_serve``) trades under load rather than faults.

  3. **Runtime numeric guards** (opt-in).  ``NumericGuards`` adds a
     per-layer NaN/Inf scan and a sampled-channel parity self-check
     against the einsum oracle to ``models.cnn.forward_spectral``, with
     a configurable policy: ``raise`` (``NumericGuardError``),
     ``demote`` (recompute the offending layer through the oracle and
     continue) or ``warn``.

  4. **Deterministic fault injection** (testing).  The module hosts the
     low-level fault registry (``install_fault`` / ``fault_check`` /
     ``fault_corrupt``) that ``repro.testing.faults`` drives, so tests
     exercise *every* edge of the degradation ladder without real
     hardware.  The hooks are no-ops (one truthiness check) when no
     fault is installed.

Import discipline: this module imports only leaf ``core`` modules
(``dataflow`` / ``sparse`` / ``spectral``); kernels, models and
``core.plan`` import *it*, and the probe/execute helpers import them
lazily.
"""

from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Any, Callable, Sequence

import jax.numpy as jnp
import numpy as np

from repro.core import dataflow as df
from repro.core import sparse as sp
from repro.core import spectral as spec


# ---------------------------------------------------------------------------
# Error taxonomy
# ---------------------------------------------------------------------------

class ResilienceError(Exception):
    """Base of the structured failure taxonomy.

    Carries the failing ``layer`` (or None for network-level failures),
    the ``site`` that detected the problem, and a list of per-layer
    ``Diagnostic`` records — so callers never have to parse a raw
    traceback to find out *which* layer, *which* invariant.
    """

    def __init__(self, message: str, *, layer: str | None = None,
                 site: str | None = None,
                 diagnostics: Sequence["Diagnostic"] = ()):
        self.layer = layer
        self.site = site
        self.diagnostics = tuple(diagnostics)
        if self.diagnostics:
            lines = [message] + [f"  - {d}" for d in self.diagnostics]
            message = "\n".join(lines)
        super().__init__(message)


class PlanValidationError(ResilienceError, ValueError):
    """A NetworkPlan/LayerPlan invariant is violated (build/validate
    time).  Subclasses ``ValueError`` so pre-taxonomy callers that
    caught the bare error keep working."""


class KernelLoweringError(ResilienceError, NotImplementedError):
    """The chosen kernel variant cannot compile/lower/execute (VMEM
    overflow, Mosaic lowering failure, unsupported grid shape...).
    Subclasses ``NotImplementedError`` for back-compat with the
    pre-PR-8 hardware-safety guard, which raised that type."""


class NumericGuardError(ResilienceError, ValueError):
    """A runtime numeric guard tripped: non-finite activations or a
    sampled parity check against the einsum oracle out of tolerance."""


@dataclasses.dataclass(frozen=True)
class Diagnostic:
    """One failed (or advisory) invariant check for one layer."""

    layer: str
    check: str               # e.g. 'tables/idx-bounds', 'vmem-budget'
    message: str
    severity: str = "error"  # 'error' | 'warn'

    def __str__(self) -> str:
        return f"[{self.layer}] {self.check} ({self.severity}): " \
               f"{self.message}"


# ---------------------------------------------------------------------------
# Fault-injection registry (driven by repro.testing.faults)
# ---------------------------------------------------------------------------

# Named sites production code consults.  Keep in sync with
# ``repro.testing.faults.FAULT_SITES``.  The ``serve_*`` sites live in
# the serving front end (``launch.spectral_serve``): a kernel fault
# mid-request, a corrupted plan fetched from the keyed plan cache, and
# injected per-batch slowness (deadline pressure).
# ``shard_tables`` is shard-scoped: its context carries the shard index
# (plus layer/strategy), so a fault can corrupt or fail ONE shard of a
# sharded plan.  It is consulted host-side — at shard-plan preparation
# and probing — never inside a shard_map body, where per-device python
# control flow does not exist (the body traces once for all devices).
FAULT_SITES = ("lowering", "vmem_overflow", "oob_index", "corrupt_value",
               "nan_activations", "shard_tables", "serve_kernel",
               "serve_plan_cache", "serve_slow")


@dataclasses.dataclass
class InjectedFault:
    """A deterministic fault installed at a named site.

    ``match`` restricts the fault to call sites whose context carries
    every listed key with an equal value (e.g. ``{"input_mode":
    "halo"}`` fails only halo-variant attempts, so a probe demoting to
    'windowed' succeeds — exactly one rung of the ladder).  ``exc`` is
    an exception *factory* for raise-sites; ``corrupt`` a value
    transform for corruption-sites.  ``fires`` counts activations so
    tests can assert the fault actually triggered.
    """

    site: str
    match: dict = dataclasses.field(default_factory=dict)
    exc: Callable[[], Exception] | None = None
    corrupt: Callable[[Any], Any] | None = None
    fires: int = 0

    def matches(self, ctx: dict) -> bool:
        return all(ctx.get(k) == v for k, v in self.match.items())


_FAULTS: list[InjectedFault] = []


def install_fault(fault: InjectedFault) -> None:
    if fault.site not in FAULT_SITES:
        raise ValueError(f"unknown fault site {fault.site!r}; "
                         f"must be one of {FAULT_SITES}")
    _FAULTS.append(fault)


def remove_fault(fault: InjectedFault) -> None:
    if fault in _FAULTS:
        _FAULTS.remove(fault)


def fault_check(site: str, **ctx) -> None:
    """Raise the injected exception if a matching fault is installed.

    Called by production code at named failure sites (kernel entry,
    staged dispatch...).  A no-op — one truthiness check — when no
    fault is active.
    """
    if not _FAULTS:
        return
    for f in _FAULTS:
        if f.site == site and f.exc is not None and f.matches(ctx):
            f.fires += 1
            raise f.exc()


def fault_corrupt(site: str, value, **ctx):
    """Return ``value`` passed through any matching corruption faults."""
    if not _FAULTS:
        return value
    for f in _FAULTS:
        if f.site == site and f.corrupt is not None and f.matches(ctx):
            f.fires += 1
            value = f.corrupt(value)
    return value


# ---------------------------------------------------------------------------
# (1) Plan validation
# ---------------------------------------------------------------------------

def validate_tables(tables, *, n_bins: int, r: int, c_out: int,
                    c_in: int, block_m: int, layer: str = "?"
                    ) -> list[Diagnostic]:
    """Bounds/dtype/shape invariants of one layer's Alg-2 tables.

    ``tables`` duck-types ``core.plan.PlanTables`` /
    ``scheduler.LayerTables`` (``idx``/``sel``/``vr``/``vi``).  Checks:

      * dtypes: idx/sel int32, vr/vi float32;
      * idx entries within [0, n_bins) — the compacted-coordinate
        gather addresses the kernel one-hots against;
      * sel entries within [0, r) — the crossbar replica columns;
      * sel/vr/vi share one shape, idx agrees on [GN, Mp, T];
      * GN * N' covers c_out and Mp equals c_in padded to block_m;
      * vr/vi finite (a NaN weight poisons every psum it touches).
    """
    out: list[Diagnostic] = []
    d = lambda check, msg: out.append(Diagnostic(layer, check, msg))

    idx = np.asarray(tables.idx)
    sel = np.asarray(tables.sel)
    vr = np.asarray(tables.vr)
    vi = np.asarray(tables.vi)
    for name, arr, want in (("idx", idx, np.int32), ("sel", sel, np.int32),
                            ("vr", vr, np.float32),
                            ("vi", vi, np.float32)):
        if arr.dtype != want:
            d(f"tables/{name}-dtype",
              f"{name} dtype {arr.dtype} != {np.dtype(want)}")
    if sel.shape != vr.shape or sel.shape != vi.shape:
        d("tables/shape", f"sel {sel.shape} / vr {vr.shape} / "
                          f"vi {vi.shape} shapes disagree")
    if idx.ndim != 4 or sel.ndim != 4 or idx.shape[:3] != sel.shape[:3]:
        d("tables/shape",
          f"idx {idx.shape} does not align with sel {sel.shape} "
          f"on [GN, Mp, T]")
        return out                       # downstream checks meaningless
    gn, mp, _, r_tab = idx.shape
    n_pe = sel.shape[3]
    if r_tab > r:
        d("tables/replicas", f"idx carries {r_tab} replica slots, "
                             f"schedule allows r={r}")
    if gn * n_pe < c_out:
        d("tables/groups", f"GN*N' = {gn}*{n_pe} covers only "
                           f"{gn * n_pe} kernels, layer has {c_out}")
    bm = min(block_m, c_in)
    mp_want = c_in + (-c_in) % max(1, bm)
    if mp != mp_want:
        d("tables/m-pad", f"channel padding Mp={mp} != {mp_want} "
                          f"(c_in={c_in} padded to block_m={bm}); the "
                          f"kernel blocks over mismatched channels")
    if idx.size and (idx.min() < 0 or idx.max() >= n_bins):
        d("tables/idx-bounds",
          f"INDEX entries outside [0, {n_bins}): min={idx.min()} "
          f"max={idx.max()} — an in-kernel gather against these "
          f"addresses reads unrelated spectra")
    if sel.size and (sel.min() < 0 or sel.max() >= max(1, r_tab)):
        d("tables/sel-bounds",
          f"sel entries outside [0, {r_tab}): min={sel.min()} "
          f"max={sel.max()}")
    if not (np.isfinite(vr).all() and np.isfinite(vi).all()):
        d("tables/value-finite", "non-finite entries in VALUE planes")
    return out


def _layer_cost(lp, batch: int) -> dict:
    """Re-price one layer's tuned config through the fused cost model."""
    tn = lp.tuning
    fa = lp.n_active_bins if lp.active is not None else None
    residual = None
    if getattr(lp.epilogue, "residual", None) == "fused":
        # keep pricing the fused shortcut operand the way the autotuner
        # placed it ('vmem' retained on-chip / 'hbm' re-read)
        residual = tn.residual or "hbm"
    return df.tpu_fused_flow_cost(
        lp.layer, lp.geo.fft_size, lp.alpha, tn.block_n, tn.block_p,
        tn.block_m, tn.flow, batch=batch, active_bins=fa,
        hadamard=lp.hadamard, input_mode=lp.input_mode,
        residual=residual)


def validate_layer_plan(lp, *, batch: int = 1,
                        vmem_budget: int = df.TPU_VMEM_BYTES,
                        hw_safe: bool = True) -> list[Diagnostic]:
    """Structured invariant checks for one ``core.plan.LayerPlan``.

    Returns a list of ``Diagnostic`` records (empty = healthy).
    Severity 'error' marks invariants whose violation makes the kernel
    wrong or un-launchable; 'warn' marks advisory findings (an
    over-budget VMEM working set still runs in interpret mode — the
    autotuner's documented smallest-footprint fallback — but will fail
    Mosaic compilation on hardware).
    """
    out: list[Diagnostic] = []
    name = lp.layer.name
    d = lambda check, msg, sev="error": out.append(
        Diagnostic(name, check, msg, sev))

    backend = getattr(lp, "backend", "fused")
    if backend not in df.EXEC_BACKENDS:
        d("modes/backend", f"backend {backend!r} not in "
                           f"{df.EXEC_BACKENDS}")
        return out
    if backend != "fused":
        return out          # staged/einsum consume only kernels+geo
    tn = lp.tuning
    if tn.flow not in df.FLOWS:
        d("modes/flow", f"flow {tn.flow!r} not in {df.FLOWS}")
        return out
    if lp.hadamard not in df.HADAMARD_MODES:
        d("modes/hadamard",
          f"hadamard {lp.hadamard!r} not in {df.HADAMARD_MODES}")
        return out
    if lp.input_mode not in df.INPUT_MODES:
        d("modes/input",
          f"input_mode {lp.input_mode!r} not in {df.INPUT_MODES}")
        return out

    # --- TPU (8, 128) tiling of the kernel's blocks -------------------
    for msg in df.tiling_violations(
            lp.layer, lp.geo.fft_size, tn.block_n, tn.block_m, tn.block_p,
            hadamard=lp.hadamard, input_mode=lp.input_mode, geo=lp.geo):
        d("tiling", f"{msg}; Mosaic refuses this block on a TPU")

    k2 = lp.geo.fft_size ** 2
    s2 = lp.geo.tile ** 2
    fa = lp.n_active_bins
    if lp.dfr.shape != (fa, k2) or lp.dvr.shape != (s2, fa):
        d("operators/shape",
          f"DFT operators dfr {lp.dfr.shape} / dvr {lp.dvr.shape} "
          f"do not match (Fa={fa}, S={k2}, S2={s2})")
    if lp.hadamard != "scheduled" and lp.wr.shape != (
            fa, lp.layer.c_out, lp.layer.c_in):
        d("operators/planes",
          f"kernel planes {lp.wr.shape} != "
          f"({fa}, {lp.layer.c_out}, {lp.layer.c_in})")
    bias = np.asarray(lp.bias)
    if bias.shape != (1, lp.layer.c_out):
        d("epilogue/bias-shape",
          f"bias {bias.shape} != (1, {lp.layer.c_out})")
    elif not np.isfinite(bias).all():
        d("epilogue/bias-finite", "non-finite bias entries")

    # --- VMEM budget vs the chosen blocks -----------------------------
    try:
        cost = _layer_cost(lp, batch)
        if cost["vmem_bytes"] > vmem_budget:
            d("vmem-budget",
              f"working set {cost['vmem_bytes'] / 2**20:.1f} MiB exceeds "
              f"budget {vmem_budget / 2**20:.1f} MiB at blocks "
              f"(n={tn.block_n}, m={tn.block_m}, p={tn.block_p}); "
              f"Mosaic compilation will fail on hardware", "warn")
    except Exception as e:          # cost model itself rejected the config
        d("vmem-budget", f"cost model rejected the tuned config: {e}")

    # --- Alg-2 tables -------------------------------------------------
    if lp.hadamard == "scheduled":
        if lp.tables is None:
            d("tables/missing", "hadamard='scheduled' but no tables "
                                "compiled into the plan")
        else:
            out.extend(validate_tables(
                lp.tables, n_bins=fa, r=df.SCHEDULE_R,
                c_out=lp.layer.c_out, c_in=lp.layer.c_in,
                block_m=tn.block_m, layer=name))

    # --- halo geometry: block starts within the raw image -------------
    t_total = lp.layer.tiles(lp.geo.fft_size) * batch
    if lp.input_mode == "halo":
        try:
            hg = spec.halo_block_geometry(lp.geo, tn.block_p)
            sh, sw = spec.halo_block_starts(lp.geo, hg)
            if (sh.size and (sh.min() < 0
                             or sh.max() + hg.rh > lp.geo.h_in)) or \
               (sw.size and (sw.min() < 0
                             or sw.max() + hg.rw > lp.geo.w_in)):
                d("halo/starts",
                  f"halo block starts leave the raw image: rows "
                  f"{sh.min()}..{sh.max()}+{hg.rh} vs H={lp.geo.h_in}, "
                  f"cols {sw.min()}..{sw.max()}+{hg.rw} vs "
                  f"W={lp.geo.w_in}")
            gr, gc = spec.halo_gather_matrices(lp.geo, hg)
            if (gr.sum(axis=2) > 1).any() or (gc.sum(axis=2) > 1).any():
                d("halo/gather-onehot",
                  "gather selector has a row with >1 non-zero — the "
                  "window 'gather' would sum raw pixels")
        except Exception as e:
            d("halo/geometry", f"halo geometry rejected block_p="
                               f"{tn.block_p}: {e}")

    # --- manual-DMA accumulator geometry (PR 8) -----------------------
    # The fused kernel streams psums through manually DMA'd VMEM tiles
    # (``kernels.fused_spectral_conv``), so any (flow, blocks, batch)
    # is legal on hardware; what a malformed batch-tuned plan can still
    # break is the accumulator geometry itself: destination tiles must
    # cover (and stay inside) the padded output, every destination must
    # see >= 1 m-revisit ending in the epilogue flush, and the staging
    # buffer must hold ``df.DMA_SLOTS`` slots.  ``hw_safe`` is accepted
    # for API compatibility; the checks below always run.
    del hw_safe
    if tn.block_n < 1 or tn.block_m < 1 or tn.block_p < 1:
        d("dma/tile-bounds",
          f"non-positive block sizes (n={tn.block_n}, m={tn.block_m}, "
          f"p={tn.block_p}) cannot address accumulator tiles")
    else:
        gn = -(-lp.layer.c_out // tn.block_n)
        gm = -(-lp.layer.c_in // tn.block_m)
        s2 = lp.geo.tile ** 2
        if lp.input_mode == "halo":
            try:
                hg = spec.halo_block_geometry(lp.geo, tn.block_p)
            except Exception:
                hg = None       # already diagnosed under halo/geometry
            if hg is not None:
                if (hg.nbh * hg.bth < lp.geo.n_tiles_h
                        or hg.nbw * hg.btw < lp.geo.n_tiles_w):
                    d("dma/tile-bounds",
                      f"halo block grid {hg.nbh}x{hg.nbw} of "
                      f"{hg.bth}x{hg.btw} tiles does not cover the "
                      f"{lp.geo.n_tiles_h}x{lp.geo.n_tiles_w} tile "
                      f"canvas — accumulator tiles would miss output")
                stage_elems = tn.block_n * (hg.bth * lp.geo.tile) \
                    * (hg.btw * lp.geo.tile)
            else:
                stage_elems = 0
        else:
            gp = -(-t_total // tn.block_p)
            if gp * tn.block_p < t_total:
                d("dma/tile-bounds",
                  f"{gp} p blocks of {tn.block_p} cover only "
                  f"{gp * tn.block_p} of {t_total} tile columns")
            stage_elems = s2 * tn.block_n * tn.block_p
        if gn * tn.block_n < lp.layer.c_out:
            d("dma/tile-bounds",
              f"{gn} n blocks of {tn.block_n} cover only "
              f"{gn * tn.block_n} of {lp.layer.c_out} output channels")
        if gm < 1:
            d("dma/revisit-order",
              f"m grid is empty ({gm} blocks of {tn.block_m} over "
              f"c_in={lp.layer.c_in}): no revisit ever flushes the "
              f"accumulator epilogue")
        if df.DMA_SLOTS < 2:
            d("dma/slot-count",
              f"DMA_SLOTS={df.DMA_SLOTS}: double-buffered accumulator "
              f"staging needs >= 2 slots")
        stage_bytes = df.DMA_SLOTS * stage_elems * 4
        if stage_bytes > vmem_budget:
            d("dma/slot-count",
              f"{df.DMA_SLOTS} accumulator slots stage "
              f"{stage_bytes / 2**20:.1f} MiB > VMEM budget "
              f"{vmem_budget / 2**20:.1f} MiB", "warn")

    if lp.pe_utilization is not None and not (
            0.0 < lp.pe_utilization <= 1.0):
        d("schedule/utilization",
          f"Eq-14 utilization {lp.pe_utilization} outside (0, 1]")
    return out


def validate_graph(plan) -> list[Diagnostic]:
    """DAG invariants of a ``core.plan.NetworkPlan`` (ISSUE 10).

    The stored graph must be topo-ordered with unique non-reserved ids,
    every edge (main + shortcut) resolving to an already-emitted node,
    conv nodes pointing at the layer that carries their name, shortcut
    shapes matching the node's post-stride output, and residual-FUSED
    epilogues only on stride-1 fused-backend layers (anything else must
    sit on the 'add' rung).
    """
    out: list[Diagnostic] = []
    graph = plan.execution_graph
    d = lambda layer, check, msg, sev="error": out.append(
        Diagnostic(layer, check, msg, sev))
    seen: set[str] = set()
    for node in graph:
        if node.id == "input" or node.id in seen:
            d(node.id, "graph/node-id",
              f"node id {node.id!r} is duplicated or reserved")
        refs = list(node.inputs)
        if node.residual_from is not None:
            refs.append(node.residual_from)
        for ref in refs:
            if ref != "input" and ref not in seen:
                d(node.id, "graph/order",
                  f"node {node.id!r} consumes {ref!r} before it is "
                  f"produced (unknown id, cycle, or bad topo order)")
        if node.kind == "conv":
            if not 0 <= node.layer_index < len(plan.layers):
                d(node.id, "graph/layer-index",
                  f"layer_index {node.layer_index} outside "
                  f"[0, {len(plan.layers)})")
            else:
                lp = plan.layers[node.layer_index]
                if lp.layer.name != node.id:
                    d(node.id, "graph/layer-index",
                      f"node {node.id!r} resolves to layer "
                      f"{lp.layer.name!r}")
                residual = getattr(lp.epilogue, "residual", None)
                stride = getattr(lp.layer, "stride", 1)
                if residual == "fused" and (
                        getattr(lp, "backend", "fused") != "fused"
                        or stride != 1):
                    d(node.id, "graph/residual-fused",
                      f"residual-fused epilogue on backend="
                      f"{getattr(lp, 'backend', 'fused')!r} stride="
                      f"{stride}: the in-kernel add needs the fused "
                      f"backend at stride 1 (demote to the "
                      f"residual-add rung)")
                if residual is not None and node.residual_from is None:
                    d(node.id, "graph/residual-fused",
                      f"epilogue residual={residual!r} but the node "
                      f"has no residual_from edge")
        seen.add(node.id)
    if not any(x.check == "graph/order" or x.check == "graph/layer-index"
               for x in out):
        from repro.core.plan import node_output_shapes
        try:
            node_output_shapes([lp.layer for lp in plan.layers], graph)
        except PlanValidationError as e:
            d(e.layer, e.site or "graph", str(e).splitlines()[0])
    return out


def validate_plan(plan, *, vmem_budget: int = df.TPU_VMEM_BYTES,
                  hw_safe: bool = True, raise_on_error: bool = True
                  ) -> list[Diagnostic]:
    """Validate every layer of a ``core.plan.NetworkPlan``, plus the
    DAG invariants of its execution graph (``validate_graph``).

    Returns all diagnostics (errors and warnings).  When
    ``raise_on_error`` (default), raises ``PlanValidationError``
    aggregating every *error*-severity diagnostic — at build time, not
    at kernel launch.
    """
    diags: list[Diagnostic] = []
    for lp in plan.layers:
        diags.extend(validate_layer_plan(
            lp, batch=plan.batch, vmem_budget=vmem_budget,
            hw_safe=hw_safe))
    diags.extend(validate_graph(plan))
    errors = [d for d in diags if d.severity == "error"]
    if errors and raise_on_error:
        raise PlanValidationError(
            f"plan {plan.name!r} failed validation "
            f"({len(errors)} error(s))",
            layer=errors[0].layer, site="validate_plan",
            diagnostics=errors)
    return diags


# ---------------------------------------------------------------------------
# Per-layer execution with a per-layer backend (the bottom ladder axis)
# ---------------------------------------------------------------------------

def _spatial_epilogue(y, lp, shortcut=None):
    """Bias -> (+shortcut) -> ReLU, the same ordering the fused
    kernel's epilogue flush uses."""
    if lp.epilogue.bias:
        y = y + lp.bias[0][None, :, None, None]
    if shortcut is not None:
        y = y + shortcut
    if lp.epilogue.relu:
        y = jnp.maximum(y, 0.0)
    return y


def execute_planned_layer(x, lp, *, interpret: bool | None = None,
                          shortcut=None):
    """Run one conv layer honoring ``LayerPlan.backend``.

    'fused' dispatches to ``kernels.fused_spectral_conv.
    execute_layer_plan`` (the plan's tuned variant); 'staged' runs the
    three-launch Pallas pipeline; 'einsum' the pure-jnp oracle — the
    ladder's terminal rung, which always executes.  Pooling stays with
    the caller.

    ``shortcut`` is the residual operand of a residual-fused DAG node
    (``EpilogueSpec.residual == 'fused'``): added after bias, before
    ReLU — inside the kernel flush on the fused backend, in the spatial
    epilogue otherwise.  On the 'add' rung the caller performs the add
    itself and must NOT pass a shortcut here.
    """
    backend = getattr(lp, "backend", "fused")
    if backend == "einsum":
        y = spec.spectral_conv2d_pretransformed(x, lp.kernels, lp.geo)
        return _spatial_epilogue(y, lp, shortcut)
    if backend == "staged":
        fault_check("lowering", layer=lp.layer.name, backend="staged")
        from repro.kernels import ops
        y = ops.spectral_conv2d_pallas(x, lp.kernels.values, lp.geo,
                                       interpret=interpret)
        return _spatial_epilogue(y, lp, shortcut)
    from repro.kernels.fused_spectral_conv import execute_layer_plan
    return execute_layer_plan(x, lp, interpret=interpret,
                              shortcut=shortcut)


# ---------------------------------------------------------------------------
# (2) Tiered graceful degradation
# ---------------------------------------------------------------------------

# The explicit demotion ladder, cheapest rung first.  Each entry is
# (axis, from, to); 'backend' rungs change which execution path runs
# the layer, the others stay on the fused kernel with a safer variant.
DEMOTION_LADDER = (
    ("input_mode", "halo", "windowed"),
    ("hadamard", "scheduled", "dense"),
    ("epilogue", "residual-fused", "residual-add"),
    ("backend", "fused", "staged"),
    ("backend", "staged", "einsum"),
)


def _summarize(err: BaseException) -> str:
    first = str(err).strip().splitlines()
    return f"{type(err).__name__}: {first[0] if first else ''}"


def _residual_add_fallback(lp):
    """Flip a residual-FUSED layer plan to the unfused-add rung: the
    kernel flushes bias-only output (in-kernel ReLU suppressed — it
    would clamp the pre-add activation) and the executor applies
    ``relu(y + shortcut)`` as a plain XLA add.  The tuning's shortcut
    placement is cleared so repricing stops charging fused-shortcut
    bytes."""
    import dataclasses as dc

    return dc.replace(
        lp,
        epilogue=dc.replace(lp.epilogue, residual="add", relu=False),
        tuning=dc.replace(lp.tuning, residual=None))


def _reprice_tuning(lp, batch: int):
    """Re-price one (possibly demoted) layer's tuning through the cost
    model so the recorded bytes/seconds stay honest for its current
    backend/modes."""
    import dataclasses as dc

    from repro.core.autotune import predict_seconds

    tn = lp.tuning
    if getattr(lp, "backend", "fused") == "fused":
        cost = _layer_cost(lp, batch)
        return dc.replace(tn, hbm_bytes=cost["hbm_bytes"],
                          vmem_bytes=cost["vmem_bytes"],
                          predicted_s=predict_seconds(cost),
                          hadamard=lp.hadamard,
                          input_mode=lp.input_mode)
    cost = df.tpu_flow_cost(lp.layer, lp.geo.fft_size, lp.alpha,
                            tn.block_n, tn.block_p, tn.block_m,
                            "output_stationary", batch=batch)
    return dc.replace(tn, hbm_bytes=cost["hbm_bytes"],
                      vmem_bytes=cost["vmem_bytes"],
                      predicted_s=predict_seconds(cost))


def demote_layer(lp, *, batch: int = 1, reason: BaseException | str = ""):
    """Demote one layer ONE rung down ``DEMOTION_LADDER``.

    Returns the demoted ``LayerPlan`` (tuning re-priced through the
    cost model so autotune's recorded numbers stay honest, demotion
    recorded in provenance), or None when the layer already sits on the
    terminal rung (einsum).
    """
    import dataclasses as dc

    note = _summarize(reason) if isinstance(reason, BaseException) \
        else str(reason)
    backend = getattr(lp, "backend", "fused")

    if backend == "fused" and lp.input_mode == "halo":
        new = dc.replace(lp, input_mode="windowed")
        rung = "input_mode halo->windowed"
    elif backend == "fused" and lp.hadamard == "scheduled":
        plane = "bin" if lp.active is not None else "dense"
        new = dc.replace(lp, hadamard=plane, tables=None)
        rung = f"hadamard scheduled->{plane}"
    elif backend == "fused" and \
            getattr(lp.epilogue, "residual", None) == "fused":
        new = _residual_add_fallback(lp)
        rung = "epilogue residual-fused->residual-add"
    elif backend == "fused":
        new = dc.replace(lp, backend="staged")
        rung = "backend fused->staged"
    elif backend == "staged":
        new = dc.replace(lp, backend="einsum")
        rung = "backend staged->einsum"
    else:
        return None

    tn = _reprice_tuning(new, batch)
    prov = getattr(lp, "provenance", ()) + (
        f"{rung} ({note})" if note else rung,)
    return dc.replace(new, tuning=tn, provenance=prov)


# The backend axis of the ladder in isolation — the rungs the serving
# front end (``launch.spectral_serve``) trades under load: each step
# swaps the whole execution path for a cheaper-to-trust one instead of
# a kernel variant (the input_mode/hadamard rungs stay with fault-driven
# hardening, where the *variant* is what failed).
BACKEND_RUNGS = ("fused", "staged", "einsum")


def demote_layer_backend(lp, *, batch: int = 1,
                         reason: BaseException | str = ""):
    """Demote one layer ONE rung along the backend axis only
    (fused -> staged -> einsum), skipping the input_mode/hadamard rungs.

    Used by the load-triggered ladder of ``launch.spectral_serve``:
    under queue/deadline pressure the server trades the whole execution
    path one rung at a time rather than individual kernel variants.
    Returns the demoted ``LayerPlan`` (re-priced, provenance-stamped
    like ``demote_layer``), or None on the terminal einsum rung.
    """
    import dataclasses as dc

    note = _summarize(reason) if isinstance(reason, BaseException) \
        else str(reason)
    backend = getattr(lp, "backend", "fused")
    nxt = {"fused": "staged", "staged": "einsum"}.get(backend)
    if nxt is None:
        return None
    new = lp
    extra = ()
    if backend == "fused" and \
            getattr(lp.epilogue, "residual", None) == "fused":
        # off the fused backend the epilogue add can't stay in-kernel;
        # drop to the unfused-add rung in the same step (the spatial
        # epilogue would otherwise ReLU before the add)
        new = _residual_add_fallback(new)
        extra = ("epilogue residual-fused->residual-add "
                 "(backend demotion)",)
    new = dc.replace(new, backend=nxt)
    tn = _reprice_tuning(new, batch)
    rung = f"backend {backend}->{nxt}"
    prov = getattr(lp, "provenance", ()) + extra + (
        f"{rung} ({note})" if note else rung,)
    return dc.replace(new, tuning=tn, provenance=prov)


def plan_at_backend_rung(plan, backend: str, *, reason: str = ""):
    """Return a copy of ``plan`` with every layer demoted to AT LEAST
    the given backend rung ('fused' | 'staged' | 'einsum').

    Layers already at (or below) the rung are untouched; the others are
    walked down ``demote_layer_backend`` one rung at a time so each
    transition is re-priced and recorded in provenance —
    ``health_report()`` on the result shows exactly what the load
    ladder traded.  ``backend='fused'`` returns the plan unchanged.
    """
    import dataclasses as dc

    if backend not in BACKEND_RUNGS:
        raise ValueError(f"backend must be one of {BACKEND_RUNGS}, "
                         f"got {backend!r}")
    target = BACKEND_RUNGS.index(backend)
    new_layers = []
    changed = False
    for lp in plan.layers:
        while BACKEND_RUNGS.index(getattr(lp, "backend", "fused")) < target:
            lp = demote_layer_backend(lp, batch=plan.batch, reason=reason)
            changed = True
        new_layers.append(lp)
    if not changed:
        return plan
    return dc.replace(plan, layers=tuple(new_layers))


def probe_layer_plan(lp, *, batch: int = 1,
                     interpret: bool | None = None
                     ) -> BaseException | None:
    """Capability probe: compile + run one layer forward on zeros.

    Returns None when the layer's chosen variant executes, else the
    exception it died with (for the hardening loop to attach to the
    demotion provenance).  In interpret mode this exercises the full
    trace/lower/execute path of the variant; on real TPU it is where a
    Mosaic lowering failure or VMEM overflow surfaces — once, at plan
    time, instead of mid-inference.
    """
    x = jnp.zeros((batch, lp.layer.c_in, lp.layer.h_in, lp.layer.w_in),
                  jnp.float32)
    shortcut = None
    if getattr(lp.epilogue, "residual", None) == "fused":
        # probe the variant that will actually run: a residual-fused
        # epilogue takes one more VMEM operand on the flush path
        hw = getattr(lp.layer, "out_hw", (lp.layer.h_in, lp.layer.w_in))
        shortcut = jnp.zeros((batch, lp.layer.c_out, hw[0], hw[1]),
                             jnp.float32)
    try:
        y = execute_planned_layer(x, lp, interpret=interpret,
                                  shortcut=shortcut)
        jnp.asarray(y).block_until_ready()
        return None
    except BaseException as e:           # noqa: BLE001 — probe boundary
        if isinstance(e, (KeyboardInterrupt, SystemExit)):
            raise
        return e


def harden_network_plan(plan, *, vmem_budget: int = df.TPU_VMEM_BYTES,
                        hw_safe: bool = True,
                        interpret: bool | None = None,
                        probe: bool = True):
    """Walk every layer down the demotion ladder until it validates AND
    its capability probe passes.

    A healthy plan comes back unchanged (same layer objects).  A layer
    whose chosen variant fails validation (error severity or VMEM
    over-budget) or fails to compile/execute is demoted one rung at a
    time — ``halo -> windowed``, ``scheduled -> dense``, ``fused ->
    staged -> einsum`` — re-probing after each rung.  The terminal
    einsum rung always executes; if even it fails, the original
    exception is re-raised wrapped in ``KernelLoweringError``.

    Returns a new ``NetworkPlan``; inspect ``health_report()`` (or each
    layer's ``provenance``) for what was demoted and why.
    """
    import dataclasses as dc

    new_layers = []
    for lp in plan.layers:
        for _ in range(len(DEMOTION_LADDER) + 1):
            issue: BaseException | None = None
            if getattr(lp, "backend", "fused") == "fused":
                diags = validate_layer_plan(
                    lp, batch=plan.batch, vmem_budget=vmem_budget,
                    hw_safe=hw_safe)
                bad = [d for d in diags
                       if d.severity == "error" or d.check == "vmem-budget"]
                if bad:
                    issue = PlanValidationError(
                        f"layer {lp.layer.name} failed validation",
                        layer=lp.layer.name, site="harden",
                        diagnostics=bad)
            if issue is None and probe:
                issue = probe_layer_plan(lp, batch=plan.batch,
                                         interpret=interpret)
            if issue is None:
                break
            demoted = demote_layer(lp, batch=plan.batch, reason=issue)
            if demoted is None:
                raise KernelLoweringError(
                    f"layer {lp.layer.name} failed on the terminal "
                    f"einsum rung: {_summarize(issue)}",
                    layer=lp.layer.name, site="harden") from issue
            lp = demoted
        new_layers.append(lp)
    return dc.replace(plan, layers=tuple(new_layers))


# ---------------------------------------------------------------------------
# (2b) Per-backend circuit breaker (serving front end)
# ---------------------------------------------------------------------------

BREAKER_STATES = ("closed", "open", "half_open")


@dataclasses.dataclass
class CircuitBreaker:
    """Consecutive-failure circuit breaker for one execution backend.

    The serving front end (``launch.spectral_serve``) keeps one breaker
    per ladder rung ('fused' / 'staged'; einsum is terminal and never
    gated).  State machine:

      closed     healthy: every request allowed.  ``failure_threshold``
                 CONSECUTIVE failures open the breaker (one success
                 resets the count).
      open       the rung is skipped entirely — requests start one rung
                 down — until ``cooldown_s`` elapses, when the breaker
                 moves to half_open.
      half_open  recovery probing: traffic is allowed through again;
                 ``recovery_successes`` consecutive successes close the
                 breaker, a single failure re-opens it (cooldown
                 restarts).

    ``clock`` is injectable for deterministic tests (any zero-arg
    callable returning seconds).  Every state change is appended to
    ``transitions`` and surfaced by ``snapshot()`` — the serve-level
    ``health_report()`` includes one snapshot per rung.
    """

    name: str = ""
    failure_threshold: int = 3
    cooldown_s: float = 1.0
    recovery_successes: int = 1
    clock: Callable[[], float] = time.monotonic
    state: str = "closed"
    failures: int = 0                 # consecutive failures
    successes: int = 0                # consecutive successes (half_open)
    opened_at: float | None = None
    n_opens: int = 0
    transitions: list = dataclasses.field(default_factory=list)

    def _to(self, state: str, why: str) -> None:
        self.transitions.append({"t": self.clock(), "from": self.state,
                                 "to": state, "why": why})
        self.state = state

    def allow(self) -> bool:
        """May a request be attempted on this backend right now?"""
        if self.state == "open":
            if self.clock() - self.opened_at >= self.cooldown_s:
                self.successes = 0
                self._to("half_open",
                         f"cooldown {self.cooldown_s}s elapsed")
                return True
            return False
        return True                   # closed or half_open (probing)

    def record_success(self) -> None:
        if self.state == "half_open":
            self.successes += 1
            if self.successes >= self.recovery_successes:
                self._to("closed", f"{self.successes} recovery "
                                   f"probe(s) succeeded")
                self.failures = 0
        else:
            self.failures = 0

    def record_failure(self, reason: str = "") -> None:
        self.successes = 0
        if self.state == "half_open":
            self.opened_at = self.clock()
            self.n_opens += 1
            self._to("open", f"recovery probe failed ({reason})"
                     if reason else "recovery probe failed")
        elif self.state == "closed":
            self.failures += 1
            if self.failures >= self.failure_threshold:
                self.opened_at = self.clock()
                self.n_opens += 1
                self._to("open",
                         f"{self.failures} consecutive failure(s)"
                         + (f" ({reason})" if reason else ""))

    def snapshot(self) -> dict:
        return {
            "name": self.name,
            "state": self.state,
            "consecutive_failures": self.failures,
            "n_opens": self.n_opens,
            "transitions": list(self.transitions),
        }


# ---------------------------------------------------------------------------
# (3) Runtime numeric guards
# ---------------------------------------------------------------------------

GUARD_POLICIES = ("raise", "demote", "warn")


@dataclasses.dataclass
class NumericGuards:
    """Opt-in per-layer runtime checks for ``forward_spectral``.

    nan_scan:  scan every layer output for NaN/Inf.
    parity:    sampled self-check against the einsum oracle — recompute
               ``parity_channels`` evenly-spaced output channels on the
               first ``parity_batch`` images through
               ``spectral_conv2d_pretransformed`` and compare to
               ``parity_tol``.  Catches corrupted kernel operands /
               tables that are numerically valid but *wrong*.
    policy:    what a tripped guard does —
               'raise'  raise ``NumericGuardError`` (default);
               'demote' recompute the offending layer through the
                        einsum oracle and continue (the run's answer
                        stays parity-bounded);
               'warn'   emit a warning and keep the suspect output.
    events:    every trip is appended here as a dict, whatever the
               policy — the run's numeric-health audit trail.
    """

    nan_scan: bool = True
    parity: bool = False
    parity_tol: float = 1e-4
    parity_channels: int = 4
    parity_batch: int = 1
    policy: str = "raise"
    events: list = dataclasses.field(default_factory=list)

    def __post_init__(self):
        if self.policy not in GUARD_POLICIES:
            raise ValueError(f"policy must be one of {GUARD_POLICIES}, "
                             f"got {self.policy!r}")


def _oracle_layer(x, lp, shortcut=None):
    y = spec.spectral_conv2d_pretransformed(x, lp.kernels, lp.geo)
    return _spatial_epilogue(y, lp, shortcut)


def _sampled_parity_err(x, y, lp, guards: NumericGuards,
                        shortcut=None) -> float:
    sk = lp.kernels
    n = sk.n_out
    sel = np.unique(np.linspace(
        0, n - 1, max(1, min(guards.parity_channels, n))).astype(int))
    sub = sp.SparseSpectralKernels(
        values=sk.values[sel], mask=sk.mask[sel],
        indices=sk.indices[sel], alpha=sk.alpha,
        active_bins=sk.active_bins)
    nb = max(1, min(guards.parity_batch, x.shape[0]))
    ref = spec.spectral_conv2d_pretransformed(x[:nb], sub, lp.geo)
    if lp.epilogue.bias:
        ref = ref + lp.bias[0][sel][None, :, None, None]
    if shortcut is not None:
        ref = ref + shortcut[:nb, np.asarray(sel)]
    if lp.epilogue.relu:
        ref = jnp.maximum(ref, 0.0)
    got = y[:nb, np.asarray(sel)]
    return float(jnp.abs(got - ref).max())


def apply_guards(x, y, lp, guards: NumericGuards, shortcut=None):
    """Run the enabled guards on one layer's output.

    ``x`` is the layer input (needed for the parity oracle and the
    demote fallback), ``y`` its computed output; ``shortcut`` is the
    residual operand already fused into ``y`` (residual-fused layers),
    so the parity oracle reproduces the same bias -> +shortcut -> ReLU
    epilogue.  Returns the output to carry forward — ``y`` itself, or
    the oracle recompute under the 'demote' policy.
    """
    name = lp.layer.name

    def trip(check: str, message: str):
        guards.events.append({"layer": name, "check": check,
                              "message": message,
                              "policy": guards.policy})
        if guards.policy == "raise":
            raise NumericGuardError(message, layer=name, site=check)
        if guards.policy == "warn":
            warnings.warn(f"[numeric-guard] {message}", RuntimeWarning,
                          stacklevel=3)
            return y
        return _oracle_layer(x, lp, shortcut)  # demote: oracle recompute

    if guards.nan_scan and not bool(jnp.isfinite(y).all()):
        return trip("nan_scan",
                    f"non-finite values in {name} output "
                    f"(backend={getattr(lp, 'backend', 'fused')}, "
                    f"hadamard={lp.hadamard}, "
                    f"input_mode={lp.input_mode})")
    if guards.parity and getattr(lp, "backend", "fused") != "einsum":
        err = _sampled_parity_err(x, y, lp, guards, shortcut)
        if not err <= guards.parity_tol:
            return trip(
                "parity",
                f"sampled parity vs einsum oracle failed on {name}: "
                f"max abs err {err:.3e} > tol {guards.parity_tol:.1e} "
                f"({guards.parity_channels} channels, "
                f"{guards.parity_batch} image(s))")
    return y


# ---------------------------------------------------------------------------
# (5) Sharded plans: partition validation + the sharded degradation ladder
# ---------------------------------------------------------------------------

def validate_layer_partition(slp, *, batch: int = 1) -> list[Diagnostic]:
    """Partition invariants of one ``core.plan.ShardedLayerPlan``:
    per-shard geometry AND the shapes every ICI collective assumes.

    The collective checks matter because a mismatch there does not
    raise — it HANGS (a psum over differently-shaped partials, or a
    ppermute whose halo width disagrees with the receiver's
    ``pre_halo_h``, deadlocks the mesh).  Checked per strategy:

      spatial   one shared band plan; ``pre_halo_h`` == k-1 (the rows
          ppermute ships), band rows == ``shard_band_rows``, band
          covers the full canvas, W-axis untouched;
      channel   D shard plans; D | c_in; every shard the SAME local
          dims, geometry and output channels (psum operands must agree
          elementwise), epilogue deferred (bias/relu post-psum, else
          the bias is summed D times), Alg-2 tables padded to one T
          (they stack into a single shard-mapped operand);
      replicate no shard plans at all.
    """
    out: list[Diagnostic] = []
    name = slp.base.layer.name
    d = lambda check, msg, sev="error": out.append(
        Diagnostic(name, check, msg, sev))

    if slp.strategy not in df.SHARD_STRATEGIES:
        d("shard/strategy", f"unknown strategy {slp.strategy!r}; must "
                            f"be one of {df.SHARD_STRATEGIES}")
        return out
    if slp.strategy == "replicate":
        if slp.shards:
            d("shard/replicate", f"replicate carries {len(slp.shards)} "
                                 f"shard plans; expected none")
        return out
    if slp.base.backend != "fused":
        d("shard/backend", f"sharded execution requires the fused "
                           f"backend; base is {slp.base.backend!r} "
                           f"(demote to 'replicate' instead)")
    geo = slp.base.geo
    ov = geo.ksize - 1
    D = slp.n_shards

    if slp.strategy == "spatial":
        if len(slp.shards) != 1:
            d("shard/spatial", f"spatial wants ONE shared band plan, "
                               f"got {len(slp.shards)}")
            return out
        band = slp.shards[0]
        bg = band.geo
        tr = spec.shard_band_rows(geo, D)
        if bg.pre_halo_h != ov:
            d("shard/halo-rows",
              f"band pre_halo_h={bg.pre_halo_h} != k-1={ov}; the "
              f"ppermute ships exactly k-1 rows per boundary — the "
              f"receiver would mis-index every tile")
        if bg.n_tiles_h != tr:
            d("shard/band-rows",
              f"band has {bg.n_tiles_h} tile rows, shard_band_rows "
              f"says {tr}")
        if bg.h_in != ov + tr * geo.tile or bg.h_pad != tr * geo.tile:
            d("shard/band-height",
              f"band h_in={bg.h_in}/h_pad={bg.h_pad} inconsistent with "
              f"{tr} tile rows of stride {geo.tile} plus {ov} halo rows")
        if D * tr < geo.n_tiles_h:
            d("shard/coverage",
              f"{D} bands x {tr} tile rows cover {D * tr} < "
              f"{geo.n_tiles_h} canvas tile rows")
        if (bg.w_in, bg.w_pad, bg.n_tiles_w) != (geo.w_in, geo.w_pad,
                                                 geo.n_tiles_w):
            d("shard/band-width",
              f"band W-axis {(bg.w_in, bg.w_pad, bg.n_tiles_w)} != "
              f"base {(geo.w_in, geo.w_pad, geo.n_tiles_w)}; spatial "
              f"sharding splits rows only")
        if band.layer.c_in != slp.base.layer.c_in:
            d("shard/band-channels",
              f"band c_in={band.layer.c_in} != {slp.base.layer.c_in}; "
              f"spatial shards keep full channels")
        return out

    # channel
    if len(slp.shards) != D:
        d("shard/channel", f"channel wants {D} shard plans, got "
                           f"{len(slp.shards)}")
        return out
    M = slp.base.layer.c_in
    if M % D:
        d("shard/divisibility", f"c_in={M} not divisible by D={D}")
        return out
    mloc = M // D
    t_lens = set()
    for i, sh in enumerate(slp.shards):
        if sh.layer.c_in != mloc:
            d("shard/local-dims",
              f"shard {i} c_in={sh.layer.c_in} != c_in/D={mloc}")
        if sh.layer.c_out != slp.base.layer.c_out or sh.geo != geo:
            d("shard/psum-shape",
              f"shard {i} output shape disagrees with the others "
              f"(c_out={sh.layer.c_out}, geo mismatch={sh.geo != geo}) "
              f"— psum operands must agree elementwise or the "
              f"collective deadlocks")
        if sh.epilogue.bias or sh.epilogue.relu:
            d("shard/epilogue",
              f"shard {i} fuses bias/relu into a PARTIAL sum; channel "
              f"shards must defer the epilogue to post-psum")
        if sh.tables is not None:
            t_lens.add(int(np.asarray(sh.tables.idx).shape[2]))
    if len(t_lens) > 1:
        d("shard/table-pad",
          f"shard Alg-2 tables disagree on cycle count T {sorted(t_lens)}"
          f"; they stack into one shard-mapped operand — pad to max T")
    return out


def validate_sharded_plan(splan, *,
                          vmem_budget: int = df.TPU_VMEM_BYTES,
                          hw_safe: bool = True,
                          raise_on_error: bool = True
                          ) -> list[Diagnostic]:
    """Validate a ``core.plan.ShardedNetworkPlan``: the base plan, every
    shard-local ``LayerPlan`` (full ``validate_layer_plan`` — shard
    plans carry LOCAL dims, so table/operand/halo checks see the shapes
    the kernel will), and the partition/collective invariants
    (``validate_layer_partition``)."""
    diags: list[Diagnostic] = []
    batch = splan.base.batch
    if len(splan.layers) != len(splan.base.layers):
        diags.append(Diagnostic(
            "<plan>", "shard/alignment",
            f"{len(splan.layers)} sharded layers vs "
            f"{len(splan.base.layers)} base layers"))
    if int(np.prod(splan.mesh_shape)) != splan.n_shards:
        diags.append(Diagnostic(
            "<plan>", "shard/mesh",
            f"mesh_shape {splan.mesh_shape} has "
            f"{int(np.prod(splan.mesh_shape))} devices, plan says "
            f"n_shards={splan.n_shards}"))
    for slp in splan.layers:
        for sh in slp.shards:
            diags.extend(validate_layer_plan(
                sh, batch=batch, vmem_budget=vmem_budget,
                hw_safe=hw_safe))
        diags.extend(validate_layer_partition(slp, batch=batch))
    errors = [d for d in diags if d.severity == "error"]
    if errors and raise_on_error:
        raise PlanValidationError(
            f"sharded plan {splan.name!r} failed validation "
            f"({len(errors)} error(s))",
            layer=errors[0].layer, site="validate_sharded_plan",
            diagnostics=errors)
    return diags


def probe_sharded_layer(slp, *, batch: int = 1,
                        interpret: bool | None = None
                        ) -> BaseException | None:
    """Capability probe for one sharded layer: consult the shard-scoped
    fault site, then compile + run every shard-local plan on zeros.

    Host-side and mesh-free by design: each shard plan executes as an
    ordinary single-device program (the collective wrappers add only
    ppermute/psum around these exact kernels), so a shard whose tables
    were corrupted or whose variant cannot lower is caught HERE — at
    plan time, before any device enters a collective it can never leave.
    """
    for i, sh in enumerate(slp.shards):
        try:
            fault_check("shard_tables", layer=slp.base.layer.name,
                        shard=i, strategy=slp.strategy)
        except BaseException as e:      # noqa: BLE001 — probe boundary
            if isinstance(e, (KeyboardInterrupt, SystemExit)):
                raise
            return e
        err = probe_layer_plan(sh, batch=batch, interpret=interpret)
        if err is not None:
            return err
    if not slp.shards:                  # replicate: probe the base
        return probe_layer_plan(slp.base, batch=batch,
                                interpret=interpret)
    return None


def harden_sharded_plan(splan, *,
                        vmem_budget: int = df.TPU_VMEM_BYTES,
                        hw_safe: bool = True,
                        interpret: bool | None = None,
                        probe: bool = True):
    """Per-layer degradation ladder for a sharded plan.

    Structured demotion instead of a collective hang: every check and
    probe runs host-side per shard (``probe_sharded_layer``), and every
    demotion is a PLAN-level decision applied before any shard_map is
    entered — all devices always trace the same program.  A failing
    layer walks the same ladder as ``harden_network_plan`` applied to
    its BASE plan (halo->windowed, scheduled->dense, fused->staged ->
    einsum), and the shard plans are REBUILT from the demoted base at
    each rung (``plan.resharded_layer_plan``); once the base leaves the
    fused backend the strategy collapses to 'replicate', whose terminal
    einsum rung always executes.

    Returns a new ``ShardedNetworkPlan`` (same objects where healthy);
    per-layer shard demotions append to ``ShardedLayerPlan.provenance``.
    """
    import dataclasses as dc

    from repro.core import plan as pl

    batch = splan.base.batch
    new_layers = []
    for slp in splan.layers:
        for _ in range(len(DEMOTION_LADDER) + 1):
            issue: BaseException | None = None
            diags = [dg for sh in slp.shards
                     for dg in validate_layer_plan(
                         sh, batch=batch, vmem_budget=vmem_budget,
                         hw_safe=hw_safe)]
            diags += validate_layer_partition(slp, batch=batch)
            bad = [dg for dg in diags
                   if dg.severity == "error" or dg.check == "vmem-budget"]
            if bad:
                issue = PlanValidationError(
                    f"sharded layer {slp.base.layer.name} failed "
                    f"validation", layer=slp.base.layer.name,
                    site="harden_sharded", diagnostics=bad)
            if issue is None and probe:
                issue = probe_sharded_layer(slp, batch=batch,
                                            interpret=interpret)
            if issue is None:
                break
            demoted = demote_layer(slp.base, batch=batch, reason=issue)
            if demoted is None:
                raise KernelLoweringError(
                    f"sharded layer {slp.base.layer.name} failed on "
                    f"the terminal replicated-einsum rung: "
                    f"{_summarize(issue)}",
                    layer=slp.base.layer.name,
                    site="harden_sharded") from issue
            slp = pl.resharded_layer_plan(
                slp, demoted, note=f"shard ladder: {_summarize(issue)}")
        new_layers.append(slp)
    base = dc.replace(splan.base,
                      layers=tuple(slp.base for slp in new_layers))
    return dc.replace(splan, base=base, layers=tuple(new_layers))
