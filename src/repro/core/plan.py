"""Compile-once network-plan IR: everything the forward pass needs,
precomputed.

The paper's headline result comes from *composing* its three
contributions — per-layer flexible dataflow (Alg 1), kernel compression
(SPEC2-style pruning), and conflict-free scheduling of the sparse
kernels (Alg 2 / Fig 6).  On the FPGA that composition happens at
synthesis time: the host compiles per-layer configurations once and the
accelerator just executes them.  This module is the TPU analogue — a
small IR built once, offline, and executed by every backend of
``models.cnn.forward_spectral`` without re-deriving anything per call:

  LayerPlan   one conv layer's precompiled state:
    * tile geometry (``SpectralGeometry``, overlap-save),
    * pruned ``SparseSpectralKernels`` (per-layer alpha),
    * the active-frequency-bin set the exact-cover schedule touches
      (== the union of non-zero kernel bins, see
      ``scheduler.active_bins_from_tables``) with the compacted kernel
      planes and restricted DFT operators derived from it,
    * the autotuned (flow, block_n, block_m, block_p, hadamard mode)
      from Alg-1-on-TPU (``core.autotune``), costed sparsity-aware so
      Alg 1 sees the kernel Alg 2 compressed AND ranks the scheduled
      element-granular datapath against bin compaction per layer,
    * for layers whose mode is 'scheduled': the full Alg-2 INDEX/VALUE
      tables (one exact-cover schedule per kernel-group x channel,
      ``scheduler.compile_layer_tables``), remapped to compacted-bin
      coordinates and padded to the tuned blocks — the fused kernel
      executes them directly,
    * a fused epilogue spec (bias + ReLU inside the kernel flush,
      2x2-max-pool flag for the spatial stage that follows),
    * Alg-2 schedule statistics (cycles, Eq-14 PE utilization) —
      sampled for plane modes, exact for scheduled layers.

  NetworkPlan  the per-layer plans plus the FC-head bookkeeping.

Plan construction is host-side numpy/python and happens exactly once;
the jitted forward path (``kernels.fused_spectral_conv.execute_layer_plan``)
only consumes device arrays and static metadata, so repeated calls hit
the jit cache directly — no schedule, pruning, compaction, autotune or
geometry work ever runs inside (or between) jitted steps.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import autotune as at
from repro.core import dataflow as df
from repro.core import resilience as res
from repro.core import scheduler as sch
from repro.core import spans
from repro.core import sparse as sp
from repro.core import spectral as spec

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class EpilogueSpec:
    """Post-conv elementwise work fused into (bias, relu) or scheduled
    right after (pool) the conv kernel.

    ``residual`` is the shortcut-add mode of a DAG node with a
    ``residual_from`` edge (ISSUE 10):

      None     no shortcut (every linear-stack layer);
      'fused'  the shortcut activation is one more VMEM operand on the
               kernel's epilogue flush — added after bias, before ReLU,
               inside the same pallas_call (requires the fused backend
               and stride 1);
      'add'    the dense fallback: the conv runs with ReLU deferred and
               the executor applies ``relu(y + shortcut)`` as an
               unfused XLA add — the degradation-ladder rung
               ``epilogue residual-fused->residual-add``.
    """

    bias: bool = True
    relu: bool = True
    pool: bool = False       # 2x2 max-pool follows this layer (spatial)
    residual: str | None = None   # None | 'fused' | 'add'


class PlanTables(NamedTuple):
    """Device-resident Alg-2 INDEX/VALUE tables for one scheduled layer
    (stacked layout of ``scheduler.LayerTables``; consumed verbatim by
    ``kernels.fused_spectral_conv.fused_spectral_pipeline_scheduled``).
    """

    idx: Array                        # [GN, Mp, T, r]  int32
    sel: Array                        # [GN, Mp, T, N'] int32
    vr: Array                         # [GN, Mp, T, N'] f32
    vi: Array

    @property
    def nbytes(self) -> int:
        return sum(a.nbytes for a in self)


@dataclasses.dataclass(frozen=True)
class PlanNode:
    """One node of the compiled DAG plan (ISSUE 10).

    The plan-level twin of the config-level ``dataflow.NodeSpec``:
    topo-ordered by ``build_network_plan``, resolved against the
    compiled ``LayerPlan`` tuple, and carrying the plan-time decisions
    a NodeSpec cannot (the ShortcutFusion on-chip verdict).

      id            stable node id; for 'conv' nodes this IS the
                    ``ConvLayer`` name (== ``layers[layer_index]``).
      kind          'conv' | 'pool'.
      inputs        producer ids (length 1; 'input' = network input).
      layer_index   index into ``NetworkPlan.layers`` (-1 for pools).
      pool          'max' | 'avg' (2x2, stride 2) for pool nodes.
      residual_from shortcut producer id, or None.
      relu          apply ReLU at this node's output.  For residual
                    nodes this is the POST-add ReLU (the in-kernel
                    epilogue relu is suppressed on the 'add' rung and
                    the executor applies ``relu(y + shortcut)``).
      shortcut_on_chip  the reuse decision for a fused shortcut: True
                    when the autotuner priced the shortcut as retained
                    VMEM bytes ('vmem' placement) and it fit the
                    budget; False when it re-reads from HBM.
    """

    id: str
    kind: str = "conv"
    inputs: tuple[str, ...] = ("input",)
    layer_index: int = -1
    pool: str = "max"
    residual_from: str | None = None
    relu: bool = True
    shortcut_on_chip: bool = False


def _linear_node_specs(layers, pool_after) -> tuple:
    """Synthesize the degenerate chain graph for a linear conv stack:
    one 'conv' node per layer, a 'max' pool node (id '<name>:pool')
    after every layer named in ``pool_after``."""
    nodes = []
    prev = "input"
    for layer in layers:
        nodes.append(df.NodeSpec(id=layer.name, inputs=(prev,)))
        prev = layer.name
        if layer.name in pool_after:
            pid = f"{layer.name}:pool"
            nodes.append(df.NodeSpec(id=pid, kind="pool", inputs=(prev,)))
            prev = pid
    return tuple(nodes)


def _topo_order_specs(specs) -> list:
    """Kahn topo-order of config NodeSpecs (shortcut edges included).

    Raises ``PlanValidationError`` (site='graph') on duplicate ids,
    references to unknown ids, or a cycle — at plan build, not at
    execution.
    """
    by_id: dict[str, object] = {}
    for s in specs:
        if s.id == "input" or s.id in by_id:
            raise res.PlanValidationError(
                f"graph node id {s.id!r} is duplicated or reserved",
                layer=s.id, site="graph")
        by_id[s.id] = s
    deps: dict[str, set] = {}
    for s in specs:
        edges = set(s.inputs)
        if getattr(s, "residual_from", None) is not None:
            edges.add(s.residual_from)
        edges.discard("input")
        unknown = edges - by_id.keys()
        if unknown:
            raise res.PlanValidationError(
                f"graph node {s.id!r} references unknown node(s) "
                f"{sorted(unknown)}", layer=s.id, site="graph")
        deps[s.id] = edges
    order, ready = [], [s for s in specs if not deps[s.id]]
    done: set[str] = set()
    while ready:
        s = ready.pop(0)
        order.append(s)
        done.add(s.id)
        for t in specs:
            if t.id not in done and t not in ready \
                    and deps[t.id] <= done:
                ready.append(t)
    if len(order) != len(list(specs)):
        stuck = sorted(set(by_id) - done)
        raise res.PlanValidationError(
            f"graph has a cycle through node(s) {stuck}",
            layer=stuck[0], site="graph")
    return order


def graph_sink(nodes) -> str:
    """Id of the network output node of a topo-ordered node sequence:
    the last node whose output no other node consumes (main or shortcut
    edge).  Falls back to the final topo node for degenerate graphs."""
    consumed: set[str] = set()
    for n in nodes:
        consumed.update(n.inputs)
        rf = getattr(n, "residual_from", None)
        if rf is not None:
            consumed.add(rf)
    sinks = [n.id for n in nodes if n.id not in consumed]
    return sinks[-1] if sinks else nodes[-1].id


def node_output_shapes(layers, specs) -> dict[str, tuple[int, int, int]]:
    """Walk a topo-ordered node sequence and return every node's output
    shape as ``{id: (C, H, W)}`` (batch elided).

    Works on both config-level ``dataflow.NodeSpec`` and plan-level
    ``PlanNode`` sequences (both carry id/kind/inputs/residual_from).
    Conv nodes produce their layer's post-stride 'same' extent
    (``ConvLayer.out_hw``); pool nodes halve H and W (2x2, stride 2,
    floor — odd edge rows/cols are dropped, matching the executor).

    Raises ``PlanValidationError`` when a conv node's declared layer
    geometry disagrees with what its producer actually emits
    (site='graph/input-shape') or a shortcut edge carries a shape
    other than the node's own output (site='graph/residual-shape') —
    the DAG checks of ISSUE 10, enforced at plan build.
    """
    by_name = {l.name: l for l in layers}
    first = next((by_name[s.id] for s in specs
                  if s.kind == "conv" and s.id in by_name), None)
    shapes: dict[str, tuple[int, int, int]] = {}
    if first is not None:
        shapes["input"] = (first.c_in, first.h_in, first.w_in)
    for s in specs:
        src = shapes.get(s.inputs[0])
        if s.kind == "pool":
            if src is None:
                raise res.PlanValidationError(
                    f"pool node {s.id!r} has no resolvable input shape",
                    layer=s.id, site="graph/input-shape")
            c, h, w = src
            out = (c, h // 2, w // 2)
        else:
            layer = by_name.get(s.id)
            if layer is None:
                raise res.PlanValidationError(
                    f"conv node {s.id!r} has no matching ConvLayer",
                    layer=s.id, site="graph/input-shape")
            want = (layer.c_in, layer.h_in, layer.w_in)
            if src is not None and src != want:
                raise res.PlanValidationError(
                    f"conv node {s.id!r} declares input {want} but its "
                    f"producer {s.inputs[0]!r} emits {src}",
                    layer=s.id, site="graph/input-shape")
            hw = getattr(layer, "out_hw", (layer.h_in, layer.w_in))
            out = (layer.c_out, hw[0], hw[1])
        rf = getattr(s, "residual_from", None)
        if rf is not None:
            sc = shapes.get(rf)
            if sc != out:
                raise res.PlanValidationError(
                    f"residual edge {rf!r} -> {s.id!r} adds shape "
                    f"{sc} to output shape {out}",
                    layer=s.id, site="graph/residual-shape")
        shapes[s.id] = out
    return shapes


@dataclasses.dataclass(frozen=True, eq=False)
class LayerPlan:
    """Precompiled state for one spectral conv layer (see module doc).

    Fields (N = c_out, M = c_in, S = K^2, S2 = tile^2, Fa = active
    bins):

      layer / geo / kernels / alpha   static layer description, tile
          geometry and the pruned spectral kernels (per-layer alpha).
      tuning      Alg-1-on-TPU result: flow, block sizes, the chosen
          Hadamard mode and its analytic cost.
      epilogue / bias                 fused bias+ReLU spec (+ pool-after
          flag); bias is [1, N] f32, zeros when disabled.
      active      compacted active-bin set (host numpy) or None (all
          K^2 bins); coordinate system of the spectral operands below.
      wr / wi     [Fa, N, M] f32 kernel planes (dense/bin modes).
      dfr / dfi   [Fa, S] forward DFT rows; dvr/dvi [S2, Fa] inverse
          DFT on valid rows — shared by every Hadamard mode.
      hadamard    'dense' | 'bin' | 'scheduled' — which datapath
          ``execute_layer_plan`` dispatches to.
      input_mode  'windowed' | 'halo' — which input path the fused
          kernel uses: host-materialized overlap-save windows, or the
          in-kernel halo gather reading the raw activation (the
          windowed path is the fallback/oracle; both are numerically
          identical).
      tables      ``PlanTables`` for scheduled layers, else None.
      schedule_cycles / pe_utilization   Alg-2 stats: exact totals when
          the full tables were compiled (scheduled mode), otherwise
          sampled (None when scheduling was skipped).
      backend     'fused' | 'staged' | 'einsum' — which execution path
          runs this layer under the pallas_fused network backend
          (``df.EXEC_BACKENDS``).  Always 'fused' at build time; the
          degradation ladder (``core.resilience``) demotes it when the
          fused variant cannot compile/execute.
      provenance  audit trail of demotions applied to this layer by
          ``resilience.harden_network_plan`` (empty = as built).
    """

    layer: df.ConvLayer
    geo: spec.SpectralGeometry
    kernels: sp.SparseSpectralKernels
    alpha: float
    tuning: at.FusedTuning
    epilogue: EpilogueSpec
    bias: Array                       # [1, N] f32 (zeros when no bias)
    active: np.ndarray | None         # compacted bin set; None = dense
    wr: Array                         # [Fa, N, M] f32 kernel planes
    wi: Array
    dfr: Array                        # [Fa, S]  forward DFT rows
    dfi: Array
    dvr: Array                        # [S2, Fa] inverse DFT (valid rows)
    dvi: Array
    schedule_cycles: int | None       # Alg-2 stats (None: skipped)
    pe_utilization: float | None      # Eq 14
    hadamard: str = "bin"             # Hadamard-stage mode
    input_mode: str = "windowed"      # fused-kernel input path
    tables: PlanTables | None = None  # Alg-2 tables (scheduled mode)
    backend: str = "fused"            # per-layer execution path
    provenance: tuple[str, ...] = ()  # demotion audit trail

    @property
    def n_active_bins(self) -> int:
        k2 = self.geo.fft_size ** 2
        return k2 if self.active is None else len(self.active)

    def stats(self) -> dict:
        """Per-layer summary row (example / benchmark reporting)."""
        return {
            "layer": self.layer.name,
            "alpha": self.alpha,
            "nnz": self.kernels.nnz,
            "active_bins": self.n_active_bins,
            "flow": self.tuning.flow,
            "hadamard": self.hadamard,
            "input_mode": self.input_mode,
            "backend": self.backend,
            "demotions": len(self.provenance),
            "block_n": self.tuning.block_n,
            "block_m": self.tuning.block_m,
            "block_p": self.tuning.block_p,
            "hbm_bytes": self.tuning.hbm_bytes,
            "table_bytes": (self.tables.nbytes
                            if self.tables is not None else 0),
            "schedule_cycles": self.schedule_cycles,
            "pe_utilization": self.pe_utilization,
            "pool": self.epilogue.pool,
        }


@dataclasses.dataclass(frozen=True, eq=False)
class NetworkPlan:
    """The compile-once artifact ``models.cnn.forward_spectral`` executes.

    ``graph`` is the topo-ordered DAG the executors walk (ISSUE 10);
    ``build_network_plan`` always populates it (linear configs get the
    synthesized chain).  Plans constructed by hand with ``graph=()``
    fall back to the chain derived from ``layers`` + the epilogue pool
    flags via ``execution_graph``.  ``phase_s`` holds the host seconds
    ``build_network_plan`` spent in each of ``spans.PLAN_PHASES``.
    """

    name: str
    fft_size: int
    batch: int                        # batch the autotune assumed
    layers: tuple[LayerPlan, ...]
    graph: tuple[PlanNode, ...] = ()
    phase_s: dict = dataclasses.field(default_factory=dict)

    @property
    def tuning(self) -> dict[str, at.FusedTuning]:
        return {lp.layer.name: lp.tuning for lp in self.layers}

    @property
    def execution_graph(self) -> tuple[PlanNode, ...]:
        """The DAG to execute — ``graph``, or the linear chain implied
        by ``layers`` (+ epilogue pool flags) for legacy plans."""
        if self.graph:
            return self.graph
        nodes, prev = [], "input"
        for i, lp in enumerate(self.layers):
            name = lp.layer.name
            nodes.append(PlanNode(id=name, kind="conv", inputs=(prev,),
                                  layer_index=i,
                                  relu=lp.epilogue.relu))
            prev = name
            if lp.epilogue.pool:
                pid = f"{name}:pool"
                nodes.append(PlanNode(id=pid, kind="pool",
                                      inputs=(prev,)))
                prev = pid
        return tuple(nodes)

    def node_plan(self, node: PlanNode) -> LayerPlan:
        """The LayerPlan a 'conv' node executes."""
        if node.kind != "conv":
            raise ValueError(f"node {node.id!r} is {node.kind!r}, "
                             f"not 'conv'")
        return self.layers[node.layer_index]

    def summary(self) -> list[dict]:
        return [lp.stats() for lp in self.layers]

    def health_report(self) -> dict:
        """Resilience status of the plan: validation diagnostics plus
        the demotion audit trail (``core.resilience``).

        Returns a dict with ``healthy`` (no error-severity diagnostics
        and no demoted layers), ``demoted_layers``, ``issues`` (count
        by severity) and one row per layer carrying its current modes,
        provenance and any outstanding diagnostics.
        """
        diags = res.validate_plan(self, raise_on_error=False)
        rows = []
        # Rows key by STABLE NODE ID, not layer index: on a DAG plan
        # positional indices are meaningless (pool nodes interleave,
        # topo order need not match cfg.layers order), and provenance
        # must survive plan rebuilds that reorder layers.
        for node in self.execution_graph:
            if node.kind != "conv":
                rows.append({"node": node.id, "kind": node.kind,
                             "pool": node.pool,
                             "demotions": [], "issues": []})
                continue
            lp = self.layers[node.layer_index]
            mine = [d for d in diags if d.layer == node.id]
            rows.append({
                "node": node.id,
                "kind": "conv",
                "layer": node.id,
                "backend": lp.backend,
                "flow": lp.tuning.flow,
                "hadamard": lp.hadamard,
                "input_mode": lp.input_mode,
                "residual": getattr(lp.epilogue, "residual", None),
                "demotions": list(lp.provenance),
                "issues": [str(d) for d in mine],
            })
        n_err = sum(d.severity == "error" for d in diags)
        n_warn = sum(d.severity == "warn" for d in diags)
        demoted = {lp.layer.name: list(lp.provenance)
                   for lp in self.layers if lp.provenance}
        return {
            "name": self.name,
            "batch": self.batch,
            "healthy": n_err == 0 and not demoted,
            "demoted_layers": list(demoted),
            "demotions_by_node": demoted,
            "issues": {"error": n_err, "warn": n_warn},
            "layers": rows,
        }


def _sampled_schedule_stats(sk: sp.SparseSpectralKernels, k2: int, *,
                            r: int, n_par: int, channel_sample: int,
                            ) -> tuple[int, float, np.ndarray]:
    """Run Alg 2 on a bounded sample of (group, channel) pairs; return
    (total cycles, Eq-14 utilization, bins the sampled schedules touch).
    The full-layer active set is the union over ALL kernels — equal to
    the union of schedule-served bins by the exact-cover property (every
    non-zero served exactly once; ``scheduler.active_bins_from_tables``
    is the table-level statement of the same fact, unit-tested) — so the
    sample's bins are always a subset of ``sk.active_bins``."""
    idx = np.asarray(sk.indices)
    n_out, c_in, _ = idx.shape
    chans = np.linspace(0, c_in - 1, min(channel_sample, c_in)).astype(int)
    group = slice(0, min(n_par, n_out))
    total_ops = 0
    total_cycles = 0
    n_pe = group.stop
    bins: set[int] = set()
    for m in np.unique(chans):
        s = sch.schedule_exact_cover(idx[group, m, :], k2, r)
        total_ops += s.total_ops
        total_cycles += s.n_cycles
        for _, fs in s.cycles:
            bins.update(fs.tolist())
    mu = total_ops / max(1, total_cycles * n_pe)
    return total_cycles, mu, np.asarray(sorted(bins), np.int64)


def _resolve_hadamard_modes(hadamard: str, alpha: float, schedule: bool,
                            active: np.ndarray | None) -> list[str]:
    """Hadamard-mode candidates for one layer, honoring availability.

    'bin' needs a compacted active set (otherwise it IS dense);
    'scheduled' needs a non-degenerate schedule (alpha > 1 and
    scheduling enabled) — when it degenerates, the request falls back
    to the plane datapath, the ISSUE's dense/bin fallback.
    """
    plane = "bin" if active is not None else "dense"
    sched_ok = schedule and alpha > 1.0
    if hadamard == "auto":
        return [plane] + (["scheduled"] if sched_ok else [])
    if hadamard == "scheduled":
        return ["scheduled"] if sched_ok else [plane]
    if hadamard == "bin":
        return [plane]
    if hadamard == "dense":
        return ["dense"]
    raise ValueError(
        f"hadamard must be 'auto' or one of {df.HADAMARD_MODES}, "
        f"got {hadamard!r}")


def _resolve_input_modes(input_mode: str) -> list[str]:
    """Input-path candidates for the autotuner ('auto' ranks both; the
    windowed path is always a valid forced fallback/oracle)."""
    if input_mode == "auto":
        return list(df.INPUT_MODES)
    if input_mode in df.INPUT_MODES:
        return [input_mode]
    raise ValueError(
        f"input_mode must be 'auto' or one of {df.INPUT_MODES}, "
        f"got {input_mode!r}")


def _plan_build_span(build):
    """Run ``build`` inside the ``plan.build`` span (arg ``batch``)."""
    @functools.wraps(build)
    def traced(params, cfg, *, batch: int = 1, **kwargs):
        with spans.span(spans.PLAN_BUILD, batch=batch):
            return build(params, cfg, batch=batch, **kwargs)
    return traced


@_plan_build_span
def build_network_plan(params: dict, cfg, *,
                       batch: int = 1,
                       prune: str = "magnitude",
                       vmem_budget: int = df.TPU_VMEM_BYTES,
                       blocks: Sequence[int] = at.BLOCK_CANDIDATES,
                       hw_safe: bool = True,
                       schedule: bool = True,
                       schedule_r: int = 10,
                       schedule_n_par: int = 64,
                       schedule_channel_sample: int = 2,
                       hadamard: str = "auto",
                       input_mode: str = "auto",
                       schedule_mu: float = df.SCHEDULE_MU,
                       step_overhead_s: float = 0.0,
                       measure: bool = False,
                       interpret: bool | None = None,
                       validate: bool = True) -> NetworkPlan:
    """Compile the whole conv stack once (see module docstring).

    Args:
      params: spatial conv weights + biases (``models.cnn.init``);
        kernels are spectrally transformed and pruned here — the
        paper's offline path — and each layer's bias is baked into the
        plan for the fused epilogue.
      cfg: duck-typed on ``layers`` / ``fft_size`` / ``alpha`` /
        ``pool_after`` / ``name`` (``models.cnn.SpectralCNNConfig``);
        ``cfg.alpha`` may be a scalar or a per-layer sequence.
      batch: images per forward call the autotuner assumes; the plan
        records it and the fused backend enforces it for RMW flows.
      prune: 'magnitude' (SPEC2-like) or 'random' (Fig-10 robustness).
      vmem_budget / blocks: Alg-1 search space, see
        ``autotune.autotune_layer``.  ``hw_safe`` is accepted for API
        compatibility and is a no-op since PR 8 (manual-DMA
        accumulators make every configuration hardware-legal).
      schedule: run Alg 2 at all (False skips schedule stats AND
        disables the scheduled datapath).
      schedule_r: r, the BRAM-replica analogue (paper S6.3: 10).
      schedule_n_par: PE-group size for the SAMPLED stats of plane-mode
        layers (scheduled layers group by the tuned block_n instead).
      schedule_channel_sample: channels sampled for those stats.
      hadamard: 'auto' (default — Alg 1 ranks the available modes per
        layer), or force 'dense' / 'bin' / 'scheduled'.  A forced
        'scheduled' falls back to the plane datapath when the schedule
        degenerates (alpha ~= 1); forced 'bin' degrades to 'dense' when
        no bin is empty.
      input_mode: 'auto' (default — Alg 1 ranks the windowed stream
        against the in-kernel halo gather per layer; the halo path's
        raw-plus-halo input bytes win essentially always), or force
        'windowed' / 'halo' (windowed is the fallback/oracle path).
      schedule_mu: estimated Eq-14 utilization used by the cost model
        to size scheduled tables before the schedules exist.
      step_overhead_s: fixed per-grid-step cost added to Alg 1's
        predictions (``dataflow.INTERPRET_STEP_S`` when the plan will
        execute in interpret mode — the serving stack's default — so
        per-bucket tunings minimize the wall clock of the backend that
        actually runs; 0.0 keeps the pure hardware roofline).
      measure: re-rank top analytic candidates by wall time
        (``autotune``); ``interpret`` selects the kernel execution mode
        for that measurement.
      validate: run ``resilience.validate_plan`` on the finished plan
        (default) so invariant violations — corrupted Alg-2 tables,
        inconsistent operators, out-of-range halo starts — are rejected
        at plan build, not at kernel launch.  VMEM/hw-safety findings
        are advisory (warn severity) here because the autotuner's
        documented fallback may legitimately exceed the budget; use
        ``resilience.harden_network_plan`` to demote such layers.

    For every layer whose chosen mode is 'scheduled', the full Alg-2
    tables are compiled here (one exact-cover schedule per kernel-group
    x input-channel — the expensive offline step the FPGA does at
    synthesis time) and stored device-resident in the plan; the fused
    kernel then executes them without any host-side work per call.
    """
    prune_fn = {"magnitude": sp.prune_magnitude,
                "random": sp.prune_random}[prune]
    layers = list(cfg.layers)
    alphas = sp.per_layer_alphas(cfg.alpha, len(layers))
    pool_after = getattr(cfg, "pool_after", frozenset())
    k2 = cfg.fft_size * cfg.fft_size
    phase_s: dict[str, float] = {}

    # --- DAG plan IR (ISSUE 10): resolve + topo-order the node graph.
    # Linear configs get the synthesized chain, so every plan carries a
    # graph and the executors have exactly one walk to implement.
    graph_specs = getattr(cfg, "graph", None)
    explicit_graph = graph_specs is not None
    if not explicit_graph:
        graph_specs = _linear_node_specs(layers, pool_after)
    order = _topo_order_specs(graph_specs)
    conv_specs = {s.id: s for s in order if s.kind == "conv"}
    names = [l.name for l in layers]
    if sorted(conv_specs) != sorted(names):
        raise res.PlanValidationError(
            f"graph conv nodes {sorted(conv_specs)} do not match "
            f"cfg.layers {sorted(names)} (each conv layer must appear "
            f"in exactly one node)", site="graph")
    node_output_shapes(layers, order)   # DAG shape checks (raises)

    shortcut_on_chip: dict[str, bool] = {}
    plans: list[LayerPlan] = []
    for layer, conv, alpha in zip(layers, params["convs"], alphas):
        geo = spec.make_geometry(layer.h_in, layer.w_in, layer.ksize,
                                 cfg.fft_size, layer.pad)
        with spans.counted(phase_s, "prune", layer=layer.name):
            w_f = spec.spectral_kernel(conv["w"], cfg.fft_size)
            sk = prune_fn(w_f, alpha)
            active = sp.compacted_active_bins(sk)
            wr, wi = sp.compact_planes(sk, active)

        cycles = mu = None
        if schedule and alpha > 1.0:
            with spans.counted(phase_s, "schedule_stats", layer=layer.name):
                cycles, mu, sampled_bins = _sampled_schedule_stats(
                    sk, k2, r=schedule_r, n_par=schedule_n_par,
                    channel_sample=schedule_channel_sample)
                full = np.asarray(sk.active_bins)
            if not np.isin(sampled_bins, full).all():
                raise res.PlanValidationError(
                    f"Alg-2 schedule for {layer.name} touched a "
                    f"frequency bin outside the pruned kernel support",
                    layer=layer.name, site="schedule-stats")

        with spans.counted(phase_s, "operators", layer=layer.name):
            ops = jnp.asarray  # device placement of the numpy operators
            dfr, dfi, dvr, dvi = (ops(a) for a in _operators(geo, active))

        measure_fn = None
        if measure:
            measure_fn = at._make_measure_fn(layer, cfg.fft_size, alpha,
                                             batch, interpret)
        modes = _resolve_hadamard_modes(hadamard, alpha, schedule, active)
        imodes = _resolve_input_modes(input_mode)
        node_spec = conv_specs[layer.name]
        stride = getattr(layer, "stride", 1)
        # Residual mode: the fused epilogue add needs the stride-1
        # output the kernel actually flushes (stride subsampling
        # happens after the kernel), so strided nodes take the dense
        # 'add' fallback from the start.
        residual_mode = None
        if node_spec.residual_from is not None:
            residual_mode = "fused" if stride == 1 else "add"

        def _tune(residual=None):
            return at.autotune_layer(
                layer, cfg.fft_size, alpha, batch=batch,
                vmem_budget=vmem_budget, blocks=blocks, hw_safe=hw_safe,
                active_bins=len(active) if active is not None else None,
                hadamard_modes=modes, input_modes=imodes,
                schedule_r=schedule_r,
                schedule_mu=schedule_mu,
                step_overhead_s=step_overhead_s,
                residual=residual, measure_fn=measure_fn)

        with spans.counted(phase_s, "autotune", layer=layer.name):
            if residual_mode == "fused":
                # ShortcutFusion reuse decision: hold the shortcut
                # on-chip (retained VMEM bytes) when the working set
                # still fits the budget, else re-read it from HBM on the
                # flush path.
                tuning = _tune(residual="vmem")
                if tuning.vmem_bytes > vmem_budget:
                    tuning = _tune(residual="hbm")
                shortcut_on_chip[layer.name] = tuning.residual == "vmem"
            else:
                tuning = _tune()

        tables = None
        if tuning.hadamard == "scheduled":
            # The paper's offline schedule compilation: one exact-cover
            # schedule per (kernel-group, channel), stacked and
            # remapped to the compacted coordinates of the operators
            # above.  Group size == the tuned block_n; channel padding
            # == the tuned block_m.
            with spans.counted(phase_s, "tables", layer=layer.name):
                lt = sch.compile_layer_tables(
                    np.asarray(sk.indices),
                    np.asarray(sk.values).reshape(layer.c_out, layer.c_in,
                                                  k2),
                    k2, schedule_r, min(tuning.block_n, layer.c_out),
                    active=active, m_pad_to=min(tuning.block_m, layer.c_in))
                tables = PlanTables(
                    jnp.asarray(lt.idx), jnp.asarray(lt.sel),
                    jnp.asarray(lt.vr), jnp.asarray(lt.vi))
            cycles, mu = lt.total_cycles, lt.pe_utilization  # exact

        # On the 'add' rung the kernel flushes bias-only output and the
        # executor applies relu(y + shortcut) — in-kernel relu would
        # clamp the pre-add activation, which is wrong.
        epi = EpilogueSpec(bias=True,
                           relu=(node_spec.relu
                                 and residual_mode != "add"),
                           pool=(not explicit_graph
                                 and layer.name in pool_after),
                           residual=residual_mode)
        bias = jnp.asarray(conv["b"], jnp.float32).reshape(1, -1)
        plans.append(LayerPlan(
            layer=layer, geo=geo, kernels=sk, alpha=alpha, tuning=tuning,
            epilogue=epi, bias=bias, active=active, wr=wr, wi=wi,
            dfr=dfr, dfi=dfi, dvr=dvr, dvi=dvi,
            schedule_cycles=cycles, pe_utilization=mu,
            hadamard=tuning.hadamard or
            ("bin" if active is not None else "dense"),
            input_mode=tuning.input_mode or "windowed",
            tables=tables))
    layer_index = {name: i for i, name in enumerate(names)}
    pnodes = tuple(
        PlanNode(id=s.id, kind="conv", inputs=tuple(s.inputs),
                 layer_index=layer_index[s.id],
                 residual_from=s.residual_from, relu=s.relu,
                 shortcut_on_chip=shortcut_on_chip.get(s.id, False))
        if s.kind == "conv" else
        PlanNode(id=s.id, kind="pool", inputs=tuple(s.inputs),
                 pool=s.pool)
        for s in order)
    net = NetworkPlan(name=getattr(cfg, "name", "spectral-cnn"),
                      fft_size=cfg.fft_size, batch=batch,
                      layers=tuple(plans), graph=pnodes, phase_s=phase_s)
    if validate:
        with spans.counted(phase_s, "validate"):
            res.validate_plan(net, vmem_budget=vmem_budget,
                              hw_safe=hw_safe)
    return net


def _operators(geo: spec.SpectralGeometry, active: np.ndarray | None):
    from repro.kernels.fused_spectral_conv import overlap_save_operators
    key = tuple(int(a) for a in active) if active is not None else None
    return overlap_save_operators(geo.fft_size, geo.ksize, key)


# ---------------------------------------------------------------------------
# Keyed plan cache (serving front end)
# ---------------------------------------------------------------------------

def plan_cache_key(cfg, batch: int, *,
                   mesh_shape: Sequence[int] | None = None,
                   **build_kwargs) -> tuple:
    """Cache key for one compiled ``NetworkPlan``: (config name,
    fft_size, per-layer alpha, batch bucket, mesh shape, build options).

    Everything else a plan depends on (layer geometry, pool placement)
    is a function of the named config; alpha is normalized so a scalar
    and the equivalent per-layer sequence key identically.  Build
    kwargs (forced hadamard/input_mode, vmem budget, ...) are folded in
    by repr so plans built with different options never collide.

    ``mesh_shape`` is the device topology the plan targets and is part
    of the key — a sharded plan's shard geometry, collective shapes and
    Alg-2 table slices are all functions of the mesh, so a plan built
    for one mesh must never be served to another (serving it would be
    silent cross-mesh cache poisoning: wrong shard math, not an error).
    ``None`` (single-device / unsharded) keys distinctly from every
    concrete mesh, including ``(1,)``.

    DAG configs additionally fold a graph signature — node ids, kinds,
    edges (main + shortcut), pool kinds and per-node relu flags — so
    two configs sharing a name but wired differently (or a config that
    gained a residual edge) never collide.  ``None`` (linear config)
    keys distinctly from an explicit chain-shaped graph.
    """
    alphas = sp.per_layer_alphas(cfg.alpha, len(list(cfg.layers)))
    mesh = (tuple(int(d) for d in mesh_shape)
            if mesh_shape is not None else None)
    graph = getattr(cfg, "graph", None)
    gsig = (None if graph is None else tuple(
        (n.id, n.kind, tuple(n.inputs), n.pool, n.residual_from,
         bool(getattr(n, "relu", True)))
        for n in graph))
    return (getattr(cfg, "name", "spectral-cnn"), int(cfg.fft_size),
            tuple(float(a) for a in alphas), int(batch),
            ("mesh", mesh), ("graph", gsig),
            tuple(sorted((k, repr(v)) for k, v in build_kwargs.items())))


@dataclasses.dataclass
class PlanCache:
    """Keyed, warmable cache of compile-once NetworkPlans.

    ``build_network_plan`` is the expensive offline step (~2 minutes on
    full VGG16: prune + Alg-2 tables + compaction + autotune); a serving
    front end cannot afford it on the request path.  The cache keys plans by
    ``plan_cache_key(cfg, batch)`` and is *warmed* at server startup
    for every batch bucket, so no request ever pays a plan build.

    ``invalidate(key)`` drops one entry (e.g. after the serving layer
    detected a corrupted plan) so the next ``get`` rebuilds it; the
    hit/miss/build/invalidation counters, cumulative build seconds and
    their split by plan phase (``phase_s``: the plans' ``phase_s``
    summed, see ``spans.PLAN_PHASES``) are surfaced via ``stats()`` for
    the serve-level health report.

    ``builder`` is injectable for tests (defaults to
    ``build_network_plan``); extra ``get`` kwargs are forwarded to it.
    """

    builder: Callable | None = None
    _plans: dict = dataclasses.field(default_factory=dict)
    hits: int = 0
    misses: int = 0
    builds: int = 0
    invalidations: int = 0
    build_s: float = 0.0
    phase_s: dict = dataclasses.field(default_factory=dict)

    def warm(self, params: dict, cfg, batches: Sequence[int],
             mesh_shape: Sequence[int] | None = None,
             **build_kwargs) -> dict:
        """Build (or confirm) one plan per batch bucket; returns
        {bucket: key} for the entries warmed."""
        return {int(b): self.key_of(params, cfg, int(b),
                                    mesh_shape=mesh_shape,
                                    **build_kwargs)
                for b in batches}

    def key_of(self, params: dict, cfg, batch: int,
               mesh_shape: Sequence[int] | None = None,
               **build_kwargs) -> tuple:
        """``get`` that returns the cache key instead of the plan."""
        self.get(params, cfg, batch, mesh_shape=mesh_shape,
                 **build_kwargs)
        return plan_cache_key(cfg, batch, mesh_shape=mesh_shape,
                              **build_kwargs)

    def get(self, params: dict, cfg, batch: int,
            mesh_shape: Sequence[int] | None = None,
            **build_kwargs) -> NetworkPlan:
        # mesh_shape participates in the KEY only: builders that target
        # a mesh (e.g. a closure over build_sharded_network_plan) carry
        # the topology themselves, and build_network_plan has no mesh
        # concept — but both must key by it (cross-mesh poisoning).
        key = plan_cache_key(cfg, batch, mesh_shape=mesh_shape,
                             **build_kwargs)
        plan = self._plans.get(key)
        if plan is not None:
            self.hits += 1
            return plan
        self.misses += 1
        import time as _time
        t0 = _time.perf_counter()
        builder = self.builder or build_network_plan
        plan = builder(params, cfg, batch=batch, **build_kwargs)
        self.build_s += _time.perf_counter() - t0
        self.builds += 1
        for phase, sec in getattr(plan, "phase_s", {}).items():
            self.phase_s[phase] = self.phase_s.get(phase, 0.0) + sec
        self._plans[key] = plan
        return plan

    def invalidate(self, key: tuple) -> bool:
        """Drop one entry; the next ``get`` for its key rebuilds."""
        if key in self._plans:
            del self._plans[key]
            self.invalidations += 1
            return True
        return False

    def __len__(self) -> int:
        return len(self._plans)

    def stats(self) -> dict:
        return {"entries": len(self._plans), "hits": self.hits,
                "misses": self.misses, "builds": self.builds,
                "invalidations": self.invalidations,
                "build_s": self.build_s, "phase_s": dict(self.phase_s)}


# ---------------------------------------------------------------------------
# Sharded plans (multi-device execution under shard_map)
# ---------------------------------------------------------------------------

def _pad_layer_tables(tabs: Sequence[sch.LayerTables]) -> list[PlanTables]:
    """Pad per-shard Alg-2 tables to a common cycle count T.

    Channel shards schedule DIFFERENT kernel slices, so their exact-cover
    schedules can differ in length; ``shard_map`` stacks the per-shard
    operands into one array and needs uniform shapes.  Padded cycles
    carry idx=0, sel=0 and vr=vi=0.0 — the zero weight kills both the
    MAC and the scatter contribution, so they are inert (the same
    convention ``scheduler.compile_layer_tables`` uses for its own
    padding).
    """
    t_max = max(t.idx.shape[2] for t in tabs)
    out = []
    for t in tabs:
        pad_t = t_max - t.idx.shape[2]
        pads4 = ((0, 0), (0, 0), (0, pad_t), (0, 0))
        out.append(PlanTables(
            jnp.asarray(np.pad(t.idx, pads4)),
            jnp.asarray(np.pad(t.sel, pads4)),
            jnp.asarray(np.pad(t.vr, pads4)),
            jnp.asarray(np.pad(t.vi, pads4))))
    return out


@dataclasses.dataclass(frozen=True, eq=False)
class ShardedLayerPlan:
    """One conv layer's multi-device execution plan.

    ``base`` is the unsharded ``LayerPlan`` (the single-device truth:
    full geometry, full kernels, the fused epilogue and the pool-after
    flag — always executable as-is, and the terminal fallback of the
    sharded degradation ladder).  ``shards`` holds the shard-LOCAL
    plans the executor runs under ``shard_map``:

      'replicate'  () — every device executes ``base`` identically;
      'spatial'    (band_plan,) — ONE plan shared by all shards: the
          shard-local layer (``dataflow.shard_local_layer``) over the
          band geometry (``spectral.make_band_geometry``), whose
          ``pre_halo_h`` rows arrive from the left mesh neighbor via
          ``ppermute`` before the kernel runs;
      'channel'    D plans — shard d owns input channels
          [d*M/D, (d+1)*M/D): kernels/planes/tables sliced on the
          channel axis, bias+ReLU DEFERRED (``EpilogueSpec(False,
          False)``) because shard outputs are partial sums — the
          executor applies ``base.epilogue`` after the psum.

    ``tuning`` is the two-level Alg-1 verdict (``autotune.ShardTuning``)
    that chose the strategy; ``provenance`` audits shard-level
    demotions (``resilience.harden_sharded_plan``).  ``operands``
    (channel only) are the shards' kernels and tables stacked on a
    leading device axis (``stack_shard_operands``), placed on the mesh
    when the plan is built for one, so each device keeps its slice
    resident across calls.
    """

    base: LayerPlan
    strategy: str                     # dataflow.SHARD_STRATEGIES
    n_shards: int
    tuning: at.ShardTuning
    shards: tuple[LayerPlan, ...]
    provenance: tuple[str, ...] = ()
    operands: tuple[Array, ...] = ()

    def stats(self) -> dict:
        row = self.base.stats()
        row.update({
            "strategy": self.strategy,
            "n_shards": self.n_shards,
            "ici_bytes": self.tuning.ici_bytes,
            "per_chip_hbm_bytes": self.tuning.per_chip_hbm_bytes,
            "sharded_s": self.tuning.sharded_s,
            "shard_demotions": len(self.provenance),
        })
        return row


@dataclasses.dataclass(frozen=True, eq=False)
class ShardedNetworkPlan:
    """A ``NetworkPlan`` plus its per-layer partitioning for one mesh.

    ``base`` remains fully executable on a single device (it IS the
    parity oracle the sharded tests compare against); ``layers`` align
    1:1 with ``base.layers``.  ``mesh_shape`` records the device
    topology the plan was built for — a plan built for one mesh must
    never serve another (see ``plan_cache_key(mesh_shape=...)``).
    """

    base: NetworkPlan
    n_shards: int
    mesh_shape: tuple[int, ...]
    layers: tuple[ShardedLayerPlan, ...]
    axis: str = "shard"

    @property
    def name(self) -> str:
        return self.base.name

    @property
    def fft_size(self) -> int:
        return self.base.fft_size

    @property
    def batch(self) -> int:
        return self.base.batch

    @property
    def strategies(self) -> dict[str, str]:
        return {slp.base.layer.name: slp.strategy for slp in self.layers}

    def summary(self) -> list[dict]:
        return [slp.stats() for slp in self.layers]


def _band_tables(lp: LayerPlan, tn: at.FusedTuning,
                 schedule_r: int) -> PlanTables | None:
    """Tables for the spatial band plan (full channels; reuses the base
    tables when the tuned blocks agree, recompiles otherwise)."""
    if tn.hadamard != "scheduled":
        return None
    n, m = lp.layer.c_out, lp.layer.c_in
    bt = lp.tuning
    if (lp.tables is not None
            and min(tn.block_n, n) == min(bt.block_n, n)
            and min(tn.block_m, m) == min(bt.block_m, m)):
        return lp.tables
    k2 = lp.geo.fft_size ** 2
    lt = sch.compile_layer_tables(
        np.asarray(lp.kernels.indices),
        np.asarray(lp.kernels.values).reshape(n, m, k2),
        k2, schedule_r, min(tn.block_n, n),
        active=lp.active, m_pad_to=min(tn.block_m, m))
    return PlanTables(jnp.asarray(lt.idx), jnp.asarray(lt.sel),
                      jnp.asarray(lt.vr), jnp.asarray(lt.vi))


def stack_shard_operands(shards: Sequence[LayerPlan],
                         tables: Sequence[PlanTables | None] | None = None
                         ) -> tuple[Array, ...]:
    """(wr, wi[, idx, sel, vr, vi]) of channel shard plans stacked on a
    leading device axis, shard d's slice at index d.  ``tables``
    overrides the shards' own tables (the executor's fault-site
    staging may rewrite them)."""
    if tables is None:
        tables = [sh.tables for sh in shards]
    ops = (jnp.stack([sh.wr for sh in shards]),      # [D, Fa, N, Mloc]
           jnp.stack([sh.wi for sh in shards]))
    if tables[0] is not None:
        ops += tuple(jnp.stack([jnp.asarray(getattr(tb, f))
                                for tb in tables])
                     for f in PlanTables._fields)
    return ops


def make_sharded_layer_plan(lp: LayerPlan, st: at.ShardTuning,
                            n_shards: int, *,
                            schedule_r: int = df.SCHEDULE_R,
                            mesh=None) -> ShardedLayerPlan:
    """Construct the shard-local plans for one layer (see
    ``ShardedLayerPlan``).  Also the REBUILD step of the sharded
    degradation ladder: after ``resilience`` demotes the base plan one
    rung, calling this again re-derives consistent shard plans.
    Given a ``mesh``, channel operands are placed on it.

    A base plan demoted off the fused backend executes replicated —
    sharded execution is a fused-kernel path; 'staged'/'einsum' rungs
    run the whole base plan on every device, with no collective (a
    plan-level, uniform decision, so no device can be left waiting on
    a collective).
    """
    strategy = st.strategy
    if (n_shards <= 1 or strategy == "replicate"
            or lp.backend != "fused"):
        return ShardedLayerPlan(
            base=lp, strategy="replicate", n_shards=n_shards,
            tuning=st, shards=())
    local = df.shard_local_layer(lp.layer, lp.geo.fft_size, n_shards,
                                 strategy)
    if local is None:                 # infeasible at this D: replicate
        return ShardedLayerPlan(
            base=lp, strategy="replicate", n_shards=n_shards,
            tuning=st, shards=())
    tn = st.base
    hadamard = tn.hadamard or lp.hadamard
    input_mode = tn.input_mode or lp.input_mode
    if strategy == "spatial":
        tr = spec.shard_band_rows(lp.geo, n_shards)
        band_geo = spec.make_band_geometry(lp.geo, tr)
        band = dataclasses.replace(
            lp, layer=local, geo=band_geo, tuning=tn,
            epilogue=dataclasses.replace(lp.epilogue, pool=False),
            hadamard=hadamard, input_mode=input_mode,
            tables=_band_tables(lp, tn, schedule_r))
        return ShardedLayerPlan(base=lp, strategy="spatial",
                                n_shards=n_shards, tuning=st,
                                shards=(band,))
    # channel: slice kernels/planes/tables on the input-channel axis;
    # shard outputs are PARTIAL sums, so bias+ReLU defer to post-psum.
    mloc = local.c_in
    k2 = lp.geo.fft_size ** 2
    no_epi = EpilogueSpec(bias=False, relu=False, pool=False)
    zero_bias = jnp.zeros_like(lp.bias)
    sliced = []
    raw_tables: list[sch.LayerTables] = []
    for d in range(n_shards):
        sl = slice(d * mloc, (d + 1) * mloc)
        sk = lp.kernels
        skd = sp.SparseSpectralKernels(
            values=sk.values[:, sl], mask=sk.mask[:, sl],
            indices=sk.indices[:, sl], alpha=sk.alpha,
            active_bins=sk.active_bins)
        sliced.append(skd)
        if hadamard == "scheduled":
            raw_tables.append(sch.compile_layer_tables(
                np.asarray(skd.indices),
                np.asarray(skd.values).reshape(lp.layer.c_out, mloc, k2),
                k2, schedule_r, min(tn.block_n, lp.layer.c_out),
                active=lp.active, m_pad_to=min(tn.block_m, mloc)))
    tables = (_pad_layer_tables(raw_tables) if raw_tables
              else [None] * n_shards)
    shards = tuple(
        dataclasses.replace(
            lp, layer=local, kernels=sliced[d], tuning=tn,
            epilogue=no_epi, bias=zero_bias,
            wr=lp.wr[:, :, d * mloc:(d + 1) * mloc],
            wi=lp.wi[:, :, d * mloc:(d + 1) * mloc],
            hadamard=hadamard, input_mode=input_mode,
            schedule_cycles=(raw_tables[d].total_cycles
                             if raw_tables else lp.schedule_cycles),
            pe_utilization=(raw_tables[d].pe_utilization
                            if raw_tables else lp.pe_utilization),
            tables=tables[d])
        for d in range(n_shards))
    operands = stack_shard_operands(shards)
    if mesh is not None:
        from jax.sharding import NamedSharding

        from repro.distributed import sharding as shd
        operands = jax.device_put(
            operands, NamedSharding(mesh, shd.spectral_stacked_spec()))
    return ShardedLayerPlan(base=lp, strategy="channel",
                            n_shards=n_shards, tuning=st, shards=shards,
                            operands=operands)


def resharded_layer_plan(slp: ShardedLayerPlan, new_base: LayerPlan, *,
                         schedule_r: int = df.SCHEDULE_R,
                         note: str | None = None) -> ShardedLayerPlan:
    """Rebuild a ``ShardedLayerPlan`` around a demoted base plan.

    The shard-local tuning inherits the demoted base's hadamard /
    input-mode so shard plans track the base down the ladder; once the
    base leaves the fused backend, ``make_sharded_layer_plan`` collapses
    the strategy to 'replicate' (terminal rung — structurally immune to
    collective hangs because it runs no collective at all).
    """
    tn = dataclasses.replace(slp.tuning.base,
                             hadamard=new_base.hadamard,
                             input_mode=new_base.input_mode)
    st = dataclasses.replace(slp.tuning, base=tn)
    rebuilt = make_sharded_layer_plan(new_base, st, slp.n_shards,
                                      schedule_r=schedule_r)
    prov = slp.provenance + ((note,) if note else ())
    return dataclasses.replace(rebuilt, provenance=prov)


def build_sharded_network_plan(params: dict, cfg, *,
                               n_shards: int,
                               mesh_shape: Sequence[int] | None = None,
                               batch: int = 1,
                               strategies: Sequence[str] | None = None,
                               validate: bool = True,
                               base: NetworkPlan | None = None,
                               mesh=None,
                               **build_kwargs) -> ShardedNetworkPlan:
    """Compile a ``NetworkPlan`` AND its per-layer partitioning.

    Builds the single-device base plan first (``build_network_plan``,
    which also serves as the parity oracle), then runs the two-level
    Alg-1 (``autotune.autotune_layer_sharded``) per layer over the
    surviving hadamard/input-mode candidates and materializes the
    shard-local plans (``make_sharded_layer_plan``).

    ``mesh_shape`` defaults to ``(n_shards,)``; ``strategies`` restricts
    the partitioning search (e.g. ``("channel",)`` for a forced-mode
    test).  Remaining kwargs flow to ``build_network_plan`` and the
    relevant ones (vmem budget, blocks, schedule knobs) are re-read for
    the sharded tuner so both levels cost the same machine.  ``base``
    is a ``NetworkPlan`` already built from the same params, config,
    batch and kwargs; it is reused instead of built again (the host
    build of a full-size plan takes minutes).  ``mesh`` is the device
    mesh the plan will run on: channel-sharded kernels are placed on it
    once, here (without it the executor moves them on every call).
    """
    if base is None:
        base = build_network_plan(params, cfg, batch=batch,
                                  validate=validate, **build_kwargs)
    elif base.batch != batch:
        raise ValueError(f"base plan was built for batch {base.batch}, "
                         f"not {batch}")
    vmem_budget = build_kwargs.get("vmem_budget", df.TPU_VMEM_BYTES)
    blocks = build_kwargs.get("blocks", at.BLOCK_CANDIDATES)
    hw_safe = build_kwargs.get("hw_safe", True)
    schedule = build_kwargs.get("schedule", True)
    schedule_r = build_kwargs.get("schedule_r", 10)
    schedule_mu = build_kwargs.get("schedule_mu", df.SCHEDULE_MU)
    step_overhead_s = build_kwargs.get("step_overhead_s", 0.0)
    hadamard = build_kwargs.get("hadamard", "auto")
    input_mode = build_kwargs.get("input_mode", "auto")

    slayers = []
    for lp in base.layers:
        modes = _resolve_hadamard_modes(hadamard, lp.alpha, schedule,
                                        lp.active)
        imodes = _resolve_input_modes(input_mode)
        # Residual layers charge the shortcut at BOTH levels: the
        # per-chip fused pricing (placement from the base tuning) and
        # the extra (D-1)/D ICI term ``shard_ici_bytes`` adds for
        # moving the Y-sized shortcut into the shards' layout.
        residual = None
        if getattr(lp.epilogue, "residual", None) is not None:
            residual = (lp.tuning.residual or "hbm"
                        if lp.epilogue.residual == "fused" else "hbm")
        st = at.autotune_layer_sharded(
            lp.layer, base.fft_size, lp.alpha, n_shards=n_shards,
            strategies=strategies, batch=batch,
            vmem_budget=vmem_budget, blocks=blocks, hw_safe=hw_safe,
            active_bins=(len(lp.active) if lp.active is not None
                         else None),
            hadamard_modes=modes, input_modes=imodes,
            schedule_r=schedule_r, schedule_mu=schedule_mu,
            step_overhead_s=step_overhead_s, residual=residual)
        slayers.append(make_sharded_layer_plan(lp, st, n_shards,
                                               schedule_r=schedule_r,
                                               mesh=mesh))
    splan = ShardedNetworkPlan(
        base=base, n_shards=n_shards,
        mesh_shape=(tuple(int(d) for d in mesh_shape)
                    if mesh_shape is not None else (n_shards,)),
        layers=tuple(slayers))
    if validate:
        res.validate_sharded_plan(splan, vmem_budget=vmem_budget,
                                  hw_safe=hw_safe)
    return splan
