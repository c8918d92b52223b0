"""Alg 1 on TPU: per-layer flow + block-size selection for the fused
spectral-conv kernel.

The paper's Alg 1 searches architecture parameters (P', N') and per-layer
streaming parameters (Ps, Ns) minimizing the worst-case DDR bandwidth
under a BRAM cap.  On TPU the analogous knobs of one fused pallas_call
(``kernels.fused_spectral_conv``) are

  flow      in {output_stationary, weight_stationary, input_stationary}
            — which operand block stays resident in VMEM between grid
            steps (the paper's reuse-kernels / reuse-activations /
            reuse-psums choice),
  block_n / block_m / block_p
            — the VMEM block sizes (the paper's N', M', P'),

and the BRAM cap becomes the VMEM budget.  The analytic model is
``dataflow.tpu_fused_flow_cost``; exactly as Alg 1, we enumerate the
candidate grid, drop configurations over budget, and keep the predicted-
latency argmin.  When a measurement callable is supplied (i.e. the fused
kernel can actually run — always true in interpret mode, but wall time is
only a *ranking* signal on real TPU), the top candidates by prediction
are re-ranked by measured time, mirroring the paper's practice of
validating Alg 1's pick against the implemented design.

The per-layer result is baked into ``core.plan.LayerPlan`` (the
compile-once IR ``models.cnn.forward_spectral`` executes) and feeds
``benchmarks/e2e_latency.py``.  The cost model is sparsity-aware — see
``autotune_layer(active_bins=...)`` — so Alg 1's choice reflects the
kernel Alg 2 compressed.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Callable, Iterable, Sequence

from repro.core import dataflow as df
from repro.core import spectral as spec
from repro.core.dataflow import FLOWS

# Power-of-two VMEM block candidates; clamped to each layer's dims, and
# only those the TPU's (8, 128) tiling admits are searched
# (``dataflow.tiling_violations``).
BLOCK_CANDIDATES = (32, 64, 128, 256)


def predict_seconds(c: dict) -> float:
    """Roofline latency of one cost-model row: pipelined kernel time
    plus any serial host-side pass (the windowed input path's window
    relayout — ``dataflow.tpu_fused_flow_cost`` 'serial_s'; staged
    ``tpu_flow_cost`` rows have none).  Public because the degradation
    ladder (``core.resilience.demote_layer``) re-prices demoted
    configurations through the same formula, keeping
    ``FusedTuning.predicted_s`` honest after a demotion.  'step_s' is
    the per-grid-step dispatch overhead term (zero unless the caller
    priced the model with ``step_overhead_s`` — the interpret-mode
    serving stack does, see ``dataflow.INTERPRET_STEP_S``)."""
    return (c.get("serial_s", 0.0) + c.get("step_s", 0.0)
            + max(c["hbm_s"], c["compute_s"]))


_predict = predict_seconds


@dataclasses.dataclass(frozen=True)
class FusedTuning:
    """Chosen fused-kernel configuration for one conv layer.

    ``hadamard`` is the Hadamard-stage mode (``df.HADAMARD_MODES``)
    when the tuner searched the mode axis, or None when it ran in
    legacy single-datapath mode (the cost model's compressed-stream
    default).  ``input_mode`` is the input path (``df.INPUT_MODES``)
    when the tuner searched that axis, or None (= 'windowed') in
    legacy mode.
    """

    layer: str
    flow: str
    block_n: int
    block_m: int
    block_p: int
    hbm_bytes: float
    vmem_bytes: float
    predicted_s: float           # serial_s + step_s + max(hbm_s, compute_s)
    measured_s: float | None = None
    hadamard: str | None = None
    input_mode: str | None = None
    grid_steps: float | None = None   # gn*gm*gp of the priced grid
    residual: str | None = None       # shortcut placement: 'hbm'|'vmem'

    def kwargs(self) -> dict:
        """Keyword arguments for ``fused_spectral_conv2d`` — includes
        the tuned ``input_mode`` so callers applying a halo-tuned
        config don't silently run the windowed path.  The Hadamard
        mode is NOT included (the scheduled datapath needs tables and
        a different entry point — dispatch on ``self.hadamard``)."""
        return {"flow": self.flow, "block_n": self.block_n,
                "block_m": self.block_m, "block_p": self.block_p,
                "input_mode": self.input_mode or "windowed"}


def _layer_candidates(layer: df.ConvLayer, fft_size: int, batch: int,
                      blocks: Sequence[int], hw_safe: bool,
                      flows: Sequence[str] = FLOWS
                      ) -> Iterable[tuple[str, int, int, int]]:
    # ``hw_safe`` is accepted for API compatibility but no longer prunes:
    # the RMW flows accumulate through manually DMA'd tiles (PR 8), so a
    # non-consecutive output revisit is legal on hardware for every
    # (flow, block) combination.
    del hw_safe
    t = layer.tiles(fft_size) * batch
    # Full-dimension blocks join the power-of-two candidates so that the
    # configuration space at batch B strictly contains the batch-1 space
    # (per-image tiles stay a candidate at every batch — this is what
    # makes the per-image predicted cost non-increasing in batch along
    # the doubling chain; see tests/test_batch_amortized.py).
    bns = sorted({min(b, layer.c_out) for b in blocks} | {layer.c_out})
    bms = sorted({min(b, layer.c_in) for b in blocks} | {layer.c_in})
    t_img = layer.tiles(fft_size)
    doubling = {t_img * (1 << i)
                for i in range(max(1, batch).bit_length())}
    bps = sorted({min(b, t) for b in blocks} | {t}
                 | {d for d in doubling if d <= t})
    for flow, bn, bm, bp in itertools.product(flows, bns, bms, bps):
        yield flow, bn, bm, bp


def _legal_block_p(layer: df.ConvLayer, fft_size: int, batch: int,
                   bn: int, bm: int, bp: int, mode: str | None,
                   imode: str | None, geo=None) -> int | None:
    """The block_p a candidate runs with, or None when Mosaic would
    refuse its blocks (``dataflow.tiling_violations``).  A windowed
    block_p sits on the lane axis, so it is recorded as the lane-padded
    block the kernel streams (a 9-tile layer runs one 128-lane block);
    a halo block_p is a tile budget and is kept."""
    if df.tiling_violations(layer, fft_size, bn, bm, bp, hadamard=mode,
                            input_mode=imode, geo=geo):
        return None
    if imode == "halo":
        return bp
    return df.lane_round(min(bp, layer.tiles(fft_size) * batch))


def autotune_layer(layer: df.ConvLayer, fft_size: int, alpha: float, *,
                   batch: int = 1,
                   vmem_budget: int = df.TPU_VMEM_BYTES,
                   blocks: Sequence[int] = BLOCK_CANDIDATES,
                   hw_safe: bool = True,
                   flows: Sequence[str] = FLOWS,
                   active_bins: int | None = None,
                   hadamard_modes: Sequence[str] | None = None,
                   input_modes: Sequence[str] | None = None,
                   schedule_r: int = df.SCHEDULE_R,
                   schedule_mu: float = df.SCHEDULE_MU,
                   step_overhead_s: float = 0.0,
                   residual: str | None = None,
                   cost_fn: Callable | None = None,
                   measure_fn: Callable[[FusedTuning], float] | None = None,
                   measure_top_k: int = 3) -> FusedTuning:
    """Pick (flow, block_n, block_m, block_p[, hadamard]) for one layer.

    Analytic pass: minimize the roofline latency max(hbm_s, compute_s)
    over all in-budget candidates (ties break toward fewer HBM bytes).
    The cost model is sparsity-aware: kernel traffic and Hadamard MACs
    scale with nnz = K^2/alpha and the spectral-transform dims with
    ``active_bins`` (pass the plan's compacted bin count so Alg 1 sees
    exactly the kernel Alg 2 compressed — this is where the two
    algorithms compose).

    ``hadamard_modes`` adds the third search axis: a subset of
    ``df.HADAMARD_MODES`` to rank per candidate (e.g. ('bin',
    'scheduled')), costed via ``cost_fn(..., hadamard=mode,
    r=schedule_r, mu=schedule_mu)``; the winning mode lands in
    ``FusedTuning.hadamard``.  None (default) keeps the legacy
    single-datapath behavior — the cost model's compressed-stream
    default and ``hadamard=None`` on the result.

    ``input_modes`` adds the fourth axis: a subset of
    ``df.INPUT_MODES`` ranking the host-materialized window stream
    against the in-kernel halo gather per candidate; the winner lands
    in ``FusedTuning.input_mode``.  None keeps the legacy windowed
    costing and ``input_mode=None`` on the result.

    ``step_overhead_s`` prices a fixed cost per grid step (gn*gm*gp),
    landing in the cost rows' 'step_s'.  The default 0.0 keeps the
    pure byte/flop roofline; the interpret-mode serving stack and the
    benchmarks pass ``dataflow.INTERPRET_STEP_S`` so per-bucket plans
    minimize the wall clock of the backend that actually runs.

    ``hw_safe`` is accepted for API compatibility but is a no-op since
    PR 8: the fused kernel accumulates through manually DMA'd tiles,
    so every (flow, block, input_mode, batch) combination is legal on
    hardware — including halo + weight-stationary at batch > 1.

    ``residual`` prices a fused shortcut add on the epilogue flush
    ('hbm' streams the shortcut back from HBM, 'vmem' holds it on-chip
    as retained bytes — the ShortcutFusion reuse decision, see
    ``dataflow.tpu_fused_flow_cost(residual=...)``); the placement is
    recorded in ``FusedTuning.residual``.

    Measured pass (optional): re-rank the ``measure_top_k`` best
    analytic candidates by ``measure_fn`` wall time.  ``cost_fn``
    defaults to the fused kernel's model; pass
    ``dataflow.tpu_flow_cost`` to tune the staged Hadamard under the
    same selection policy.
    """
    if cost_fn is None:
        cost_fn = df.tpu_fused_flow_cost
    modes: Sequence[str | None] = (
        [None] if hadamard_modes is None else list(hadamard_modes))
    imodes: Sequence[str | None] = (
        [None] if input_modes is None else list(input_modes))

    def cost(bn, bp, bm, flow, mode, imode):
        kw = {} if mode is None else {"hadamard": mode, "r": schedule_r,
                                      "mu": schedule_mu}
        if imode is not None:
            kw["input_mode"] = imode
        if step_overhead_s:
            kw["step_overhead_s"] = step_overhead_s
        if residual is not None:
            kw["residual"] = residual
        return cost_fn(layer, fft_size, alpha, bn, bp, bm, flow,
                       batch=batch, active_bins=active_bins, **kw)

    scored: list[FusedTuning] = []
    over: list[FusedTuning] = []
    seen = set()
    for flow, bn, bm, bp in _layer_candidates(layer, fft_size, batch,
                                              blocks, hw_safe, flows):
        for mode in modes:
            for imode in imodes:
                bp_ = _legal_block_p(layer, fft_size, batch, bn, bm, bp,
                                     mode, imode)
                if bp_ is None or (flow, bn, bm, bp_, mode, imode) in seen:
                    continue
                seen.add((flow, bn, bm, bp_, mode, imode))
                c = cost(bn, bp_, bm, flow, mode, imode)
                (over if c["vmem_bytes"] > vmem_budget else scored).append(
                    FusedTuning(
                        layer.name, flow, bn, bm, bp_, c["hbm_bytes"],
                        c["vmem_bytes"], _predict(c),
                        hadamard=mode, input_mode=imode,
                        grid_steps=c.get("grid_steps"),
                        residual=residual))
    if not scored:
        # Nothing fits the budget: return the smallest-footprint legal
        # config anyway.  Interpret mode runs it regardless; on real TPU
        # an over-budget working set fails at Mosaic compile time, so
        # the caller sees vmem_bytes > budget on the returned tuning and
        # can shrink blocks/batch before hitting that opaque error.
        return min(over, key=lambda tn: (tn.vmem_bytes, tn.predicted_s))
    scored.sort(key=lambda tn: (tn.predicted_s,
                                tn.grid_steps if tn.grid_steps is not None
                                else 0.0,
                                tn.hbm_bytes))
    if measure_fn is None:
        return scored[0]
    best, best_t = None, float("inf")
    for cand in scored[:measure_top_k]:
        t = measure_fn(cand)
        if t < best_t:
            best, best_t = cand, t
    return dataclasses.replace(best, measured_s=best_t)


def autotune_network(layers: Sequence[df.ConvLayer] = df.VGG16_LAYERS,
                     fft_size: int = 8,
                     alpha: "float | Sequence[float]" = 4.0, *,
                     batch: int = 1,
                     vmem_budget: int = df.TPU_VMEM_BYTES,
                     blocks: Sequence[int] = BLOCK_CANDIDATES,
                     hw_safe: bool = True,
                     active_bins: dict[str, int] | None = None,
                     hadamard_modes: Sequence[str] | None = None,
                     input_modes: Sequence[str] | None = None,
                     schedule_r: int = df.SCHEDULE_R,
                     schedule_mu: float = df.SCHEDULE_MU,
                     step_overhead_s: float = 0.0,
                     measure: bool = False,
                     interpret: bool | None = None
                     ) -> dict[str, FusedTuning]:
    """Alg-1-on-TPU over a conv stack -> {layer name: FusedTuning}.

    Args:
      layers: the conv stack to tune (default: the paper's VGG16).
      fft_size: spectral tile size K.
      alpha: kernel compression ratio — a scalar broadcasts, a sequence
        supplies one alpha per layer (the paper prunes non-uniformly).
      batch: images per forward call; scales the tile count the blocks
        are chosen against (plans are batch-specific, see
        ``models.cnn.forward_spectral``).
      vmem_budget: the BRAM-cap analogue — candidates whose working set
        exceeds it are dropped.
      blocks: candidate block sizes for each of block_n/block_m/block_p
        (clamped to the layer dims).
      hw_safe: accepted for API compatibility; a no-op since PR 8
        (manual-DMA accumulators make every configuration legal on
        hardware).
      active_bins: optional {layer name: Fa} — the compacted bin count
        realized by that layer's pruned kernels, so the cost model sees
        the kernel Alg 2 compressed.
      hadamard_modes: optional subset of ``df.HADAMARD_MODES`` to rank
        as a third search axis per layer (None = legacy single
        datapath); the winner lands in ``FusedTuning.hadamard``.
      input_modes: optional subset of ``df.INPUT_MODES`` to rank as a
        fourth axis — windowed stream vs in-kernel halo gather (None =
        legacy windowed costing); the winner lands in
        ``FusedTuning.input_mode``.
      schedule_r / schedule_mu: Alg-2 replica count and estimated Eq-14
        utilization used to cost 'scheduled' candidates — keep them in
        sync with what the tables will actually be compiled with.
      step_overhead_s: fixed cost per grid step added to predictions
        (``dataflow.INTERPRET_STEP_S`` for interpret-mode serving;
        default 0.0 keeps the pure roofline).
      measure: re-rank top analytic candidates by wall time on
        synthetic layer data (``interpret`` as in the kernels).

    Returns {layer name: ``FusedTuning``}.
    """
    from repro.core.sparse import per_layer_alphas

    layers = list(layers)
    alphas = per_layer_alphas(alpha, len(layers))
    plan: dict[str, FusedTuning] = {}
    for layer, a in zip(layers, alphas):
        measure_fn = None
        if measure:
            measure_fn = _make_measure_fn(layer, fft_size, a, batch,
                                          interpret)
        plan[layer.name] = autotune_layer(
            layer, fft_size, a, batch=batch, vmem_budget=vmem_budget,
            blocks=blocks, hw_safe=hw_safe,
            active_bins=(active_bins or {}).get(layer.name),
            hadamard_modes=hadamard_modes, input_modes=input_modes,
            schedule_r=schedule_r, schedule_mu=schedule_mu,
            step_overhead_s=step_overhead_s,
            measure_fn=measure_fn)
    return plan


def _make_measure_fn(layer: df.ConvLayer, fft_size: int, alpha: float,
                     batch: int, interpret: bool | None
                     ) -> Callable[[FusedTuning], float]:
    """Wall-clock one fused pallas_call on synthetic layer data, pruned
    to ``alpha`` so the measured workload (active-bin compaction
    included) is the one the plan will execute."""
    import time

    import jax
    import jax.numpy as jnp

    from repro.core import sparse as sp
    from repro.core import spectral as spec
    from repro.kernels.fused_spectral_conv import (
        fused_spectral_conv2d, fused_spectral_conv2d_scheduled)

    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (batch, layer.c_in, layer.h_in, layer.w_in),
                          jnp.float32)
    w = jax.random.normal(key, (layer.c_out, layer.c_in, layer.ksize,
                                layer.ksize), jnp.float32)
    geo = spec.make_geometry(layer.h_in, layer.w_in, layer.ksize, fft_size,
                             layer.pad)
    w_f = spec.spectral_kernel(w, fft_size)
    if alpha > 1.0:
        w_f = sp.prune_magnitude(w_f, alpha)

    def measure(tn: FusedTuning, iters: int = 3) -> float:
        imode = tn.input_mode or "windowed"
        if tn.hadamard == "scheduled" and hasattr(w_f, "values"):
            # Compile the Alg-2 tables ONCE per candidate, outside the
            # timing loop — the wall time ranked here must be the
            # kernel's, not the host scheduler's.
            from repro.core import scheduler as sch
            import numpy as np
            k2 = fft_size * fft_size
            tabs = sch.compile_layer_tables(
                np.asarray(w_f.indices),
                np.asarray(w_f.values).reshape(w_f.n_out, w_f.n_in, k2),
                k2, df.SCHEDULE_R, min(tn.block_n, w_f.n_out),
                active=sp.compacted_active_bins(w_f),
                m_pad_to=min(tn.block_m, w_f.n_in))
            fn = lambda: fused_spectral_conv2d_scheduled(
                x, w_f, geo, n_par=tn.block_n, flow=tn.flow,
                block_m=tn.block_m, block_p=tn.block_p, tables=tabs,
                input_mode=imode, interpret=interpret)
        else:
            fn = lambda: fused_spectral_conv2d(x, w_f, geo,
                                               interpret=interpret,
                                               **tn.kwargs())
        fn().block_until_ready()          # compile
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn()
        out.block_until_ready()
        return (time.perf_counter() - t0) / iters

    return measure


# ---------------------------------------------------------------------------
# Two-level Alg-1: strategy x (flow, blocks, modes) per layer (ISSUE 9)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardTuning:
    """Chosen (partitioning strategy, shard-local kernel config) for one
    conv layer on a D-shard mesh.

    ``base`` is the per-chip ``FusedTuning`` of the shard-local
    sub-problem (``dataflow.shard_local_layer``): its blocks are clamped
    to the LOCAL dims (channel sharding tunes against c_in/D), and its
    ``predicted_s`` is the per-chip roofline WITHOUT the collective —
    ``sharded_s = predicted_s + ici_s`` is the two-level objective this
    tuning minimizes.
    """

    base: FusedTuning
    strategy: str                # one of dataflow.SHARD_STRATEGIES
    n_shards: int
    ici_bytes: float
    ici_s: float
    per_chip_hbm_bytes: float
    sharded_s: float


def autotune_layer_sharded(layer: df.ConvLayer, fft_size: int,
                           alpha: float, *, n_shards: int,
                           strategies: Sequence[str] | None = None,
                           batch: int = 1,
                           vmem_budget: int = df.TPU_VMEM_BYTES,
                           blocks: Sequence[int] = BLOCK_CANDIDATES,
                           hw_safe: bool = True,
                           flows: Sequence[str] = FLOWS,
                           active_bins: int | None = None,
                           hadamard_modes: Sequence[str] | None = None,
                           input_modes: Sequence[str] | None = None,
                           schedule_r: int = df.SCHEDULE_R,
                           schedule_mu: float = df.SCHEDULE_MU,
                           step_overhead_s: float = 0.0,
                           residual: str | None = None) -> ShardTuning:
    """Pick (strategy, flow, blocks[, hadamard, input_mode]) for one
    layer on a ``n_shards``-device mesh — Alg 1 run one level up.

    The candidate grid is the per-strategy product of
    ``dataflow.SHARD_STRATEGIES`` (infeasible strategies drop out:
    channel needs D | c_in, spatial needs a tile row per shard;
    'replicate' is always feasible, so the search never comes back
    empty) with the usual (flow, block) grid enumerated against the
    SHARD-LOCAL layer dims.  Every candidate is priced by
    ``dataflow.tpu_sharded_flow_cost`` and ranked by ``sharded_s`` =
    per-chip roofline + ICI serialization, ties toward fewer grid steps
    then fewer total (HBM + ICI) bytes — the same policy as
    ``autotune_layer`` with the collective folded in.
    """
    strategies = (df.SHARD_STRATEGIES if strategies is None
                  else list(strategies))
    modes: Sequence[str | None] = (
        [None] if hadamard_modes is None else list(hadamard_modes))
    imodes: Sequence[str | None] = (
        [None] if input_modes is None else list(input_modes))
    scored: list[ShardTuning] = []
    for strategy in strategies:
        local = df.shard_local_layer(layer, fft_size, n_shards, strategy)
        if local is None:
            continue
        geo = None                    # spatial shards run the band geometry
        if strategy == "spatial" and n_shards > 1:
            full = spec.make_geometry(layer.h_in, layer.w_in, layer.ksize,
                                      fft_size, layer.pad)
            geo = spec.make_band_geometry(
                full, spec.shard_band_rows(full, n_shards))
        seen = set()
        for flow, bn, bm, bp in _layer_candidates(local, fft_size, batch,
                                                  blocks, hw_safe, flows):
            for mode in modes:
                for imode in imodes:
                    bp_ = _legal_block_p(local, fft_size, batch, bn, bm,
                                         bp, mode, imode, geo=geo)
                    if (bp_ is None
                            or (flow, bn, bm, bp_, mode, imode) in seen):
                        continue
                    seen.add((flow, bn, bm, bp_, mode, imode))
                    kw = {} if mode is None else {
                        "hadamard": mode, "r": schedule_r,
                        "mu": schedule_mu}
                    if imode is not None:
                        kw["input_mode"] = imode
                    if step_overhead_s:
                        kw["step_overhead_s"] = step_overhead_s
                    if residual is not None:
                        kw["residual"] = residual
                    c = df.tpu_sharded_flow_cost(
                        layer, fft_size, alpha, bn, bp_, bm, flow,
                        n_shards=n_shards, strategy=strategy,
                        batch=batch, active_bins=active_bins, **kw)
                    if c is None or c["vmem_bytes"] > vmem_budget:
                        continue
                    tn = FusedTuning(
                        layer.name, flow, bn, bm, bp_, c["hbm_bytes"],
                        c["vmem_bytes"], _predict(c), hadamard=mode,
                        input_mode=imode,
                        grid_steps=c.get("grid_steps"),
                        residual=residual)
                    scored.append(ShardTuning(
                        base=tn, strategy=strategy, n_shards=n_shards,
                        ici_bytes=c["ici_bytes"], ici_s=c["ici_s"],
                        per_chip_hbm_bytes=c["per_chip_hbm_bytes"],
                        sharded_s=c["sharded_s"]))
    if not scored:
        # Nothing fit the budget: replicate with the single-chip
        # fallback tuning (autotune_layer's own over-budget escape
        # hatch) so the caller still gets an executable config.
        tn = autotune_layer(
            layer, fft_size, alpha, batch=batch,
            vmem_budget=vmem_budget, blocks=blocks, hw_safe=hw_safe,
            flows=flows, active_bins=active_bins,
            hadamard_modes=hadamard_modes, input_modes=input_modes,
            schedule_r=schedule_r, schedule_mu=schedule_mu,
            step_overhead_s=step_overhead_s, residual=residual)
        return ShardTuning(base=tn, strategy="replicate",
                           n_shards=n_shards, ici_bytes=0.0, ici_s=0.0,
                           per_chip_hbm_bytes=tn.hbm_bytes,
                           sharded_s=tn.predicted_s)
    scored.sort(key=lambda st: (st.sharded_s,
                                st.base.grid_steps
                                if st.base.grid_steps is not None else 0.0,
                                st.per_chip_hbm_bytes + st.ici_bytes))
    return scored[0]


def autotune_network_sharded(layers: Sequence[df.ConvLayer]
                             = df.VGG16_LAYERS,
                             fft_size: int = 8,
                             alpha: "float | Sequence[float]" = 4.0, *,
                             n_shards: int,
                             batch: int = 1,
                             vmem_budget: int = df.TPU_VMEM_BYTES,
                             blocks: Sequence[int] = BLOCK_CANDIDATES,
                             active_bins: dict[str, int] | None = None,
                             hadamard_modes: Sequence[str] | None = None,
                             input_modes: Sequence[str] | None = None,
                             schedule_r: int = df.SCHEDULE_R,
                             schedule_mu: float = df.SCHEDULE_MU,
                             step_overhead_s: float = 0.0
                             ) -> dict[str, ShardTuning]:
    """Two-level Alg-1 over a conv stack -> {layer name: ShardTuning}.
    Per-layer independent (activation layouts are reconciled at layer
    boundaries by the sharded executor, so strategies mix freely —
    channel-heavy late convs typically pick 'channel', large-image
    early convs 'spatial')."""
    from repro.core.sparse import per_layer_alphas

    layers = list(layers)
    alphas = per_layer_alphas(alpha, len(layers))
    return {
        layer.name: autotune_layer_sharded(
            layer, fft_size, a, n_shards=n_shards, batch=batch,
            vmem_budget=vmem_budget, blocks=blocks,
            active_bins=(active_bins or {}).get(layer.name),
            hadamard_modes=hadamard_modes, input_modes=input_modes,
            schedule_r=schedule_r, schedule_mu=schedule_mu,
            step_overhead_s=step_overhead_s)
        for layer, a in zip(layers, alphas)}
