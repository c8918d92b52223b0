"""End-to-end spectral-VGG16 inference latency + HBM-traffic benchmark.

Compares the three conv-stack backends of ``models.cnn.forward_spectral``
— pure-jnp einsum oracle, staged Pallas (3 pallas_calls/layer with
spectral intermediates round-tripping through HBM), and the fused single
pallas_call executing a compile-once ``core.plan.NetworkPlan`` whose
Hadamard stage runs per layer in the mode Alg 1 chose (dense / bin /
scheduled) — and emits ``BENCH_e2e.json``.

  PYTHONPATH=src python -m benchmarks.e2e_latency [--full] [--quick]
      [--json OUT] [--iters N]

``--quick`` is the CI smoke path: smoke-scale model everywhere, no
full-resolution plan build or parity sweeps (the scripts must not
crash; the committed BENCH_e2e.json comes from a full run).

BENCH_e2e.json schema
---------------------
  bench / backend / interpret_mode / model / fft_size / alpha / quick
      run metadata (``interpret_mode`` is true off-TPU: wall times are
      correctness-path trend signals, the analytic numbers are the
      hardware-portable ones).
  latency.{smoke,full}.batch{B}
      plan_build_ms, then {backend}_ms wall-clock per forward call.
      One plan per bucket, tuned AT that batch with the interpret-mode
      per-step overhead priced in (``dataflow.INTERPRET_STEP_S``).
  batch_sweep
      the gating per-bucket table: fused_ms vs einsum_ms at every
      serving bucket and the acceptance boolean
      ``fused_le_einsum_all_buckets`` (CI fails when the fused path
      loses to its own fallback at any bucket — the graduated form of
      the old ``known_gaps`` batch-8 entry).
  plan_build_s
      one-off full-VGG16 plan construction time (prune + Alg 2 +
      compaction + table compilation + autotune).
  layers[]  (one row per conv layer, analytic, at the TUNED config)
      layer / flow / hadamard / input_mode / block_n / block_m / block_p
          the plan's Alg-1 choice, incl. the Hadamard and input modes.
      alpha / nnz / active_bins / pe_utilization / schedule_cycles
          sparsity + Alg-2 stats (exact for scheduled layers).
      launches_fused / launches_staged
          kernel launches per layer (1 vs 3).
      fused_hbm_bytes / fused_hbm_bytes_dense
          total analytic HBM traffic of the fused kernel in the plan's
          modes vs the fully dense datapath (alpha = 1, windowed
          input) at the same config.
      input_hbm_bytes{,_windowed,_halo}
          the input-operand share of HBM traffic (stream * flow
          re-read factor + the one-off materialization / gather-
          selector bytes): the plan's input mode, then both modes at
          the same config.  halo counts raw-plus-halo words read
          straight from the NCHW activation; windowed counts the
          host-materialized [B, M, T, K, K] window tensor (one
          relayout pass + the ~(K/t)^2 duplicated stream).
      halo_lt_windowed
          acceptance flag: halo input bytes < windowed at this config.
      kernel_hbm_bytes{,_dense,_bin,_scheduled}
          the kernel-operand share of HBM traffic (re-read factors
          included): the plan's mode, then each mode at the same
          config.  The scheduled column counts the Alg-2 INDEX/VALUE
          table stream — the paper's O(nnz) kernel traffic — using the
          ACTUAL compiled table bytes when the plan carries tables
          (exact padding), else the nnz/mu analytic estimate.
      table_bytes
          actual bytes of the compiled tables (0 for plane modes).
      hadamard_flops{_dense,_bin,_scheduled}
          Hadamard-stage MACs per mode; the scheduled entry is the
          honest one-hot-matmul realization, not the paper's element
          count.
      scheduled_le_bin
          acceptance flag: scheduled kernel bytes <= bin-compacted.
      staged_os_hadamard_hbm_bytes / staged_fft_io_hbm_bytes /
      fused_le_staged_os / fused_predicted_us /
      staged_hadamard_predicted_us
          the staged-pipeline baseline at its own best blocks;
          ``fused_le_staged_os`` compares the fused kernel against the
          staged pipeline's TOTAL traffic (Hadamard + FFT/IFFT
          round-trips — the three launches it actually needs).
  totals
      aggregates of the above (MB), kernel_bytes vs dense/bin/
      scheduled, input_bytes vs windowed/halo, per-mode layer counts,
      mean Eq-14 PE utilization, launch counts, and the acceptance
      booleans ``all_layers_fused_le_staged_os``,
      ``all_sparse_scheduled_le_bin`` and
      ``all_layers_halo_input_lt_windowed`` (CI asserts the last one).
  parity / parity_sparse
      fused vs spatial (alpha = 1, <= 1e-3) and fused-sparse+epilogue
      vs einsum oracle (alpha = 4, <= 1e-4) on full-resolution VGG16.
  parity_scheduled
      acceptance: the SCHEDULED fused datapath vs the einsum oracle,
      <= 1e-5 — per-layer on the conv5 trio at full channel counts and
      end-to-end on the smoke network with every layer forced
      scheduled.
  parity_halo
      acceptance: the halo input path (in-kernel gather from the raw
      activation) vs the einsum oracle, <= 1e-5, across ALL THREE
      flows x ALL THREE Hadamard modes, plus the max deviation from
      the windowed path (one-hot gather => 0.0).
  sharded  (the multi-device column)
      cost_model: the two-level Alg-1 cost model at D shards (full
      VGG16 at D=8; smoke at D=4 under --quick) — per layer the chosen
      partitioning strategy (spatial / channel / replicate), per-chip
      HBM vs the single-chip autotuned footprint, ICI bytes and
      serialization, plus the gating booleans
      ``strategy_diversity_ge_2`` (Alg 1 run per layer must pick >= 2
      distinct strategies), ``ici_bytes_positive`` and
      ``per_chip_hbm_le_single_chip_all_layers``.
      parity: live end-to-end check (present when >= 2 devices are
      visible, e.g. under XLA_FLAGS=--xla_force_host_platform_device_
      count=8): channel- and spatial-forced ShardedNetworkPlans under
      shard_map vs the single-device einsum oracle, <= 1e-5.
  resnet  (the residual-DAG column, ISSUE 10)
      the ResNet-18-style smoke preset (stride-2 downsample, max/avg
      pool nodes, four residual-FUSED shortcut epilogues) at alpha = 1
      against the spatial DAG oracle (``cnn.forward_spatial``):
      graph composition (n_nodes / n_residual / n_stride2 / pools /
      shortcut_on_chip reuse verdicts), then the gating sections
      ``parity`` (all three backends <= 1e-5), ``shortcuts`` (per
      residual edge the analytic HBM bytes of the residual-fused
      epilogue <= the unfused kernel + 3-Y-pass XLA add;
      ``fused_le_unfused_all``), ``demotion`` (an injected 'lowering'
      fault matched on residual='fused' walks every residual node down
      the residual-fused -> residual-add ladder rung and the hardened
      plan still passes <= 1e-5), and — when >= 2 devices are visible —
      ``sharded`` (channel- and spatial-forced DAG execution under
      shard_map, <= 1e-5).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

STAGED_LAUNCHES_PER_LAYER = 3     # fft8 + spectral_hadamard + ifft8
FUSED_LAUNCHES_PER_LAYER = 1


def _time(fn, iters: int = 3) -> float:
    out = fn()
    jax.block_until_ready(out)            # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def latency_table(cfg, batches=(1, 2, 4, 8),
                  backends=("einsum", "pallas_staged", "pallas_fused"),
                  iters: int = 3) -> dict:
    """Wall-clock per forward call, one plan PER BATCH BUCKET: each
    bucket's plan is tuned at its own batch
    (``dataflow.INTERPRET_STEP_S`` priced in — calibrated to zero, see
    its comment) — the fix for the old batch-8 ``known_gaps`` entry,
    which timed a batch-8 forward on batch-1 block choices."""
    from repro.core import dataflow as df
    from repro.core.plan import build_network_plan
    from repro.models import cnn

    key = jax.random.PRNGKey(0)
    params = cnn.init(key, cfg)
    step_s = (df.INTERPRET_STEP_S if jax.default_backend() != "tpu"
              else 0.0)
    out: dict = {}
    for batch in batches:
        t0 = time.perf_counter()
        plan = build_network_plan(params, cfg, batch=batch,
                                  step_overhead_s=step_s)
        plan_s = time.perf_counter() - t0
        x = jax.random.normal(key, (batch, 3, cfg.image_size,
                                    cfg.image_size), jnp.float32)
        row = {"plan_build_ms": 1e3 * plan_s}
        for backend in backends:
            row[f"{backend}_ms"] = 1e3 * _time(
                lambda b=backend: cnn.forward_spectral(
                    params, plan, x, backend=b),
                iters=iters)
        out[f"batch{batch}"] = row
    return out


def bucket_gate(latency_smoke: dict) -> dict:
    """The gating acceptance check that replaced the ``known_gaps``
    entry: at EVERY serving bucket the fused backend must beat (or
    match) the einsum oracle it would otherwise degrade to."""
    per_bucket = {}
    for name, row in sorted(latency_smoke.items()):
        if "pallas_fused_ms" not in row or "einsum_ms" not in row:
            continue
        per_bucket[name] = {
            "fused_ms": row["pallas_fused_ms"],
            "einsum_ms": row["einsum_ms"],
            "fused_le_einsum": bool(
                row["pallas_fused_ms"] <= row["einsum_ms"]),
        }
    return {
        "per_bucket": per_bucket,
        "fused_le_einsum_all_buckets": all(
            r["fused_le_einsum"] for r in per_bucket.values()),
    }


def per_layer_traffic(plan, fft_size: int, batch: int = 1) -> list[dict]:
    """Analytic per-layer HBM bytes from the plan's tuned fused config:
    the plan's Hadamard mode vs every mode at the SAME config (the
    dense/bin/scheduled trade Alg 1 ranked), vs the staged pipeline's
    output-stationary Hadamard prediction (the fusion saving), plus
    Alg-2 PE utilization."""
    from repro.core import autotune
    from repro.core import dataflow as df

    def best_staged_os(layer, alpha):
        """Give the staged baseline its own best block sizes under the
        SAME selection policy as the fused tuner (not a straw man)."""
        tn = autotune.autotune_layer(
            layer, fft_size, alpha, batch=batch, hw_safe=False,
            flows=("output_stationary",), cost_fn=df.tpu_flow_cost)
        return df.tpu_flow_cost(layer, fft_size, alpha, tn.block_n,
                                tn.block_p, tn.block_m, tn.flow,
                                batch=batch)

    rows = []
    for lp in plan.layers:
        layer, tn = lp.layer, lp.tuning
        fa = lp.n_active_bins
        cost = lambda a, bins, mode, imode: df.tpu_fused_flow_cost(
            layer, fft_size, a, tn.block_n, tn.block_p, tn.block_m,
            tn.flow, batch=batch, active_bins=bins, hadamard=mode,
            input_mode=imode)
        fused_plan = cost(lp.alpha, fa, lp.hadamard, lp.input_mode)
        fused_dense = cost(1.0, None, "dense", "windowed")
        mode_cost = {m: cost(lp.alpha, fa, m, lp.input_mode)
                     for m in df.HADAMARD_MODES}
        input_cost = {im: cost(lp.alpha, fa, lp.hadamard, im)
                      for im in df.INPUT_MODES}
        staged_os = best_staged_os(layer, lp.alpha)
        # Scheduled kernel bytes: prefer the ACTUAL compiled table
        # stream (exact t_max/channel padding) over the nnz/mu estimate
        # whenever the plan carries tables; same per-flow re-read
        # factor as the cost model.
        sched_bytes = mode_cost["scheduled"]["kernel_hbm_bytes"]
        if lp.tables is not None:
            t = layer.tiles(fft_size) * batch
            gp = max(1, -(-t // tn.block_p))
            reread = 1 if tn.flow == "weight_stationary" else gp
            sched_bytes = float(lp.tables.nbytes * reread)
        # staged pipeline additionally round-trips tiles through the
        # separate FFT/IFFT kernels (real in, 2 f32 planes out and
        # back), and consumes the same host-materialized window tensor
        # the windowed fused path does (raw read + windowed write) —
        # counted for symmetry with the fused input accounting.
        k2 = fft_size * fft_size
        t = layer.tiles(fft_size) * batch
        fft_io = (layer.c_in * t * (k2 + 2 * k2)
                  + layer.c_out * t * (2 * k2 + k2)
                  + layer.c_in * (layer.h_in * layer.w_in * batch
                                  + k2 * t)) * 4
        rows.append({
            "layer": layer.name,
            "launches_fused": FUSED_LAUNCHES_PER_LAYER,
            "launches_staged": STAGED_LAUNCHES_PER_LAYER,
            "flow": tn.flow,
            "hadamard": lp.hadamard,
            "input_mode": lp.input_mode,
            "block_n": tn.block_n, "block_m": tn.block_m,
            "block_p": tn.block_p,
            "alpha": lp.alpha,
            "nnz": lp.kernels.nnz,
            "active_bins": fa,
            "pe_utilization": lp.pe_utilization,
            "schedule_cycles": lp.schedule_cycles,
            "fused_hbm_bytes": fused_plan["hbm_bytes"],
            "fused_hbm_bytes_dense": fused_dense["hbm_bytes"],
            "input_hbm_bytes": fused_plan["input_hbm_bytes"],
            "input_hbm_bytes_windowed":
                input_cost["windowed"]["input_hbm_bytes"],
            "input_hbm_bytes_halo": input_cost["halo"]["input_hbm_bytes"],
            "halo_lt_windowed": bool(
                input_cost["halo"]["input_hbm_bytes"]
                < input_cost["windowed"]["input_hbm_bytes"]),
            "kernel_hbm_bytes": fused_plan["kernel_hbm_bytes"],
            "kernel_hbm_bytes_dense": fused_dense["kernel_hbm_bytes"],
            "kernel_hbm_bytes_bin": mode_cost["bin"]["kernel_hbm_bytes"],
            "kernel_hbm_bytes_scheduled": sched_bytes,
            "table_bytes": (lp.tables.nbytes
                            if lp.tables is not None else 0),
            "hadamard_flops_dense": mode_cost["dense"]["had_flops"],
            "hadamard_flops_bin": mode_cost["bin"]["had_flops"],
            "hadamard_flops_scheduled":
                mode_cost["scheduled"]["had_flops"],
            "scheduled_le_bin": bool(
                sched_bytes <= mode_cost["bin"]["kernel_hbm_bytes"]),
            "kernel_bytes_reduction": (
                fused_dense["kernel_hbm_bytes"]
                / fused_plan["kernel_hbm_bytes"]),
            "staged_os_hadamard_hbm_bytes": staged_os["hbm_bytes"],
            "staged_fft_io_hbm_bytes": float(fft_io),
            "fused_le_staged_os": bool(
                fused_plan["hbm_bytes"]
                <= staged_os["hbm_bytes"] + fft_io),
            "fused_predicted_us": 1e6 * (
                fused_plan["serial_s"] + max(fused_plan["hbm_s"],
                                             fused_plan["compute_s"])),
            "staged_hadamard_predicted_us": 1e6 * max(staged_os["hbm_s"],
                                                      staged_os["compute_s"]),
        })
    return rows


def fused_parity_vs_spatial(layers, fft_size: int, batch: int = 1,
                            seed: int = 0) -> dict:
    """Per-layer fused-vs-spatial max abs error at full resolution,
    unpruned (alpha = 1) so the spectral path is numerically equivalent."""
    from repro.core import autotune
    from repro.core import spectral as spec
    from repro.kernels.fused_spectral_conv import fused_spectral_conv2d

    rng = np.random.default_rng(seed)
    tuning = autotune.autotune_network(layers, fft_size, 1.0, batch=batch)
    per_layer = {}
    worst = 0.0
    for layer in layers:
        x = jnp.asarray(rng.standard_normal(
            (batch, layer.c_in, layer.h_in, layer.w_in)), jnp.float32)
        w = jnp.asarray(rng.standard_normal(
            (layer.c_out, layer.c_in, layer.ksize, layer.ksize))
            * (2.0 / (layer.c_in * layer.ksize ** 2)) ** 0.5, jnp.float32)
        geo = spec.make_geometry(layer.h_in, layer.w_in, layer.ksize,
                                 fft_size, layer.pad)
        tn = tuning[layer.name]
        y = fused_spectral_conv2d(x, spec.spectral_kernel(w, fft_size),
                                  geo, **tn.kwargs())
        y_ref = spec.spatial_conv2d(x, w)
        err = float(jnp.abs(y - y_ref).max())
        per_layer[layer.name] = err
        worst = max(worst, err)
    return {"batch": batch, "alpha": 1.0, "max_abs_err": worst,
            "per_layer": per_layer,
            "passes_1e-3": bool(worst <= 1e-3)}


def fused_sparse_parity_vs_oracle(layers, fft_size: int, alpha: float = 4.0,
                                  batch: int = 1, seed: int = 0) -> dict:
    """Acceptance check: the fused-sparse backend (active-bin compaction
    + in-kernel bias+ReLU epilogue) matches the sparse-aware einsum
    oracle to <= 1e-4 on every full-resolution VGG16 layer."""
    from repro.core import autotune, sparse as sp
    from repro.core import spectral as spec
    from repro.kernels.fused_spectral_conv import fused_spectral_conv2d

    rng = np.random.default_rng(seed)
    per_layer = {}
    worst = 0.0
    for layer in layers:
        x = jnp.asarray(rng.standard_normal(
            (batch, layer.c_in, layer.h_in, layer.w_in)), jnp.float32)
        w = jnp.asarray(rng.standard_normal(
            (layer.c_out, layer.c_in, layer.ksize, layer.ksize))
            * (2.0 / (layer.c_in * layer.ksize ** 2)) ** 0.5, jnp.float32)
        b = jnp.asarray(0.1 * rng.standard_normal(layer.c_out), jnp.float32)
        geo = spec.make_geometry(layer.h_in, layer.w_in, layer.ksize,
                                 fft_size, layer.pad)
        sk = sp.prune_magnitude(spec.spectral_kernel(w, fft_size), alpha)
        tn = autotune.autotune_layer(layer, fft_size, alpha, batch=batch)
        y = fused_spectral_conv2d(x, sk, geo, bias=b, relu=True,
                                  **tn.kwargs())
        y_ref = jax.nn.relu(
            spec.spectral_conv2d_pretransformed(x, sk, geo)
            + b[None, :, None, None])
        err = float(jnp.abs(y - y_ref).max())
        per_layer[layer.name] = err
        worst = max(worst, err)
    return {"batch": batch, "alpha": alpha, "epilogue": "bias+relu",
            "max_abs_err": worst, "per_layer": per_layer,
            "passes_1e-4": bool(worst <= 1e-4)}


def scheduled_parity_vs_oracle(layers, fft_size: int, alpha: float = 4.0,
                               batch: int = 1, seed: int = 0) -> dict:
    """Acceptance: the SCHEDULED fused datapath — Alg-2 INDEX/VALUE
    tables executed element-granularly inside the single pallas_call —
    matches the sparse-aware einsum oracle to <= 1e-5, bias+ReLU
    in-kernel, at the Alg-1 configuration tuned for the mode."""
    from repro.core import autotune, sparse as sp
    from repro.core import spectral as spec
    from repro.kernels.fused_spectral_conv import (
        fused_spectral_conv2d_scheduled)

    rng = np.random.default_rng(seed)
    per_layer = {}
    worst = 0.0
    for layer in layers:
        x = jnp.asarray(rng.standard_normal(
            (batch, layer.c_in, layer.h_in, layer.w_in)), jnp.float32)
        w = jnp.asarray(rng.standard_normal(
            (layer.c_out, layer.c_in, layer.ksize, layer.ksize))
            * (2.0 / (layer.c_in * layer.ksize ** 2)) ** 0.5, jnp.float32)
        b = jnp.asarray(0.1 * rng.standard_normal(layer.c_out), jnp.float32)
        geo = spec.make_geometry(layer.h_in, layer.w_in, layer.ksize,
                                 fft_size, layer.pad)
        sk = sp.prune_magnitude(spec.spectral_kernel(w, fft_size), alpha)
        tn = autotune.autotune_layer(layer, fft_size, alpha, batch=batch,
                                     hadamard_modes=("scheduled",))
        y = fused_spectral_conv2d_scheduled(
            x, sk, geo, bias=b, relu=True, n_par=tn.block_n,
            flow=tn.flow, block_m=tn.block_m, block_p=tn.block_p)
        y_ref = jax.nn.relu(
            spec.spectral_conv2d_pretransformed(x, sk, geo)
            + b[None, :, None, None])
        err = float(jnp.abs(y - y_ref).max())
        per_layer[layer.name] = err
        worst = max(worst, err)
    return {"batch": batch, "alpha": alpha, "epilogue": "bias+relu",
            "max_abs_err": worst, "per_layer": per_layer,
            "passes_1e-5": bool(worst <= 1e-5)}


def scheduled_network_parity(cfg, batch: int = 1) -> dict:
    """End-to-end: the smoke network with EVERY layer forced to the
    scheduled datapath vs the einsum oracle on the same plan."""
    from repro.core.plan import build_network_plan
    from repro.models import cnn

    key = jax.random.PRNGKey(0)
    params = cnn.init(key, cfg)
    plan = build_network_plan(params, cfg, batch=batch,
                              hadamard="scheduled")
    x = jax.random.normal(key, (batch, 3, cfg.image_size, cfg.image_size),
                          jnp.float32)
    ref = cnn.forward_spectral(params, plan, x, backend="einsum")
    out = cnn.forward_spectral(params, plan, x, backend="pallas_fused")
    err = float(jnp.abs(out - ref).max())
    return {"model": cfg.name, "batch": batch,
            "modes": [lp.hadamard for lp in plan.layers],
            "max_abs_logit_err": err,
            "passes_1e-5": bool(err <= 1e-5)}


def halo_parity_matrix(fft_size: int = 8, alpha: float = 4.0,
                       batch: int = 1, seed: int = 0,
                       small: bool = False) -> dict:
    """Acceptance: the halo input path (in-kernel window gather from the
    raw activation) matches the einsum oracle <= 1e-5 across ALL THREE
    flows x ALL THREE Hadamard modes, bias+ReLU fused.  Also reports
    the max |halo - windowed| deviation, which the one-hot gather makes
    exactly 0.0."""
    from repro.core import dataflow as df
    from repro.core import sparse as sp
    from repro.core import spectral as spec
    from repro.kernels.fused_spectral_conv import (
        fused_spectral_conv2d, fused_spectral_conv2d_scheduled)

    rng = np.random.default_rng(seed)
    layer = (df.ConvLayer("halo_matrix_smoke", 8, 8, 12, 12) if small
             else df.ConvLayer("halo_matrix", 48, 64, 28, 28))
    x = jnp.asarray(rng.standard_normal(
        (batch, layer.c_in, layer.h_in, layer.w_in)), jnp.float32)
    w = jnp.asarray(rng.standard_normal(
        (layer.c_out, layer.c_in, layer.ksize, layer.ksize))
        * (2.0 / (layer.c_in * layer.ksize ** 2)) ** 0.5, jnp.float32)
    b = jnp.asarray(0.1 * rng.standard_normal(layer.c_out), jnp.float32)
    geo = spec.make_geometry(layer.h_in, layer.w_in, layer.ksize,
                             fft_size, layer.pad)
    sk = sp.prune_magnitude(spec.spectral_kernel(w, fft_size), alpha)
    y_ref = jax.nn.relu(
        spec.spectral_conv2d_pretransformed(x, sk, geo)
        + b[None, :, None, None])

    cells = {}
    worst_oracle = worst_windowed = 0.0
    for flow in df.FLOWS:
        for mode in df.HADAMARD_MODES:
            out = {}
            for imode in df.INPUT_MODES:
                bn = min(16, layer.c_out)
                bm = min(16, layer.c_in)
                if mode == "scheduled":
                    out[imode] = fused_spectral_conv2d_scheduled(
                        x, sk, geo, n_par=bn, flow=flow, block_m=bm,
                        block_p=32, bias=b, relu=True, input_mode=imode)
                else:
                    w_f = sk.values if mode == "dense" else sk
                    out[imode] = fused_spectral_conv2d(
                        x, w_f, geo, flow=flow, block_n=bn, block_m=bm,
                        block_p=32, bias=b, relu=True, input_mode=imode)
            e_or = float(jnp.abs(out["halo"] - y_ref).max())
            e_win = float(jnp.abs(out["halo"] - out["windowed"]).max())
            cells[f"{flow}/{mode}"] = {"vs_oracle": e_or,
                                       "vs_windowed": e_win}
            worst_oracle = max(worst_oracle, e_or)
            worst_windowed = max(worst_windowed, e_win)
    return {"layer": layer.name, "alpha": alpha, "epilogue": "bias+relu",
            "cells": cells,
            "max_abs_err_vs_oracle": worst_oracle,
            "max_abs_err_vs_windowed": worst_windowed,
            "passes_1e-5": bool(worst_oracle <= 1e-5)}


def sharded_cost_model(layers, fft_size: int = 8, alpha: float = 4.0,
                       n_shards: int = 8, batch: int = 1) -> dict:
    """The multi-device column: the two-level Alg-1 cost model
    (``autotune.autotune_layer_sharded`` via
    ``distributed.planner.spectral_plan_cell``) over the conv stack at
    D shards.  Analytic — needs no devices — and gated:

      strategy_diversity_ge_2   the tuner must pick >= 2 DISTINCT
          partitionings across the stack (the whole point of running
          Alg 1 per layer instead of per network: early large-canvas
          convs shard spatially, late channel-heavy convs by channel).
      ici_bytes_positive        a sharded network that claims zero
          wire traffic is mis-modeling its collectives.
      per_chip_hbm_le_single_chip_all_layers
          every layer's per-chip HBM footprint under the chosen
          strategy is <= the single-chip autotuned footprint of the
          FULL layer — sharding must never inflate per-chip traffic.
    """
    from repro.core import autotune
    from repro.distributed.planner import spectral_plan_cell

    cell = spectral_plan_cell(layers, fft_size, alpha,
                              n_shards=n_shards, batch=batch)
    single = autotune.autotune_network(layers, fft_size, alpha,
                                       batch=batch)
    rows = []
    for layer in layers:
        t = cell["tunings"][layer.name]
        s = single[layer.name]
        rows.append({
            "layer": layer.name,
            "strategy": t.strategy,
            "flow": t.base.flow,
            "hadamard": t.base.hadamard,
            "input_mode": t.base.input_mode,
            "block_n": t.base.block_n,
            "block_m": t.base.block_m,
            "block_p": t.base.block_p,
            "per_chip_hbm_bytes": t.per_chip_hbm_bytes,
            "single_chip_hbm_bytes": s.hbm_bytes,
            "ici_bytes": t.ici_bytes,
            "ici_s": t.ici_s,
            "sharded_s": t.sharded_s,
            "single_chip_predicted_s": s.predicted_s,
            "per_chip_le_single_chip": bool(
                t.per_chip_hbm_bytes <= s.hbm_bytes),
        })
    distinct = sorted({r["strategy"] for r in rows})
    return {
        "n_shards": n_shards,
        "batch": batch,
        "alpha": alpha,
        "layers": rows,
        "strategy_counts": {
            "spatial": cell["n_spatial"],
            "channel": cell["n_channel"],
            "replicate": cell["n_replicate"],
        },
        "distinct_strategies": distinct,
        "per_chip_hbm_mb_worst": cell["per_chip_hbm_bytes"] / 1e6,
        "ici_mb_total": cell["ici_bytes_total"] / 1e6,
        "ici_s_total": cell["ici_s_total"],
        "sharded_s_total": cell["sharded_s_total"],
        "single_chip_s_total": sum(r["single_chip_predicted_s"]
                                   for r in rows),
        "strategy_diversity_ge_2": bool(len(distinct) >= 2),
        "ici_bytes_positive": bool(cell["ici_bytes_total"] > 0),
        "per_chip_hbm_le_single_chip_all_layers": all(
            r["per_chip_le_single_chip"] for r in rows),
    }


def sharded_parity(cfg, n_shards: int = 2, batch: int = 1) -> dict:
    """Live multi-device acceptance: channel- AND spatial-forced
    ``ShardedNetworkPlan`` forward passes under ``shard_map`` on a real
    ``n_shards``-device mesh match the single-device einsum oracle to
    <= 1e-5 end-to-end (conv stack + pools + FC head).  Layers where a
    forced strategy is infeasible (e.g. channel with D not dividing
    c_in) fall back to 'replicate' per the plan builder — the mixed
    plan still exercises the collectives on every feasible layer."""
    from repro.core.plan import (build_network_plan,
                                 build_sharded_network_plan)
    from repro.distributed.executor import forward_spectral_sharded
    from repro.launch.mesh import make_spectral_mesh
    from repro.models import cnn

    key = jax.random.PRNGKey(0)
    params = cnn.init(key, cfg)
    x = jax.random.normal(key, (batch, 3, cfg.image_size, cfg.image_size),
                          jnp.float32)
    base = build_network_plan(params, cfg, batch=batch)
    ref = cnn.forward_spectral(params, base, x, backend="einsum")
    mesh = make_spectral_mesh(n_shards)
    out: dict = {"model": cfg.name, "n_shards": n_shards, "batch": batch}
    worst = 0.0
    for strat in ("channel", "spatial"):
        splan = build_sharded_network_plan(
            params, cfg, n_shards=n_shards, strategies=(strat,),
            batch=batch, base=base)
        y = forward_spectral_sharded(params, splan, x, mesh=mesh)
        err = float(jnp.abs(y - ref).max())
        counts = {}
        for s in splan.strategies.values():
            counts[s] = counts.get(s, 0) + 1
        out[strat] = {"max_abs_logit_err": err,
                      "strategy_counts": counts}
        worst = max(worst, err)
    out["max_abs_err"] = worst
    out["passes_1e-5"] = bool(worst <= 1e-5)
    return out


def resnet_dag_column(batch: int = 1) -> dict:
    """The gated ``resnet`` column (ISSUE 10): the residual-DAG plan IR
    on the ResNet-18-style smoke preset — stride-2 downsample, max- and
    avg-pool nodes, and four residual-FUSED shortcut epilogues.

    Four acceptance surfaces, all against the SPATIAL DAG oracle
    (``cnn.forward_spatial`` walking the same graph) at alpha = 1
    (pruning off — the oracle does not prune, so parity is only defined
    dense):

      parity     all three backends <= 1e-5 end-to-end;
      shortcuts  per residual edge, the analytic HBM bytes of the
                 residual-FUSED epilogue (shortcut priced at the tuned
                 'vmem'/'hbm' placement) <= the unfused alternative
                 (same kernel without the shortcut operand + a separate
                 XLA add pass re-reading y and the shortcut and writing
                 y back: 3 extra Y-passes);
      demotion   an injected 'lowering' fault matched on
                 ``residual='fused'`` must walk every residual node down
                 the NEW ladder rung (residual-fused -> residual-add)
                 and the hardened plan must still match the oracle;
      sharded    when >= 2 devices are visible, a channel- and a
                 spatial-FORCED ShardedNetworkPlan of the same DAG must
                 match the oracle under shard_map.
    """
    import dataclasses

    from repro.configs import resnet18_spectral
    from repro.core import dataflow as df
    from repro.core import resilience as res
    from repro.core.plan import build_network_plan
    from repro.models import cnn
    from repro.testing import faults

    cfg = dataclasses.replace(resnet18_spectral.SMOKE, alpha=1.0)
    key = jax.random.PRNGKey(0)
    params = cnn.init(key, cfg)
    x = jax.random.normal(key, (batch, 3, cfg.image_size, cfg.image_size),
                          jnp.float32)
    plan = build_network_plan(params, cfg, batch=batch)
    ref = cnn.forward_spatial(params, cfg, x)

    graph = plan.execution_graph
    residual_nodes = [n for n in graph if n.residual_from is not None]
    out: dict = {
        "model": cfg.name,
        "alpha": cfg.alpha,
        "batch": batch,
        "n_nodes": len(graph),
        "n_residual": len(residual_nodes),
        "n_stride2": sum(
            n.kind == "conv"
            and plan.layers[n.layer_index].layer.stride == 2
            for n in graph),
        "pools": [n.pool for n in graph if n.kind == "pool"],
        "residual_fused_nodes": [
            n.id for n in residual_nodes
            if plan.layers[n.layer_index].epilogue.residual == "fused"],
        "shortcut_on_chip": {n.id: n.shortcut_on_chip
                             for n in residual_nodes},
    }

    per_backend = {}
    for backend in ("einsum", "pallas_staged", "pallas_fused"):
        y = cnn.forward_spectral(params, plan, x, backend=backend)
        per_backend[backend] = float(jnp.abs(y - ref).max())
    worst = max(per_backend.values())
    out["parity"] = {"per_backend": per_backend, "max_abs_err": worst,
                     "passes_1e-5": bool(worst <= 1e-5)}

    # Analytic shortcut gate: fusing the residual add into the epilogue
    # must never cost more HBM than the unfused alternative (kernel
    # without the shortcut operand + a 3-Y-pass XLA add: read y, read
    # shortcut, write y).
    rows = []
    for n in residual_nodes:
        lp = plan.layers[n.layer_index]
        tn = lp.tuning
        place = tn.residual or "hbm"

        def cost(residual):
            return df.tpu_fused_flow_cost(
                lp.layer, cfg.fft_size, lp.alpha, tn.block_n,
                tn.block_p, tn.block_m, tn.flow, batch=batch,
                active_bins=lp.n_active_bins, hadamard=lp.hadamard,
                input_mode=lp.input_mode, residual=residual)

        hw = lp.layer.out_hw
        y_bytes = 4 * batch * lp.layer.c_out * hw[0] * hw[1]
        fused = cost(place)["hbm_bytes"]
        unfused = cost(None)["hbm_bytes"] + 3 * y_bytes
        rows.append({
            "node": n.id,
            "placement": place,
            "shortcut_on_chip": n.shortcut_on_chip,
            "fused_hbm_bytes": fused,
            "unfused_hbm_bytes": unfused,
            "fused_le_unfused": bool(fused <= unfused),
        })
    out["shortcuts"] = {
        "per_edge": rows,
        "fused_le_unfused_all": all(r["fused_le_unfused"] for r in rows),
    }

    # Injected lowering fault on every residual-FUSED variant: the
    # hardening loop must take the NEW ladder rung (residual-fused ->
    # residual-add) and the demoted plan must still match the oracle.
    with faults.inject("lowering", residual="fused") as fault:
        hard = res.harden_network_plan(plan)
    demoted = {n.id: list(hard.layers[n.layer_index].provenance)
               for n in residual_nodes}
    rung_hit = all(
        any("residual-fused->residual-add" in p for p in prov)
        for prov in demoted.values())
    y = cnn.forward_spectral(params, hard, x, backend="pallas_fused")
    derr = float(jnp.abs(y - ref).max())
    out["demotion"] = {
        "fault_fires": fault.fires,
        "provenance": demoted,
        "all_residual_nodes_demoted_to_add": bool(rung_hit),
        "max_abs_err": derr,
        "passes_1e-5": bool(derr <= 1e-5),
    }

    if len(jax.devices()) >= 2:
        from repro.core.plan import build_sharded_network_plan
        from repro.distributed.executor import forward_spectral_sharded
        from repro.launch.mesh import make_spectral_mesh
        mesh = make_spectral_mesh(2)
        sh: dict = {"n_shards": 2}
        sworst = 0.0
        for strat in ("channel", "spatial"):
            splan = build_sharded_network_plan(
                params, cfg, n_shards=2, strategies=(strat,),
                batch=batch, base=plan)
            y = forward_spectral_sharded(params, splan, x, mesh=mesh)
            err = float(jnp.abs(y - ref).max())
            sh[strat] = {"max_abs_err": err}
            sworst = max(sworst, err)
        sh["max_abs_err"] = sworst
        sh["passes_1e-5"] = bool(sworst <= 1e-5)
        out["sharded"] = sh
    return out


def main() -> None:
    from repro.configs import vgg16_spectral
    from repro.core import dataflow as df
    from repro.core.plan import build_network_plan
    from repro.models import cnn

    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default="BENCH_e2e.json",
                    help="output path for the JSON report")
    ap.add_argument("--full", action="store_true",
                    help="also time the full 224x224 model (slow on CPU)")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke path: smoke-scale model everywhere, "
                    "skip full-resolution plan/parity sweeps")
    ap.add_argument("--iters", type=int, default=3)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    traffic_cfg = (vgg16_spectral.SMOKE if args.quick
                   else vgg16_spectral.CONFIG)
    report: dict = {
        "bench": "e2e_latency",
        "backend": jax.default_backend(),
        "interpret_mode": jax.default_backend() != "tpu",
        # --quick swaps the traffic/parity model for the smoke config;
        # the metadata must say so (smoke layer NAMES shadow real VGG16
        # layers at much smaller channel counts).
        "model": traffic_cfg.name,
        "fft_size": traffic_cfg.fft_size,
        "alpha": traffic_cfg.alpha,
        "quick": bool(args.quick),
    }

    print("[1/8] latency: oracle vs staged Pallas vs fused Pallas "
          "(plan built per batch bucket, batch-tuned)")
    report["latency"] = {"smoke": latency_table(
        vgg16_spectral.SMOKE, iters=args.iters)}
    if args.full:
        report["latency"]["full"] = latency_table(
            vgg16_spectral.CONFIG, batches=(1,), iters=1)
    for scale, tbl in report["latency"].items():
        for b, row in tbl.items():
            pretty = ", ".join(f"{k}={v:.1f}" for k, v in row.items())
            print(f"      {scale}/{b}: {pretty}")
    report["batch_sweep"] = bucket_gate(report["latency"]["smoke"])
    print(f"      fused<=einsum at every bucket: "
          f"{report['batch_sweep']['fused_le_einsum_all_buckets']}")

    print(f"[2/8] {traffic_cfg.name} NetworkPlan (compile once: prune + "
          "Alg 2 tables + compaction + mode-aware autotune)")
    t0 = time.perf_counter()
    params_full = cnn.init(jax.random.PRNGKey(0), traffic_cfg)
    plan_full = build_network_plan(params_full, traffic_cfg, batch=1)
    report["plan_build_s"] = time.perf_counter() - t0
    n_sched = sum(lp.hadamard == "scheduled" for lp in plan_full.layers)
    print(f"      built in {report['plan_build_s']:.1f}s "
          f"({n_sched}/{len(plan_full.layers)} layers scheduled)")

    print("[3/8] per-layer launches + analytic HBM traffic "
          "(dense vs bin vs scheduled vs staged) + Alg-2 PE utilization")
    layer_rows = per_layer_traffic(plan_full, 8, batch=1)
    report["layers"] = layer_rows
    tot_fused = sum(r["fused_hbm_bytes"] for r in layer_rows)
    tot_fused_dense = sum(r["fused_hbm_bytes_dense"] for r in layer_rows)
    tot_staged = sum(r["staged_os_hadamard_hbm_bytes"]
                     + r["staged_fft_io_hbm_bytes"] for r in layer_rows)
    tot_k = sum(r["kernel_hbm_bytes"] for r in layer_rows)
    tot_k_dense = sum(r["kernel_hbm_bytes_dense"] for r in layer_rows)
    tot_k_bin = sum(r["kernel_hbm_bytes_bin"] for r in layer_rows)
    tot_k_sched = sum(r["kernel_hbm_bytes_scheduled"] for r in layer_rows)
    tot_in = sum(r["input_hbm_bytes"] for r in layer_rows)
    tot_in_win = sum(r["input_hbm_bytes_windowed"] for r in layer_rows)
    tot_in_halo = sum(r["input_hbm_bytes_halo"] for r in layer_rows)
    mus = [r["pe_utilization"] for r in layer_rows
           if r["pe_utilization"] is not None]
    sparse_rows = [r for r in layer_rows if r["alpha"] > 1.0]
    report["totals"] = {
        "fused_hbm_mb": tot_fused / 1e6,
        "fused_dense_hbm_mb": tot_fused_dense / 1e6,
        "staged_hbm_mb": tot_staged / 1e6,
        "hbm_reduction_vs_staged_pct": 100 * (1 - tot_fused / tot_staged),
        "kernel_hbm_mb": tot_k / 1e6,
        "kernel_dense_hbm_mb": tot_k_dense / 1e6,
        "kernel_bin_hbm_mb": tot_k_bin / 1e6,
        "kernel_scheduled_hbm_mb": tot_k_sched / 1e6,
        "kernel_bytes_reduction": tot_k_dense / tot_k,
        "input_hbm_mb": tot_in / 1e6,
        "input_windowed_hbm_mb": tot_in_win / 1e6,
        "input_halo_hbm_mb": tot_in_halo / 1e6,
        "input_bytes_reduction": tot_in_win / tot_in_halo,
        "mean_pe_utilization": float(np.mean(mus)) if mus else None,
        "launches_fused": FUSED_LAUNCHES_PER_LAYER * len(layer_rows),
        "launches_staged": STAGED_LAUNCHES_PER_LAYER * len(layer_rows),
        "hadamard_modes": {m: sum(r["hadamard"] == m for r in layer_rows)
                           for m in df.HADAMARD_MODES},
        "input_modes": {m: sum(r["input_mode"] == m for r in layer_rows)
                        for m in df.INPUT_MODES},
        "all_layers_fused_le_staged_os": all(
            r["fused_le_staged_os"] for r in layer_rows),
        "all_sparse_scheduled_le_bin": all(
            r["scheduled_le_bin"] for r in sparse_rows),
        "all_layers_halo_input_lt_windowed": all(
            r["halo_lt_windowed"] for r in layer_rows),
    }
    t = report["totals"]
    print(f"      fused {t['fused_hbm_mb']:.1f} MB (dense "
          f"{t['fused_dense_hbm_mb']:.1f} MB) vs staged "
          f"{t['staged_hbm_mb']:.1f} MB HBM "
          f"({t['hbm_reduction_vs_staged_pct']:.0f}% less than staged); "
          f"kernel bytes {t['kernel_hbm_mb']:.1f} MB (dense "
          f"{t['kernel_dense_hbm_mb']:.1f} / bin "
          f"{t['kernel_bin_hbm_mb']:.1f} / scheduled "
          f"{t['kernel_scheduled_hbm_mb']:.1f} MB; "
          f"{t['kernel_bytes_reduction']:.1f}x vs dense); "
          f"input bytes {t['input_hbm_mb']:.1f} MB (windowed "
          f"{t['input_windowed_hbm_mb']:.1f} / halo "
          f"{t['input_halo_hbm_mb']:.1f} MB; "
          f"{t['input_bytes_reduction']:.1f}x, halo<windowed on all "
          f"layers: {t['all_layers_halo_input_lt_windowed']}); "
          f"scheduled<=bin on all sparse layers: "
          f"{t['all_sparse_scheduled_le_bin']}; modes "
          f"{t['hadamard_modes']} / {t['input_modes']}; mean PE util "
          f"{t['mean_pe_utilization']:.1%}; launches "
          f"{t['launches_fused']} vs {t['launches_staged']}")

    if not args.quick:
        print("[4/8] parity on full VGG16 (batch 1): fused vs spatial "
              "(alpha=1) and fused-sparse+epilogue vs oracle (alpha=4)")
        report["parity"] = fused_parity_vs_spatial(df.VGG16_LAYERS, 8,
                                                   batch=1)
        print(f"      dense vs spatial: max abs err "
              f"{report['parity']['max_abs_err']:.2e} "
              f"(<= 1e-3: {report['parity']['passes_1e-3']})")
        report["parity_sparse"] = fused_sparse_parity_vs_oracle(
            df.VGG16_LAYERS, 8, alpha=4.0, batch=1)
        print(f"      sparse+epilogue vs oracle: max abs err "
              f"{report['parity_sparse']['max_abs_err']:.2e} "
              f"(<= 1e-4: {report['parity_sparse']['passes_1e-4']})")

    print("[5/8] SCHEDULED-fused parity vs einsum oracle (acceptance "
          "<= 1e-5)")
    sched = {"network_smoke": scheduled_network_parity(
        vgg16_spectral.SMOKE, batch=1)}
    if not args.quick:
        sched["per_layer_conv5"] = scheduled_parity_vs_oracle(
            df.VGG16_LAYERS[-3:], 8, alpha=4.0, batch=1)
        print(f"      conv5 trio (512ch, tables in-kernel): max abs err "
              f"{sched['per_layer_conv5']['max_abs_err']:.2e} "
              f"(<= 1e-5: {sched['per_layer_conv5']['passes_1e-5']})")
    report["parity_scheduled"] = sched
    print(f"      smoke net, all layers scheduled: max abs logit err "
          f"{sched['network_smoke']['max_abs_logit_err']:.2e} "
          f"(<= 1e-5: {sched['network_smoke']['passes_1e-5']})")

    print("[6/8] HALO input path parity vs einsum oracle, 3 flows x "
          "3 Hadamard modes (acceptance <= 1e-5)")
    report["parity_halo"] = halo_parity_matrix(8, alpha=4.0, batch=1,
                                               small=args.quick)
    ph = report["parity_halo"]
    print(f"      {ph['layer']}: max abs err vs oracle "
          f"{ph['max_abs_err_vs_oracle']:.2e} (<= 1e-5: "
          f"{ph['passes_1e-5']}); vs windowed path "
          f"{ph['max_abs_err_vs_windowed']:.2e}")

    print("[7/8] multi-device column: two-level Alg-1 cost model "
          "(strategy per layer) + live sharded parity when the mesh "
          "has devices")
    if args.quick:
        cost = sharded_cost_model(list(traffic_cfg.layers), 8,
                                  alpha=traffic_cfg.alpha, n_shards=4)
    else:
        cost = sharded_cost_model(list(df.VGG16_LAYERS), 8, alpha=4.0,
                                  n_shards=8)
    report["sharded"] = {"cost_model": cost}
    sc = cost["strategy_counts"]
    print(f"      D={cost['n_shards']}: strategies "
          f"spatial={sc['spatial']} channel={sc['channel']} "
          f"replicate={sc['replicate']} "
          f"(diversity>=2: {cost['strategy_diversity_ge_2']}); "
          f"ICI {cost['ici_mb_total']:.1f} MB on the wire; worst "
          f"per-chip HBM {cost['per_chip_hbm_mb_worst']:.1f} MB "
          f"(<= single-chip on all layers: "
          f"{cost['per_chip_hbm_le_single_chip_all_layers']}); "
          f"predicted {1e3 * cost['sharded_s_total']:.2f} ms sharded "
          f"vs {1e3 * cost['single_chip_s_total']:.2f} ms single-chip")
    n_dev = len(jax.devices())
    if n_dev >= 2:
        par = sharded_parity(vgg16_spectral.SMOKE, n_shards=2, batch=1)
        report["sharded"]["parity"] = par
        print(f"      live parity on {par['n_shards']}/{n_dev} devices "
              f"(channel + spatial forced): max abs logit err "
              f"{par['max_abs_err']:.2e} (<= 1e-5: {par['passes_1e-5']})")
    else:
        print(f"      live parity skipped: {n_dev} device(s) visible "
              "(set XLA_FLAGS=--xla_force_host_platform_device_count=8)")

    print("[8/8] resnet column: residual-DAG plan IR on the "
          "ResNet-18-style smoke preset (alpha=1 vs the spatial DAG "
          "oracle)")
    rn = resnet_dag_column()
    report["resnet"] = rn
    print(f"      {rn['model']}: {rn['n_nodes']} nodes "
          f"({rn['n_residual']} residual, {rn['n_stride2']} stride-2, "
          f"pools {rn['pools']}); parity 3 backends max abs err "
          f"{rn['parity']['max_abs_err']:.2e} (<= 1e-5: "
          f"{rn['parity']['passes_1e-5']}); fused<=unfused shortcut "
          f"bytes on all edges: "
          f"{rn['shortcuts']['fused_le_unfused_all']}; fault-demoted "
          f"to residual-add rung on all residual nodes: "
          f"{rn['demotion']['all_residual_nodes_demoted_to_add']} "
          f"(parity {rn['demotion']['max_abs_err']:.2e})")
    if "sharded" in rn:
        print(f"      forced channel+spatial sharding on "
              f"{rn['sharded']['n_shards']} devices: max abs err "
              f"{rn['sharded']['max_abs_err']:.2e} (<= 1e-5: "
              f"{rn['sharded']['passes_1e-5']})")

    _write_report_atomic(report, args.json)
    print(f"wrote {args.json}")

    failed = _failed_gates(report)
    if failed:
        print("[gates] FAILED:", file=sys.stderr)
        for name, value in failed:
            print(f"  - {name} = {value!r}", file=sys.stderr)
        sys.exit(1)
    print("[gates] all acceptance gates pass")


def _write_report_atomic(report: dict, path: str) -> None:
    """Write the JSON report via a temp file in the same directory +
    ``os.replace`` so a crash (or a concurrent reader, e.g. CI tailing
    the file) never observes a truncated BENCH_e2e.json."""
    dirname = os.path.dirname(os.path.abspath(path))
    fd, tmp = tempfile.mkstemp(dir=dirname, prefix=".bench_e2e_",
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


def _failed_gates(report: dict) -> list[tuple[str, object]]:
    """Collect acceptance-gate violations from a finished report.

    The report is written FIRST (atomically), then the gates fail the
    process with a nonzero exit so CI blocks on a parity or
    halo<windowed regression while the artifact stays inspectable."""
    gates: list[tuple[str, object]] = [
        ("batch_sweep.fused_le_einsum_all_buckets",
         report["batch_sweep"]["fused_le_einsum_all_buckets"]),
        ("totals.all_layers_halo_input_lt_windowed",
         report["totals"]["all_layers_halo_input_lt_windowed"]),
        ("totals.all_layers_fused_le_staged_os",
         report["totals"]["all_layers_fused_le_staged_os"]),
        ("totals.all_sparse_scheduled_le_bin",
         report["totals"]["all_sparse_scheduled_le_bin"]),
        ("parity_scheduled.network_smoke.passes_1e-5",
         report["parity_scheduled"]["network_smoke"]["passes_1e-5"]),
        ("parity_halo.passes_1e-5",
         report["parity_halo"]["passes_1e-5"]),
        ("sharded.cost_model.strategy_diversity_ge_2",
         report["sharded"]["cost_model"]["strategy_diversity_ge_2"]),
        ("sharded.cost_model.ici_bytes_positive",
         report["sharded"]["cost_model"]["ici_bytes_positive"]),
        ("sharded.cost_model.per_chip_hbm_le_single_chip_all_layers",
         report["sharded"]["cost_model"]
         ["per_chip_hbm_le_single_chip_all_layers"]),
    ]
    if "resnet" in report:
        rn = report["resnet"]
        gates += [
            ("resnet.parity.passes_1e-5", rn["parity"]["passes_1e-5"]),
            ("resnet.shortcuts.fused_le_unfused_all",
             rn["shortcuts"]["fused_le_unfused_all"]),
            ("resnet.demotion.all_residual_nodes_demoted_to_add",
             rn["demotion"]["all_residual_nodes_demoted_to_add"]),
            ("resnet.demotion.passes_1e-5",
             rn["demotion"]["passes_1e-5"]),
        ]
        if "sharded" in rn:
            gates.append(("resnet.sharded.passes_1e-5",
                          rn["sharded"]["passes_1e-5"]))
    # live multi-device parity (absent on single-device hosts)
    if "parity" in report.get("sharded", {}):
        gates.append(("sharded.parity.passes_1e-5",
                      report["sharded"]["parity"]["passes_1e-5"]))
    # full-run-only sweeps (absent under --quick)
    if "parity" in report:
        gates.append(("parity.passes_1e-3",
                      report["parity"]["passes_1e-3"]))
    if "parity_sparse" in report:
        gates.append(("parity_sparse.passes_1e-4",
                      report["parity_sparse"]["passes_1e-4"]))
    if "per_layer_conv5" in report.get("parity_scheduled", {}):
        gates.append(
            ("parity_scheduled.per_layer_conv5.passes_1e-5",
             report["parity_scheduled"]["per_layer_conv5"]["passes_1e-5"]))
    return [(name, value) for name, value in gates if not value]


if __name__ == "__main__":
    main()
